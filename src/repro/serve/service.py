"""Batched multi-instance sampling service.

C-SAW's out-of-memory design rests on batched multi-instance sampling —
packing many concurrent sampling instances into one device pass to amortize
transfers (paper §V-C).  This module lifts that idea one level up, to
*independent user requests*: a :class:`SamplingService` accepts many
concurrent, heterogeneous requests (different seed sets, walk lengths,
:class:`~repro.core.api.SamplingSpec`\\ s), fuses the compatible ones into
shared device launches, and unpacks per-request results.

The pipeline per :meth:`SamplingService.drain`:

1. :class:`~repro.serve.queue.RequestQueue` groups pending requests into
   padding-bucket **cohorts** keyed on the lowered transition program
   (``queue.cohort_key``) — one compiled trace per cohort shape.
2. Each cohort's seed sets are packed into one ``(R, W)`` matrix (one row
   per request, ``-1``-padded to the width bucket) with stacked per-request
   PRNG keys, and run through ``engine.random_walk_segments`` — a single
   fused launch whose row ``r`` is bit-identical to the standalone
   ``random_walk(graph, padded_seeds_r, key_r, depth=bucket)`` call on
   either backend.
3. When the service holds a *partitioned* graph instead of an in-memory
   one, the cohort routes to the §V frontier-queue drain
   (``oom_random_walk``): all member requests merge into one flat instance
   axis with per-instance ``depth_limits``, so one partition-scheduling
   pass serves every request in the cohort.
4. Results are sliced back per request: row padding off, depth bucket
   truncated to the request's own walk length.

Because fusing is a pure batching transform, ``ServiceConfig(fuse=False)``
(one launch per request, same padding) returns bit-identical responses —
that invariance is tested, and the throughput gap between the two modes is
the service's reason to exist (``benchmarks/bench_serve.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro import obs
from repro.core.api import SamplingSpec
from repro.core import backend as bk
from repro.core import transition as tp
from repro.core.engine import flat_method_plan, random_walk, random_walk_segments
from repro.core.oom import oom_random_walk
from repro.graph.csr import CSRGraph
from repro.graph.partition import RangePartition
from repro.shard.walk import sharded_random_walk
from repro.serve.queue import (
    AdmissionError,
    Cohort,
    RequestQueue,
    SamplingRequest,
    ServiceConfig,
    _pow2_bucket,
)


class DrainError(RuntimeError):
    """A cohort launch failed mid-drain.

    No request is lost: the failing cohort's and all not-yet-served
    requests are re-queued (same ids — ``drain()`` again to retry), and
    results of cohorts that completed before the failure are on
    ``completed``.
    """

    def __init__(self, message: str, completed: "Dict[int, RequestResult]"):
        super().__init__(message)
        self.completed = completed


class RequestResult(NamedTuple):
    """Per-request response: exactly the requested geometry, padding gone."""

    request_id: int
    walks: np.ndarray  # (n, depth+1) int32, -1 after termination
    lengths: np.ndarray  # (n,) realized lengths (# vertices)
    sampled_edges: int  # total edges this request sampled


class RequestLatency(NamedTuple):
    """One streamed request's life-cycle timing (``serve.stream``).

    ``queue_ms`` is submission → launch start: the policy's own wait (until
    the cohort was due: its batching window, its deadline slack, or the
    moment it filled; none for a request that joins a cohort already due)
    plus ``blocked_ms``, the time from then to the launch start, spent
    behind the launch in flight.
    ``launch_ms`` is the request's cohort launch wall time, ``total_ms``
    submission → result delivery.  ``deadline_met`` is ``None`` for
    requests submitted without a deadline.
    """

    request_id: int
    tier: int  # Priority value (lower = more urgent)
    queue_ms: float
    launch_ms: float
    total_ms: float
    reason: str  # what launched the cohort: fill / slack / window / flush / immediate
    deadline_met: Optional[bool]
    blocked_ms: float = 0.0


@dataclasses.dataclass
class ServiceStats:
    """Serving counters since construction (the benchmark's raw material)."""

    requests_served: int = 0
    walkers_served: int = 0
    launches: int = 0  # fused in-memory launches
    oom_launches: int = 0  # partition-scheduler passes
    sharded_launches: int = 0  # device-mesh frontier-exchange drains
    padded_walker_slots: int = 0  # launched slots minus real walkers
    plans_prewarmed: int = 0  # explicit prewarm() selection-plan builds
    #: placements prewarm() has warmed (plan and/or compiled launch trace)
    prewarmed_placements: tuple = ()
    # --- streaming (serve.stream) ---------------------------------------
    stream_requests: int = 0  # admitted through StreamingSamplingService
    stream_launches: int = 0  # cohort launches the scheduling loop issued
    stream_failed_requests: int = 0  # futures completed with an error
    stream_deadline_misses: int = 0  # deadline'd requests delivered late
    stream_quota_rejections: int = 0  # tenant token-bucket AdmissionErrors
    #: per-request queue/launch/total latency (RequestLatency entries, in
    #: delivery order) — the open-loop benchmark's raw material
    stream_latencies: list = dataclasses.field(default_factory=list)


def _slice_result(req: SamplingRequest, walks: np.ndarray) -> RequestResult:
    """Cut one request's rows out of a launch: drop row padding, truncate the
    depth bucket to the request's own walk length, recompute the per-request
    summary the standalone engine would have reported."""
    w = walks[: req.num_walkers, : req.depth + 1]
    lengths = (w >= 0).sum(axis=1).astype(np.int32)
    sampled = int(np.maximum(lengths - 1, 0).sum())
    return RequestResult(req.request_id, w, lengths, sampled)


class SamplingService:
    """Fuses concurrent sampling requests into shared device launches.

    Construct with EITHER an in-memory ``graph`` (requests run through the
    fused ``random_walk_segments`` path) OR host-resident ``partitions`` +
    ``total_vertices`` (requests run through the §V out-of-memory
    frontier-queue drain) OR a ``graph`` plus ``mesh`` and
    ``placement="sharded"`` (the graph is range-sharded over the mesh and
    cohorts run through the owner-routed frontier exchange,
    ``repro.shard`` / DESIGN.md §12).  ``submit()`` admits a request
    (raising :class:`~repro.serve.queue.AdmissionError` over capacity) and
    returns a request id; ``drain()`` serves everything pending and returns
    ``{request_id: RequestResult}``.

    On the in-memory path each request gets its own PRNG key (derived from
    the service key and the request id unless passed explicitly), so a
    request's result does not depend on which other requests happen to
    share its launch.  OOM- and shard-routed cohorts are different by
    construction: both merge all member requests into one flat instance
    axis under a single launch-level key, so results are deterministic for
    a fixed submission set but NOT composition-independent, and per-request
    ``key=`` values are unused there (see DESIGN.md §11/§12).
    """

    def __init__(
        self,
        graph: Optional[CSRGraph] = None,
        *,
        partitions: Optional[List[RangePartition]] = None,
        total_vertices: Optional[int] = None,
        max_degree: Optional[int] = None,
        method: str = "its_brs",
        backend: bk.Backend = "auto",
        config: Optional[ServiceConfig] = None,
        key: Optional[jax.Array] = None,
        oom_memory_capacity: int = 2,
        oom_num_streams: int = 2,
        oom_chunk: int = 1024,
        mesh: Optional[Mesh] = None,
        placement: Optional[str] = None,
        shard_axis: str = "data",
    ):
        if (graph is None) == (partitions is None):
            raise ValueError(
                "pass exactly one of graph= (in-memory / sharded) or "
                "partitions= (out-of-memory)"
            )
        if placement is None:
            placement = "oom" if partitions is not None else (
                "sharded" if mesh is not None else "memory"
            )
        if placement not in ("memory", "oom", "sharded"):
            raise ValueError(f"unknown placement {placement!r}")
        if placement == "sharded" and (graph is None or mesh is None):
            raise ValueError('placement="sharded" needs graph= and mesh=')
        if placement != "sharded" and mesh is not None:
            # a mesh the service would silently never use means the caller
            # configured one execution path and got another
            raise ValueError(
                f'mesh= is only meaningful with placement="sharded", '
                f"got placement={placement!r}"
            )
        if placement == "oom" and partitions is None:
            raise ValueError('placement="oom" needs partitions=')
        if placement == "memory" and graph is None:
            raise ValueError('placement="memory" needs graph=')
        self.placement = placement
        self.mesh = mesh
        self.shard_axis = shard_axis
        self.graph = graph
        self.partitions = partitions
        if graph is not None:
            self.num_vertices = graph.num_vertices
            self.max_degree = int(max_degree or graph.max_degree())
        else:
            if total_vertices is None:
                raise ValueError("partitions= needs total_vertices=")
            self.num_vertices = int(total_vertices)
            if max_degree is None:
                max_degree = max(
                    (int(np.diff(p.indptr).max()) for p in partitions if p.num_vertices),
                    default=1,
                )
            self.max_degree = int(max_degree)
        self.method = method
        self.backend = backend
        self.config = config or ServiceConfig()
        self._queue = RequestQueue(self.config)
        base = key if key is not None else jax.random.PRNGKey(0)
        # disjoint streams: per-request keys fold request ids into _key,
        # OOM partition-scheduler passes fold launch counters into _oom_key
        self._key, self._oom_key = jax.random.split(base)
        self._next_id = 0
        self._oom_launch = 0
        self._oom_kwargs = dict(
            memory_capacity=oom_memory_capacity,
            num_streams=oom_num_streams,
            chunk=oom_chunk,
        )
        self.stats = ServiceStats()

    # -- intake ------------------------------------------------------------

    @property
    def pending(self) -> int:
        return len(self._queue)

    def submit(
        self,
        seeds,
        *,
        depth: int,
        spec: SamplingSpec,
        key: Optional[jax.Array] = None,
    ) -> int:
        """Admit one request; returns its id (the ``drain()`` result key).

        ``seeds``: (n,) start vertices in ``[0, num_vertices)``; ``depth``:
        walk length in steps; ``spec``: the request's sampling algorithm;
        ``key``: the request's PRNG stream (in-memory serving only — the
        OOM drain keys per launch, not per request).
        Raises :class:`~repro.serve.queue.AdmissionError` on malformed or
        over-capacity requests — admission happens HERE, not at drain time,
        so callers get back-pressure while they can still shed load.
        """
        req = self._make_request(seeds, depth=depth, spec=spec, key=key)
        self._queue.submit(req)  # may raise — then the id is NOT consumed
        self._next_id += 1
        return req.request_id

    def _make_request(
        self, seeds, *, depth: int, spec: SamplingSpec,
        key: Optional[jax.Array] = None,
    ) -> SamplingRequest:
        """Validate seeds and build the next :class:`SamplingRequest` —
        shared by batch ``submit`` and the streaming front door
        (``serve.stream``), so both allocate ids and per-request keys from
        the same sequence.  Does NOT consume the id: callers bump
        ``_next_id`` only after their own admission checks pass."""
        seeds = np.asarray(seeds)
        if seeds.ndim == 1 and seeds.size and (
            seeds.min() < 0 or seeds.max() >= self.num_vertices
        ):
            raise AdmissionError(
                f"seeds outside [0, num_vertices={self.num_vertices}): "
                f"min={seeds.min()} max={seeds.max()}"
            )
        rid = self._next_id
        return SamplingRequest(
            request_id=rid,
            # always copy: the queue holds the array past this call, and a
            # caller mutating its buffer would bypass the range check above
            seeds=np.array(seeds, dtype=np.int32),
            depth=int(depth),
            spec=spec,
            key=key if key is not None else jax.random.fold_in(self._key, rid),
        )

    def prewarm(
        self,
        spec: SamplingSpec,
        *,
        depth: Optional[int] = None,
        width: Optional[int] = None,
        requests: int = 1,
    ) -> tuple:
        """Warm ``spec``'s serving path NOW, so no live request pays it.

        Two independent layers, covering every placement:

        1. **Selection plan** (memory and sharded placements, flat-bias
           specs): build the adaptive method plan and its alias/rejection
           tables (DESIGN.md §13).  They live in the per-(graph, bias fn)
           cache of ``core.methods`` — the sharded drain reuses the
           full-graph plan, so one build serves both placements.  OOM
           tables are partition-local and built at first residency inside
           the drain; the compile warm below triggers exactly that.
        2. **Launch trace** (all placements): when ``depth`` is given, run
           one throwaway launch at the padded geometry a request of
           ``(width, depth)`` would occupy — ``width`` defaults to the
           smallest walker bucket; ``requests`` sizes the fused request
           axis on the memory placement — through the placement's real
           engine entry point, so the jit trace (and, for OOM, the lazy
           partition tables) exist before traffic arrives.  Without it, a
           first streaming request on the partitioned or sharded paths
           eats a multi-second compile inside its latency budget.

        The warm launch uses a fixed throwaway key and does not advance
        the service's request-id or launch-key sequences, so prewarming
        never changes what any subsequent request samples.  Returns the
        per-cohort method plan (empty when there is nothing to plan).
        """
        program = tp.lower(spec)
        methods: tuple = ()
        if self.placement != "oom" and program.mode == "flat":
            methods, _tables = flat_method_plan(self.graph, program, self.max_degree)
            self.stats.plans_prewarmed += 1
        if depth is not None:
            self._prewarm_launch(spec, depth=depth, width=width, requests=requests)
        if self.placement not in self.stats.prewarmed_placements:
            self.stats.prewarmed_placements += (self.placement,)
        return methods

    def _prewarm_launch(
        self, spec: SamplingSpec, *, depth: int, width: Optional[int],
        requests: int,
    ) -> None:
        """One throwaway launch at the bucketed geometry, placement-routed.

        Seeds are vertex 0 plus ``-1`` padding (an all-padding launch would
        terminate before the OOM/sharded drain bodies ever compile); the
        key is a constant, and no service stats/counters move, so the warm
        launch is invisible to serving semantics and benchmarks alike.
        Under a running profiler it records the engine's ``csaw.walk.*``
        spans like any launch, and no ``csaw.serve.*`` span.
        """
        cfg = self.config
        depth_b = _pow2_bucket(int(depth), cfg.min_depth_bucket)
        width_b = _pow2_bucket(int(width or 1), cfg.min_walker_bucket)
        key = jax.random.PRNGKey(0)
        if self.placement == "memory":
            r_pad = _pow2_bucket(max(int(requests), 1), 1)
            seeds = np.full((r_pad, width_b), -1, np.int32)
            seeds[:, 0] = 0
            keys = jnp.stack([key] * r_pad)
            random_walk_segments(
                self.graph, jnp.asarray(seeds), keys, depth=depth_b,
                spec=spec, max_degree=self.max_degree, method=self.method,
                backend=self.backend,
            ).walks.block_until_ready()
            return
        # OOM / sharded: cohorts pack one flat instance axis (128-multiple,
        # mirroring _pack_flat) with per-instance depth limits
        i_pad = _pow2_bucket(width_b * max(int(requests), 1), 128)
        seeds = np.full((i_pad,), -1, np.int32)
        seeds[0] = 0
        limits = np.zeros((i_pad,), np.int32)
        limits[0] = depth_b
        if self.placement == "oom":
            oom_random_walk(
                self.partitions, self.num_vertices, seeds, key,
                depth=depth_b, spec=spec, max_degree=self.max_degree,
                backend=self.backend, depth_limits=limits, **self._oom_kwargs,
            )
        else:
            jax.block_until_ready(sharded_random_walk(
                self.mesh, self.graph, seeds, key, depth=depth_b, spec=spec,
                max_degree=self.max_degree, axis=self.shard_axis,
                backend=self.backend, depth_limits=limits,
            ).walks)

    # -- serving -----------------------------------------------------------

    def drain(self) -> Dict[int, RequestResult]:
        """Serve every pending request; returns ``{request_id: result}``.

        If a cohort launch fails, its requests and every not-yet-served
        cohort's are re-queued and a :class:`DrainError` carrying the
        already-completed results is raised — no admitted request is ever
        silently dropped.
        """
        out: Dict[int, RequestResult] = {}
        cohorts = self._queue.take_cohorts(bucket_by_shape=self.placement == "memory")
        for i, cohort in enumerate(cohorts):
            try:
                self._run_cohort(cohort, out)
            except Exception as e:
                # _run_sequential may have partially filled `out` for this
                # cohort; don't serve those twice on retry
                for c in cohorts[i:]:
                    for req in c.requests:
                        if req.request_id not in out:
                            self._queue.submit(req)  # fits: was admitted before
                raise DrainError(
                    f"cohort launch failed ({type(e).__name__}: {e}); "
                    f"unserved requests re-queued, {len(out)} completed "
                    f"results on .completed",
                    out,
                ) from e
        return out

    def _run_cohort(self, cohort: Cohort, out: Dict[int, RequestResult]) -> None:
        """Launch one cohort through this service's placement (the single
        dispatch point ``drain()`` and the streaming scheduler share) and
        account it.  On failure, ``out`` holds whatever the launch delivered
        before raising (only the sequential path delivers partially)."""
        if self.placement == "oom":
            self._run_oom(cohort, out)
        elif self.placement == "sharded":
            self._run_sharded(cohort, out)
        elif self.config.fuse:
            self._run_fused(cohort, out)
        else:
            self._run_sequential(cohort, out)
        self.stats.requests_served += len(cohort.requests)
        self.stats.walkers_served += cohort.num_walkers

    def _pack(self, cohort: Cohort) -> tuple:
        """Pad cohort members into the launch geometry: ``(R_pad, W)`` seeds
        (rows beyond ``R`` are all--1 ghosts so the request axis is also
        bucketed) and ``R_pad`` stacked keys."""
        reqs = cohort.requests
        r_pad = _pow2_bucket(len(reqs), 1)
        seeds = np.full((r_pad, cohort.width), -1, np.int32)
        for i, req in enumerate(reqs):
            seeds[i, : req.num_walkers] = req.seeds
        keys = jnp.stack(
            [r.key for r in reqs]
            + [jax.random.PRNGKey(0)] * (r_pad - len(reqs))
        )
        return jnp.asarray(seeds), keys, r_pad

    def _run_fused(self, cohort: Cohort, out: Dict[int, RequestResult]) -> None:
        with obs.span("serve.pack"):
            seeds, keys, r_pad = self._pack(cohort)
        with obs.span("serve.dispatch"):
            res = random_walk_segments(
                self.graph, seeds, keys, depth=cohort.depth,
                spec=cohort.requests[0].spec, max_degree=self.max_degree,
                method=self.method, backend=self.backend,
            )
        with obs.span("serve.fetch"):
            walks = np.asarray(res.walks)
        with obs.span("serve.slice"):
            for i, req in enumerate(cohort.requests):
                out[req.request_id] = _slice_result(req, walks[i])
        self.stats.launches += 1
        self.stats.padded_walker_slots += r_pad * cohort.width - cohort.num_walkers

    def _run_sequential(self, cohort: Cohort, out: Dict[int, RequestResult]) -> None:
        """One launch per request, same padded geometry as the fused path —
        the bit-identical baseline the benchmark compares against."""
        for req in cohort.requests:
            with obs.span("serve.pack"):
                row = np.full((cohort.width,), -1, np.int32)
                row[: req.num_walkers] = req.seeds
            with obs.span("serve.dispatch"):
                res = random_walk(
                    self.graph, jnp.asarray(row), req.key, depth=cohort.depth,
                    spec=req.spec, max_degree=self.max_degree,
                    method=self.method, backend=self.backend,
                )
            with obs.span("serve.fetch"):
                walks = np.asarray(res.walks)
            with obs.span("serve.slice"):
                out[req.request_id] = _slice_result(req, walks)
            self.stats.launches += 1
            self.stats.padded_walker_slots += cohort.width - req.num_walkers

    def _pack_flat(self, cohort: Cohort) -> tuple:
        """Merge a cohort's requests into one flat instance axis: ``-1``-
        padded seeds and per-instance ``depth_limits`` (power-of-two
        instance count so recurring cohort shapes reuse the drain trace),
        plus ``(request, row offset)`` spans for unpacking and the
        launch-level key (one per partition-scheduling pass — the OOM and
        sharded drains key per launch, not per request)."""
        total = cohort.num_walkers
        i_pad = _pow2_bucket(total, 128)
        seeds = np.full((i_pad,), -1, np.int32)
        limits = np.zeros((i_pad,), np.int32)
        spans = []
        at = 0
        for req in cohort.requests:
            n = req.num_walkers
            seeds[at : at + n] = req.seeds
            limits[at : at + n] = req.depth
            spans.append((req, at))
            at += n
        self._oom_launch += 1
        key = jax.random.fold_in(self._oom_key, self._oom_launch)
        return seeds, limits, spans, key, i_pad - total

    @staticmethod
    def _unpack_flat(spans, walks: np.ndarray, out: Dict[int, RequestResult]) -> None:
        for req, at in spans:
            out[req.request_id] = _slice_result(req, walks[at : at + req.num_walkers])

    def _run_oom(self, cohort: Cohort, out: Dict[int, RequestResult]) -> None:
        """Route one cohort through the §V frontier-queue drain: member
        requests merge into one flat instance axis (per-instance
        ``depth_limits`` let mixed walk lengths share the partition
        schedule)."""
        with obs.span("serve.pack"):
            seeds, limits, spans, key, ghost = self._pack_flat(cohort)
        # the drain returns host arrays: its dispatch includes the fetch
        with obs.span("serve.dispatch"):
            walks, _stats = oom_random_walk(
                self.partitions, self.num_vertices, seeds, key,
                depth=cohort.depth, spec=cohort.requests[0].spec,
                max_degree=self.max_degree, backend=self.backend,
                depth_limits=limits, **self._oom_kwargs,
            )
        with obs.span("serve.slice"):
            self._unpack_flat(spans, walks, out)
        self.stats.oom_launches += 1
        self.stats.padded_walker_slots += ghost

    def _run_sharded(self, cohort: Cohort, out: Dict[int, RequestResult]) -> None:
        """Route one cohort through the owner-routed mesh drain
        (``repro.shard``, DESIGN.md §12): same flat-instance-axis packing
        and launch-key contract as the OOM path."""
        with obs.span("serve.pack"):
            seeds, limits, spans, key, ghost = self._pack_flat(cohort)
        with obs.span("serve.dispatch"):
            res = sharded_random_walk(
                self.mesh, self.graph, seeds, key,
                depth=cohort.depth, spec=cohort.requests[0].spec,
                max_degree=self.max_degree, axis=self.shard_axis,
                backend=self.backend, depth_limits=limits,
            )
        with obs.span("serve.fetch"):
            walks = np.asarray(res.walks)
        with obs.span("serve.slice"):
            self._unpack_flat(spans, walks, out)
        self.stats.sharded_launches += 1
        self.stats.padded_walker_slots += ghost

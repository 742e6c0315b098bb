"""C-SAW sampling engines (paper Fig. 2(b) MAIN loop, §IV).

Two drivers, both batched over thousands of concurrent instances
(the paper's inter-warp parallelism; here: leading array dims):

  - ``random_walk``       — NeighborSize=1 path-per-instance (Table I left).
  - ``traversal_sample``  — frontier-pool sampling (neighbor / layer /
                            forest-fire / snowball / MDRW).

Both are jit-compiled, use counted RNG, fixed shapes, masked semantics, and
route all bias-based selection through the backend dispatcher
(``core.backend``), so they run unchanged under vmap / shard_map / the
partition scheduler.  Walk steps dispatch on the spec's lowered transition
program (``core.transition``, DESIGN.md §10): flat- and window-bias
programs run the degree-bucketed scheduler on BOTH backends —
``backend="pallas"`` swaps in the fused Pallas kernels, ``"reference"``
their bit-identical pure-jnp mirrors — and declarative epilogues fuse into
one shared post-select step; only opaque programs keep the dense gather.
``"auto"`` picks per device (DESIGN.md §6).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.api import EdgeCtx, SamplingSpec, VertexCtx
from repro.core import backend as bk
from repro.core import methods as mt
from repro.core import select as sel
from repro.core import transition as tp
from repro.graph.csr import CSRGraph, neighbors_padded


def _degree(graph: CSRGraph, v: jax.Array) -> jax.Array:
    safe = jnp.maximum(v, 0)
    return jnp.where(v >= 0, graph.indptr[safe + 1] - graph.indptr[safe], 0)


def _edge_ctx(graph: CSRGraph, v, prev, depth, max_degree, needs_prev_neighbors,
              *, partition=None):
    """Build the EDGEBIAS context for a batch of frontier vertices.

    With ``partition`` (a ``graph.partition.DevicePartition``) set, ``graph``
    is its compact local-id CSR with a phantom sink row (DESIGN.md §8): row
    lookups happen on localized ids while the context exposes global ids;
    neighbors outside the partition localize to the phantom row, so their
    ``deg_u`` is 0 — the §V semantics where only partition-resident edge
    data informs the bias.
    """
    local = partition is not None
    if local:
        indices_global = partition.indices_global
        vq, pq = partition.localize(v), partition.localize(prev)
    else:
        vq, pq = jnp.maximum(v, 0), jnp.maximum(prev, 0)
    nbrs, wts, mask = neighbors_padded(graph, vq, max_degree)
    nbrs_row = nbrs  # row-lookup ids (local in partition mode)
    if local:
        eidx = graph.indptr[vq][..., None] + jnp.arange(max_degree, dtype=jnp.int32)
        nbrs = jnp.where(mask, indices_global[jnp.where(mask, eidx, 0)], -1)
    nbrs = jnp.where((v >= 0)[..., None] & mask, nbrs, -1)
    mask = nbrs >= 0
    ipn = None
    if needs_prev_neighbors:
        if local:
            _, _, pmask = neighbors_padded(graph, pq, max_degree)
            peidx = graph.indptr[pq][..., None] + jnp.arange(max_degree, dtype=jnp.int32)
            pnbrs = jnp.where(pmask, indices_global[jnp.where(pmask, peidx, 0)], -2)
        else:
            pnbrs, _, pmask = neighbors_padded(graph, pq, max_degree)
        pnbrs = jnp.where((prev >= 0)[..., None] & pmask & (pnbrs >= 0), pnbrs, -2)
        # membership: u in N(prev) — O(D^2) lane-parallel compare (global ids)
        ipn = jnp.any(nbrs[..., :, None] == pnbrs[..., None, :], axis=-1) & mask
    deg_u = _degree(graph, nbrs_row) if local else _degree(graph, nbrs)
    return (
        EdgeCtx(
            v=v,
            u=nbrs,
            weight=wts,
            deg_v=_degree(graph, vq if local else v),
            deg_u=jnp.where(mask, deg_u, 0),
            prev=prev,
            is_prev_neighbor=ipn,
            depth=depth,
        ),
        mask,
    )


def _select_epilogue(key, graph, program, spec, v, prev, depth, u, vq, row_of, home):
    """Fused post-select step shared by the flat and window fast paths:
    build the minimal D=1 EdgeCtx of the selected edge and run the lowered
    epilogue (``transition.apply_epilogue`` — identity/MH/teleport fuse into
    a few jnp ops; opaque falls back to ``spec.update``).  The minimal ctx
    carries a unit placeholder ``weight`` (fast-path contract,
    api.flat_edge_bias)."""
    alive = u >= 0
    ctx = EdgeCtx(
        v=v,
        u=u[..., None],
        weight=jnp.ones(u.shape + (1,), jnp.float32),
        deg_v=_degree(graph, vq),
        deg_u=_degree(graph, u if row_of is None else row_of(u))[..., None],
        prev=prev,
        is_prev_neighbor=None,
        depth=depth,
    )
    nxt = tp.apply_epilogue(jax.random.fold_in(key, 2), program, spec, ctx, u, home)
    return jnp.where(alive, nxt, -1)


def walk_flat_transition(key: jax.Array, graph: CSRGraph, indices_out: jax.Array,
                         flat_bias: jax.Array, padded, v: jax.Array, prev: jax.Array,
                         depth, spec: SamplingSpec, be: str, *,
                         buckets: tuple, use_chunked: bool,
                         max_degree: int | None = None, row_of=None,
                         program: tp.TransitionProgram | None = None,
                         home: jax.Array | None = None,
                         methods: tuple | None = None,
                         tables=None) -> jax.Array:
    """SELECT + epilogue of one flat-bias walk step (shared by the in-memory
    engine and the §V out-of-memory drain loop).

    Dispatches the degree-bucketed scheduler (DESIGN.md §6): Pallas kernels
    under ``be="pallas"``, the bit-identical pure-jnp mirror under
    ``"reference"``.  ``row_of`` maps global vertex ids to ``graph``'s
    row-lookup ids (identity in-memory; partition localization in the OOM
    drain); ``indices_out`` holds the ids the walk emits (global).  The
    post-select update runs the spec's lowered transition-program epilogue.

    ``methods``/``tables`` (from ``core.methods.plan_for_graph``) engage the
    adaptive per-bucket selection runtime (DESIGN.md §13); an absent or
    all-ITS plan keeps the legacy kernel/mirror pair — bit-for-bit the
    pre-adaptive walks.
    """
    program = tp.lower(spec) if program is None else program
    vq = v if row_of is None else row_of(v)
    kf = jax.random.fold_in(key, 1)
    if methods is not None and not mt.is_trivial(methods):
        u = bk.walk_step_adaptive(kf, graph.indptr, indices_out, flat_bias,
                                  padded, vq, buckets=buckets,
                                  use_chunked=use_chunked, methods=methods,
                                  tables=tables, backend=be,
                                  max_degree=max_degree)
    elif be == "pallas":
        u = bk.walk_step_bucketed(kf, graph.indptr, indices_out, flat_bias,
                                  padded, vq, buckets=buckets, use_chunked=use_chunked)
    else:
        u = bk.walk_step_flat_reference(kf, graph.indptr, indices_out, flat_bias,
                                        padded, vq, buckets=buckets,
                                        use_chunked=use_chunked, max_degree=max_degree)
    return _select_epilogue(key, graph, program, spec, v, prev, depth, u, vq, row_of, home)


def _is_prev_neighbor_window(indptr, ids_sorted, prow, prev, u, mask, *, steps: int):
    """Membership of window candidates in N(prev): per-candidate lower-bound
    binary search over prev's sorted CSR row (``csr_from_edges`` sorts rows;
    partition localization preserves the order).  O(D·log deg_prev) — the
    windowed replacement for the dense path's O(D²) lane compare — and exact
    for ANY prev degree (the dense path truncates N(prev) at max_degree).

    prow: (W,) row-lookup ids of prev (localized in partition mode);
    u: (W, D) candidate GLOBAL ids; returns (W, D) bool.

    ``steps`` is sized from the caller's max-degree bound.  If that bound is
    understated, the search may not converge on longer prev rows — which can
    only produce false NEGATIVES (``lo`` always lands inside the row, so a
    positive requires a genuine element match): the same truncation-class
    degradation as the dense path's ``neighbors_padded`` cap on N(prev).
    """
    e = ids_sorted.shape[0]
    lo = jnp.broadcast_to(indptr[prow][..., None], u.shape).astype(jnp.int32)
    hi_row = indptr[prow + 1][..., None]
    hi = jnp.broadcast_to(hi_row, u.shape).astype(jnp.int32)

    def body(_, lohi):
        lo, hi = lohi
        open_ = lo < hi
        mid = (lo + hi) // 2
        vmid = ids_sorted[jnp.clip(mid, 0, e - 1)]
        go_right = vmid < u
        lo = jnp.where(open_ & go_right, mid + 1, lo)
        hi = jnp.where(open_ & ~go_right, mid, hi)
        return lo, hi

    lo, _ = jax.lax.fori_loop(0, steps, body, (lo, hi))
    found = (lo < hi_row) & (ids_sorted[jnp.clip(lo, 0, e - 1)] == u)
    return found & mask & (prev >= 0)[..., None] & (u >= 0)


def _walker_rows(rows, *state):
    """Per-walker state arrays restricted to the walkers ``rows`` (all when
    None); scalars (a step index shared by every walker) pass through."""
    if rows is None:
        return state
    return tuple(x if jnp.ndim(x) == 0 else x[rows] for x in state)


def _window_bias_fn(graph: CSRGraph, program: tp.TransitionProgram,
                    v, prev, depth, row_of, ids_sorted,
                    max_degree: int | None = None):
    """Close the spec's dynamic edge-bias hook over the walker state so the
    backend scheduler can evaluate it on any gathered edge window.

    The returned ``bias_of(u, w, mask, eidx=None, rows=None)`` builds a
    window EdgeCtx for the walkers ``rows`` (all when None) — candidate ids/weights straight off the CSR window, degrees by
    row lookup (localized in partition mode, so non-resident neighbors read
    deg 0 off the phantom row, §V semantics), prev-membership by binary
    search — and runs ``WindowBias.fn`` on it.  ``eidx`` (the window's edge
    positions in the caller's CSR edge arrays) is accepted for signature
    compatibility with the sharded drain's carried-state hook
    (``shard.walk._carried_window_bias`` resolves ``deg_u`` through a
    per-edge degree lane instead of row lookups) and ignored here.
    """
    wb = program.bias
    assert isinstance(wb, tp.WindowBias), wb
    vq = v if row_of is None else row_of(v)
    pq = jnp.maximum(prev, 0) if row_of is None else row_of(prev)
    deg_v = _degree(graph, vq)
    # lower-bound halvings: enough for the longest row (``max_degree`` is the
    # true max row degree on this path), else for the whole edge array
    bound = int(ids_sorted.shape[0]) if max_degree is None else max(max_degree, 1)
    bs_steps = min(32, max(1, bound.bit_length()))

    def bias_of(u, w, mask, eidx=None, rows=None):
        with obs.scope("walk.window_hook"):
            del eidx  # in-memory/OOM: degrees come from row lookups below
            if wb.needs_deg_u:
                uq = u if row_of is None else row_of(u)
                deg_u = jnp.where(mask, _degree(graph, uq), 0)
            else:  # declared unused — skip two window-wide indptr gathers
                deg_u = jnp.zeros(u.shape, jnp.int32)
            v_, prev_, pq_, deg_v_, depth_ = _walker_rows(rows, v, prev, pq, deg_v, depth)
            ipn = None
            if wb.needs_prev_neighbors:
                ipn = _is_prev_neighbor_window(
                    graph.indptr, ids_sorted, pq_, prev_, u, mask, steps=bs_steps
                )
            ctx = EdgeCtx(
                v=v_, u=u, weight=w, deg_v=deg_v_, deg_u=deg_u, prev=prev_,
                is_prev_neighbor=ipn, depth=depth_,
            )
            return wb.fn(ctx)

    return bias_of


def window_envelope(bias: tp.WindowBias, weights) -> jax.Array:
    """Upper bound of every bias a ``max_ratio`` hook returns on ``weights``
    (the rejection envelope of every walker of a window-bias step)."""
    return jnp.float32(bias.max_ratio) * jnp.maximum(jnp.max(weights), 0.0)


def walk_window_transition(key: jax.Array, graph: CSRGraph, indices_out: jax.Array,
                           padded, v: jax.Array, prev: jax.Array,
                           depth, spec: SamplingSpec, program: tp.TransitionProgram,
                           be: str, *, buckets: tuple, use_chunked: bool,
                           max_degree: int | None = None, row_of=None,
                           home: jax.Array | None = None) -> jax.Array:
    """SELECT + epilogue of one window-bias (dynamic) walk step — the
    transition-program path that puts node2vec-class specs on the
    degree-bucketed scheduler (shared by the in-memory engine and the §V
    out-of-memory drain loop).  ``padded`` maps bucket segments to padded
    (ids, WEIGHTS) arrays.  A hook that declares ``max_ratio`` draws by
    counted rejection first; the dynamic hook is evaluated per bucket on the
    kernel's gathered windows, chunk-wise on the huge-degree tail, for the
    walkers still without a pick."""
    vq = v if row_of is None else row_of(v)
    kf = jax.random.fold_in(key, 1)
    bias_of = _window_bias_fn(
        graph, program, v, prev, depth, row_of, indices_out, max_degree
    )
    envelope = None
    if program.bias.max_ratio is not None:
        envelope = window_envelope(program.bias, graph.weights)
    u = bk.walk_step_bucketed_window(
        kf, graph.indptr, indices_out, graph.weights, padded, vq, bias_of,
        buckets=buckets, use_chunked=use_chunked, backend=be, envelope=envelope,
    )
    return _select_epilogue(key, graph, program, spec, v, prev, depth, u, vq, row_of, home)


def walk_gather_transition(key: jax.Array, ctx: EdgeCtx, mask: jax.Array,
                           spec: SamplingSpec, be: str,
                           program: tp.TransitionProgram | None = None,
                           home: jax.Array | None = None) -> jax.Array:
    """SELECT + epilogue of one gather-based walk step — the dense
    full-context fallback for opaque transition programs (shared by the
    in-memory engine and the §V out-of-memory drain loop).

    Dispatches the ITS draw through the backend (bit-identical across
    backends for k=1, DESIGN.md §4/§6); returns next vertices, -1 for dead
    ends and already-finished walkers.
    """
    program = tp.lower(spec) if program is None else program
    biases = jnp.where(mask, spec.edge_bias(ctx), 0.0)
    idx = bk.select_with_replacement(
        jax.random.fold_in(key, 1), biases, mask, 1, backend=be
    )[..., 0]
    u = jnp.take_along_axis(ctx.u, idx[..., None], axis=-1)[..., 0]
    alive = (ctx.v >= 0) & jnp.any(mask, axis=-1)
    u = jnp.where(alive, u, -1)
    nxt = tp.apply_epilogue(jax.random.fold_in(key, 2), program, spec, ctx, u, home)
    return jnp.where(alive, nxt, -1)


class WalkResult(NamedTuple):
    walks: jax.Array  # (I, depth+1) int32, -1 after termination
    lengths: jax.Array  # (I,) realized lengths (# vertices)
    sampled_edges: jax.Array  # () total sampled edges (for SEPS)
    #: optional host-side execution counters; only the mesh-sharded walk
    #: fills it (exchange/hub-hit telemetry, DESIGN.md §14) — engines that
    #: construct results inside jit leave the default None (an empty pytree
    #: leaf, so shard_map/vmap out-specs written for the 3-field layout
    #: keep working unchanged)
    stats: Optional[dict] = None


def flat_method_plan(
    graph: CSRGraph,
    program: tp.TransitionProgram,
    max_degree: int,
) -> tuple[tuple, mt.MethodTables]:
    """Host-side adaptive selection plan for a flat-bias program.

    Returns ``(methods, tables)`` for ``walk_flat_transition``: the
    cost-model pick per degree cohort plus the prebuilt tables it needs
    (cached per (graph, bias fn) — ``core.methods``).  Degrades to the
    legacy all-ITS plan when planning is impossible or pointless: non-flat
    programs (empty plan), a forced ``method="its"``, or a TRACED graph
    (``random_walk`` under vmap/make_jaxpr cannot inspect concrete bucket
    stats — those callers keep the pre-adaptive behavior).
    """
    if program.mode != "flat":
        return (), mt.EMPTY_TABLES
    buckets, use_chunked = bk.walk_bucket_plan(max_degree)
    n = len(buckets) + (1 if use_chunked else 0)
    if program.method == "its" or isinstance(graph.indices, jax.core.Tracer):
        return ("its",) * n, mt.EMPTY_TABLES
    override = None if program.method == "auto" else program.method
    return mt.plan_for_graph(
        graph, program.bias.fn, buckets=buckets, use_chunked=use_chunked,
        override=override,
    )


def random_walk(
    graph: CSRGraph,
    seeds: jax.Array,
    key: jax.Array,
    *,
    depth: int,
    spec: SamplingSpec,
    max_degree: int,
    method: str = "its_brs",
    backend: bk.Backend = "auto",
) -> WalkResult:
    """Run one random-walk step per scan iteration for all instances.

    Dispatch is on the spec's lowered transition program (DESIGN.md §10):
    flat-bias programs run the degree-bucketed scheduler straight off the
    flat CSR arrays, window-bias programs (node2vec-class dynamic hooks)
    evaluate their hook per degree bucket on the kernel's gathered edge
    windows — on BOTH backends (Pallas kernels vs the bit-identical jnp
    mirrors), so no padded ``(W, max_degree)`` neighbor tensors are ever
    materialized.  Only opaque programs keep the dense full-context gather,
    still dispatching the ITS draw to the selection kernel.

    Flat-bias programs additionally run the adaptive selection runtime
    (DESIGN.md §13): a host-side cost model picks ITS / alias-table /
    rejection per degree cohort (``TransitionProgram.method`` overrides it)
    and the prebuilt tables are cached per (graph, bias), so repeated
    launches reuse them.

    Seeds may be ``-1``: those instances are dead on arrival and emit all--1
    rows (the padding contract the batched service relies on).

    Example — 4 unbiased walks of 3 steps on a toy 4-cycle:

    >>> import jax, jax.numpy as jnp
    >>> from repro.core import algorithms as alg
    >>> from repro.core.engine import random_walk
    >>> from repro.graph import csr_from_edges
    >>> g = csr_from_edges(4, [0, 1, 2, 3], [1, 2, 3, 0], symmetrize=True)
    >>> res = random_walk(g, jnp.array([0, 1, 2, 3]), jax.random.PRNGKey(0),
    ...                   depth=3, spec=alg.deepwalk(), max_degree=2)
    >>> res.walks.shape, int(res.sampled_edges)
    ((4, 4), 12)
    >>> bool(jnp.all(res.lengths == 4))  # no dead ends on a cycle
    True
    """
    with obs.span("walk.plan"):
        sel_methods, tables = flat_method_plan(graph, tp.lower(spec), max_degree)
    with obs.span("walk.dispatch"):
        return _random_walk_impl(
            graph, seeds, key, tables, depth=depth, spec=spec,
            max_degree=max_degree, method=method, backend=backend,
            sel_methods=sel_methods,
        )


@functools.partial(
    jax.jit,
    static_argnames=("depth", "spec", "max_degree", "method", "backend", "sel_methods"),
)
def _random_walk_impl(
    graph: CSRGraph,
    seeds: jax.Array,
    key: jax.Array,
    tables: mt.MethodTables,
    *,
    depth: int,
    spec: SamplingSpec,
    max_degree: int,
    method: str = "its_brs",
    backend: bk.Backend = "auto",
    sel_methods: tuple = (),
) -> WalkResult:
    """Jitted body of :func:`random_walk` — the selection plan
    (``sel_methods``, static) and its tables (dynamic pytree; ``None``
    fields cost nothing) arrive precomputed from the host-side wrapper."""
    num_inst = seeds.shape[0]
    be = bk.resolve_backend(backend)
    program = tp.lower(spec)
    mode = program.mode
    with obs.scope("walk.graph_prep"):
        if mode == "flat":
            flat_bias = program.bias.fn(graph)
            buckets, use_chunked = bk.walk_bucket_plan(max_degree)
            padded = bk.pad_walk_csr(graph.indices, flat_bias, buckets)
        elif mode == "window":
            # the window path treats max_degree as the TRUE max row degree
            # (exact bucket plan; chunked tail above the top segment)
            buckets, use_chunked = bk.walk_bucket_plan_window(max_degree)
            padded = bk.pad_walk_csr(graph.indices, graph.weights, buckets)
    home = seeds.astype(jnp.int32) if program.carries_home else None

    def step(carry, it):
        cur, prev = carry
        kstep = jax.random.fold_in(key, it)
        if mode == "flat":
            # max_degree stays None: the caller's bound may be understated,
            # and only a TRUE max degree (like the OOM drain computes) may
            # truncate the reference mirror's windows
            nxt = walk_flat_transition(
                kstep, graph, graph.indices, flat_bias, padded, cur, prev, it,
                spec, be, buckets=buckets, use_chunked=use_chunked,
                program=program, home=home, methods=sel_methods or None,
                tables=tables,
            )
        elif mode == "window":
            nxt = walk_window_transition(
                kstep, graph, graph.indices, padded, cur, prev, it, spec,
                program, be, buckets=buckets, use_chunked=use_chunked,
                max_degree=max_degree, home=home,
            )
        else:
            ctx, mask = _edge_ctx(graph, cur, prev, it, max_degree, spec.needs_prev_neighbors)
            nxt = walk_gather_transition(kstep, ctx, mask, spec, be, program, home)
        return (nxt, cur), nxt

    (_, _), path = jax.lax.scan(step, (seeds.astype(jnp.int32), jnp.full((num_inst,), -1, jnp.int32)), jnp.arange(depth))
    walks = jnp.concatenate([seeds[None].astype(jnp.int32), path], axis=0).T  # (I, depth+1)
    lengths = jnp.sum(walks >= 0, axis=-1)
    return WalkResult(walks, lengths, jnp.sum(jnp.maximum(lengths - 1, 0)))


def random_walk_segments(
    graph: CSRGraph,
    seeds: jax.Array,
    keys: jax.Array,
    *,
    depth: int,
    spec: SamplingSpec,
    max_degree: int,
    method: str = "its_brs",
    backend: bk.Backend = "auto",
) -> WalkResult:
    """Multi-request segment path: R independent requests, ONE device launch.

    The batched serving layer (``repro.serve``) packs concurrent user
    requests that share a lowered transition program into a ``(R, W)`` seed
    matrix — one row per request, rows padded with ``-1`` to the cohort's
    walker width — and runs them all in a single fused launch.  Each row
    carries its own PRNG key (``keys``: R stacked keys), so row ``r`` of the
    result is bit-identical to the standalone call
    ``random_walk(graph, seeds[r], keys[r], ...)`` on either backend: the
    fused launch is a pure batching transform (``vmap`` over the request
    axis), never a semantic one.  Requests are isolated by construction —
    no RNG stream, carry state, or bias evaluation crosses rows.

    Returns a :class:`WalkResult` with a leading request axis: ``walks``
    ``(R, W, depth+1)``, ``lengths`` ``(R, W)``, ``sampled_edges`` ``(R,)``.

    >>> import jax, jax.numpy as jnp
    >>> from repro.core import algorithms as alg
    >>> from repro.core.engine import random_walk, random_walk_segments
    >>> from repro.graph import csr_from_edges
    >>> g = csr_from_edges(4, [0, 1, 2, 3], [1, 2, 3, 0], symmetrize=True)
    >>> seeds = jnp.array([[0, 1, -1, -1],   # request 0: 2 walkers (padded)
    ...                    [2, 3, 1, 0]])    # request 1: 4 walkers
    >>> keys = jax.vmap(jax.random.fold_in, (None, 0))(
    ...     jax.random.PRNGKey(7), jnp.arange(2))
    >>> fused = random_walk_segments(g, seeds, keys, depth=3,
    ...                              spec=alg.deepwalk(), max_degree=2)
    >>> solo = random_walk(g, seeds[1], keys[1], depth=3,
    ...                    spec=alg.deepwalk(), max_degree=2)
    >>> bool(jnp.array_equal(fused.walks[1], solo.walks))
    True
    """
    with obs.span("walk.plan"):
        sel_methods, tables = flat_method_plan(graph, tp.lower(spec), max_degree)
    with obs.span("walk.dispatch"):
        return _random_walk_segments(
            graph, seeds, keys, tables, depth=depth, spec=spec, max_degree=max_degree,
            method=method, backend=backend, sel_methods=sel_methods,
        )


@functools.partial(
    jax.jit,
    static_argnames=("depth", "spec", "max_degree", "method", "backend", "sel_methods"),
)
def _random_walk_segments(graph, seeds, keys, tables, *, depth, spec, max_degree,
                          method, backend, sel_methods):
    # the OUTER jit is what makes fused serving cheap: a jitted callee
    # invoked under vmap is traced inline (no cache), so without this
    # wrapper every fused launch would re-trace the walk per call.  The
    # selection plan is computed ONCE by the public wrapper (vmapping the
    # public random_walk would hand its planner a traced graph); tables are
    # closed over, i.e. broadcast across the request axis.
    inner = functools.partial(
        _random_walk_impl, depth=depth, spec=spec, max_degree=max_degree,
        method=method, backend=backend, sel_methods=sel_methods,
    )
    return jax.vmap(lambda s, k: inner(graph, s, k, tables))(seeds, keys)


class SampleResult(NamedTuple):
    edges_src: jax.Array  # (I, cap) int32 sampled edge sources (-1 pad)
    edges_dst: jax.Array  # (I, cap) int32 sampled edge dests
    num_edges: jax.Array  # (I,) per-instance sampled edge count
    frontier_pool: jax.Array  # (I, C) final pool
    iters: jax.Array  # () total selection retry iterations (Fig. 11)
    searches: jax.Array  # () total CTPS searches (Fig. 12)


@functools.partial(
    jax.jit,
    static_argnames=("depth", "spec", "max_degree", "pool_capacity", "method", "max_vertices", "backend"),
)
def traversal_sample(
    graph: CSRGraph,
    seed_pools: jax.Array,  # (I, S) initial pools, -1 padded
    key: jax.Array,
    *,
    depth: int,
    spec: SamplingSpec,
    max_degree: int,
    pool_capacity: int,
    method: str = "its_brs",
    max_vertices: int = 0,  # >0 enables visited bitmap of that many vertices
    backend: bk.Backend = "auto",
) -> SampleResult:
    """Paper Fig. 2(b) MAIN: iterate SELECT-frontier / GATHER / SELECT-neighbors / UPDATE.

    The depth loop is a single ``jax.lax.scan`` over preallocated edge
    buffers, so trace/compile size is independent of ``depth``.

    Example — 2-hop neighbor sampling from two 1-seed instances on a toy
    4-cycle (every sampled edge is a real graph edge):

    >>> import jax, jax.numpy as jnp
    >>> from repro.core import algorithms as alg
    >>> from repro.core.engine import traversal_sample
    >>> from repro.graph import csr_from_edges
    >>> g = csr_from_edges(4, [0, 1, 2, 3], [1, 2, 3, 0], symmetrize=True)
    >>> res = traversal_sample(g, jnp.array([[0], [2]]), jax.random.PRNGKey(0),
    ...                        depth=2, spec=alg.unbiased_neighbor_sampling(),
    ...                        max_degree=2, pool_capacity=8,
    ...                        max_vertices=g.num_vertices)
    >>> res.edges_src.shape  # (instances, depth * frontier * neighbor)
    (2, 32)
    >>> bool(jnp.all(res.num_edges >= 1))
    True
    """
    num_inst, _ = seed_pools.shape
    be = bk.resolve_backend(backend)
    program = tp.lower(spec)
    fs, ns = spec.frontier_size, spec.neighbor_size
    edges_per_iter = fs * ns if spec.per_vertex else ns
    cap = depth * edges_per_iter
    track = spec.track_visited and max_vertices > 0

    pool0 = jnp.full((num_inst, pool_capacity), -1, jnp.int32)
    pool0 = pool0.at[:, : seed_pools.shape[1]].set(seed_pools.astype(jnp.int32))
    if track:
        visited0 = jnp.zeros((num_inst, max_vertices), bool)
        seed_oh = jax.nn.one_hot(jnp.maximum(seed_pools, 0), max_vertices, dtype=bool)
        visited0 = visited0 | jnp.any(seed_oh & (seed_pools >= 0)[..., None], axis=1)
    else:
        visited0 = jnp.zeros((num_inst, 1), bool)  # inert carry placeholder

    def step(carry, it):
        pool, visited, esrc, edst, ecnt, tot_iters, tot_searches = carry
        kit = jax.random.fold_in(key, it)
        # ---- SELECT frontier from pool (line 4) --------------------------
        pmask = pool >= 0
        vctx = VertexCtx(v=pool, deg=jnp.where(pmask, _degree(graph, pool), 0), depth=it)
        vbias = jnp.where(pmask, spec.vertex_bias(vctx), 0.0)
        fres = bk.select_without_replacement(
            jax.random.fold_in(kit, 0), vbias, pmask, fs, method=method, backend=be
        )
        frontier = jnp.where(
            fres.valid, jnp.take_along_axis(pool, jnp.maximum(fres.indices, 0), axis=-1), -1
        )  # (I, fs)
        tot_iters = tot_iters + jnp.sum(fres.iters)
        tot_searches = tot_searches + jnp.sum(fres.searches)

        # ---- GATHER + EDGEBIAS (lines 5-6) ------------------------------
        ctx, emask = _edge_ctx(graph, frontier, jnp.full_like(frontier, -1), it, max_degree, spec.needs_prev_neighbors)
        ebias = jnp.where(emask, spec.edge_bias(ctx), 0.0)
        if track:
            seen = jnp.take_along_axis(
                visited[:, None, :], jnp.maximum(ctx.u, 0), axis=-1
            ) & (ctx.u >= 0)
            ebias = jnp.where(seen, 0.0, ebias)
            emask = emask & ~seen

        if spec.per_vertex:
            # independent NeighborPool per frontier vertex (neighbor sampling)
            nres = bk.select_without_replacement(
                jax.random.fold_in(kit, 1), ebias, emask, ns, method=method, backend=be
            )
            src = jnp.broadcast_to(frontier[..., None], frontier.shape + (ns,))
            dst = jnp.where(
                nres.valid, jnp.take_along_axis(ctx.u, jnp.maximum(nres.indices, 0), axis=-1), -1
            )
            if spec.burn_prob is not None:
                # forest fire: keep a geometric(p_f) prefix of the ns draws
                g = jax.random.uniform(jax.random.fold_in(kit, 7), dst.shape)
                keep = jnp.cumprod((g < spec.burn_prob).astype(jnp.int32), axis=-1) > 0
                keep = keep | (jnp.arange(ns) == 0)  # burn at least one
                dst = jnp.where(keep, dst, -1)
            src, dst = src.reshape(num_inst, -1), dst.reshape(num_inst, -1)
            if spec.track_visited:
                # sampling-without-replacement across the whole instance:
                # two frontier vertices may draw the same neighbor in the
                # same round (separate NeighborPools) — keep the first.
                eq = dst[..., :, None] == dst[..., None, :]
                both = (dst >= 0)[..., :, None] & (dst >= 0)[..., None, :]
                k_flat = dst.shape[-1]
                tri = jnp.tril(jnp.ones((k_flat, k_flat), bool), -1)
                dup = jnp.any(eq & both & tri, axis=-1)
                dst = jnp.where(dup, -1, dst)
            valid = dst >= 0
            tot_iters = tot_iters + jnp.sum(nres.iters)
            tot_searches = tot_searches + jnp.sum(nres.searches)
        else:
            # pooled NeighborPool over all frontier vertices (layer / MDRW)
            flat_bias = ebias.reshape(num_inst, -1)
            flat_mask = emask.reshape(num_inst, -1)
            flat_u = ctx.u.reshape(num_inst, -1)
            flat_v = jnp.broadcast_to(frontier[..., None], ctx.u.shape).reshape(num_inst, -1)
            nres = bk.select_without_replacement(
                jax.random.fold_in(kit, 1), flat_bias, flat_mask, ns, method=method, backend=be
            )
            gi = jnp.maximum(nres.indices, 0)
            src = jnp.where(nres.valid, jnp.take_along_axis(flat_v, gi, axis=-1), -1)
            dst = jnp.where(nres.valid, jnp.take_along_axis(flat_u, gi, axis=-1), -1)
            valid = dst >= 0
            tot_iters = tot_iters + jnp.sum(nres.iters)
            tot_searches = tot_searches + jnp.sum(nres.searches)

        # ---- record sampled edges (line 8) -------------------------------
        esrc = jax.lax.dynamic_update_slice(esrc, src, (0, it * edges_per_iter))
        edst = jax.lax.dynamic_update_slice(edst, dst, (0, it * edges_per_iter))
        ecnt = ecnt + jnp.sum(valid, axis=-1, dtype=jnp.int32)

        # ---- UPDATE pool (line 7) ----------------------------------------
        ectx_flat = EdgeCtx(
            v=src, u=dst, weight=jnp.ones_like(dst, jnp.float32),
            deg_v=jnp.where(src >= 0, _degree(graph, src), 0),
            deg_u=jnp.where(dst >= 0, _degree(graph, dst), 0),
            prev=jnp.full((num_inst,), -1, jnp.int32), is_prev_neighbor=None, depth=it,
        )
        # UPDATE lowers to the same fused epilogue the walk engines run
        new_v = tp.apply_epilogue(jax.random.fold_in(kit, 2), program, spec, ectx_flat, dst)
        new_v = jnp.where(valid, new_v, -1)
        if track:
            oh = jax.nn.one_hot(jnp.maximum(new_v, 0), max_vertices, dtype=bool)
            visited = visited | jnp.any(oh & (new_v >= 0)[..., None], axis=1)
        if spec.replace_selected:
            # MDRW: drop selected frontier vertices from the pool, insert new.
            drop = jnp.any(pool[..., :, None] == jnp.where(frontier >= 0, frontier, -2)[..., None, :], axis=-1)
            pool = jnp.where(drop, -1, pool)
            pool = _insert_into_pool(pool, new_v)
        elif spec.per_vertex:
            # BFS-style: next pool is exactly the newly sampled layer.
            pool = jnp.full_like(pool, -1)
            pool = _insert_into_pool(pool, new_v)
        else:
            pool = _insert_into_pool(pool, new_v)
        return (pool, visited, esrc, edst, ecnt, tot_iters, tot_searches), None

    init = (
        pool0,
        visited0,
        jnp.full((num_inst, cap), -1, jnp.int32),
        jnp.full((num_inst, cap), -1, jnp.int32),
        jnp.zeros((num_inst,), jnp.int32),
        jnp.zeros((), jnp.int32),
        jnp.zeros((), jnp.int32),
    )
    (pool, _, esrc, edst, ecnt, tot_iters, tot_searches), _ = jax.lax.scan(
        step, init, jnp.arange(depth)
    )
    return SampleResult(esrc, edst, ecnt, pool, tot_iters, tot_searches)


def _insert_into_pool(pool: jax.Array, new_v: jax.Array) -> jax.Array:
    """Insert new vertices into -1 slots (left-compacting both sides).

    Single cumsum-based compaction over the concatenated (pool, new) row:
    surviving pool entries keep their relative order in slots 0..n-1, new
    entries append after them, overflow past capacity is dropped (DESIGN.md
    §7 — replaces the earlier double argsort).
    """
    cap = pool.shape[-1]
    merged = jnp.concatenate([pool, new_v], axis=-1)
    valid = merged >= 0
    pos = jnp.cumsum(valid, axis=-1) - 1  # target slot of each valid entry
    ok = valid & (pos < cap)
    onehot = (pos[..., None] == jnp.arange(cap)) & ok[..., None]
    return jnp.max(jnp.where(onehot, merged[..., None], -1), axis=-2)

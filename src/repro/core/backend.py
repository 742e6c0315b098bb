"""Selection backend dispatcher: reference jnp vs compiled Pallas (DESIGN.md §6).

The engines never call the Pallas kernels directly — every ``select_*`` call
routes through this module, which owns the plumbing the kernels need:

- backend resolution: ``"auto"`` compiles through Mosaic on TPU and falls
  back to the pure-jnp reference path elsewhere (interpret-mode kernels are
  correct everywhere but only *fast* on TPU);
- lane-aligned padding of candidate pools to multiples of 128 (zero-bias pad
  candidates get zero-width CTPS regions, so results are unchanged);
- pre-generated counted-RNG retry budgets (:func:`repro.core.select.retry_randoms`)
  so the kernel's fixed ``ITERS`` unroll consumes bit-for-bit the same
  uniforms as the reference retry loop — ``backend="pallas"`` and
  ``backend="reference"`` agree exactly whenever the budget suffices;
- degree-bucketed walk scheduling (:func:`walk_step_bucketed`): per step,
  walkers are partitioned by degree into small/medium cohorts served by
  :func:`repro.kernels.walk_step.walk_step_pallas` with per-bucket
  ``max_seg`` windows, and a huge-degree cohort served by the chunked
  two-pass scan — the TPU analogue of the paper's workload-aware
  (KnightKing-style) scheduling.
"""
from __future__ import annotations

import contextlib
import logging
from typing import Literal, Mapping

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import select as sel
from repro.kernels import ref
from repro.kernels.alias_select import alias_step_pallas
from repro.kernels.its_select import its_select_pallas
from repro.kernels.walk_step import (
    pad_csr_for_kernel,
    reject_step_pallas,
    walk_step_pallas,
    walk_step_window_pallas,
)

_logger = logging.getLogger(__name__)

Backend = Literal["auto", "reference", "pallas"]

#: candidate pools are padded to multiples of the TPU lane width
LANES = 128

#: default degree-bucket ladder for the walk fast path (DESIGN.md §6):
#: deg ∈ (0, 128] → small cohort, (128, 512] → medium cohort, > 512 → chunked
WALK_BUCKETS = (128, 512)

#: chunk width of the two-pass huge-degree scan
CHUNK = 512

#: walkers per round of a window-bias cohort when every walker draws by ITS
#: (one (8, 128) walker tile of the kernels)
WINDOW_TILE = 1024

#: rejection budget of every live window-bias walker, whatever its degree
#: (hooks that declare ``WindowBias.max_ratio``)
TAIL_REJECT_ITERS = 16

#: walkers per round of the exact draws after a rejection stage (cohort
#: windows and the hub scan; one sublane of the kernels' walker tile): only
#: walkers whose budget ran out are left, so rounds are narrow
TAIL_SCAN_ROUND = 8


def resolve_backend(backend: str = "auto") -> str:
    """Resolve ``"auto"`` → ``"pallas"`` on TPU, ``"reference"`` elsewhere."""
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "reference"
    if backend not in ("reference", "pallas"):
        raise ValueError(f"unknown backend {backend!r} (use auto/reference/pallas)")
    return backend


def pad_lanes(biases: jax.Array) -> jax.Array:
    """Pad the candidate (last) dim to a lane multiple with zero bias."""
    p = biases.shape[-1]
    pad = (-p) % LANES
    if pad:
        biases = jnp.pad(biases, [(0, 0)] * (biases.ndim - 1) + [(0, pad)])
    return biases


def _masked(biases: jax.Array, mask: jax.Array | None) -> jax.Array:
    b = jnp.maximum(biases.astype(jnp.float32), 0.0)
    if mask is not None:
        b = jnp.where(mask, b, 0.0)
    return b


def select_without_replacement(
    key: jax.Array,
    biases: jax.Array,
    mask: jax.Array | None,
    k: int,
    *,
    method: sel.SelectMethod = "its_brs",
    backend: Backend = "auto",
    max_iters: int = 32,
    blk_i: int = 8,
) -> sel.SelectResult:
    """Backend-dispatched without-replacement selection.

    ``its_brs`` has a fused Pallas kernel; ``gumbel`` is already TPU-native
    vector code and ``repeated``/``updated`` are diagnostic baselines, so all
    three run the reference implementation on every backend.  With the same
    ``max_iters`` the two backends agree bit-for-bit on indices, validity and
    the iteration/search counters (shared counted-RNG budget).
    """
    be = resolve_backend(backend)
    if be == "reference" or method != "its_brs":
        res = sel.select_without_replacement(key, biases, mask, k, method=method, max_iters=max_iters)
        if be == "pallas":
            # requested the kernel path but the method has no kernel: serve
            # from reference and SAY SO — the returned flag (and this
            # trace-time log) keep the adaptive auto-pick observable instead
            # of a silent substitution (DESIGN.md §13).
            _logger.debug(
                "select_without_replacement(method=%r) has no pallas kernel; "
                "serving backend=%r request from the reference path",
                method,
                backend,
            )
            res = res._replace(fell_back=True)
        return res

    b = _masked(biases, mask)
    batch_shape = b.shape[:-1]
    p = b.shape[-1]
    rands = sel.retry_randoms(key, batch_shape, max_iters, k)
    bf = pad_lanes(b.reshape(-1, p))
    rf = rands.reshape(-1, max_iters, k)
    idx, stats = its_select_pallas(bf, rf, blk_i=blk_i, with_stats=True)
    idx = idx.reshape(batch_shape + (k,))
    stats = stats.reshape(batch_shape + (2,))
    return sel.SelectResult(idx, idx >= 0, stats[..., 0], stats[..., 1])


def select_with_replacement(
    key: jax.Array,
    biases: jax.Array,
    mask: jax.Array | None,
    k: int,
    *,
    backend: Backend = "auto",
    blk_i: int = 8,
) -> jax.Array:
    """Backend-dispatched with-replacement ITS draw (random-walk case).

    Only ``k == 1`` has a kernel route (a single draw cannot self-collide, so
    the without-replacement kernel with a one-round budget computes exactly
    the with-replacement draw); larger ``k`` runs the reference path.
    Degenerate all-zero rows return ``P - 1`` like the reference (callers
    mask dead instances).
    """
    be = resolve_backend(backend)
    if be == "reference" or k != 1:
        return sel.select_with_replacement(key, biases, mask, k)
    b = _masked(biases, mask)
    batch_shape = b.shape[:-1]
    p = b.shape[-1]
    # same bits as the reference's uniform(key, batch + (1,)) draw
    r = jax.random.uniform(key, tuple(batch_shape) + (1, 1), dtype=jnp.float32)
    idx = its_select_pallas(pad_lanes(b.reshape(-1, p)), r.reshape(-1, 1, 1), blk_i=blk_i)
    idx = idx.reshape(batch_shape + (1,))
    return jnp.where(idx >= 0, idx, p - 1)


# ---------------------------------------------------------------------------
# Degree-bucketed walk scheduling (DESIGN.md §6)
# ---------------------------------------------------------------------------


def walk_bucket_plan(
    max_degree: int, segs: tuple = WALK_BUCKETS, exact: bool = False
) -> tuple[tuple, bool]:
    """Static per-graph schedule: kernel segment sizes + need for chunked tail.

    Returns ``(buckets, use_chunked)``: one :func:`walk_step_pallas` cohort
    per bucket segment, plus the two-pass chunked scan for degrees above the
    last segment.  Buckets the graph cannot populate are dropped at trace
    time.  With ``exact=True`` the caller asserts ``max_degree`` is the TRUE
    max row degree (not a possibly-understated padding bound), and the top
    segment shrinks to the smallest multiple of the previous bucket covering
    it (a graph with max degree 219 runs its top cohort in 256-wide windows,
    not 512-wide) — shrinking on an understated bound would leave real hub
    degrees with no cohort, silently killing their walkers.
    """
    buckets = []
    lo = 0
    for s in segs:
        if max_degree > lo:
            buckets.append(s)
        lo = s
    if not buckets:
        buckets = [segs[0]]
    if exact:
        base = buckets[-2] if len(buckets) > 1 else LANES
        fit = max(-(-max(max_degree, 1) // base) * base, LANES)
        buckets[-1] = min(buckets[-1], fit)
    return tuple(buckets), max_degree > segs[-1]


def pad_walk_csr(indices: jax.Array, flat_bias: jax.Array, buckets: tuple) -> dict:
    """Pre-pad flat CSR edge arrays once, shared by every bucket.

    One padding to the largest segment satisfies all smaller ones: the
    padded length is a multiple of every smaller ``seg`` (segments are
    powers-of-two multiples of 128) and the single spare ``buckets[-1]``
    block covers each cohort's ``blk+1`` window, so no per-bucket copies
    of the (E,) arrays are materialized.
    """
    big = max(buckets)
    padded = pad_csr_for_kernel(indices, flat_bias, big)
    assert all(big % seg == 0 for seg in buckets), buckets
    return {seg: padded for seg in buckets}


def walk_step_bucketed(
    key: jax.Array,
    indptr: jax.Array,
    indices: jax.Array,
    flat_bias: jax.Array,
    padded: Mapping[int, tuple],
    cur: jax.Array,
    *,
    buckets: tuple,
    use_chunked: bool,
    interpret: bool | None = None,
    rand: jax.Array | None = None,
    tail_rand: jax.Array | None = None,
) -> jax.Array:
    """One bias-weighted transition for all walkers, scheduled by degree.

    ``flat_bias`` is the (E,) per-edge bias aligned with CSR order
    (``SamplingSpec.flat_edge_bias``); ``padded`` maps each bucket segment to
    its :func:`pad_csr_for_kernel` output.  Walkers outside a cohort run with
    ``deg = 0`` (a dead-end no-op) and take their result from their own
    cohort.  Returns next vertices (W,) int32; -1 for finished walkers and
    dead ends.  ``rand`` / ``tail_rand`` override the bucket / chunked-tail
    uniforms (the mesh-sharded drain supplies instance-indexed draws so a
    walker's pick matches the single-device stream wherever it runs,
    DESIGN.md §12); the default draws stay ``fold_in(key, 0)`` /
    ``fold_in(key, 1)``.
    """
    with obs.scope("walk.select"):
        safe = jnp.maximum(cur, 0)
        starts = indptr[safe]
        deg = jnp.where(cur >= 0, indptr[safe + 1] - starts, 0)
        if rand is None:
            rand = jax.random.uniform(jax.random.fold_in(key, 0), cur.shape, dtype=jnp.float32)
        r = rand

        nxt = jnp.full_like(cur, -1)
        lo = 0
        for i, seg in enumerate(buckets):
            inds_p, bias_p = padded[seg]
            # understated max_degree degrades to NEIGHBORHOOD TRUNCATION (the
            # dense-gather contract), never silent walker death: without a
            # chunked tail the top cohort absorbs any larger degree, capped at
            # its window (same policy as the window scheduler below)
            absorb = i == len(buckets) - 1 and not use_chunked
            inb = (deg > lo) & ((deg <= seg) | absorb)
            cand = walk_step_pallas(
                jnp.where(inb, starts, 0),
                jnp.where(inb, jnp.minimum(deg, seg), 0),
                inds_p,
                bias_p,
                r,
                max_seg=seg,
                interpret=interpret,
            )
            nxt = jnp.where(inb, cand, nxt)
            lo = seg

        if use_chunked:
            nxt = _chunked_tail(
                jax.random.fold_in(key, 1), indptr, indices, flat_bias, safe, deg, buckets[-1], nxt,
                rand=tail_rand,
            )
        return nxt


def _chunked_tail(key, indptr, indices, flat_bias, safe, deg, seg_hi, nxt, rand=None):
    """Route walkers with ``deg > seg_hi`` through the two-pass chunked scan."""
    with obs.scope("walk.hub_tail"):
        huge = deg > seg_hi
        safe_cur = jnp.where(huge, safe, 0)
        off = sel.walk_transition_chunked(key, indptr, flat_bias, safe_cur, chunk=CHUNK, rand=rand)
        eidx = jnp.clip(indptr[safe_cur] + jnp.maximum(off, 0), 0, indices.shape[0] - 1)
        cand = jnp.where(off >= 0, indices[eidx], -1)
        return jnp.where(huge, cand, nxt)


def walk_step_flat_reference(
    key: jax.Array,
    indptr: jax.Array,
    indices: jax.Array,
    flat_bias: jax.Array,
    padded: Mapping[int, tuple],
    cur: jax.Array,
    *,
    buckets: tuple,
    use_chunked: bool,
    max_degree: int | None = None,
    rand: jax.Array | None = None,
    tail_rand: jax.Array | None = None,
) -> jax.Array:
    """Pure-jnp mirror of :func:`walk_step_bucketed` — same bits, same picks.

    Replays the kernel's exact arithmetic (block-aligned window at the
    walker's ``start % seg`` offset, masked prefix sum, count-crossings pick) on
    the SAME padded edge arrays and the SAME ``fold_in(key, 0)`` /
    ``fold_in(key, 1)`` uniforms, so the §V drain loop gets bit-identical
    walks from ``backend="reference"`` and ``backend="pallas"`` while the
    reference path stays kernel-free.  The shared prefix sum
    (``kernels.common.prefix_sum``) combines prefix ``i`` in a tree fixed by
    ``i`` alone, so elements must sit at the kernel's window offsets — but
    the window TAIL may be truncated: when ``max_degree`` is given the
    window shrinks from ``2*seg`` to ``seg + min(seg, max_degree)`` without
    changing any prefix up to the row's end.
    """
    with obs.scope("walk.select"):
        safe = jnp.maximum(cur, 0)
        starts = indptr[safe]
        deg = jnp.where(cur >= 0, indptr[safe + 1] - starts, 0)
        if rand is None:
            rand = jax.random.uniform(jax.random.fold_in(key, 0), cur.shape, dtype=jnp.float32)
        r = rand

        nxt = jnp.full_like(cur, -1)
        lo = 0
        for i, seg in enumerate(buckets):
            inds_p, bias_p = padded[seg]
            # same truncation-absorb policy as walk_step_bucketed — the two must
            # mirror each other bit-for-bit
            absorb = i == len(buckets) - 1 and not use_chunked
            inb = (deg > lo) & ((deg <= seg) | absorb)
            width = 2 * seg if max_degree is None else seg + min(seg, max_degree)
            cand = ref.walk_step_block_ref(
                jnp.where(inb, starts, 0), jnp.where(inb, jnp.minimum(deg, seg), 0),
                inds_p, bias_p, r, seg=seg, width=width,
            )
            nxt = jnp.where(inb, cand, nxt)
            lo = seg

        if use_chunked:
            nxt = _chunked_tail(
                jax.random.fold_in(key, 1), indptr, indices, flat_bias, safe, deg, buckets[-1], nxt,
                rand=tail_rand,
            )
        return nxt


# ---------------------------------------------------------------------------
# Adaptive per-bucket method dispatch (DESIGN.md §13)
# ---------------------------------------------------------------------------


def walk_step_adaptive(
    key: jax.Array,
    indptr: jax.Array,
    indices: jax.Array,
    flat_bias: jax.Array,
    padded: Mapping[int, tuple],
    cur: jax.Array,
    *,
    buckets: tuple,
    use_chunked: bool,
    methods: tuple,
    tables,
    backend: str,
    max_degree: int | None = None,
    interpret: bool | None = None,
    rand: jax.Array | None = None,
    tail_rand: jax.Array | None = None,
    rej_rand: jax.Array | None = None,
) -> jax.Array:
    """One flat-bias transition with a per-cohort selection method.

    The adaptive generalization of :func:`walk_step_bucketed` /
    :func:`walk_step_flat_reference`: ``methods`` (static, from
    ``core.methods.plan_methods``) names the draw each degree cohort runs —
    ``"its"`` (the prefix-sum kernel/mirror), ``"alias"`` (O(1) draw from
    ``tables.prob``/``tables.alias``), or ``"rejection"`` (counted-budget
    envelope test against ``tables.row_max``) — one entry per bucket plus
    one for the chunked tail when present.  ONE function serves both
    backends: alias and rejection cohorts dispatch a Pallas kernel under
    ``backend="pallas"`` and the bit-identical pure-jnp flat draws under
    ``"reference"``; ITS cohorts keep the existing kernel/mirror pair.

    Counted RNG (all cohorts, both backends): the single bucket uniform is
    ``fold_in(key, 0)`` — alias draws consume the SAME uniform an ITS cohort
    would, so each walker's stream is method-independent plumbing-wise; the
    ITS/alias tail uses ``fold_in(key, 1)``; the rejection budget (shared by
    every rejection cohort including the tail — each walker lives in exactly
    one cohort) is ``rejection_randoms(fold_in(key, 2))``, generated only
    when some cohort rejects.  ``rand`` / ``tail_rand`` / ``rej_rand``
    override the draws (the mesh-sharded drain supplies instance-indexed
    streams, DESIGN.md §12).

    O(1) methods have no O(degree) window constraint, so alias/rejection
    TAILS draw over the full row via the shared flat-gather helpers —
    removing the two-pass chunked scan from hub vertices entirely; only an
    ITS tail still scans.
    """
    with obs.scope("walk.select"):
        safe = jnp.maximum(cur, 0)
        starts = indptr[safe]
        deg = jnp.where(cur >= 0, indptr[safe + 1] - starts, 0)
        if rand is None:
            rand = jax.random.uniform(jax.random.fold_in(key, 0), cur.shape, dtype=jnp.float32)
        r = rand
        if any(m == "rejection" for m in methods) and rej_rand is None:
            rej_rand = sel.rejection_randoms(jax.random.fold_in(key, 2), cur.shape)
        rmv = None
        if tables.row_max is not None:
            rmv = jnp.where(cur >= 0, tables.row_max[safe], 0.0)
        pal = backend == "pallas"
        tables_p = None
        if pal and any(m == "alias" for m in methods):
            # one padding to the largest segment serves every alias cohort (the
            # same geometry argument as pad_walk_csr); pad values are never read
            # for real rows
            a_pad, p_pad = pad_csr_for_kernel(tables.alias, tables.prob, max(buckets))
            tables_p = (p_pad, a_pad)

        nxt = jnp.full_like(cur, -1)
        lo = 0
        for i, seg in enumerate(buckets):
            inds_p, bias_p = padded[seg]
            # same truncation-absorb policy as walk_step_bucketed: an understated
            # max_degree degrades to neighborhood truncation (cap = seg inside
            # each draw), never silent walker death
            absorb = i == len(buckets) - 1 and not use_chunked
            inb = (deg > lo) & ((deg <= seg) | absorb)
            st = jnp.where(inb, starts, 0)
            dg = jnp.where(inb, deg, 0)
            m = methods[i]
            if m == "alias":
                if pal:
                    cand = alias_step_pallas(
                        st, dg, inds_p, tables_p[0], tables_p[1], r,
                        max_seg=seg, interpret=interpret,
                    )
                else:
                    cand = sel.alias_draw_flat(
                        st, dg, tables.prob, tables.alias, indices, r, cap=seg
                    )
            elif m == "rejection":
                if pal:
                    cand = reject_step_pallas(
                        st, dg, inds_p, bias_p, rmv, rej_rand,
                        max_seg=seg, interpret=interpret,
                    )
                else:
                    cand = sel.rejection_draw_flat(
                        st, dg, flat_bias, rmv, indices, rej_rand, cap=seg
                    )
            elif pal:
                cand = walk_step_pallas(
                    st, jnp.minimum(dg, seg), inds_p, bias_p, r,
                    max_seg=seg, interpret=interpret,
                )
            else:
                width = 2 * seg if max_degree is None else seg + min(seg, max_degree)
                cand = ref.walk_step_block_ref(
                    st, jnp.minimum(dg, seg), inds_p, bias_p, r, seg=seg, width=width
                )
            nxt = jnp.where(inb, cand, nxt)
            lo = seg

        mt = methods[len(buckets)] if use_chunked else None
        if mt in ("alias", "rejection"):
            with obs.scope("walk.hub_tail"):
                huge = deg > buckets[-1]
                st = jnp.where(huge, starts, 0)
                dg = jnp.where(huge, deg, 0)
                if mt == "alias":
                    if tail_rand is None:
                        tail_rand = jax.random.uniform(
                            jax.random.fold_in(key, 1), cur.shape, dtype=jnp.float32
                        )
                    cand = sel.alias_draw_flat(
                        st, dg, tables.prob, tables.alias, indices, tail_rand
                    )
                else:
                    cand = sel.rejection_draw_flat(st, dg, flat_bias, rmv, indices, rej_rand)
                nxt = jnp.where(huge, cand, nxt)
        elif use_chunked:
            nxt = _chunked_tail(
                jax.random.fold_in(key, 1), indptr, indices, flat_bias, safe, deg,
                buckets[-1], nxt, rand=tail_rand,
            )
        return nxt


# ---------------------------------------------------------------------------
# Degree-bucketed WINDOW-bias walk scheduling (transition programs, §10)
# ---------------------------------------------------------------------------


def walk_bucket_plan_window(max_degree: int, segs: tuple = WALK_BUCKETS) -> tuple[tuple, bool]:
    """Bucket plan for the window-bias path: exact, and ladder-merged.

    Window biases are *evaluated* per cohort, so every extra bucket re-runs
    the dynamic hook (and its prev-membership search) over all walkers at
    that cohort's width — a small bucket only pays for itself when the top
    segment is much wider.  Plan exactly (the window path treats
    ``max_degree`` as the true max row degree, like the OOM drain), then
    collapse the ladder into the top cohort when it is at most twice the
    bottom one.  Degrees above the top segment take the chunked dynamic
    tail.
    """
    buckets, use_chunked = walk_bucket_plan(max_degree, segs, exact=True)
    if len(buckets) > 1 and buckets[-1] <= 2 * buckets[0]:
        buckets = buckets[-1:]
    return tuple(buckets), use_chunked


def walk_step_bucketed_window(
    key: jax.Array,
    indptr: jax.Array,
    indices: jax.Array,
    weights: jax.Array,
    padded: Mapping[int, tuple],
    cur: jax.Array,
    bias_of,
    *,
    buckets: tuple,
    use_chunked: bool,
    backend: str,
    interpret: bool | None = None,
    rand: jax.Array | None = None,
    tail_rand: jax.Array | None = None,
    envelope: jax.Array | None = None,
    rej_rand: jax.Array | None = None,
) -> jax.Array:
    """One dynamic-bias transition for all walkers, scheduled by degree.

    The ``WindowBias`` analogue of :func:`walk_step_bucketed` /
    :func:`walk_step_flat_reference` — ONE function serves both backends
    because the expensive, semantics-bearing part (evaluating the dynamic
    edge-bias hook) runs in shared jnp either way.

    With an ``envelope`` (an upper bound of every bias the hook returns),
    every live walker, whatever its degree, first makes the counted
    rejection draw (:func:`window_rejection_stage`): ``TAIL_REJECT_ITERS``
    proposals a walker, evaluated as one window.  Only the walkers left
    without an accepted proposal go on, ``TAIL_SCAN_ROUND`` at a time.
    Without an envelope every live walker goes on, ``WINDOW_TILE`` at a
    time.  Those walkers take the exact draw of their cohort:

    per bucket, the bucket's walkers — taken in rounds, in degree-cohort
    order, so a walker pays only for its own cohort's width and a cohort
    with no walker runs no round — gather their *compact* ``(round, seg)``
    row windows from the padded CSR arrays (``padded[seg] = (ids,
    weights)``, :func:`pad_walk_csr` over edge WEIGHTS, not a flat bias) and
    ``bias_of(u, w, mask, eidx) -> biases`` is evaluated on it — the
    narrowest arrays the hook (and its prev-membership search) can see.  The
    computed bias is then re-aligned into the kernel's block-aligned
    ``(round, 2·seg)`` window (one cheap row-local gather; per-edge bias
    values are unchanged) and the ITS pick, on the walker's own uniform
    ``rand``, runs :func:`~repro.kernels.walk_step.walk_step_window_pallas`
    under ``backend="pallas"`` or the bit-identical
    :func:`~repro.kernels.ref.walk_step_window_block_ref` mirror under
    ``"reference"`` — same bias rows, same uniforms, same picks.  Degrees
    above the last bucket take the exact two-pass chunked scan
    (:func:`_window_tail`), compacted into rounds so no other walker pays for
    a hub row — no ``(W, max_degree)`` tensor exists on any path.

    Accepted proposals follow the bias exactly, and so does every exact
    draw, so the step is exact either way.  ``bias_of(u, w, mask, eidx,
    rows=None)`` evaluates the hook for the walkers ``rows`` (all when
    None).  ``rand`` / ``tail_rand`` / ``rej_rand`` override the cohort,
    tail and rejection streams (defaults ``fold_in(key, 0)``,
    ``fold_in(key, 1)``, ``rejection_randoms(fold_in(key, 2))``).  Returns
    next vertices (W,) int32; -1 for finished walkers and dead ends.
    """
    with obs.scope("walk.select"):
        safe = jnp.maximum(cur, 0)
        starts = indptr[safe]
        deg = jnp.where(cur >= 0, indptr[safe + 1] - starts, 0)
        if not use_chunked:
            # an understated max_degree (possible in-memory, where the caller's
            # bound is trusted for the exact bucket plan) degrades to
            # NEIGHBORHOOD TRUNCATION — the dense-gather path's contract — never
            # silent walker death: without a chunked tail the top cohort absorbs
            # any larger degree, capped at its window, on every draw
            deg = jnp.minimum(deg, buckets[-1])
        if rand is None:
            rand = jax.random.uniform(jax.random.fold_in(key, 0), cur.shape, dtype=jnp.float32)
        r = rand

        w = cur.shape[-1]
        nxt = jnp.full_like(cur, -1)
        pending = deg > 0
        k = min(WINDOW_TILE, w)
        rounds = contextlib.nullcontext()
        if envelope is not None:
            nxt, pending = window_rejection_stage(
                key, indices, weights, starts, deg, bias_of, envelope, rej_rand
            )
            k = min(TAIL_SCAN_ROUND, w)
            rounds = obs.scope("walk.window_fallback")

        # each pending walker's cohort: its bucket, or len(buckets) for the
        # tail; everyone else (picked, finished, dead end) is in no cohort
        cohort = jnp.full(cur.shape, len(buckets), jnp.int32)
        lo = 0
        for i, seg in enumerate(buckets):
            cohort = jnp.where(pending & (deg > lo) & (deg <= seg), i, cohort)
            lo = seg
        with rounds:
            order = jnp.argsort(cohort, stable=True)
            begin = jnp.zeros((), jnp.int32)
            for i, seg in enumerate(buckets):
                inds_p, wts_p = padded[seg]
                n = jnp.sum((cohort == i).astype(jnp.int32))

                def tile(carry, seg=seg, inds_p=inds_p, wts_p=wts_p, begin=begin, n=n):
                    j, nxt = carry
                    pos = j * k + jnp.arange(k, dtype=jnp.int32)
                    live = pos < n
                    rows = order[jnp.minimum(begin + pos, w - 1)]
                    st = jnp.where(live, starts[rows], 0)
                    dg = jnp.where(live, deg[rows], 0)
                    # compact row-aligned windows for the hook (row fits: dg <=
                    # seg, and the padded arrays keep a spare trailing block, so
                    # st+seg is safe)
                    offs_c = jnp.arange(seg, dtype=jnp.int32)
                    cmask = offs_c < dg[..., None]
                    ceidx = st[..., None] + offs_c
                    u_c = jnp.where(cmask, inds_p[ceidx], -1)
                    w_c = jnp.where(cmask, wts_p[ceidx], 0.0)
                    # the hook also receives the window's edge positions
                    # (``ceidx``) so per-edge side lanes (the sharded drain's
                    # replicated degree lane) can be gathered without row
                    # lookups; in-memory hooks ignore it
                    bias_c = jnp.where(
                        cmask, jnp.maximum(bias_of(u_c, w_c, cmask, ceidx, rows=rows), 0.0), 0.0
                    )
                    # re-align to the kernel's 2-block window at offset start %
                    # seg (same geometry the reference pick uses — shared helper
                    # keeps the bit-parity contract in one place)
                    local, _, offs, mask = ref._block_window(st, dg, seg, 2 * seg)
                    src = jnp.clip(offs - local[..., None], 0, seg - 1)
                    bias_win = jnp.where(mask, jnp.take_along_axis(bias_c, src, axis=-1), 0.0)
                    if backend == "pallas":
                        cand = walk_step_window_pallas(
                            st, dg, inds_p, bias_win, r[rows], max_seg=seg, interpret=interpret
                        )
                    else:
                        cand = ref.walk_step_window_block_ref(
                            st, dg, inds_p, bias_win, r[rows], seg=seg
                        )
                    # out-of-range index for idle slots: a clipped row may repeat
                    return j + 1, nxt.at[jnp.where(live, rows, w)].set(cand, mode="drop")

                _, nxt = jax.lax.while_loop(
                    lambda c, n=n: c[0] * k < n, tile, (jnp.zeros((), jnp.int32), nxt)
                )
                begin = begin + n

        if use_chunked:
            nxt = _window_tail(
                key, indices, weights, starts, deg, pending & (deg > buckets[-1]), bias_of, nxt,
                narrow=envelope is not None, rand=tail_rand,
            )
        return nxt


def window_rejection_stage(key, indices, weights, starts, deg, bias_of, envelope, rej_rand=None):
    """The counted rejection draw of every live walker (``deg > 0``) of a
    window-bias step (:func:`~repro.core.select.window_rejection_draw`).

    ``envelope`` bounds every bias the hook returns; the budget is
    ``rejection_randoms(fold_in(key, 2))`` with ``TAIL_REJECT_ITERS``
    proposals a walker unless ``rej_rand`` overrides it.  Returns ``(nxt,
    pending)``: the accepted picks (-1 elsewhere), and the live walkers
    without one, which must draw exactly another way.
    """
    with obs.scope("walk.window_reject"):
        if rej_rand is None:
            rej_rand = sel.rejection_randoms(
                jax.random.fold_in(key, 2), deg.shape, iters=TAIL_REJECT_ITERS
            )
        cand, acc = sel.window_rejection_draw(
            starts, deg, indices, weights, bias_of, envelope, rej_rand
        )
        return jnp.where(acc, cand, -1), (deg > 0) & ~acc


def _window_tail(key, indices, weights, starts, deg, pending, bias_of, nxt, *,
                 narrow=False, rand=None):
    """Next vertices of the walkers in ``pending`` (rows above the top
    bucket) by the exact chunked scan (uniform ``fold_in(key, 1)`` unless
    ``rand`` overrides it), widest rows first: ``TAIL_SCAN_ROUND`` walkers
    per round when ``narrow`` (after a rejection stage, which leaves few) and
    all of them in one round otherwise.  Each round loops only to the widest
    row among its walkers.
    """
    with obs.scope("walk.hub_tail"):
        if rand is None:
            rand = jax.random.uniform(jax.random.fold_in(key, 1), pending.shape, dtype=jnp.float32)
        w = pending.shape[-1]
        k = min(TAIL_SCAN_ROUND, w) if narrow else w
        order = jnp.argsort(jnp.where(pending, -deg, 1))  # pending first, widest first
        npend = jnp.sum(pending.astype(jnp.int32))

        def scan_round(carry):
            i, nxt = carry
            pos = i * k + jnp.arange(k, dtype=jnp.int32)
            live = pos < npend
            rows = order[jnp.minimum(pos, w - 1)]
            st = jnp.where(live, starts[rows], 0)
            dg = jnp.where(live, deg[rows], 0)
            off = sel.walk_transition_chunked_window(
                None, st, dg, indices, weights,
                lambda u, wt, m, e: bias_of(u, wt, m, e, rows=rows),
                chunk=CHUNK, rand=rand[rows],
            )
            eidx = jnp.clip(st + jnp.maximum(off, 0), 0, indices.shape[0] - 1)
            cand = jnp.where(off >= 0, indices[eidx], -1)
            # out-of-range index for idle slots: a clipped row may repeat
            nxt = nxt.at[jnp.where(live, rows, w)].set(cand, mode="drop")
            return i + 1, nxt

        _, nxt = jax.lax.while_loop(
            lambda c: c[0] * k < npend, scan_round, (jnp.zeros((), jnp.int32), nxt)
        )
        return nxt

"""Bias-based vertex selection (paper §II-B, §IV).

This module is the heart of C-SAW: turning per-candidate *biases* into
selections via Inverse Transform Sampling (ITS) over the Cumulative
Transition Probability Space (CTPS), with *bipartite region search* (BRS,
paper §IV-B, Theorem 2) to mitigate selection collisions when sampling
without replacement.

All functions are batched over arbitrary leading instance dimensions and are
jit/vmap/shard_map friendly (fixed shapes, masked semantics, counted RNG).

Selection modes (``SelectMethod``):
  - ``its_brs``   — paper-faithful: ITS + bipartite region search retry.
  - ``repeated``  — naive baseline (paper Fig. 6(a)): fresh re-draw on collision.
  - ``updated``   — recompute the CTPS excluding selected (paper Fig. 6(b)).
  - ``gumbel``    — beyond-paper TPU-native: Gumbel top-k (Plackett-Luce);
                    distributionally identical to sequential without-replacement
                    ITS, collision-free by construction.
"""
from __future__ import annotations

import functools
from typing import Literal, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import its_ctps

SelectMethod = Literal["its_brs", "repeated", "updated", "gumbel"]

_EPS = 1e-12

#: Rejection-sampling retry budget per walk step (counted-RNG rounds).  Under
#: the cost model's near-uniform guard (acceptance rate >= 0.75) the chance of
#: exhausting all rounds is <= 0.25**8 ~ 1.5e-5; exhaustion falls back to the
#: last candidate (still a real neighbor) rather than killing the walker.
REJECT_ITERS = 8


def build_ctps(biases: jax.Array, mask: jax.Array | None = None) -> jax.Array:
    """Inclusive normalized prefix sum of biases: the CTPS (paper Eq. 1).

    Region of candidate ``j`` is ``[ctps[j-1], ctps[j])`` with ``ctps[-1]=0``.
    Masked/zero-bias candidates get zero-width regions (unselectable).  The
    sum is the ITS kernel's own (``kernels.common.its_ctps``), so the
    reference and Pallas selection backends agree bit-for-bit.
    """
    if mask is not None:
        biases = jnp.where(mask, biases, 0.0)
    return its_ctps(jnp.maximum(biases.astype(jnp.float32), 0.0))


def its_search(ctps: jax.Array, r: jax.Array) -> jax.Array:
    """Locate the CTPS region containing ``r`` (vectorized 'binary search').

    On TPU a lane-parallel compare-count beats a serial binary search for
    pool sizes up to a few thousand; this is also exactly what the Pallas
    kernel does.  ``r`` has shape ``ctps.shape[:-1] + (k,)``.
    """
    # count of regions with upper boundary <= r  ==  index of containing region
    idx = jnp.sum(ctps[..., None, :] <= r[..., :, None], axis=-1)
    return jnp.clip(idx, 0, ctps.shape[-1] - 1).astype(jnp.int32)


def select_with_replacement(
    key: jax.Array, biases: jax.Array, mask: jax.Array | None, k: int
) -> jax.Array:
    """ITS selection *with* replacement (random-walk case, paper Table I)."""
    ctps = build_ctps(biases, mask)
    r = jax.random.uniform(key, ctps.shape[:-1] + (k,), dtype=jnp.float32)
    return its_search(ctps, r)


class SelectResult(NamedTuple):
    indices: jax.Array  # (..., k) int32, -1 where selection failed/invalid
    valid: jax.Array  # (..., k) bool
    iters: jax.Array  # (...,) int32 — retry-loop trip count (paper Fig. 11)
    searches: jax.Array  # (...,) int32 — total CTPS searches (paper Fig. 12)
    #: True when the dispatcher silently served a pallas request from the
    #: reference path (method without a kernel) — observability for the
    #: adaptive method auto-pick (DESIGN.md §13).
    fell_back: bool = False


def _dedup_priority(cand: jax.Array, active: jax.Array) -> jax.Array:
    """Within-round conflict resolution: lowest-lane duplicate wins.

    TPU adaptation of the paper's atomic bitmap (DESIGN.md §2): a K×K
    equality matrix + lower-triangular priority replaces atomicCAS.
    Returns a boolean 'winner' mask over the k draws.
    """
    k = cand.shape[-1]
    eq = cand[..., :, None] == cand[..., None, :]  # (..., k, k)
    both = active[..., :, None] & active[..., None, :]
    lower = jnp.tril(jnp.ones((k, k), dtype=bool), k=-1)
    beaten = jnp.any(eq & both & lower, axis=-1)  # an earlier lane took it
    return active & ~beaten


def retry_randoms(key: jax.Array, batch_shape: tuple, iters: int, k: int) -> jax.Array:
    """Pre-generated retry budget: ``(..., iters, k)`` uniforms.

    Round ``t`` holds exactly the bits ``_select_its_loop`` draws in round
    ``t`` (``uniform(fold_in(key, t), batch + (k,))``) — this is the counted
    RNG contract that makes the Pallas kernel path in ``core.backend``
    bit-identical to the reference retry loop (DESIGN.md §6).
    """
    if iters < 1:
        raise ValueError(f"retry budget needs at least one round, got iters={iters}")
    rs = [
        jax.random.uniform(jax.random.fold_in(key, t), tuple(batch_shape) + (k,), dtype=jnp.float32)
        for t in range(iters)
    ]
    return jnp.stack(rs, axis=-2)


def select_without_replacement(
    key: jax.Array,
    biases: jax.Array,
    mask: jax.Array | None,
    k: int,
    method: SelectMethod = "its_brs",
    max_iters: int = 32,
) -> SelectResult:
    """Select ``k`` distinct candidates with probability proportional to bias.

    biases: (..., P); mask: (..., P) bool or None; returns indices (..., k).
    If fewer than k candidates are selectable the tail is marked invalid.
    """
    if method == "gumbel":
        return _select_gumbel(key, biases, mask, k)
    if method == "updated":
        return _select_updated(key, biases, mask, k)
    return _select_its_loop(key, biases, mask, k, use_brs=(method == "its_brs"), max_iters=max_iters)


def _select_gumbel(key, biases, mask, k) -> SelectResult:
    b = jnp.maximum(biases.astype(jnp.float32), 0.0)
    if mask is not None:
        b = jnp.where(mask, b, 0.0)
    logits = jnp.log(jnp.maximum(b, _EPS))
    logits = jnp.where(b > 0, logits, -jnp.inf)
    g = jax.random.gumbel(key, b.shape, dtype=jnp.float32)
    keys_ = jnp.where(jnp.isfinite(logits), logits + g, -jnp.inf)
    _, idx = jax.lax.top_k(keys_, k)
    navail = jnp.sum((b > 0), axis=-1)
    valid = jnp.arange(k) < navail[..., None]
    idx = jnp.where(valid, idx, -1).astype(jnp.int32)
    zeros = jnp.zeros(b.shape[:-1], dtype=jnp.int32)
    return SelectResult(idx, valid, zeros + 1, zeros + k)


def _select_updated(key, biases, mask, k) -> SelectResult:
    """Paper Fig. 6(b): recompute CTPS after every selection (oracle baseline)."""
    b = jnp.maximum(biases.astype(jnp.float32), 0.0)
    if mask is not None:
        b = jnp.where(mask, b, 0.0)
    batch_shape = b.shape[:-1]

    def body(i, carry):
        b_cur, out, valid = carry
        ctps = build_ctps(b_cur)
        r = jax.random.uniform(jax.random.fold_in(key, i), batch_shape + (1,))
        idx = its_search(ctps, r)[..., 0]
        ok = jnp.take_along_axis(b_cur, idx[..., None], axis=-1)[..., 0] > 0
        out = out.at[..., i].set(jnp.where(ok, idx, -1))
        valid = valid.at[..., i].set(ok)
        b_cur = b_cur * (1.0 - jax.nn.one_hot(idx, b.shape[-1], dtype=b.dtype))
        return b_cur, out, valid

    out = jnp.full(batch_shape + (k,), -1, dtype=jnp.int32)
    valid = jnp.zeros(batch_shape + (k,), dtype=bool)
    _, out, valid = jax.lax.fori_loop(0, k, body, (b, out, valid))
    zeros = jnp.zeros(batch_shape, dtype=jnp.int32)
    return SelectResult(out, valid, zeros + k, zeros + k)


def _select_its_loop(key, biases, mask, k, *, use_brs: bool, max_iters: int) -> SelectResult:
    """ITS without replacement with the paper's retry loop (Fig. 5 lines 9-14).

    Each round, every unfinished draw gets a fresh uniform r'; draws that hit
    an already-selected region either (a) re-draw next round (``repeated``) or
    (b) apply one bipartite-region-search adjustment within the round
    (``its_brs``, paper steps 1-5) and only fall back to a fresh random if the
    adjusted r *also* lands on a selected region ("go to 1").
    """
    b = jnp.maximum(biases.astype(jnp.float32), 0.0)
    if mask is not None:
        b = jnp.where(mask, b, 0.0)
    batch_shape = b.shape[:-1]
    p = b.shape[-1]
    ctps = build_ctps(b)
    lower = jnp.concatenate([jnp.zeros_like(ctps[..., :1]), ctps[..., :-1]], axis=-1)
    navail = jnp.sum(b > 0, axis=-1)
    want = jnp.minimum(navail, k)  # can't select more than available

    def sel_at(selmask, idx):
        return jnp.take_along_axis(selmask, idx, axis=-1)

    def cond(carry):
        it, done, _, _, _, _ = carry
        return jnp.logical_and(it < max_iters, jnp.any(~done))

    def body(carry):
        it, done, out, selmask, iters, searches = carry
        # NOTE: this per-round draw is the counted-RNG contract shared with
        # retry_randoms()/the Pallas kernel path — change both or neither.
        rkey = jax.random.fold_in(key, it)
        r1 = jax.random.uniform(rkey, batch_shape + (k,), dtype=jnp.float32)
        pending = ~done
        idx1 = its_search(ctps, r1)
        hit1 = sel_at(selmask, idx1)  # collided with previously-selected
        searches = searches + jnp.sum(pending, axis=-1)
        if use_brs:
            # Bipartite region search (paper §IV-B): transform r, reuse CTPS.
            l = jnp.take_along_axis(lower, idx1, axis=-1)
            h = jnp.take_along_axis(ctps, idx1, axis=-1)
            delta = h - l
            r2 = r1 * (1.0 - delta)
            r2 = jnp.where(r2 < l, r2, r2 + delta)
            r2 = jnp.clip(r2, 0.0, 1.0 - _EPS)
            idx2 = its_search(ctps, r2)
            hit2 = sel_at(selmask, idx2)
            searches = searches + jnp.sum(pending & hit1, axis=-1)
            cand = jnp.where(hit1, idx2, idx1)
            ok = pending & ~jnp.where(hit1, hit2, hit1)
        else:
            cand = idx1
            ok = pending & ~hit1
        # candidate must carry probability mass
        ok = ok & (jnp.take_along_axis(b, cand, axis=-1) > 0)
        # within-round dedup: lowest lane wins (DESIGN.md conflict matrix)
        win = _dedup_priority(cand, ok)
        # rank of each newly finished draw -> stable output order
        out = jnp.where(win, cand, out)
        onehot = jax.nn.one_hot(jnp.where(win, cand, 0), p, dtype=bool) & win[..., None]
        selmask = selmask | jnp.any(onehot, axis=-2)
        done_new = done | win
        # stop instances that already have `want` selections
        got = jnp.sum(done_new, axis=-1)
        exhausted = got >= want
        done_new = done_new | (exhausted[..., None] & (jnp.arange(k) >= want[..., None]))
        iters = iters + jnp.any(~done, axis=-1).astype(jnp.int32)
        return it + 1, done_new, out, selmask, iters, searches

    init = (
        jnp.zeros((), jnp.int32),
        jnp.arange(k) >= want[..., None],  # draws beyond availability are done/invalid
        jnp.full(batch_shape + (k,), -1, jnp.int32),
        jnp.zeros(batch_shape + (p,), bool),
        jnp.zeros(batch_shape, jnp.int32),
        jnp.zeros(batch_shape, jnp.int32),
    )
    _, done, out, selmask, iters, searches = jax.lax.while_loop(cond, body, init)
    valid = out >= 0
    return SelectResult(out, valid, iters, searches)


# ---------------------------------------------------------------------------
# Chunked ITS for unbounded-degree rows (no padding): two-pass scan.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("chunk",))
def walk_transition_chunked(
    key: jax.Array,
    indptr: jax.Array,
    weights: jax.Array,
    cur: jax.Array,
    chunk: int = 512,
    rand: jax.Array | None = None,
) -> jax.Array:
    """One weighted ITS draw per walker over arbitrarily large neighbor rows.

    Two-pass chunked scan (DESIGN.md §2): pass 1 accumulates the row total,
    pass 2 locates the chunk+offset where the cumulative bias crosses
    ``r * total``.  Returns the *edge offset* within each row (int32), or -1
    for dead ends.  O(max_deg/chunk) steps, fixed memory.  ``rand`` overrides
    the per-walker uniforms (the mesh-sharded drain supplies instance-indexed
    draws so picks match the single-device stream, DESIGN.md §12).
    """
    start = indptr[cur]
    deg = indptr[cur + 1] - start
    # the loops run to the widest row in the batch, not to E/chunk: a bound
    # fixed by the edge count costs E/chunk loop trips per step on any graph
    nchunks = jnp.maximum((jnp.max(deg) + chunk - 1) // chunk, 1)

    def p1_body(c, tot):
        offs = c * chunk + jnp.arange(chunk, dtype=jnp.int32)
        m = offs < deg[..., None]
        w = jnp.where(m, weights[jnp.where(m, start[..., None] + offs, 0)], 0.0)
        return tot + jnp.sum(w, axis=-1)

    total = jax.lax.fori_loop(0, nchunks, p1_body, jnp.zeros(cur.shape, jnp.float32))
    r = jax.random.uniform(key, cur.shape, dtype=jnp.float32) if rand is None else rand
    target = r * total

    def p2_body(c, carry):
        cum, found = carry
        offs = c * chunk + jnp.arange(chunk, dtype=jnp.int32)
        m = offs < deg[..., None]
        w = jnp.where(m, weights[jnp.where(m, start[..., None] + offs, 0)], 0.0)
        cw = jnp.cumsum(w, axis=-1) + cum[..., None]
        hit = (cw > target[..., None]) & m & (found[..., None] < 0)
        any_hit = jnp.any(hit, axis=-1)
        first = jnp.argmax(hit, axis=-1) + c * chunk
        found = jnp.where((found < 0) & any_hit, first, found)
        return cw[..., -1], found

    cum0 = jnp.zeros(cur.shape, jnp.float32)
    found0 = jnp.full(cur.shape, -1, jnp.int32)
    _, found = jax.lax.fori_loop(0, nchunks, p2_body, (cum0, found0))
    # numerical edge: r*total == total -> take last valid edge
    found = jnp.where((found < 0) & (deg > 0) & (total > 0), deg - 1, found)
    return jnp.where((deg > 0) & (total > 0), found, -1)


def walk_transition_chunked_window(
    key: jax.Array,
    start: jax.Array,
    deg: jax.Array,
    indices: jax.Array,
    weights: jax.Array,
    bias_of,
    chunk: int = 512,
    rand: jax.Array | None = None,
) -> jax.Array:
    """Dynamic-bias variant of :func:`walk_transition_chunked`.

    The per-edge bias is not a flat array — it is ``bias_of(u, w, mask,
    eidx)``, the transition program's window-bias hook evaluated on each
    ``(W, chunk)`` edge window (``u`` = neighbor ids from ``indices``, ``w``
    = edge weights, ``eidx`` = the window's positions in the edge arrays,
    padding masked).  Both passes evaluate the (pure) hook on identical
    windows, so pass-2 crossings agree with pass-1 totals exactly.  Pure jnp,
    shared verbatim by both backends (the huge-degree tail of the bucketed
    window scheduler).  Rows are given by their edge ``start`` and ``deg``
    (0 for walkers that take no part).  Returns per-row edge offsets, -1 for
    dead ends.  Not jitted here: ``bias_of`` is a closure — callers jit the
    enclosing step.
    """
    nchunks = jnp.maximum((jnp.max(deg) + chunk - 1) // chunk, 1)

    def chunk_bias(c):
        offs = c * chunk + jnp.arange(chunk, dtype=jnp.int32)
        m = offs < deg[..., None]
        eidx = jnp.where(m, start[..., None] + offs, 0)
        u = jnp.where(m, indices[eidx], -1)
        w = jnp.where(m, weights[eidx], 0.0)
        return jnp.where(m, jnp.maximum(bias_of(u, w, m, eidx), 0.0), 0.0), m

    def p1_body(c, tot):
        b, _ = chunk_bias(c)
        return tot + jnp.sum(b, axis=-1)

    total = jax.lax.fori_loop(0, nchunks, p1_body, jnp.zeros(deg.shape, jnp.float32))
    r = jax.random.uniform(key, deg.shape, dtype=jnp.float32) if rand is None else rand
    target = r * total

    def p2_body(c, carry):
        cum, found = carry
        b, m = chunk_bias(c)
        cw = jnp.cumsum(b, axis=-1) + cum[..., None]
        hit = (cw > target[..., None]) & m & (found[..., None] < 0)
        any_hit = jnp.any(hit, axis=-1)
        first = jnp.argmax(hit, axis=-1) + c * chunk
        found = jnp.where((found < 0) & any_hit, first, found)
        return cw[..., -1], found

    cum0 = jnp.zeros(deg.shape, jnp.float32)
    found0 = jnp.full(deg.shape, -1, jnp.int32)
    _, found = jax.lax.fori_loop(0, nchunks, p2_body, (cum0, found0))
    found = jnp.where((found < 0) & (deg > 0) & (total > 0), deg - 1, found)
    return jnp.where((deg > 0) & (total > 0), found, -1)


def window_rejection_draw(
    start: jax.Array,
    deg: jax.Array,
    indices: jax.Array,
    weights: jax.Array,
    bias_of,
    envelope: jax.Array,
    rej: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Counted-budget rejection draw for a dynamic (window) bias.

    ``rej`` is a :func:`rejection_randoms` budget ``(W, iters, 2)``.  Trial
    ``t`` proposes ``slot = floor(r_slot * deg)`` (uniform over the row) and
    accepts iff ``r_acc * envelope < bias(slot)``, where ``envelope`` bounds
    every bias the hook can return; the first accepted trial wins, so an
    accepted pick is distributed exactly as the bias.  All trials are
    evaluated as one ``(W, iters)`` window of the hook.  Returns the picked
    neighbour ids and whether any trial was accepted — walkers without an
    acceptance (or with ``deg == 0``) must draw another way.
    """
    degf = deg.astype(jnp.float32)[..., None]
    last = jnp.maximum(deg - 1, 0)[..., None]
    slot = jnp.minimum((rej[..., 0] * degf).astype(jnp.int32), last)
    m = jnp.broadcast_to((deg > 0)[..., None], slot.shape)
    eidx = jnp.where(m, start[..., None] + slot, 0)
    u = jnp.where(m, indices[eidx], -1)
    w = jnp.where(m, weights[eidx], 0.0)
    b = jnp.where(m, jnp.maximum(bias_of(u, w, m, eidx), 0.0), 0.0)
    acc = rej[..., 1] * envelope < b
    first = jnp.argmax(acc, axis=-1)
    cand = jnp.take_along_axis(u, first[..., None], axis=-1)[..., 0]
    return cand, jnp.any(acc, axis=-1)


# ---------------------------------------------------------------------------
# Alias tables (Vose) and rejection sampling — the adaptive selection
# runtime's O(1) draw methods (DESIGN.md §13).  Construction is host-side
# numpy (once per (graph, FlatBias)); draws are pure jnp, shared verbatim by
# the reference backend and the Pallas kernels' tails, and mirrored exactly
# (same f32 arithmetic) by the kernels themselves.
# ---------------------------------------------------------------------------


def build_alias(indptr, bias) -> tuple[np.ndarray, np.ndarray]:
    """Per-row alias tables over a flat CSR bias array (Vose's method).

    Vectorized over rows grouped by exact degree: each group forms an
    ``(R, d)`` matrix and the small/large pairing loop retires one column per
    iteration for every row simultaneously.  Internally float64 so the
    probability identity ``prob[j] + sum(1 - prob[i] for alias[i] == j) ==
    d * bias[j] / total`` holds to f32 round-off after the final cast.

    Returns ``(prob, alias)``: ``prob`` float32 ``(E,)`` acceptance
    thresholds, ``alias`` int32 ``(E,)`` row-LOCAL redirect offsets.
    Zero-total rows get ``prob = 0`` / ``alias = -1`` (the draw reads the
    -1 as a dead end).  Deterministic: numpy argmax first-index tie-breaks.
    """
    indptr = np.asarray(indptr)
    bias = np.maximum(np.asarray(bias, dtype=np.float64), 0.0)
    e = bias.shape[0]
    deg = np.diff(indptr).astype(np.int64)
    prob_out = np.zeros(e, dtype=np.float32)
    alias_out = np.full(e, -1, dtype=np.int32)
    for d in np.unique(deg):
        if d <= 0:
            continue
        d = int(d)
        starts = indptr[:-1][deg == d].astype(np.int64)
        w = bias[starts[:, None] + np.arange(d)[None, :]]  # (R, d)
        tot = w.sum(axis=1)
        ok = tot > 0.0
        if not ok.any():
            continue
        starts, w, tot = starts[ok], w[ok], tot[ok]
        r = starts.shape[0]
        # normalize first: d / tot overflows for a subnormal row total
        p = (w / tot[:, None]) * d  # scaled to sum d
        alias = np.full((r, d), -1, dtype=np.int32)
        active = np.ones((r, d), dtype=bool)
        for _ in range(max(d - 1, 0)):
            small = active & (p < 1.0)
            large = active & (p >= 1.0)
            has = small.any(axis=1) & large.any(axis=1)
            if not has.any():
                break
            rows = np.nonzero(has)[0]
            s = np.argmax(small[rows], axis=1)  # first active small
            g = np.argmax(large[rows], axis=1)  # first active large
            alias[rows, s] = g
            active[rows, s] = False
            p[rows, g] -= 1.0 - p[rows, s]
        # leftovers (all-large or all-small residue): certain acceptance
        lr, lc = np.nonzero(active)
        p[lr, lc] = 1.0
        alias[lr, lc] = lc
        flat = (starts[:, None] + np.arange(d)[None, :]).ravel()
        prob_out[flat] = p.astype(np.float32).ravel()
        alias_out[flat] = alias.ravel()
    return prob_out, alias_out


def build_row_max(indptr, bias) -> np.ndarray:
    """Per-vertex max bias, ``(V,)`` float32 — the rejection envelope."""
    indptr = np.asarray(indptr)
    bias = np.maximum(np.asarray(bias, dtype=np.float64), 0.0)
    deg = np.diff(indptr)
    rm = np.zeros(deg.shape[0], dtype=np.float32)
    live = deg > 0
    if live.any():
        # segments between consecutive non-empty row starts are exactly
        # those rows (empty rows own no edges)
        rm[live] = np.maximum.reduceat(bias[: indptr[-1]], indptr[:-1][live])
    return rm


def rejection_randoms(key: jax.Array, batch_shape: tuple, iters: int = REJECT_ITERS) -> jax.Array:
    """Pre-generated rejection budget: ``(..., iters, 2)`` uniforms.

    Round ``t`` consumes ``uniform(fold_in(key, 2t))`` for the candidate
    slot and ``uniform(fold_in(key, 2t + 1))`` for the accept test — the
    counted-RNG contract shared by the reference draw, the Pallas kernel,
    and the sharded drain's instance-indexed streams (change all or none).
    """
    if iters < 1:
        raise ValueError(f"rejection budget needs at least one round, got iters={iters}")
    rs = [
        jax.random.uniform(jax.random.fold_in(key, t), tuple(batch_shape), dtype=jnp.float32)
        for t in range(2 * iters)
    ]
    return jnp.stack(rs, axis=-1).reshape(tuple(batch_shape) + (iters, 2))


def alias_draw_flat(
    starts: jax.Array,
    degs: jax.Array,
    prob: jax.Array,
    alias: jax.Array,
    indices: jax.Array,
    rand: jax.Array,
    *,
    cap: int | None = None,
) -> jax.Array:
    """One O(1) alias draw per walker from flat CSR-aligned tables.

    ``rand`` is the SAME single uniform an ITS cohort would consume (each
    walker lives in exactly one cohort, so the streams never collide).
    ``cap`` truncates rows to the bucket segment exactly like the kernels'
    2-block window does for absorbed oversized rows (understated
    ``max_degree``): slots and alias redirects clamp into ``[0, cap)`` so
    reference and Pallas stay bit-identical even in that degenerate case.
    Returns next vertices (int32), -1 for dead ends (zero-total rows carry
    ``alias = -1``).
    """
    deg_eff = degs if cap is None else jnp.minimum(degs, cap)
    u = rand * deg_eff.astype(jnp.float32)
    slot = jnp.minimum(u.astype(jnp.int32), jnp.maximum(deg_eff - 1, 0))
    frac = u - slot.astype(jnp.float32)
    pos = jnp.clip(starts + slot, 0, prob.shape[0] - 1)
    a = alias[pos]
    chosen = jnp.where(frac < prob[pos], slot, a)
    chosen = jnp.clip(chosen, 0, jnp.maximum(deg_eff - 1, 0))
    nxt = indices[jnp.clip(starts + chosen, 0, indices.shape[0] - 1)]
    dead = (degs <= 0) | (a < 0)
    return jnp.where(dead, -1, nxt).astype(jnp.int32)


def rejection_draw_flat(
    starts: jax.Array,
    degs: jax.Array,
    flat_bias: jax.Array,
    row_max: jax.Array,
    indices: jax.Array,
    rej: jax.Array,
    *,
    cap: int | None = None,
) -> jax.Array:
    """Counted-RNG rejection draw per walker over flat CSR bias.

    ``rej`` is the ``(..., iters, 2)`` budget from
    :func:`rejection_randoms`; ``row_max`` is each walker's envelope (its
    row's max bias, gathered by the caller).  Round ``t`` proposes
    ``slot = floor(r_slot * deg)`` and accepts iff
    ``r_acc * row_max < bias[slot]`` — first acceptance wins; an exhausted
    budget falls back to the last candidate if it carries mass.  Static
    unroll (iters is a compile-time constant), bit-identical to the Pallas
    kernel's loop.
    """
    iters = rej.shape[-2]
    deg_eff = degs if cap is None else jnp.minimum(degs, cap)
    degf = deg_eff.astype(jnp.float32)
    chosen = jnp.full(degs.shape, -1, jnp.int32)
    done = jnp.zeros(degs.shape, bool)
    last = jnp.zeros(degs.shape, jnp.int32)
    last_b = jnp.zeros(degs.shape, jnp.float32)
    for t in range(iters):
        slot = jnp.minimum((rej[..., t, 0] * degf).astype(jnp.int32), jnp.maximum(deg_eff - 1, 0))
        bval = flat_bias[jnp.clip(starts + slot, 0, flat_bias.shape[0] - 1)]
        acc = rej[..., t, 1] * row_max < bval
        chosen = jnp.where(~done & acc, slot, chosen)
        last, last_b = slot, bval
        done = done | acc
    chosen = jnp.where(done, chosen, jnp.where(last_b > 0, last, -1))
    nxt = indices[jnp.clip(starts + jnp.maximum(chosen, 0), 0, indices.shape[0] - 1)]
    dead = (degs <= 0) | (row_max <= 0) | (chosen < 0)
    return jnp.where(dead, -1, nxt).astype(jnp.int32)

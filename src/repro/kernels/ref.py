"""Pure-jnp oracles for the Pallas kernels.

Each oracle consumes the *same* pre-generated random budget as its kernel and
runs the same floating-point formula (the prefix sum and ITS pick of
``kernels.common``, called by both), so tests compare integer outputs
exactly.  Gathers are direct indexing: the kernels' one-hot masked sums
have a single nonzero term, so the values are the same.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import _EPS, its_ctps, shift_pad, window_pick


def _gather(table: jax.Array, idx: jax.Array) -> jax.Array:
    return jnp.take_along_axis(table, idx, axis=-1)


def its_select_ref(biases: jax.Array, rands: jax.Array) -> jax.Array:
    """ITS + bipartite-region-search without replacement (oracle).

    biases: (I, P) float; rands: (I, ITERS, K) float in [0,1).
    Returns selected indices (I, K) int32, -1 where the random budget was
    exhausted or no candidate remains.
    """
    i_dim, p = biases.shape
    iters, k = rands.shape[1], rands.shape[2]
    b = jnp.maximum(biases.astype(jnp.float32), 0.0)
    ctps = its_ctps(b)
    lower = shift_pad(ctps, 1)
    navail = jnp.sum(b > 0, axis=-1)
    want = jnp.minimum(navail, k)

    def search(r):
        idx = jnp.sum(ctps[:, None, :] <= r[:, :, None], axis=-1)
        return jnp.clip(idx, 0, p - 1).astype(jnp.int32)

    def body(it, carry):
        done, out, selmask = carry
        r1 = rands[:, it, :]
        idx1 = search(r1)
        hit1 = _gather(selmask.astype(jnp.float32), idx1) > 0.5
        l = _gather(lower, idx1)
        h = _gather(ctps, idx1)
        delta = h - l
        r2 = r1 * (1.0 - delta)
        r2 = jnp.where(r2 < l, r2, r2 + delta)
        r2 = jnp.clip(r2, 0.0, 1.0 - _EPS)
        idx2 = search(r2)
        hit2 = _gather(selmask.astype(jnp.float32), idx2) > 0.5
        cand = jnp.where(hit1, idx2, idx1)
        ok = ~done & ~jnp.where(hit1, hit2, hit1)
        ok = ok & (_gather(b, cand) > 0)
        eq = cand[:, :, None] == cand[:, None, :]
        both = ok[:, :, None] & ok[:, None, :]
        beaten = jnp.any(eq & both & jnp.tril(jnp.ones((k, k), bool), -1), axis=-1)
        win = ok & ~beaten
        out = jnp.where(win, cand, out)
        oh = jax.nn.one_hot(jnp.where(win, cand, 0), p, dtype=bool) & win[..., None]
        selmask = selmask | jnp.any(oh, axis=-2)
        done = done | win
        got = jnp.sum(done, axis=-1)
        done = done | ((got >= want)[..., None] & (jnp.arange(k) >= want[..., None]))
        return done, out, selmask

    done0 = jnp.arange(k)[None, :] >= want[:, None]
    out0 = jnp.full((i_dim, k), -1, jnp.int32)
    sel0 = jnp.zeros((i_dim, p), bool)
    _, out, _ = jax.lax.fori_loop(0, iters, body, (done0, out0, sel0))
    return out


def _block_window(starts: jax.Array, degs: jax.Array, seg: int, width: int):
    """Block-aligned window coordinates of each walker's CSR segment.

    The kernel DMAs the two consecutive ``seg``-blocks containing a walker's
    row (``walk_step_pallas``); its window starts at ``blk0 = start//seg*seg``
    and the row occupies offsets ``[local, local+deg)`` with
    ``local = start % seg``.  Returns ``(local, blk0, offs, mask)`` with
    ``offs`` of length ``width`` (``2*seg`` for the kernel's full window; a
    truncated tail never changes any prefix sum up to the row's end)."""
    local = starts % seg
    blk0 = starts // seg * seg
    offs = jnp.arange(width, dtype=jnp.int32)
    mask = (offs >= local[..., None]) & (offs < (local + degs)[..., None])
    return local, blk0, offs, mask


def _window_pick(local, blk0, degs, wts, rand, inds_p) -> jax.Array:
    """Masked ITS pick over block-aligned windows — the kernel's arithmetic
    (``kernels.common.window_pick``, DESIGN.md §6).  Prefix ``i`` is summed
    in a tree fixed by ``i`` alone, so ``wts`` must sit at the kernel's
    window offsets; then reference and Pallas agree bit-for-bit."""
    pos, dead = window_pick(wts, local[..., None], degs[..., None], rand[..., None])
    return jnp.where(dead[..., 0], -1, inds_p[blk0 + pos[..., 0]])


def walk_step_block_ref(
    starts: jax.Array,
    degs: jax.Array,
    inds_p: jax.Array,
    bias_p: jax.Array,
    rand: jax.Array,
    *,
    seg: int,
    width: int | None = None,
) -> jax.Array:
    """Pure-jnp mirror of one flat-bias ``walk_step_pallas`` cohort.

    ``inds_p``/``bias_p`` are the SAME padded flat CSR arrays the kernel
    DMAs (``pad_csr_for_kernel``); bias is gathered from the flat array at
    the window offsets.  ``width`` defaults to the kernel's full ``2*seg``
    window; callers that know the true max row degree may truncate the tail
    (``seg + min(seg, max_degree)``) without changing any prefix."""
    width = 2 * seg if width is None else width
    local, blk0, _, mask = _block_window(starts, degs, seg, width)
    win = blk0[..., None] + jnp.arange(width, dtype=jnp.int32)
    wts = jnp.where(mask, bias_p[win], 0.0)
    return _window_pick(local, blk0, degs, wts, rand, inds_p)


def walk_step_window_block_ref(
    starts: jax.Array,
    degs: jax.Array,
    inds_p: jax.Array,
    bias_win: jax.Array,
    rand: jax.Array,
    *,
    seg: int,
) -> jax.Array:
    """Pure-jnp mirror of one window-bias ``walk_step_window_pallas`` cohort.

    ``bias_win`` is the per-walker ``(W, 2*seg)`` bias evaluated on the
    block-aligned edge window (``core.backend.walk_step_bucketed_window``
    computes it ONCE, in shared jnp, for both backends — so cross-backend
    parity reduces to the pick arithmetic, which this mirrors exactly)."""
    local, blk0, _, mask = _block_window(starts, degs, seg, bias_win.shape[-1])
    wts = jnp.where(mask, bias_win, 0.0)
    return _window_pick(local, blk0, degs, wts, rand, inds_p)


def alias_step_block_ref(
    starts: jax.Array,
    degs: jax.Array,
    inds_p: jax.Array,
    prob_p: jax.Array,
    alias_p: jax.Array,
    rand: jax.Array,
    *,
    seg: int,
) -> jax.Array:
    """Pure-jnp mirror of one ``alias_step_pallas`` cohort (DESIGN.md §13).

    ``inds_p``/``prob_p``/``alias_p`` are the SAME padded flat arrays the
    kernel DMAs; one uniform splits into slot ``⌊u·deg⌋`` and coin, the coin
    routes through the prebuilt redirect.  The kernel's one-hot masked
    sums are exact (single nonzero term), so a direct gather is
    bit-identical."""
    deg_eff = jnp.minimum(degs, seg)  # absorbed oversized rows truncate
    local = starts % seg
    blk0 = starts // seg * seg
    u = rand * deg_eff.astype(jnp.float32)
    slot = jnp.minimum(u.astype(jnp.int32), jnp.maximum(deg_eff - 1, 0))
    frac = u - slot.astype(jnp.float32)
    pos = blk0 + local + slot
    pval = prob_p[pos]
    aval = alias_p[pos]
    chosen = jnp.clip(
        jnp.where(frac < pval, slot, aval), 0, jnp.maximum(deg_eff - 1, 0)
    )
    nxt = inds_p[blk0 + local + chosen]
    dead = (degs <= 0) | (aval < 0)  # zero-total rows carry alias = -1
    return jnp.where(dead, -1, nxt).astype(jnp.int32)


def reject_step_block_ref(
    starts: jax.Array,
    degs: jax.Array,
    inds_p: jax.Array,
    bias_p: jax.Array,
    row_max: jax.Array,
    rej: jax.Array,
    *,
    seg: int,
) -> jax.Array:
    """Pure-jnp mirror of one ``reject_step_pallas`` cohort (DESIGN.md §13).

    ``rej`` is the (W, iters, 2) counted budget from
    ``core.select.rejection_randoms``: round ``t`` proposes
    ``slot = ⌊r_slot·deg⌋`` and accepts iff ``r_acc·row_max < bias[slot]``;
    first acceptance wins, an exhausted budget keeps the last proposal
    carrying mass — exactly the kernel's statically-unrolled loop."""
    iters = rej.shape[1]
    deg_eff = jnp.minimum(degs, seg)
    degf = deg_eff.astype(jnp.float32)
    local = starts % seg
    blk0 = starts // seg * seg
    chosen = jnp.full_like(starts, -1)
    done = jnp.zeros(starts.shape, bool)
    last = jnp.zeros_like(starts)
    last_b = jnp.zeros(starts.shape, jnp.float32)
    for t in range(iters):
        slot = jnp.minimum(
            (rej[:, t, 0] * degf).astype(jnp.int32), jnp.maximum(deg_eff - 1, 0)
        )
        bval = bias_p[blk0 + local + slot]
        acc = rej[:, t, 1] * row_max < bval
        chosen = jnp.where(~done & acc, slot, chosen)
        last, last_b = slot, bval
        done = done | acc
    chosen = jnp.where(done, chosen, jnp.where(last_b > 0, last, -1))
    nxt = inds_p[blk0 + local + jnp.maximum(chosen, 0)]
    dead = (degs <= 0) | (row_max <= 0) | (chosen < 0)
    return jnp.where(dead, -1, nxt).astype(jnp.int32)

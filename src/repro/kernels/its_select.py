"""Pallas TPU kernel: fused ITS selection with bipartite region search.

TPU mapping of the paper's warp-centric SELECT (DESIGN.md §2, §6):

- grid over *instance blocks* — each grid step owns ``(BLK_I, P)`` bias rows
  resident in VMEM (the paper's "one warp per instance" becomes "one tile of
  instances per grid step").
- prefix-sum + normalize + search + BRS retry are fused in one kernel: the
  CTPS never round-trips to HBM (the paper's key win over updated sampling).
- gathers are one-hot masked lane sums — no atomics, no irregular
  addressing.  The K draws of a round run in lane order, and a round mask of
  the candidates earlier draws claimed resolves within-round collisions by
  lane priority, replacing the strided atomic bitmap.
- the retry budget is a static ``ITERS`` loop over pre-generated randoms
  (counted RNG outside the kernel keeps it deterministic and testable).

VMEM budget: biases+CTPS+masks ≈ 5·BLK_I·P·4B; with BLK_I=8, P=2048 ≈ 330 KiB,
comfortably inside the scoped VMEM limit with room for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import _EPS, its_ctps, pick_lane, resolve_interpret, shift_roll


def _its_select_kernel(biases_ref, rands_ref, out_ref, stats_ref, *, iters: int, k: int):
    b = jnp.maximum(biases_ref[...].astype(jnp.float32), 0.0)  # (BLK_I, P)
    blk_i, p = b.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (blk_i, p), 1)
    ctps = its_ctps(b, shift_roll)
    lower = shift_roll(ctps, 1)
    navail = jnp.sum((b > 0).astype(jnp.int32), axis=-1, keepdims=True)
    want = jnp.minimum(navail, k)  # (BLK_I, 1)
    rands = rands_ref[...]  # (BLK_I, ITERS*K): round t's draws at t*K ...

    def search(r):
        idx = jnp.sum((ctps <= r).astype(jnp.int32), axis=-1, keepdims=True)
        return jnp.clip(idx, 0, p - 1)

    def body(it, carry):
        done, out, selmask, it_acc, se_acc = carry
        # loop carries hold int32 flags: Mosaic cannot carry i1 vectors
        done, out = [d > 0 for d in done], list(out)
        pending = [~d for d in done]
        any_pending = functools.reduce(jnp.logical_or, pending)
        # retry-loop accounting (paper Figs. 11/12), bit-identical to the
        # reference loop in core.select._select_its_loop
        it_acc = it_acc + any_pending.astype(jnp.int32)
        claimed = jnp.zeros((blk_i, p), jnp.float32)  # candidates of ok lanes this round
        for j in range(k):
            r1 = pick_lane(rands, it * k + j)
            idx1 = search(r1)
            hit1 = pick_lane(selmask, idx1) > 0.5
            se_acc = se_acc + pending[j].astype(jnp.int32)
            se_acc = se_acc + (pending[j] & hit1).astype(jnp.int32)
            l = pick_lane(lower, idx1)
            h = pick_lane(ctps, idx1)
            delta = h - l
            r2 = r1 * (1.0 - delta)
            r2 = jnp.where(r2 < l, r2, r2 + delta)
            r2 = jnp.clip(r2, 0.0, 1.0 - _EPS)
            idx2 = search(r2)
            hit2 = pick_lane(selmask, idx2) > 0.5
            cand = jnp.where(hit1, idx2, idx1)
            ok = ~done[j] & ~(hit1 & hit2)
            ok = ok & (pick_lane(b, cand) > 0)
            # lowest lane wins: an earlier ok lane already claimed `cand`
            win = ok & ~(pick_lane(claimed, cand) > 0.5)
            claimed = jnp.where((lane == cand) & ok, 1.0, claimed)
            out[j] = jnp.where(win, cand, out[j])
            done[j] = done[j] | win
        # the set of won candidates is the set of ok candidates
        selmask = jnp.maximum(selmask, claimed)
        got = functools.reduce(jnp.add, [d.astype(jnp.int32) for d in done])
        done = [d | ((got >= want) & (j >= want)) for j, d in enumerate(done)]
        return tuple(d.astype(jnp.int32) for d in done), tuple(out), selmask, it_acc, se_acc

    done = tuple((want <= j).astype(jnp.int32) for j in range(k))
    out = tuple(jnp.full((blk_i, 1), -1, jnp.int32) for _ in range(k))
    zero = jnp.zeros((blk_i, 1), jnp.int32)
    carry = (done, out, jnp.zeros((blk_i, p), jnp.float32), zero, zero)
    _, out, _, it_acc, se_acc = jax.lax.fori_loop(0, iters, body, carry)
    kl = jax.lax.broadcasted_iota(jnp.int32, (blk_i, k), 1)
    out_ref[...] = functools.reduce(
        jnp.add, [jnp.where(kl == j, o, 0) for j, o in enumerate(out)]
    )
    sl = jax.lax.broadcasted_iota(jnp.int32, (blk_i, 2), 1)
    stats_ref[...] = jnp.where(sl == 0, it_acc, se_acc)


@functools.partial(jax.jit, static_argnames=("blk_i", "interpret", "with_stats"))
def its_select_pallas(
    biases: jax.Array,
    rands: jax.Array,
    *,
    blk_i: int = 8,
    interpret: bool | None = None,
    with_stats: bool = False,
):
    """Fused without-replacement ITS+BRS selection.

    biases: (I, P) float — per-instance candidate biases (<=0 → unselectable).
    rands:  (I, ITERS, K) float — pre-generated retry budget.
    Returns indices (I, K) int32 (-1 = unfilled); with ``with_stats=True``
    also an (I, 2) int32 array of (retry iterations, CTPS searches) per
    instance (paper Figs. 11/12 accounting).

    Any I works — instances are padded internally to a multiple of ``blk_i``
    and the pad rows sliced off.  P should be lane-aligned (multiple of 128)
    for best TPU layout (any P works functionally; the dispatcher in
    ``core.backend`` pads pools to lane multiples, DESIGN.md §6).
    """
    i_dim, p = biases.shape
    iters, k = rands.shape[1], rands.shape[2]
    pad_i = (-i_dim) % blk_i
    if pad_i:
        # zero-bias pad rows select nothing; sliced off below
        biases = jnp.pad(biases, ((0, pad_i), (0, 0)))
        rands = jnp.pad(rands, ((0, pad_i), (0, 0), (0, 0)))
    i_pad = i_dim + pad_i
    kernel = functools.partial(_its_select_kernel, iters=iters, k=k)
    out, stats = pl.pallas_call(
        kernel,
        grid=(i_pad // blk_i,),
        in_specs=[
            pl.BlockSpec((blk_i, p), lambda i: (i, 0)),
            pl.BlockSpec((blk_i, iters * k), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((blk_i, k), lambda i: (i, 0)),
            pl.BlockSpec((blk_i, 2), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((i_pad, k), jnp.int32),
            jax.ShapeDtypeStruct((i_pad, 2), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
        name="csaw_its_select",
    )(biases, rands.reshape(i_pad, iters * k))
    if with_stats:
        return out[:i_dim], stats[:i_dim]
    return out[:i_dim]

"""Pallas TPU kernels: fused random-walk steps (segment DMA + one draw).

One grid step advances one walker: the walker's CSR neighbor segment is
DMA'd into VMEM by BlockSpec index_maps driven by scalar-prefetched row
starts (the TPU analogue of the paper's coalesced warp loads), then the draw
happens entirely in VMEM.

Degree bucketing (DESIGN.md §6): segments must satisfy ``deg <= max_seg``;
the engine routes larger rows through ``select.walk_transition_chunked``.
A segment can straddle a ``max_seg`` block boundary, so the index_maps pull
TWO consecutive blocks (same input bound twice with maps ``blk`` and
``blk+1``) and the kernel offsets into their concatenation.  Edge arrays must
be padded with one extra trailing block so ``blk+1`` always exists; they
enter the kernel as ``(1, E)`` rows so each window is a lane-dense
``(1, max_seg)`` block (``max_seg`` a multiple of 128 on the chip).

Per-walker scalars (uniforms, envelopes, the output) travel in ``(8, 128)``
walker tiles (``kernels.common``); the draws use the arithmetic the oracles
in ``kernels.ref`` share, so both backends agree bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    pick_lane,
    resolve_interpret,
    shift_roll,
    tile_mask,
    tile_pick,
    tile_spec,
    to_tiles,
    window_pick,
)


def _window(lo_ref, hi_ref):
    """The walker's 2-block window, ``(1, 2*max_seg)``."""
    return jnp.concatenate([lo_ref[...], hi_ref[...]], axis=1)


def _store(out_ref, m, dead, nxt):
    out_ref[...] = jnp.where(m, jnp.where(dead, -1, nxt), out_ref[...])


def _its_pick(out_ref, m, start, deg, wts, rand, ids, max_seg):
    local = start % max_seg
    lane = jax.lax.broadcasted_iota(jnp.int32, wts.shape, 1)
    wts = jnp.where((lane >= local) & (lane < local + deg), wts, 0.0)
    pos, dead = window_pick(wts, local, deg, rand, shift_roll)
    _store(out_ref, m, dead, pick_lane(ids, pos))


def _walk_step_kernel(
    starts_ref,  # scalar-prefetch (W,)
    degs_ref,  # scalar-prefetch (W,)
    rand_ref,  # (8, 128) walker tile of uniforms
    idx_lo_ref,  # (1, max_seg) neighbor-id block containing `start`
    idx_hi_ref,  # (1, max_seg) following block
    w_lo_ref,  # (1, max_seg) weight blocks
    w_hi_ref,
    out_ref,  # (8, 128) walker tile of next vertices
    *,
    max_seg: int,
):
    i = pl.program_id(0)
    m = tile_mask(i)
    _its_pick(
        out_ref, m, starts_ref[i], degs_ref[i], _window(w_lo_ref, w_hi_ref),
        tile_pick(rand_ref[...], m), _window(idx_lo_ref, idx_hi_ref), max_seg,
    )


def _walk_step_window_kernel(
    starts_ref,  # scalar-prefetch (W,)
    degs_ref,  # scalar-prefetch (W,)
    rand_ref,  # (8, 128) walker tile of uniforms
    bias_ref,  # (8, 2*max_seg) window-aligned bias rows of 8 walkers
    idx_lo_ref,  # (1, max_seg) neighbor-id block containing `start`
    idx_hi_ref,  # (1, max_seg) following block
    out_ref,  # (8, 128) walker tile of next vertices
    *,
    max_seg: int,
):
    """Window-bias variant of the walk step (transition programs, DESIGN.md
    §10): the per-edge bias is a *computed operand* — evaluated by the
    engine's dynamic edge-bias hook on this walker's gathered edge window —
    instead of a slice of a static flat CSR array.  Neighbor ids still
    arrive by segment DMA; the ITS pick is identical to the flat kernel."""
    i = pl.program_id(0)
    m = tile_mask(i)
    rows = bias_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
    wts = jnp.sum(jnp.where(row == i % 8, rows, 0.0), axis=0, keepdims=True)
    _its_pick(
        out_ref, m, starts_ref[i], degs_ref[i], wts, tile_pick(rand_ref[...], m),
        _window(idx_lo_ref, idx_hi_ref), max_seg,
    )


def _reject_step_kernel(
    starts_ref,  # scalar-prefetch (W,)
    degs_ref,  # scalar-prefetch (W,)
    rej_ref,  # (2*iters, 8, 128) walker tiles of [slot, accept] uniforms
    rowmax_ref,  # (8, 128) walker tile of rejection envelopes (row max bias)
    idx_lo_ref,  # (1, max_seg) neighbor-id blocks
    idx_hi_ref,
    w_lo_ref,  # (1, max_seg) bias blocks
    w_hi_ref,
    out_ref,  # (8, 128) walker tile of next vertices
    *,
    max_seg: int,
    iters: int,
):
    """Counted-RNG rejection walk step (adaptive selection runtime,
    DESIGN.md §13): round ``t`` proposes ``slot = floor(r_slot * deg)`` and
    accepts iff ``r_acc * row_max < bias[slot]`` — first acceptance wins,
    an exhausted budget falls back to the last candidate carrying mass.
    Static unroll; exactly ``core.select.rejection_draw_flat`` with
    ``cap = max_seg`` (bit-identical across backends)."""
    i = pl.program_id(0)
    m = tile_mask(i)
    start = starts_ref[i]
    deg = degs_ref[i]
    deg_eff = jnp.minimum(deg, max_seg)
    top = jnp.maximum(deg_eff - 1, 0)
    local = start % max_seg
    wts = _window(w_lo_ref, w_hi_ref)
    rm = tile_pick(rowmax_ref[...], m)
    degf = jnp.full((1, 1), deg_eff, jnp.int32).astype(jnp.float32)
    chosen = jnp.full((1, 1), -1, jnp.int32)
    done = jnp.zeros((1, 1), jnp.bool_)
    last = jnp.zeros((1, 1), jnp.int32)
    last_b = jnp.zeros((1, 1), jnp.float32)
    for t in range(iters):
        slot = jnp.minimum((tile_pick(rej_ref[2 * t], m) * degf).astype(jnp.int32), top)
        bval = pick_lane(wts, local + slot)
        acc = tile_pick(rej_ref[2 * t + 1], m) * rm < bval
        chosen = jnp.where(~done & acc, slot, chosen)
        last, last_b = slot, bval
        done = done | acc
    chosen = jnp.where(done, chosen, jnp.where(last_b > 0, last, -1))
    nxt = pick_lane(_window(idx_lo_ref, idx_hi_ref), local + jnp.maximum(chosen, 0))
    _store(out_ref, m, (deg <= 0) | (rm <= 0) | (chosen < 0), nxt)


def pad_csr_for_kernel(indices: jax.Array, weights: jax.Array, max_seg: int):
    """Pad flat CSR edge arrays to a block multiple plus one spill block."""
    e = indices.shape[0]
    target = ((e + max_seg - 1) // max_seg + 1) * max_seg
    pad = target - e
    return (
        jnp.pad(indices, (0, pad), constant_values=0),
        jnp.pad(weights, (0, pad), constant_values=0.0),
    )


def _edge_specs(max_seg: int, pairs: int) -> list:
    """``pairs`` × (block containing the row start, following block)."""

    def lo_map(i, starts_ref, degs_ref):
        return (0, starts_ref[i] // max_seg)

    def hi_map(i, starts_ref, degs_ref):
        return (0, starts_ref[i] // max_seg + 1)

    return [pl.BlockSpec((1, max_seg), f) for _ in range(pairs) for f in (lo_map, hi_map)]


def _walker_call(kernel, name, w, in_specs, interpret, starts, degs, *args):
    """Run a per-walker kernel over ``grid=(W,)``; returns ``(W,)`` int32.
    ``name`` is the kernel's name in compiled programs and device traces."""
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(w,),
        in_specs=in_specs,
        out_specs=tile_spec(),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(to_tiles(starts).shape, jnp.int32),
        interpret=resolve_interpret(interpret),
        name=name,
    )(starts, degs, *args)
    return out.reshape(-1)[:w]


def _rows(x: jax.Array) -> jax.Array:
    """A flat padded edge array as one ``(1, E)`` lane-dense row."""
    assert x.ndim == 1, x.shape
    return x.reshape(1, -1)


@functools.partial(jax.jit, static_argnames=("max_seg", "interpret"))
def walk_step_pallas(
    starts: jax.Array,
    degs: jax.Array,
    indices: jax.Array,
    weights: jax.Array,
    rand: jax.Array,
    *,
    max_seg: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """One weighted walk step for W walkers.

    starts/degs: (W,) int32 row offsets/degrees (deg <= max_seg — the
    engine's degree-bucketed scheduler guarantees this per cohort,
    DESIGN.md §6); indices/weights: flat CSR arrays padded via
    :func:`pad_csr_for_kernel`; rand: (W,) uniforms.  Returns next
    vertices (W,) int32 (-1 dead end).
    """
    assert indices.shape[0] % max_seg == 0, "pad CSR edge arrays with pad_csr_for_kernel"
    ind, wts = _rows(indices), _rows(weights)
    kernel = functools.partial(_walk_step_kernel, max_seg=max_seg)
    in_specs = [tile_spec()] + _edge_specs(max_seg, 2)
    return _walker_call(
        kernel, "csaw_walk_step", starts.shape[0], in_specs, interpret, starts, degs,
        to_tiles(rand), ind, ind, wts, wts,
    )


@functools.partial(jax.jit, static_argnames=("max_seg", "interpret"))
def walk_step_window_pallas(
    starts: jax.Array,
    degs: jax.Array,
    indices: jax.Array,
    bias_win: jax.Array,
    rand: jax.Array,
    *,
    max_seg: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """One dynamic-bias walk step for W walkers (transition programs).

    Like :func:`walk_step_pallas` but the per-edge bias is ``bias_win``:
    ``(W, 2*max_seg)`` float32 rows, one per walker, aligned with the
    kernel's 2-block window (the walker's neighbors sit at offsets
    ``[start % max_seg, start % max_seg + deg)``).  ``indices`` is the
    padded flat CSR id array (:func:`pad_csr_for_kernel`).  The bias rows
    arrive eight walkers per block, so no block grows with ``W``.
    """
    w = starts.shape[0]
    assert indices.shape[0] % max_seg == 0, "pad CSR edge arrays with pad_csr_for_kernel"
    assert bias_win.shape == (w, 2 * max_seg), bias_win.shape
    ind = _rows(indices)
    bias = jnp.pad(bias_win, ((0, (-w) % 8), (0, 0)))
    kernel = functools.partial(_walk_step_window_kernel, max_seg=max_seg)
    in_specs = [
        tile_spec(),
        pl.BlockSpec((8, 2 * max_seg), lambda i, *_: (i // 8, 0)),
    ] + _edge_specs(max_seg, 1)
    return _walker_call(
        kernel, "csaw_walk_step_window", w, in_specs, interpret, starts, degs,
        to_tiles(rand), bias, ind, ind,
    )


@functools.partial(jax.jit, static_argnames=("max_seg", "interpret"))
def reject_step_pallas(
    starts: jax.Array,
    degs: jax.Array,
    indices: jax.Array,
    weights: jax.Array,
    row_max: jax.Array,
    rej: jax.Array,
    *,
    max_seg: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """One rejection-sampled walk step for W walkers (near-uniform biases).

    starts/degs: (W,) int32 row offsets/degrees; indices/weights: flat CSR
    arrays padded via :func:`pad_csr_for_kernel`; row_max: (W,) float32
    per-walker envelopes (each walker's row max bias, gathered by the
    engine); rej: (W, iters, 2) counted budget from
    ``core.select.rejection_randoms``.  Returns next vertices (W,) int32
    (-1 dead end).
    """
    w = starts.shape[0]
    assert indices.shape[0] % max_seg == 0, "pad CSR edge arrays with pad_csr_for_kernel"
    assert rej.ndim == 3 and rej.shape[0] == w and rej.shape[2] == 2, rej.shape
    iters = rej.shape[1]
    # round t's [slot, accept] uniforms → tiles 2t, 2t+1
    rej_t = to_tiles(rej.transpose(1, 2, 0).reshape(2 * iters, w))
    ind, wts = _rows(indices), _rows(weights)
    kernel = functools.partial(_reject_step_kernel, max_seg=max_seg, iters=iters)
    in_specs = [tile_spec((2 * iters,)), tile_spec()] + _edge_specs(max_seg, 2)
    return _walker_call(
        kernel, "csaw_reject_step", w, in_specs, interpret, starts, degs,
        rej_t, to_tiles(row_max), ind, ind, wts, wts,
    )

"""Pallas TPU kernel: O(1) alias-table walk step (adaptive selection runtime).

One grid step advances one walker by a single alias draw: the walker's CSR
segment blocks of the *prebuilt* per-row alias tables (``prob``/``alias``
from ``core.select.build_alias``) arrive by the same scalar-prefetch-driven
2-block DMA as the ITS walk kernel, then the draw is two one-hot gathers —
no cumsum, no O(degree) scan.  This is the static-bias (FlatBias) fast path
the cost model picks when a graph's tables are prebuilt and reused
(DESIGN.md §13); the serving service amortizes construction across requests.

Bit-parity contract: the kernel performs exactly the arithmetic of
``core.select.alias_draw_flat`` with ``cap = max_seg`` (its gathers are
one-hot masked sums, exact for any value), so reference and Pallas backends
agree bit-for-bit, including the truncation semantics for oversized rows
absorbed into the top bucket.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import pick_lane, tile_mask, tile_pick, tile_spec, to_tiles
from repro.kernels.walk_step import _edge_specs, _rows, _store, _walker_call, _window


def _alias_step_kernel(
    starts_ref,  # scalar-prefetch (W,)
    degs_ref,  # scalar-prefetch (W,)
    rand_ref,  # (8, 128) walker tile of uniforms (the stream an ITS cohort uses)
    p_lo_ref,  # (1, max_seg) acceptance-threshold block containing `start`
    p_hi_ref,  # (1, max_seg) following block
    a_lo_ref,  # (1, max_seg) alias-offset blocks (row-local redirects)
    a_hi_ref,
    idx_lo_ref,  # (1, max_seg) neighbor-id blocks
    idx_hi_ref,
    out_ref,  # (8, 128) walker tile of next vertices
    *,
    max_seg: int,
):
    i = pl.program_id(0)
    m = tile_mask(i)
    start = starts_ref[i]
    deg = degs_ref[i]
    deg_eff = jnp.minimum(deg, max_seg)  # absorbed oversized rows truncate
    top = jnp.maximum(deg_eff - 1, 0)
    local = start % max_seg  # offset inside the 2-block window
    u = tile_pick(rand_ref[...], m) * jnp.full((1, 1), deg_eff, jnp.int32).astype(jnp.float32)
    slot = jnp.minimum(u.astype(jnp.int32), top)
    frac = u - slot.astype(jnp.float32)
    pval = pick_lane(_window(p_lo_ref, p_hi_ref), local + slot)
    aval = pick_lane(_window(a_lo_ref, a_hi_ref), local + slot)
    chosen = jnp.clip(jnp.where(frac < pval, slot, aval), 0, top)
    nxt = pick_lane(_window(idx_lo_ref, idx_hi_ref), local + chosen)
    _store(out_ref, m, (deg <= 0) | (aval < 0), nxt)  # zero-total rows carry alias = -1


@functools.partial(jax.jit, static_argnames=("max_seg", "interpret"))
def alias_step_pallas(
    starts: jax.Array,
    degs: jax.Array,
    indices: jax.Array,
    prob: jax.Array,
    alias: jax.Array,
    rand: jax.Array,
    *,
    max_seg: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """One alias-table walk step for W walkers.

    starts/degs: (W,) int32 row offsets/degrees; indices/prob/alias: flat
    CSR-aligned arrays padded to the kernel geometry (``pad_csr_for_kernel``
    — pad values are never read for real rows); rand: (W,) uniforms.
    Returns next vertices (W,) int32 (-1 dead end).
    """
    e = indices.shape[0]
    assert e % max_seg == 0, "pad CSR edge arrays with pad_csr_for_kernel"
    assert prob.shape[0] == e and alias.shape[0] == e, (prob.shape, alias.shape, e)
    p, a, ind = _rows(prob), _rows(alias), _rows(indices)
    kernel = functools.partial(_alias_step_kernel, max_seg=max_seg)
    in_specs = [tile_spec()] + _edge_specs(max_seg, 3)
    return _walker_call(
        kernel, "csaw_alias_step", starts.shape[0], in_specs, interpret, starts, degs,
        to_tiles(rand), p, p, a, a, ind, ind,
    )

"""Arithmetic and layout shared by the Pallas kernels and their oracles.

Bit-parity between ``backend="pallas"`` and ``backend="reference"`` rests on
both sides running the SAME floating-point formula, so the pieces a kernel
and its oracle have in common live here and are called from both:

- :func:`prefix_sum` — a log-step (Hillis–Steele) inclusive scan built from
  shifted adds.  Mosaic has no ``cumsum`` lowering; in a kernel the shift is
  a masked lane rotation (:func:`shift_roll`), in an oracle a pad
  (:func:`shift_pad`).  The values added are the same, so the sums are too.
  Prefix ``i`` is summed in a tree fixed by ``i`` alone, so a window may be
  truncated past its last live element without changing any prefix.
- :func:`its_ctps` / :func:`window_pick` — the ITS arithmetic on top of it.
- walker tiles: per-walker scalars (uniforms, envelopes, outputs) travel as
  ``(8, 128)`` tiles of 1024 walkers — the TPU's native block — and a grid
  step picks or writes its own slot with a one-hot mask (exact: one nonzero
  term).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_EPS = 1e-12

#: walkers per ``(8, 128)`` tile of per-walker scalars
TILE = 1024


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` → interpret off-TPU, compile through Mosaic on TPU.

    This is the CPU/test path: a caller that must run on the chip passes
    ``interpret=False`` and fails loudly where Mosaic is unavailable.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def shift_pad(x: jax.Array, s: int) -> jax.Array:
    """``x`` moved ``s`` lanes up its last axis, zeros shifted in (jnp).

    A pad, not ``jnp.roll``: the TPU compiler aborts on some standalone
    (eagerly dispatched) rolls, whose concatenate it fuses into an unaligned
    dynamic-update-slice."""
    return jnp.pad(x[..., :-s], [(0, 0)] * (x.ndim - 1) + [(s, 0)])


def shift_roll(x: jax.Array, s: int) -> jax.Array:
    """:func:`shift_pad` inside a kernel: a lane rotation, wrapped lanes zeroed."""
    axis = x.ndim - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    return jnp.where(lane >= s, pltpu.roll(x, s, axis), jnp.zeros_like(x))


def prefix_sum(x: jax.Array, shift=shift_pad) -> jax.Array:
    """Inclusive prefix sum along the last axis by log-step shifted adds."""
    s = 1
    while s < x.shape[-1]:
        x = x + shift(x, s)
        s *= 2
    return x


def pick_lane(x: jax.Array, at: jax.Array) -> jax.Array:
    """``x[..., at]`` with ``keepdims`` by a one-hot masked sum (exact)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.sum(jnp.where(lane == at, x, jnp.zeros_like(x)), axis=-1, keepdims=True)


def its_ctps(b: jax.Array, shift=shift_pad) -> jax.Array:
    """Normalized inclusive prefix sum of non-negative biases (the CTPS).

    The total is the prefix at the row's last positive lane and every lane
    from there on is exactly 1, so lanes of zero-bias padding past a row
    never change a search — whatever the padded width.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, b.shape, b.ndim - 1)
    sums = prefix_sum(b, shift)
    last = jnp.max(jnp.where(b > 0, lane, -1), axis=-1, keepdims=True)
    total = pick_lane(sums, last)
    return jnp.where((lane >= last) & (last >= 0), 1.0, sums / jnp.maximum(total, _EPS))


def window_pick(wts, local, deg, rand, shift=shift_pad):
    """Masked ITS pick over a block-aligned edge window.

    ``wts`` (..., n) holds the row's biases at offsets ``[local, local+deg)``
    and zeros elsewhere; ``local``/``deg``/``rand`` broadcast against
    ``wts[..., :1]``.  Returns ``(pos, dead)``: the picked window offset and
    the dead-end flag, both with a trailing unit axis.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, wts.shape, wts.ndim - 1)
    mask = (lane >= local) & (lane < local + deg)
    cum = prefix_sum(wts, shift)
    total = pick_lane(cum, local + deg - 1)
    target = rand * total
    pick = jnp.sum(((cum <= target) & mask).astype(jnp.int32), axis=-1, keepdims=True)
    pos = jnp.minimum(local + pick, local + jnp.maximum(deg - 1, 0))
    dead = (deg <= 0) | (total <= _EPS)
    return pos, dead


# ---------------------------------------------------------------------------
# Walker tiles
# ---------------------------------------------------------------------------


def to_tiles(x: jax.Array) -> jax.Array:
    """``(..., W)`` per-walker values → ``(..., ceil(W/1024)*8, 128)``."""
    pad = (-x.shape[-1]) % TILE
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(x.shape[:-1] + (-1, 128))


def tile_spec(lead: tuple = ()) -> pl.BlockSpec:
    """Block of walker ``i``'s tile (leading axes, if any, taken whole)."""
    zeros = (0,) * len(lead)
    return pl.BlockSpec(lead + (8, 128), lambda i, *_: zeros + (i // TILE, 0))


def tile_mask(i) -> jax.Array:
    """``(8, 128)`` one-hot of walker ``i``'s slot in its tile."""
    t = i % TILE
    row = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
    return (row == t // 128) & (col == t % 128)


def tile_pick(tile: jax.Array, mask: jax.Array) -> jax.Array:
    """Walker's value from its ``(8, 128)`` tile, shape ``(1, 1)``."""
    return jnp.sum(jnp.where(mask, tile, jnp.zeros_like(tile)), axis=(0, 1), keepdims=True)

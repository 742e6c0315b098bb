"""CSR graphs built on the device from edge draws, and their summaries.

A graph kind (``bench/graphs/<kind>.py``) makes the directed edge draws
``(src, dst, w)``; ``csr_from_draws`` symmetrises them, drops self-loops,
stable-sorts by (src, dst) with ``lax.sort``, drops duplicates (the first
copy keeps its weight) and finds each row's start by binary search.  The
result equals ``repro.graph.csr_from_edges(n, src, dst, w, symmetrize=True)``
on the same draws: every row sorted ascending.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


class DeviceCSR(NamedTuple):
    indptr: jax.Array  # (n + 1,) int32
    indices: jax.Array  # (E,) int32, rows sorted ascending
    weights: jax.Array  # (E,) float32


@functools.partial(jax.jit, static_argnames=("n",))
def _sort_dedup(src, dst, w, *, n):
    """Symmetrise, push self-loops past every row (src = n), stable-sort by
    (src, dst), flag the first copy of each edge; returns the sorted arrays,
    the flags and their count."""
    s = jnp.concatenate([src, dst])
    d = jnp.concatenate([dst, src])
    ww = jnp.concatenate([w, w])
    s = jnp.where(s != d, s, n)
    s, d, ww = lax.sort((s, d, ww), num_keys=2, is_stable=True)
    first = jnp.concatenate([
        jnp.ones((1,), bool), (s[1:] != s[:-1]) | (d[1:] != d[:-1])
    ])
    keep = first & (s < n)
    return s, d, ww, keep, jnp.sum(keep, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("n", "num_edges"))
def _compact(s, d, ww, keep, *, n, num_edges):
    """Kept entries first, in order (a stable sort on the drop flag), then
    ``indptr`` by binary search of each row's first entry."""
    drop = (~keep).astype(jnp.int32)
    _, s, d, ww = lax.sort((drop, s, d, ww), num_keys=1, is_stable=True)
    s, d, ww = s[:num_edges], d[:num_edges], ww[:num_edges]
    rows = jnp.arange(n + 1, dtype=jnp.int32)
    indptr = jnp.searchsorted(s, rows, side="left").astype(jnp.int32)
    return DeviceCSR(indptr, d, ww)


def csr_from_draws(src, dst, w, n: int) -> DeviceCSR:
    """CSR of the symmetrised, self-loop-free, deduplicated draws."""
    s, d, ww, keep, count = _sort_dedup(src, dst, w, n=n)
    return _compact(s, d, ww, keep, n=n, num_edges=int(count))


@jax.jit
def degree_summary(indptr):
    """(max degree, non-isolated vertices) of a CSR."""
    deg = indptr[1:] - indptr[:-1]
    return jnp.max(deg), jnp.sum(deg > 0, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("count",))
def live_permutation(key, indptr, *, count):
    """The non-isolated vertices in a random order (``count`` of them)."""
    deg = indptr[1:] - indptr[:-1]
    ids = jnp.arange(deg.shape[0], dtype=jnp.int32)
    _, ids = lax.sort(((deg == 0).astype(jnp.int32), ids), num_keys=1, is_stable=True)
    return jax.random.permutation(key, ids[:count])


def host_csr(g: DeviceCSR) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    return np.asarray(g.indptr), np.asarray(g.indices), np.asarray(g.weights)

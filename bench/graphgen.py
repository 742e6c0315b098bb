"""R-MAT graphs built on the device, in CSR form.

The benchmark's own copy of the Graph500 generator, so the yardstick does not
move when the program's host generator (``repro.graph.generators``) changes.

Steps, all on the device:

1. ``edge_factor << scale`` R-MAT edge draws (quadrant probabilities a, b, c,
   d = 1 - a - b - c), one uniform per edge and level, from ``structure_key``;
2. Graph500's vertex relabelling: a random permutation of the ids, from the
   run's key, so hubs do not sit at the low ids;
3. weights U[0.1, 1.1) per drawn edge, from the run's key;
4. symmetrise, drop self-loops, stable sort by (src, dst) with ``lax.sort``,
   drop duplicates (the first copy keeps its weight) and find each row's
   start by binary search.

The result equals ``repro.graph.csr_from_edges(n, src, dst, w,
symmetrize=True)`` on the same draws: every row sorted ascending.  The edge
multiset depends only on ``structure_key``, so every run key gives an
isomorphic graph with the same number of entries, the same degree sequence
and the same compiled shapes; the run key picks the labels and the weights.
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


@functools.partial(jax.jit, static_argnames=("scale", "edge_factor", "a", "b", "c"))
def rmat_draws(structure_key, run_key, *, scale, edge_factor, a, b, c):
    """``(src, dst, w)`` of the directed R-MAT draws, relabelled and weighted."""
    n = 1 << scale
    m = edge_factor << scale

    def level(i, sd):
        src, dst = sd
        r = jax.random.uniform(jax.random.fold_in(structure_key, i), (m,))
        go_right = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        go_down = r >= a + b
        return (src | (go_down.astype(jnp.int32) << i),
                dst | (go_right.astype(jnp.int32) << i))

    zero = jnp.zeros((m,), jnp.int32)
    src, dst = lax.fori_loop(0, scale, level, (zero, zero))
    perm = jax.random.permutation(jax.random.fold_in(run_key, 0), n).astype(jnp.int32)
    w = jax.random.uniform(jax.random.fold_in(run_key, 1), (m,), jnp.float32) + 0.1
    return perm[src], perm[dst], w


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


def rmat_csr(structure_key, run_key, *, scale, edge_factor, a, b, c) -> DeviceCSR:
    src, dst, w = rmat_draws(structure_key, run_key, scale=scale,
                             edge_factor=edge_factor, a=a, b=b, c=c)
    return csr_from_draws(src, dst, w, 1 << scale)


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

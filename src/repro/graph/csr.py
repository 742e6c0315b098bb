"""CSR graph storage as a JAX pytree.

The paper stores graphs in CSR (Table II reports "Size (of CSR)").  We keep
the same layout: ``indptr`` (V+1), ``indices`` (E), optional ``weights`` (E).
All arrays are device arrays so the structure can flow through jit/shard_map.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CSRGraph:
    """Compressed sparse row graph.

    indptr:  (V+1,) int32 — neighbor list offsets.
    indices: (E,)   int32 — neighbor vertex ids.
    weights: (E,)   float32 — edge weights (all-ones if unweighted).

    INVARIANT: ``indices`` is sorted ascending within each row.  Every
    constructor in ``repro.graph`` guarantees it (``csr_from_edges``
    lexsorts; the generators build through it; partition localization
    preserves row order).  The windowed prev-membership search of the
    transition-program fast path (DESIGN.md §10) binary-searches rows and
    silently misses neighbors on unsorted rows — code that builds a
    CSRGraph directly from raw arrays must sort rows first.
    """

    indptr: jax.Array
    indices: jax.Array
    weights: jax.Array

    # -- pytree protocol -------------------------------------------------
    def tree_flatten(self):
        return (self.indptr, self.indices, self.weights), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    # -- basic properties -------------------------------------------------
    @property
    def num_vertices(self) -> int:
        return self.indptr.shape[0] - 1

    @property
    def num_edges(self) -> int:
        return self.indices.shape[0]

    def degree(self, v: jax.Array) -> jax.Array:
        """Degree of vertex (or vertices) ``v``."""
        return self.indptr[v + 1] - self.indptr[v]

    def max_degree(self) -> int:
        return int(jnp.max(self.indptr[1:] - self.indptr[:-1]))


def csr_from_edges(
    num_vertices: int,
    src: np.ndarray,
    dst: np.ndarray,
    weights: Optional[np.ndarray] = None,
    symmetrize: bool = False,
    dedup: bool = True,
) -> CSRGraph:
    """Build a CSRGraph from an edge list (host-side, numpy)."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    if weights is None:
        w = np.ones(src.shape[0], dtype=np.float32)
    else:
        w = np.asarray(weights, dtype=np.float32)
    if symmetrize:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
        w = np.concatenate([w, w])
    # Remove self loops.
    keep = src != dst
    src, dst, w = src[keep], dst[keep], w[keep]
    # stable sort by (src, dst) — the order np.lexsort((dst, src)) gives,
    # from one int64 key, which sorts several times faster on large graphs
    base = int(dst.max()) + 1 if dst.size else 1
    order = np.argsort(src * base + dst, kind="stable")
    src, dst, w = src[order], dst[order], w[order]
    if dedup and src.size:
        uniq = np.ones(src.shape[0], dtype=bool)
        uniq[1:] = (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])
        src, dst, w = src[uniq], dst[uniq], w[uniq]
    counts = np.bincount(src, minlength=num_vertices)
    indptr = np.zeros(num_vertices + 1, dtype=np.int32)
    np.cumsum(counts, out=indptr[1:])
    return CSRGraph(
        indptr=jnp.asarray(indptr, dtype=jnp.int32),
        indices=jnp.asarray(dst, dtype=jnp.int32),
        weights=jnp.asarray(w, dtype=jnp.float32),
    )


def degrees(graph: CSRGraph) -> jax.Array:
    return graph.indptr[1:] - graph.indptr[:-1]


def neighbors_padded(
    graph: CSRGraph, vertices: jax.Array, max_degree: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Gather padded neighbor lists for a batch of vertices.

    Returns (neighbors, weights, mask) each of shape vertices.shape+(max_degree,).
    Padded slots hold neighbor=-1, weight=0, mask=False.  Degrees above
    ``max_degree`` are truncated — callers that need exactness route large
    degrees through the chunked path in ``core.select``.
    """
    start = graph.indptr[vertices]
    deg = graph.indptr[vertices + 1] - start
    offs = jnp.arange(max_degree, dtype=jnp.int32)
    idx = start[..., None] + offs
    mask = offs < deg[..., None]
    safe = jnp.where(mask, idx, 0)
    nbrs = jnp.where(mask, graph.indices[safe], -1)
    wts = jnp.where(mask, graph.weights[safe], 0.0)
    return nbrs, wts, mask

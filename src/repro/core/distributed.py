"""Multi-device sampling (paper §V-D + beyond-paper graph sharding).

Paper-faithful mode — ``instance_parallel_walk``: sampling instances are
split into equal disjoint groups across devices, the graph is replicated,
and *no* inter-device communication happens (the paper's multi-GPU design).

Beyond-paper mode — graph sharding: the CSR is range-partitioned across
devices (each device owns a contiguous vertex range, HBM use scales 1/D)
and walkers are ROUTED to the shard owning their frontier vertex each step.
That owner-routed frontier-exchange engine lives in ``repro.shard``
(DESIGN.md §12); :func:`graph_sharded_walk` survives here as a thin
compatibility wrapper over it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.api import SamplingSpec
from repro.core.engine import WalkResult, random_walk
from repro.distributed.sharding import shard_map_unchecked
from repro.graph.csr import CSRGraph
from repro.shard.walk import (  # noqa: F401  (re-exported for compatibility)
    replicated_psum_walk,
    shard_graph_for_mesh,
    sharded_random_walk,
)


def instance_parallel_walk(
    mesh: Mesh,
    graph: CSRGraph,
    seeds: jax.Array,
    key: jax.Array,
    *,
    depth: int,
    spec: SamplingSpec,
    max_degree: int,
    axis: str = "data",
) -> WalkResult:
    """Shard instances over ``axis``; replicate the graph; zero collectives."""

    @functools.partial(
        shard_map_unchecked,
        mesh=mesh,
        in_specs=(P(), P(axis), P()),
        out_specs=WalkResult(P(axis), P(axis), P()),
    )
    def _run(graph, seeds, key):
        # fold the axis SIZE, then the device index, into the key: device d
        # of a D-way mesh draws from stream (D, d), so the same seeds on 4-
        # and 8-device meshes use provably disjoint streams (distinct (D, d)
        # pairs), instead of device d colliding across mesh widths
        didx = jax.lax.axis_index(axis)
        ndev = jnp.int32(mesh.shape[axis])  # static: mesh is closed over
        kdev = jax.random.fold_in(jax.random.fold_in(key, ndev), didx)
        res = random_walk(graph, seeds, kdev,
                          depth=depth, spec=spec, max_degree=max_degree)
        return WalkResult(res.walks, res.lengths,
                          jax.lax.psum(res.sampled_edges, axis))

    return _run(graph, seeds, key)


def graph_sharded_walk(
    mesh: Mesh,
    graph: CSRGraph,
    seeds: jax.Array,
    key: jax.Array,
    *,
    depth: int,
    spec: SamplingSpec,
    max_degree: int,
    axis: str = "data",
) -> jax.Array:
    """Compatibility wrapper: walks over a device-sharded graph.

    Returns walks (I, depth+1).  Delegates to
    :func:`repro.shard.sharded_random_walk` — the owner-routed
    frontier-exchange engine (per-device HBM ∝ 1/D, one ``all_to_all`` per
    round, bit-identical to single-device ``random_walk`` for flat- and
    window-bias programs).  Specs outside that envelope take its
    replicated-``psum`` fallback, the design this wrapper used to implement
    inline.
    """
    res = sharded_random_walk(
        mesh, graph, seeds, key,
        depth=depth, spec=spec, max_degree=max_degree, axis=axis,
    )
    return res.walks

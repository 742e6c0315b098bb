"""Algorithm zoo: paper Table I expressed through the C-SAW bias API.

Each constructor returns a :class:`SamplingSpec`.  The point of the paper's
API is that *all* of these fit the same three hooks; this module is the
living proof (and the test surface for expressiveness).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.api import (
    EdgeCtx,
    SamplingSpec,
    degree_edge_bias,
    degree_vertex_bias,
    identity_update,
    uniform_edge_bias,
    uniform_vertex_bias,
    weight_edge_bias,
)
from repro.core.transition import (
    FlatBias,
    MHAcceptEpilogue,
    TeleportEpilogue,
    TransitionProgram,
    WindowBias,
)

# ---------------------------------------------------------------------------
# Random walks (NeighborSize = 1 per step)
# ---------------------------------------------------------------------------


# flat per-edge biases (CSR order) unlocking the compiled walk fast path;
# each must agree with its EdgeCtx counterpart on every real edge
def _flat_uniform(g) -> jax.Array:
    return jnp.ones_like(g.weights)


def _flat_weight(g) -> jax.Array:
    return g.weights


def _flat_degree(g) -> jax.Array:
    deg = g.indptr[1:] - g.indptr[:-1]
    return deg[g.indices].astype(jnp.float32)


def deepwalk() -> SamplingSpec:
    """Unbiased simple random walk (DeepWalk)."""
    return SamplingSpec(
        edge_bias=uniform_edge_bias,
        flat_edge_bias=_flat_uniform,
        transition=TransitionProgram(bias=FlatBias(_flat_uniform)),
        name="deepwalk",
        track_visited=False,
    )


def biased_random_walk() -> SamplingSpec:
    """Static biased walk: neighbor degree as bias (Biased DeepWalk)."""
    return SamplingSpec(
        edge_bias=degree_edge_bias,
        flat_edge_bias=_flat_degree,
        transition=TransitionProgram(bias=FlatBias(_flat_degree)),
        name="biased_rw",
        track_visited=False,
    )


def weighted_random_walk() -> SamplingSpec:
    """Static biased walk on edge weights."""
    return SamplingSpec(
        edge_bias=weight_edge_bias,
        flat_edge_bias=_flat_weight,
        transition=TransitionProgram(bias=FlatBias(_flat_weight)),
        name="weighted_rw",
        track_visited=False,
    )


def node2vec(p: float = 2.0, q: float = 0.5) -> SamplingSpec:
    """Dynamic bias from the previous step (paper Fig. 3(a)).

    The bias reads only per-edge context (u, weight, is-prev-neighbor) and
    the carried prev vertex, so it declares a :class:`WindowBias` and runs
    degree-bucketed on the kernel's gathered edge windows — never on the
    dense ``(W, max_degree)`` gather.
    """

    def edge_bias(ctx: EdgeCtx) -> jax.Array:
        w = ctx.weight
        back = ctx.u == ctx.prev[..., None]
        near = ctx.is_prev_neighbor
        first_step = (ctx.prev < 0)[..., None]
        bias = jnp.where(near, w, w * (1.0 / q))
        bias = jnp.where(back, w * (1.0 / p), bias)
        return jnp.where(first_step, w, bias)

    return SamplingSpec(
        edge_bias=edge_bias,
        needs_prev_neighbors=True,
        transition=TransitionProgram(
            bias=WindowBias(
                edge_bias, needs_prev_neighbors=True,
                needs_deg_u=False,  # bias reads weights/membership only
                max_ratio=max(1.0, 1.0 / p, 1.0 / q),
            )
        ),
        name="node2vec",
        track_visited=False,
    )


def metropolis_hastings_walk() -> SamplingSpec:
    """MHRW: propose uniform neighbor u, accept w.p. min(1, deg(v)/deg(u))."""

    def update(key: jax.Array, ctx: EdgeCtx, u: jax.Array) -> jax.Array:
        deg_u = jnp.where(u >= 0, jnp.take_along_axis(ctx.deg_u, jnp.argmax(ctx.u == u[..., None], -1)[..., None], -1)[..., 0], 1)
        accept_p = jnp.minimum(1.0, ctx.deg_v / jnp.maximum(deg_u, 1))
        stay = jax.random.uniform(key, u.shape) >= accept_p
        return jnp.where(stay & (ctx.v >= 0), ctx.v, u)

    return SamplingSpec(
        edge_bias=uniform_edge_bias,
        flat_edge_bias=_flat_uniform,
        update=update,
        transition=TransitionProgram(
            bias=FlatBias(_flat_uniform), epilogue=MHAcceptEpilogue()
        ),
        name="mhrw",
        track_visited=False,
    )


def random_walk_with_jump(jump_prob: float, num_vertices: int) -> SamplingSpec:
    """Jump to a uniformly random vertex with probability ``jump_prob``."""

    def update(key: jax.Array, ctx: EdgeCtx, u: jax.Array) -> jax.Array:
        kj, kv = jax.random.split(key)
        jump = jax.random.uniform(kj, u.shape) < jump_prob
        tgt = jax.random.randint(kv, u.shape, 0, num_vertices)
        return jnp.where(jump, tgt, u)

    return SamplingSpec(
        edge_bias=uniform_edge_bias,
        flat_edge_bias=_flat_uniform,
        update=update,
        transition=TransitionProgram(
            bias=FlatBias(_flat_uniform),
            epilogue=TeleportEpilogue(jump_prob, "uniform", num_vertices=num_vertices),
        ),
        name="rw_jump",
        track_visited=False,
    )


def random_walk_with_restart(restart_prob: float, home: int | None = None) -> SamplingSpec:
    """Restart with probability ``restart_prob``: to the predetermined vertex
    ``home``, or (``home=None``) to the walk's own seed — the engines carry
    the per-instance home vertex as transition-program state."""

    def update(key: jax.Array, ctx: EdgeCtx, u: jax.Array) -> jax.Array:
        if home is None:
            raise NotImplementedError(
                "restart-to-seed needs the engines' home carry; use the "
                "transition-program path (spec.transition), not the raw hook"
            )
        restart = jax.random.uniform(key, u.shape) < restart_prob
        return jnp.where(restart, jnp.full_like(u, home), u)

    epilogue = (
        TeleportEpilogue(restart_prob, "home")
        if home is None
        else TeleportEpilogue(restart_prob, "fixed", vertex=home)
    )
    return SamplingSpec(
        edge_bias=uniform_edge_bias,
        flat_edge_bias=_flat_uniform,
        update=update,
        transition=TransitionProgram(bias=FlatBias(_flat_uniform), epilogue=epilogue),
        name="rw_restart",
        track_visited=False,
    )


# ---------------------------------------------------------------------------
# Traversal-based sampling (frontier pools)
# ---------------------------------------------------------------------------


def unbiased_neighbor_sampling(neighbor_size: int = 2, frontier_size: int = 8) -> SamplingSpec:
    return SamplingSpec(
        edge_bias=uniform_edge_bias,
        frontier_size=frontier_size,
        neighbor_size=neighbor_size,
        per_vertex=True,
        name="neighbor_unbiased",
    )


def biased_neighbor_sampling(neighbor_size: int = 2, frontier_size: int = 8) -> SamplingSpec:
    """Constant NeighborSize per vertex, edge-weight bias."""
    return SamplingSpec(
        edge_bias=weight_edge_bias,
        frontier_size=frontier_size,
        neighbor_size=neighbor_size,
        per_vertex=True,
        name="neighbor_biased",
    )


def forest_fire_sampling(p_f: float = 0.7, max_burn: int = 8, frontier_size: int = 8) -> SamplingSpec:
    """Probabilistic neighbor sampling: geometric(p_f) burn count per vertex."""
    return SamplingSpec(
        edge_bias=uniform_edge_bias,
        frontier_size=frontier_size,
        neighbor_size=max_burn,
        per_vertex=True,
        burn_prob=p_f,
        name="forest_fire",
    )


def layer_sampling(neighbor_size: int = 8, frontier_size: int = 8) -> SamplingSpec:
    """Constant NeighborSize per *layer* over the pooled frontier neighbors."""
    return SamplingSpec(
        edge_bias=weight_edge_bias,
        frontier_size=frontier_size,
        neighbor_size=neighbor_size,
        per_vertex=False,
        name="layer",
    )


def snowball_sampling(max_degree_keep: int = 16, frontier_size: int = 8) -> SamplingSpec:
    """Add (up to a cap of) all neighbors of every sampled vertex."""
    return SamplingSpec(
        edge_bias=uniform_edge_bias,
        frontier_size=frontier_size,
        neighbor_size=max_degree_keep,
        per_vertex=True,
        name="snowball",
    )


def multi_dimensional_random_walk(frontier_size: int = 1) -> SamplingSpec:
    """MDRW / frontier sampling (paper Figs. 3(b), 4): degree-biased frontier
    selection, uniform neighbor choice, selected vertex replaced in the pool."""
    return SamplingSpec(
        vertex_bias=degree_vertex_bias,
        edge_bias=uniform_edge_bias,
        update=identity_update,
        frontier_size=frontier_size,
        neighbor_size=1,
        per_vertex=False,
        replace_selected=True,
        track_visited=False,
        name="mdrw",
    )


ALGORITHMS = {
    "deepwalk": deepwalk,
    "biased_rw": biased_random_walk,
    "weighted_rw": weighted_random_walk,
    "node2vec": node2vec,
    "mhrw": metropolis_hastings_walk,
    "neighbor_unbiased": unbiased_neighbor_sampling,
    "neighbor_biased": biased_neighbor_sampling,
    "forest_fire": forest_fire_sampling,
    "layer": layer_sampling,
    "snowball": snowball_sampling,
    "mdrw": multi_dimensional_random_walk,
}

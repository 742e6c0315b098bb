"""Graph kind ``rmat``: Graph500 R-MAT graphs built on the device, in CSR form.

The benchmark's own copy of the Graph500 generator, so the yardstick does not
move when the program's host generator (``repro.graph.generators``) changes.
Keys of the configuration's ``graph``: ``scale``, ``edge_factor``, ``a``,
``b``, ``c`` and ``structure_seed``.

Steps, all on the device:

1. ``edge_factor << scale`` R-MAT edge draws (quadrant probabilities a, b, c,
   d = 1 - a - b - c), one uniform per edge and level, from ``structure_key``;
2. Graph500's vertex relabelling: a random permutation of the ids, from the
   run's key, so hubs do not sit at the low ids;
3. weights U[0.1, 1.1) per drawn edge, from the run's key;
4. ``graphgen.csr_from_draws``: symmetrise, drop self-loops and duplicates,
   sort every row.

The edge multiset depends only on ``structure_key``, so every run key gives an
isomorphic graph with the same number of entries, the same degree sequence
and the same compiled shapes; the run key picks the labels and the weights.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
from jax import lax

import graphgen


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


def rmat_csr(structure_key, run_key, *, scale, edge_factor, a, b, c) -> graphgen.DeviceCSR:
    src, dst, w = rmat_draws(structure_key, run_key, scale=scale,
                             edge_factor=edge_factor, a=a, b=b, c=c)
    return graphgen.csr_from_draws(src, dst, w, 1 << scale)


def build(spec: dict, key) -> tuple[graphgen.DeviceCSR, dict]:
    """The graph ``spec`` describes, on the default device, with ``key`` as the
    run's key; and the seconds its steps took (``draws_s``, ``csr_s``)."""
    t = time.perf_counter()
    draws = rmat_draws(
        jax.random.PRNGKey(int(spec["structure_seed"])), key,
        scale=int(spec["scale"]), edge_factor=int(spec["edge_factor"]),
        a=float(spec["a"]), b=float(spec["b"]), c=float(spec["c"]),
    )
    jax.block_until_ready(draws)
    t_draws = time.perf_counter()
    g = graphgen.csr_from_draws(*draws, 1 << int(spec["scale"]))
    del draws
    jax.block_until_ready(g)
    return g, {"draws_s": t_draws - t, "csr_s": time.perf_counter() - t_draws}

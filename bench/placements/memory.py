"""Placement ``memory``: the whole graph as one CSR in one chip's HBM.

The configuration's graph kind builds it on the default device; closed mixes
launch ``repro.core.random_walk`` on it, open mixes are served by a
``SamplingService`` over it, and the comparison reads the whole CSR back to
the host.  Weights are float32.
"""
from __future__ import annotations

import sys
import time
from typing import Any, NamedTuple

import jax
import numpy as np

import graphgen
import parts
import reference


class InMemory(NamedTuple):
    graph: Any  # repro.graph.csr.CSRGraph
    max_degree: int


def build(config: dict, key, devices) -> tuple[InMemory, np.ndarray, dict]:
    """The graph on the default device (the first of ``devices``), its
    non-isolated vertices in a seeded order, and its summary for the
    ``graph:`` line."""
    from repro.graph.csr import CSRGraph

    if config["weights_dtype"] != "float32":
        raise ValueError("the in-HBM placement holds float32 weights only")
    kind = parts.load("graphs", config["graph"]["kind"])
    g, info = kind.build(config["graph"], jax.random.fold_in(key, 1))
    t = time.perf_counter()
    max_deg, live = (int(x) for x in graphgen.degree_summary(g.indptr))
    order = graphgen.live_permutation(jax.random.fold_in(key, 2), g.indptr, count=live)
    order = np.asarray(order)
    nbytes = int(g.indptr.nbytes + g.indices.nbytes + g.weights.nbytes)
    info.update({
        "order_s": time.perf_counter() - t,
        "vertices": int(g.indptr.shape[0] - 1), "entries": int(g.indices.shape[0]),
        "max_degree": max_deg, "non_isolated": live,
        "hubs_over_512": int(np.sum(np.diff(np.asarray(g.indptr)) > 512)),
        "bytes": nbytes, "bytes_per_device": nbytes,
    })
    return InMemory(CSRGraph(g.indptr, g.indices, g.weights), max_deg), order, info


def launch(placed: InMemory, starts: np.ndarray, key, *, depth, spec, backend):
    """One launch from ``starts``: ``(walks, sampled_edges)`` as the device
    holds them, not waited for."""
    import repro.core  # looked up at each call, so a test's patch reaches it

    res = repro.core.random_walk(
        placed.graph, jax.device_put(starts.astype(np.int32)), key, depth=depth,
        spec=spec, max_degree=placed.max_degree, backend=backend,
    )
    return res.walks, res.sampled_edges


def service(placed: InMemory, key, *, spec, depth: int, width: int, backend):
    """A ``SamplingService`` over the graph, every request bucket up to its
    per-launch cap compiled."""
    from repro.serve import SamplingService

    svc = SamplingService(placed.graph, max_degree=placed.max_degree, backend=backend, key=key)
    r = 1
    while r <= svc.config.max_requests_per_launch:
        t = time.perf_counter()
        svc.prewarm(spec, depth=depth, width=width, requests=r)
        print(f"prewarm requests={r}: {time.perf_counter() - t:.3f} s", file=sys.stderr)
        r *= 2
    return svc


def host_graph(placed: InMemory, walks) -> reference.HostGraph:
    """Every row on the host, whatever ``walks`` reads."""
    return reference.HostGraph(*graphgen.host_csr(placed.graph))

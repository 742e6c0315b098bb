"""The program's own names on the profiler's clock (``repro.obs``).

- every ``csaw.walk.*`` device scope lands in the ``op_name`` metadata of
  the compiled walk wherever its path runs: graph preparation, selection,
  the node2vec window hook, its rejection draw and the exact fallback
  rounds after it (only for a hook that declares ``max_ratio``), the hub
  tail (a row wider than the top bucket) and the epilogue (restart walks);
- the streaming service records its ``csaw.serve.*`` host spans, and the
  engine its ``csaw.walk.*`` ones, in a profiler trace.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import algorithms as alg
from repro.core import engine
from repro.core import transition as tp
from repro.graph import csr_from_edges
from repro.serve import SamplingService, StreamConfig, StreamingSamplingService


@pytest.fixture(scope="module")
def hub_graph():
    """A path 1..699 plus vertex 0 joined to 600 of them: one row (600
    entries) wider than the top bucket (512)."""
    src = [0] * 600 + list(range(1, 699))
    dst = list(range(1, 601)) + list(range(2, 700))
    w = np.random.default_rng(0).uniform(0.1, 1.1, len(src)).astype(np.float32)
    return csr_from_edges(700, np.array(src), np.array(dst), w, symmetrize=True)


def _scopes_in_compiled_walk(graph, spec) -> set:
    max_degree = int(graph.max_degree())
    methods, tables = engine.flat_method_plan(graph, tp.lower(spec), max_degree)
    hlo = engine._random_walk_impl.lower(
        graph, jnp.arange(8, dtype=jnp.int32), jax.random.PRNGKey(0), tables,
        depth=4, spec=spec, max_degree=max_degree, backend="reference",
        sel_methods=methods,
    ).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', hlo)
    return {re.findall(r"csaw\.[\w.]+", o)[-1] for o in op_names if obs.PREFIX in o}


WALK = {"csaw.walk.graph_prep", "csaw.walk.select", "csaw.walk.hub_tail"}
WINDOW_REJECT = {"csaw.walk.window_hook", "csaw.walk.window_reject", "csaw.walk.window_fallback"}
N2V = alg.node2vec()
# node2vec's own hook, without the bound that lets it draw by rejection
NO_RATIO = dataclasses.replace(
    N2V.transition, bias=dataclasses.replace(N2V.transition.bias, max_ratio=None))


@pytest.mark.parametrize("name,spec,expected", [
    ("deepwalk", alg.deepwalk(), WALK),
    ("node2vec", alg.node2vec(), WALK | WINDOW_REJECT),
    ("node2vec_no_ratio", dataclasses.replace(N2V, transition=NO_RATIO),
     WALK | {"csaw.walk.window_hook"}),
    ("restart", alg.random_walk_with_restart(0.15), WALK | {"csaw.walk.epilogue"}),
])
def test_walk_scopes_reach_the_op_name_metadata(hub_graph, name, spec, expected):
    assert _scopes_in_compiled_walk(hub_graph, spec) == expected


def _host_events(trace_dir) -> list:
    from pathlib import Path

    from jax.profiler import ProfileData

    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    data = ProfileData.from_file(str(path))
    return [ev.name for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def test_serve_spans_recorded_under_the_profiler(hub_graph, tmp_path):
    clock = [0.0]
    svc = SamplingService(hub_graph, backend="reference", key=jax.random.PRNGKey(1))
    stream = StreamingSamplingService(
        svc, StreamConfig(max_batch_window_ms=20), clock=lambda: clock[0], start=False)
    futures = [stream.submit([i, i + 1], depth=4, spec=alg.deepwalk()) for i in range(3)]
    clock[0] = 0.05
    with jax.profiler.trace(str(tmp_path)):
        assert stream.poll() == 1
    assert all(f.result(timeout=0).walks.shape == (2, 5) for f in futures)
    names = _host_events(tmp_path)
    for step in ("launch", "pack", "dispatch", "fetch", "slice", "deliver"):
        assert names.count(f"csaw.serve.{step}") == 1, step
    for step in ("plan", "dispatch"):
        assert names.count(f"csaw.walk.{step}") == 1, step


"""Tests of the trace split by the program's own names (``scopes.py``,
``trace_layers.py``), on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

A synthetic trace with scoped ops and ``csaw.`` spans gives the expected
device time per scope, host time per span and gap labels, while
``tracefile``'s own numbers for the same ops stay as they were; the
protobuf reader finds the scopes in a real CPU profile's HLO; a small run
through ``trace_layers`` records the service's spans.
"""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import scopes  # noqa: E402
import tracefile  # noqa: E402

MS = 1_000_000


@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/while/body/csaw.walk.select/csaw.walk.window_hook/gather", "csaw.walk.window_hook"),
    ("jit(f)/vmap(jit(g))/csaw.walk.graph_prep/jit(_pad)/pad", "csaw.walk.graph_prep"),
    ("csaw.walk.select/reduce_sum", "csaw.walk.select"),
    ("jit(f)/jit(reject_step_pallas)/csaw_reject_step/pallas_call", ""),
    ("", ""),
])
def test_innermost_scope(op_name, scope):
    assert scopes.innermost_scope(op_name) == scope


def _ops():
    ops = [
        tracefile.Op("while.3", 0 * MS, 15 * MS, False),  # a loop around the next two
        tracefile.Op("fusion.1", 0 * MS, 10 * MS, False),
        tracefile.Op("csaw_walk_step.1", 10 * MS, 5 * MS, True),
        tracefile.Op("fusion.2", 40 * MS, 20 * MS, False),
        tracefile.Op("csaw_walk_step.1", 90 * MS, 5 * MS, True),
    ]
    tracefile.set_self_times(ops)
    return ops


TABLE = {
    "while.3": "jit(walk)/while",
    "fusion.1": "jit(walk)/while/body/csaw.walk.select/csaw.walk.window_hook/gather",
    "csaw_walk_step.1": "jit(walk)/while/body/csaw.walk.select/csaw_walk_step/pallas_call",
    "fusion.2": "jit(walk)/csaw.walk.graph_prep/pad",
}


def test_scoped_reduction_on_a_synthetic_trace():
    ops = _ops()
    programs = [(0, 100 * MS, "jit_walk(7)")]
    found = scopes.op_scopes(ops, programs, {"jit_walk(7)": TABLE})
    assert [s for s, _ in found] == ["", "csaw.walk.window_hook", "csaw.walk.select",
                                      "csaw.walk.graph_prep", "csaw.walk.select"]
    spans = [tracefile.Span("bench.block", 0, 60 * MS),
             tracefile.Span("bench.launch", 62 * MS, 30 * MS),
             tracefile.Span("csaw.serve.launch", 10 * MS, 60 * MS),
             tracefile.Span("csaw.serve.fetch", 12 * MS, 18 * MS),
             tracefile.Span("csaw.serve.launch", 75 * MS, 20 * MS)]
    s = scopes.summarize([ops], spans, [found])
    assert s.scope_s == {"": 0.0, "csaw.walk.window_hook": pytest.approx(0.010),
                         "csaw.walk.select": pytest.approx(0.010),
                         "csaw.walk.graph_prep": pytest.approx(0.020)}
    assert s.span_s == {"csaw.serve.launch": [2, pytest.approx(0.080)],
                        "csaw.serve.fetch": [1, pytest.approx(0.018)]}
    assert s.found == {"hlo_proto": 5}
    # gaps: [60, 90) mostly under the second launch span (15 ms of it);
    # [15, 40) under the first (25 ms, the fetch span only 15)
    assert s.gaps[0] == ("csaw.serve.launch", pytest.approx(0.030))
    assert s.gaps[1] == ("csaw.serve.launch", pytest.approx(0.025))
    # a span nested in another that covers the gap as well names it
    nested = spans + [tracefile.Span("csaw.serve.dispatch", 58 * MS, 40 * MS)]
    assert scopes.summarize([ops], nested, [found]).gaps[0] == ("csaw.serve.dispatch",
                                                                 pytest.approx(0.030))
    # with no program span over a gap, the harness's span names it
    bare = scopes.summarize([ops], spans[:2], [found])
    assert bare.gaps == [("bench.launch", pytest.approx(0.030)),
                         ("bench.block", pytest.approx(0.025))]
    # tracefile's reduction of the same ops is as it was
    t = tracefile.summarize([ops], spans[:2])
    assert (t.busy_s, t.pallas_s, t.xla_s) == (pytest.approx(0.040), pytest.approx(0.010),
                                               pytest.approx(0.030))
    assert sum(s.scope_s.values()) == pytest.approx(t.pallas_s + t.xla_s)


def test_ops_outside_their_programs_hlo_have_no_scope():
    ops = _ops()
    tables = {"jit_walk(7)": TABLE}
    # another program's interval, and no program's: nothing to look up
    assert scopes.op_scopes(ops[1:2], [(0, 100 * MS, "jit_other(9)")], tables) == [("", "none")]
    assert scopes.op_scopes(ops[1:2], [], tables) == [("", "none")]
    # an op after its program's interval ended
    assert scopes.op_scopes(ops[4:], [(0, 80 * MS, "jit_walk(7)")], tables) == [("", "none")]


def test_wire_reader_finds_scopes_in_a_cpu_profile(tmp_path):
    @jax.jit
    def f(x):
        with jax.named_scope("csaw.walk.select"):
            y = jnp.sin(x) * 2
        with jax.named_scope("csaw.walk.epilogue"):
            return y + 1

    x = jnp.ones((1024,))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        f(x).block_until_ready()
    tables = scopes.program_op_names(tracefile.find_xplane(tmp_path))
    [table] = [t for name, t in tables.items() if name.startswith("jit_f(")]
    found = {scopes.innermost_scope(o) for o in table.values()}
    assert {"csaw.walk.select", "csaw.walk.epilogue"} <= found


def test_blocked_reader():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "m", BENCH / "metrics" / "blocked_ms_p50.query.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    lat = [SimpleNamespace(blocked_ms=v) for v in (1.0, 9.0, 4.0)]
    assert mod.read(SimpleNamespace(latencies=lat)) == 4.0
    # a program whose latencies have no blocked_ms reports nothing
    assert mod.read(SimpleNamespace(latencies=[SimpleNamespace(queue_ms=3.0)])) is None
    assert mod.read(SimpleNamespace(latencies=None)) is None


def test_layer_metrics_of_a_split_trace():
    import trace_layers

    s = scopes.Scoped(
        scope_s={"": 0.5, "csaw.walk.window_hook": 8.0, "csaw.walk.hub_tail": 0.5,
                 "csaw.walk.graph_prep": 1.0},
        span_s={"csaw.serve.launch": [4, 2.0], "csaw.serve.pack": [4, 0.04],
                "csaw.serve.dispatch": [4, 0.02], "csaw.serve.fetch": [4, 1.9],
                "csaw.serve.slice": [4, 0.02]},
        gaps=[], found={})
    out = trace_layers.layers(s, {"traced_hops": 1000}, {"busy_s": 10.0},
                              [SimpleNamespace(blocked_ms=v) for v in (3.0, 5.0)])
    assert out["scoped_share"] == pytest.approx(95.0)
    assert out["window_hook_ns_per_hop"] == pytest.approx(8e6)
    assert out["hub_tail_ns_per_hop"] == pytest.approx(5e5)
    assert out["graph_prep_share"] == pytest.approx(10.0)
    assert out["launch_host_ms"] == pytest.approx(20.0)
    assert out["blocked_ms_p50"] == pytest.approx(4.0)


def test_small_traced_query_run_records_the_service_spans(tmp_path):
    import trace_layers

    graph = {"kind": "rmat", "scale": 9, "structure_seed": 0, "edge_factor": 8,
             "a": 0.57, "b": 0.19, "c": 0.19}
    lines = list(trace_layers.trace(
        "lj-ppr.steady", [2**31 + 977], 1.5, hlo=tmp_path / "hlo", keep=tmp_path / "keep",
        allow_cpu=True,
        overrides={"config": {"graph": graph, "backend": "reference"},
                   "mix": {"walkers": 16, "depth": 8, "rate": 10.0,
                           "compare": {"queries": 8}}}))
    run, hlo = lines
    assert run["correct"]
    for step in ("launch", "pack", "dispatch", "fetch", "slice", "deliver"):
        assert run["span_s"][f"csaw.serve.{step}"][0] >= 1, step
    assert run["layers"]["launch_host_ms"] > 0
    assert run["layers"]["blocked_ms_p50"] >= 0
    assert run["metrics"]["blocked_ms_p50.query"]["value"] == run["layers"]["blocked_ms_p50"]
    assert hlo["hlo"] and all((tmp_path / "hlo" / h["file"]).is_file() for h in hlo["hlo"])
    assert len(list((tmp_path / "keep").glob("*.xplane.pb"))) == 1


def test_scopes_leave_the_compiled_program_as_it_was():
    import trace_layers

    def f(x):
        return jnp.cumsum(jnp.sin(x) * 2) + 1

    def scoped(x):
        with jax.named_scope("csaw.walk.select"):
            y = jnp.sin(x) * 2
        with jax.named_scope("csaw.walk.epilogue"):
            return jnp.cumsum(y) + 1

    x = jnp.ones((1024,))
    texts = [jax.jit(g).lower(x).compile().as_text() for g in (f, scoped)]
    assert texts[0] != texts[1]  # the metadata differs
    plain, named = (trace_layers.without_metadata(t) for t in texts)
    assert "csaw." not in named and "metadata=" not in named
    assert plain.replace("jit_f", "jit_scoped") == named

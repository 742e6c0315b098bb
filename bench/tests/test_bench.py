"""Tests of the benchmark harness, on the CPU at small sizes.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They cover the device CSR builder against the program's host builder, the
traffic generator's determinism, the trace reduction on a synthetic trace,
the refusal of a run without a chip, parts found by name (a placement and a
graph kind added as files, an unknown one refused), the controls (each
reference law with a broken guarantee fails the comparison), the reference's
row view against the whole graph, and whole runs with the timed path broken
underneath (each fault makes ``correct`` false).
"""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import graphgen  # noqa: E402
import load  # noqa: E402
import parts  # noqa: E402
import reference  # noqa: E402
import tracefile  # noqa: E402

rmat = parts.load("graphs", "rmat")

RMAT = dict(edge_factor=8, a=0.57, b=0.19, c=0.19)
SMALL_GRAPH = {"kind": "rmat", "scale": 9, "structure_seed": 0, **RMAT}
SEED = 2**31 + 977  # wider than 32 signed bits, as the driver's seeds are


def _env_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


# ---------------------------------------------------------------------------
# Graph, traffic, trace reduction
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scale", [6, 9, 11])
def test_device_csr_equals_csr_from_edges(scale):
    from repro.graph import csr_from_edges

    src, dst, w = rmat.rmat_draws(jax.random.PRNGKey(3), jax.random.PRNGKey(scale),
                                      scale=scale, **RMAT)
    g = graphgen.csr_from_draws(src, dst, w, 1 << scale)
    ref = csr_from_edges(1 << scale, np.asarray(src), np.asarray(dst), np.asarray(w),
                         symmetrize=True)
    for got, want in zip(graphgen.host_csr(g), (ref.indptr, ref.indices, ref.weights)):
        np.testing.assert_array_equal(got, np.asarray(want))
    indptr, indices, _ = graphgen.host_csr(g)
    for v in range(1 << scale):
        row = indices[indptr[v]:indptr[v + 1]]
        assert np.all(np.diff(row) > 0), v


def test_run_key_picks_labels_not_structure():
    """Two run keys give isomorphic graphs: same entries, same degree multiset."""
    a = rmat.rmat_csr(jax.random.PRNGKey(0), jax.random.PRNGKey(1), scale=9, **RMAT)
    b = rmat.rmat_csr(jax.random.PRNGKey(0), jax.random.PRNGKey(2), scale=9, **RMAT)
    assert a.indices.shape == b.indices.shape
    da, db = np.diff(np.asarray(a.indptr)), np.diff(np.asarray(b.indptr))
    np.testing.assert_array_equal(np.sort(da), np.sort(db))
    assert not np.array_equal(da, db)


@pytest.mark.parametrize("mix_name", ["node2vec-corpus", "deepwalk-corpus", "ppr-steady"])
def test_traffic_is_deterministic_from_its_seed(mix_name):
    mix = load.load_mix(BENCH / "traffic" / f"{mix_name}.json")
    order = np.random.default_rng(0).permutation(5000)
    if mix["kind"] == "closed":
        a = [load.closed_starts(order, 700, i) for i in range(10)]
        b = [load.closed_starts(order, 700, i) for i in range(10)]
        np.testing.assert_array_equal(np.concatenate(a), np.concatenate(b))
        # consecutive launches walk the permutation, wrapping round
        np.testing.assert_array_equal(np.concatenate(a)[:5000], order)
    else:
        d1, v1 = load.open_arrivals(mix, 40.0, order, np.random.default_rng([SEED, 7]))
        d2, v2 = load.open_arrivals(mix, 40.0, order, np.random.default_rng([SEED, 7]))
        d3, _ = load.open_arrivals(mix, 40.0, order, np.random.default_rng([SEED + 1, 7]))
        np.testing.assert_array_equal(d1, d2)
        np.testing.assert_array_equal(v1, v2)
        assert d1.size == d3.size == round(mix["rate"] * 40.0)
        assert not np.array_equal(d1, d3)
        assert np.all(np.diff(d1) >= 0) and 0 <= d1[0] and d1[-1] < 40.0


def test_zipf_ranks_follow_the_law():
    ranks = load.zipf_ranks(1000, 1.0, 200_000, np.random.default_rng(1))
    h = np.sum(1.0 / np.arange(1, 1001))
    assert abs(np.mean(ranks == 0) - 1 / h) < 0.005
    assert abs(np.mean(ranks == 9) - 0.1 / h) < 0.002


def test_trace_reduction_on_a_synthetic_trace():
    ms = 1_000_000
    ops = [
        tracefile.Op("while.3", 0 * ms, 15 * ms, False),  # a loop around the next two
        tracefile.Op("fusion.1", 0 * ms, 10 * ms, False),
        tracefile.Op("walk_step.1", 10 * ms, 5 * ms, True),
        tracefile.Op("fusion.2", 40 * ms, 20 * ms, False),
        tracefile.Op("walk_step.1", 90 * ms, 5 * ms, True),
    ]
    tracefile.set_self_times(ops)
    assert [o.self_ns for o in ops] == [0, 10 * ms, 5 * ms, 20 * ms, 5 * ms]
    spans = [tracefile.Span("bench.block", 0, 60 * ms),
             tracefile.Span("bench.launch", 62 * ms, 30 * ms)]
    s = tracefile.summarize([ops], spans)
    assert s.chips == 1
    assert s.busy_s == pytest.approx(0.040)  # [0, 15) + [40, 60) + [90, 95)
    assert s.pallas_s == pytest.approx(0.010)
    assert s.xla_s == pytest.approx(0.030)
    assert s.ops[0] == ("fusion.2", pytest.approx(0.020))
    # gaps: [15, 40) under bench.block, [60, 90) mostly under bench.launch
    assert s.gaps[0] == ("bench.launch", pytest.approx(0.030))
    assert s.gaps[1] == ("bench.block", pytest.approx(0.025))
    two = tracefile.summarize([ops, ops[1:2]], spans)
    assert two.busy_s == pytest.approx((0.040 + 0.010) / 2)


def test_hlo_text_is_named_and_kernels_told_apart():
    kernel = ('%reject_step_pallas.16 = s32[512,128]{1,0} custom-call(s32[65536]{0} %a), '
              'custom_call_target="tpu_custom_call"')
    alloc = '%custom-call.24 = s32[40,65536]{1,0} custom-call(), custom_call_target="AllocateBuffer"'
    fusion = "%fusion.85 = s32[65536]{0} fusion(s32[65244868]{0} %x), kind=kCustom"
    assert tracefile.op_name(kernel) == "reject_step_pallas.16"
    assert tracefile.op_name(fusion) == "fusion.85"
    assert tracefile._is_pallas(kernel)
    assert not tracefile._is_pallas(alloc) and not tracefile._is_pallas(fusion)


def test_metric_readers_return_nothing_without_a_trace():
    import importlib.util
    from types import SimpleNamespace

    empty = SimpleNamespace(trace=None, window_s=None, hops=None, stats=None,
                            latencies=None, peaks={"hbm_bytes_per_s": 819e9})
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        spec = importlib.util.spec_from_file_location("m", BENCH / "metrics" / f"{m['name']}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.read(empty) is None, m["name"]


def test_benchmark_json_names_existing_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in bench["workloads"]:
        load.load_mix(BENCH / "traffic" / f"{w['traffic']}.json")
    for m in bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


# ---------------------------------------------------------------------------
# No chip, no program: no result
# ---------------------------------------------------------------------------


def test_run_refuses_a_cpu_only_platform():
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "lj-walks.deepwalk",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_env_cpu(), capture_output=True, text=True, timeout=300)
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "no chip" in p.stderr


def test_run_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "lj-walks.deepwalk",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_env_cpu(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# ---------------------------------------------------------------------------
# Parts found by name
# ---------------------------------------------------------------------------

ADDED_PLACEMENT = '''"""A placement for the tests: the in-HBM one, with no service."""
import parts

memory = parts.load("placements", "memory")


def build(config, key, devices):
    return memory.build(config, key, devices)


def launch(placed, starts, key, **kwargs):
    return memory.launch(placed, starts, key, **kwargs)


def host_graph(placed, walks):
    return memory.host_graph(placed, walks)
'''

ADDED_KIND = '''"""A graph kind for the tests: R-MAT at structure seed 7."""
import parts

rmat = parts.load("graphs", "rmat")


def build(spec, key):
    return rmat.build({**spec, "structure_seed": 7}, key)
'''

#: runs cells of the checkout in the working directory, one result line each
DRIVE = '''import json, sys
sys.path.insert(0, "bench")
import run
for cell, mix in json.loads(sys.argv[1]).items():
    try:
        res = run.run(cell, int(sys.argv[2]), 1.5, False, allow_cpu=True,
                      overrides={"mix": mix}, log=lambda *a, **k: print(*a, file=sys.stderr))
    except ValueError as e:
        res = {"error": str(e)}
    print(json.dumps(res), flush=True)
'''


def test_added_placement_and_graph_kind_need_no_edit(tmp_path):
    """A checkout with a placement, a graph kind, a configuration and cells
    added as files, and every file it had left as it was, runs them."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    (tmp_path / "bench" / "placements" / "wrapped.py").write_text(ADDED_PLACEMENT)
    (tmp_path / "bench" / "graphs" / "rmat7.py").write_text(ADDED_KIND)
    config = json.loads((BENCH / "configs" / "lj-walks.json").read_text())
    config.update(name="added", placement="wrapped", backend="reference",
                  graph={**SMALL_GRAPH, "kind": "rmat7"})
    (tmp_path / "bench" / "configs" / "added.json").write_text(json.dumps(config))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "added", "source": "a test", "reduced": [], "why": "a test",
                             "file": "bench/configs/added.json"})
    bench["workloads"] += [
        {"name": f"added.{mix}", "config": "added", "traffic": mix, "chips": 1, "why": "a test"}
        for mix in ("deepwalk-corpus", "ppr-steady")]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cells = {"added.deepwalk-corpus": SMALL["lj-walks.deepwalk"],
             "added.ppr-steady": SMALL["lj-ppr.steady"]}
    p = subprocess.run([sys.executable, "-c", DRIVE, json.dumps(cells), str(SEED)],
                       cwd=tmp_path, env=_env_cpu(), capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    closed, open_ = (json.loads(line) for line in p.stdout.strip().splitlines()[-2:])
    assert closed["correct"], closed["compared"]
    [graph] = [json.loads(line[len("graph: "):]) for line in p.stderr.splitlines()
               if line.startswith("graph: ")]
    entries = {seed: rmat.rmat_csr(jax.random.PRNGKey(seed), jax.random.PRNGKey(0),
                                   scale=9, **RMAT).indices.shape[0] for seed in (0, 7)}
    assert graph["entries"] == entries[7] != entries[0]
    assert graph["bytes_per_device"] == graph["bytes"]
    # a placement with no service is refused an open mix, by name
    assert "defines no service" in open_["error"] and "wrapped.py" in open_["error"]


@pytest.mark.parametrize("folder", ["placements", "graphs"])
def test_unknown_part_fails_before_any_device_work(monkeypatch, folder):
    import run as R

    def device_work(*a, **k):
        raise AssertionError("device work began")

    monkeypatch.setattr(R, "check_device", device_work)
    config = ({"placement": "nowhere"} if folder == "placements"
              else {"graph": {**SMALL_GRAPH, "kind": "nowhere"}})
    with pytest.raises(FileNotFoundError, match=re.escape(str(BENCH / folder / "nowhere.py"))):
        R.run("lj-walks.deepwalk", SEED, 1.5, False, allow_cpu=True,
              overrides={"config": config}, log=lambda *a, **k: None)


def test_part_names_stay_in_their_folder():
    with pytest.raises(ValueError):
        parts.path("placements", "../run")


# ---------------------------------------------------------------------------
# The comparison: sound laws pass, controls fail
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def host_graph():
    g = rmat.rmat_csr(jax.random.PRNGKey(0), jax.random.PRNGKey(5), scale=15, **RMAT)
    return reference.HostGraph(*graphgen.host_csr(g))


PROGRAMS = {
    "deepwalk": ({"name": "deepwalk"}, "hub_rows", 40),
    "node2vec": ({"name": "node2vec", "p": 2.0, "q": 0.5}, "membership", 80),
    "restart": ({"name": "restart", "alpha": 0.15}, "restart", 32),
}


def _starts(host, n, seed):
    live = np.nonzero(host.deg > 0)[0]
    return np.random.default_rng(seed).choice(live, n)


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_sound_law_passes_and_control_fails(host_graph, name):
    program, broken, depth = PROGRAMS[name]
    limits = {"bad_rows": 0, "bad_hops": 0, "max_abs_z": 6.0}
    starts = _starts(host_graph, 1500, 1)
    sound = reference.Law.of(program)
    walks = reference.walks_from_law(host_graph, sound, starts, depth, np.random.default_rng(2))
    ok, _ = reference.verdict(
        reference.compare(host_graph, sound, walks, starts, np.random.default_rng(3)), limits)
    assert ok
    control = reference.Law.of(program, broken=broken)
    walks = reference.walks_from_law(host_graph, control, starts, depth, np.random.default_rng(2))
    numbers = reference.compare(host_graph, control, walks, starts, np.random.default_rng(3))
    assert numbers["bad_rows"] == numbers["bad_hops"] == 0
    assert numbers["max_abs_z"] > 3 * 6.0, numbers["z"]


def _row_view(host, walks):
    """The rows ``compare`` reads for ``walks``, cut out of the whole graph."""
    held = reference.row_vertices(walks, host.n)
    lengths = host.deg[held]
    take = np.repeat(host.start[held] - (np.cumsum(lengths) - lengths), lengths)
    take += np.arange(take.size)
    return reference.HostGraph(host.start, host.indices[take], host.weights[take],
                               held=held, max_weight=host.max_weight)


@pytest.mark.parametrize("walks_from", ["sound", "control", "altered"])
@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_row_view_compares_as_the_whole_graph(host_graph, name, walks_from):
    program, broken, depth = PROGRAMS[name]
    starts = _starts(host_graph, 300, 4)
    law = reference.Law.of(program, broken=broken if walks_from == "control" else None)
    walks = reference.walks_from_law(host_graph, law, starts, depth, np.random.default_rng(5))
    if walks_from == "altered":  # off-edge hops, one a row
        walks[:, depth // 2] = (walks[:, depth // 2] + 7919) % host_graph.n
    held = np.unique(walks)
    other = np.setdiff1d(np.nonzero(host_graph.deg)[0], held)[:1]
    # the heaviest entry sits in a row the view does not hold
    weights = host_graph.weights.copy()
    weights[host_graph.start[other]] = 3.0
    whole = reference.HostGraph(host_graph.start, host_graph.indices, weights)
    rows = _row_view(whole, walks)
    np.testing.assert_array_equal(rows.held, held)
    assert rows.keys.size == whole.deg[held].sum() < whole.keys.size
    want = reference.compare(whole, law, walks, starts, np.random.default_rng(6))
    got = reference.compare(rows, law, walks, starts, np.random.default_rng(6))
    assert got == want
    assert want["bad_hops"] > 0 if walks_from == "altered" else want["bad_hops"] == 0
    with pytest.raises(KeyError):
        rows.row_start(other)


# ---------------------------------------------------------------------------
# Whole runs with the timed path broken underneath
# ---------------------------------------------------------------------------

SMALL = {
    "lj-walks.node2vec": {"walkers": 64, "depth": 8, "compare": {"hops": 4000}},
    "lj-walks.deepwalk": {"walkers": 128, "depth": 8, "compare": {"hops": 4000}},
    "lj-ppr.steady": {"walkers": 16, "depth": 8, "rate": 10.0, "compare": {"queries": 8}},
}


def _small_run(cell):
    import run as R

    return R.run(cell, SEED, 1.5, False, allow_cpu=True,
                 overrides={"config": {"graph": SMALL_GRAPH, "backend": "reference"},
                            "mix": SMALL[cell]},
                 log=lambda *a, **k: None)


def _unchanged(walks):
    return jnp.broadcast_to(walks[:, :1], walks.shape)


def _half_left_out(walks):
    half = walks.shape[0] // 2
    return walks.at[half:].set(-1)


def _hop_altered(walks):
    # one vertex per row replaced where it is produced
    return walks.at[:, walks.shape[1] // 2].set((walks[:, walks.shape[1] // 2] + 7919) % 512)


FAULTS = {"unchanged": _unchanged, "half_left_out": _half_left_out, "hop_altered": _hop_altered}


def _break(monkeypatch, cell, fault):
    import repro.core
    import repro.serve.service as service

    if cell == "lj-ppr.steady":
        real = service.random_walk_segments

        def broken(*a, **k):
            res = real(*a, **k)
            w = res.walks
            flat = fault(w.reshape(-1, w.shape[-1])).reshape(w.shape)
            return res._replace(walks=flat)

        monkeypatch.setattr(service, "random_walk_segments", broken)
    else:
        real = repro.core.random_walk

        def broken(*a, **k):
            res = real(*a, **k)
            return res._replace(walks=fault(res.walks))

        monkeypatch.setattr(repro.core, "random_walk", broken)


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_sound_small_run_is_correct(cell):
    res = _small_run(cell)
    assert res["correct"], res["compared"]
    assert list(res)[-1] == "compared"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", sorted(SMALL))
def test_broken_timed_path_is_not_correct(monkeypatch, cell, fault):
    _break(monkeypatch, cell, FAULTS[fault])
    res = _small_run(cell)
    assert not res["correct"], res["compared"]

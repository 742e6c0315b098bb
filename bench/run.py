#!/usr/bin/env python3
"""The sampler's benchmark: one cell, one run, one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``<cell>`` is a ``workloads`` entry of ``BENCHMARK.json`` at the checkout's
root.  A run sets up (graph on the device from ``--seed``, the program's
plans, every compiled shape the cell's traffic uses), measures for
``--seconds`` seconds, checks what the measured window produced against the
plain reference (``reference.py``), and prints as its last line of standard
output one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device`` and, last, ``compared`` (each compared number with its limit).
The compared numbers are also the last lines on standard error.  With no TPU,
or fewer chips than the cell asks for, it exits 3 and prints no result.

Everything that belongs to one configuration, traffic mix or per-layer metric
is a file of its own, found by the name ``BENCHMARK.json`` gives it; a later
change adds one by adding a file and an entry, and edits no file here.

``bench/configs/<config>.json`` — a deployment:
    ``graph``: ``{"kind": "rmat", "scale", "edge_factor", "a", "b", "c",
    "structure_seed"}`` (the only kind so far; ``graphgen.py``);
    ``weights_dtype`` (``"float32"``); ``placement`` (``"memory"``);
    ``backend`` (``"pallas"``); for open mixes ``stream``, the keyword
    arguments of the program's ``StreamConfig``, and ``service_limit_ms``,
    the p99 limit ``sweep.py`` finds the knee against; ``guarantees``;
    ``source``; ``assumed``; ``reduced``.  Closed mixes drive
    ``repro.core.random_walk``; open ones ``StreamingSamplingService``.
``bench/traffic/<mix>.json`` — a mix for the one generator (``load.py``):
    ``kind`` (``"closed"`` or ``"open"``), ``program`` (``{"name":
    "deepwalk"}``, ``{"name": "node2vec", "p", "q"}`` or ``{"name":
    "restart", "alpha"}``), ``depth``, ``walkers``; open mixes add ``rate``
    (queries/s) and ``zipf``; ``compare`` (closed: ``hops``, the hops sampled
    for the comparison; open: ``queries``); ``correct``, the limit of each
    compared number (``bad_rows``, ``bad_hops``, ``max_abs_z``).
``bench/metrics/<metric>.py`` — a per-layer metric: ``read(run)`` returns
    the number, or ``None`` when the run has nothing to read.  ``run`` has
    ``trace`` (``tracefile.TraceSummary`` of the traced window, or None),
    ``window_s`` (the traced window's length), ``hops`` (hops sampled by
    the launches inside it), ``stats`` (the service's ``ServiceStats``, or
    None), ``latencies`` (its ``RequestLatency`` list, or None) and ``peaks``
    (this chip's row of ``peaks.json``).

A new program name needs its constructor in ``PROGRAMS`` below and its law in
``reference.Law``; a new graph kind needs a generator in ``graphgen.py``.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import graphgen  # noqa: E402
import load  # noqa: E402
import reference  # noqa: E402
import tracefile  # noqa: E402

#: seconds of steady window traced with ``--trace 1`` in a closed mix: the
#: trace starts after the window's first launch and stops at the first launch
#: end past this.  An open mix is traced from a second into its window to the
#: window's close: stopping the profiler takes seconds, and inside the window
#: it would hold up the generator
TRACE_SECONDS = 5.0
#: how long past the window's close an open run waits for its last answers
ANSWER_WAIT_S = 60.0
#: exit code of a run that finds no chip (or too few)
NO_CHIP = 3


class NoChip(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------


def load_spec(cell_name: str, root: Path = ROOT) -> tuple[dict, dict, dict, dict]:
    """(BENCHMARK.json, cell, configuration, traffic mix) of one cell."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if cell_name not in cells:
        raise SystemExit(f"unknown workload {cell_name!r}; known: {sorted(cells)}")
    cell = cells[cell_name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    mix = load.load_mix(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, config, mix


def import_program():
    """The system under test, from this checkout's ``src/`` and nowhere else."""
    src = ROOT / "src"
    sys.path.insert(0, str(src))
    import repro  # noqa: F401  (ImportError ends the run: no program, no result)

    where = [Path(p).resolve() for p in repro.__path__]
    if [p for p in where if p.parent != src.resolve()]:
        raise ImportError(f"repro imported from {where}, not from {src}")
    return repro


def check_device(chips: int, allow_cpu: bool):
    import jax

    devices = jax.devices()
    if not allow_cpu and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(
            f"need {chips} TPU chip(s), found {len(devices)} {devices[0].platform} device(s)"
        )
    return devices[:chips]


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent cache in a fixed directory inside the checkout."""
    import jax

    path = (root / ".jax_cache").resolve()
    path.mkdir(exist_ok=True)  # JAX writes no entry into a missing directory
    path = str(path)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: it reads an access-time file beside every entry, and one
    # entry without it stops every later write
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def run_key(seed: int):
    """A PRNG key from any whole ``seed`` (wider than 32 bits is fine)."""
    import jax

    k = jax.random.PRNGKey(0)
    for part in (seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF, seed >> 62):
        k = jax.random.fold_in(k, part)
    return k


PROGRAMS = {
    "deepwalk": lambda alg, p: alg.deepwalk(),
    "node2vec": lambda alg, p: alg.node2vec(p=float(p["p"]), q=float(p["q"])),
    "restart": lambda alg, p: alg.random_walk_with_restart(float(p["alpha"])),
}


def make_spec(program: dict):
    from repro.core import algorithms as alg

    return PROGRAMS[program["name"]](alg, program)


def build_graph(config: dict, key):
    """The configuration's graph on the device and its summary."""
    import jax

    spec = config["graph"]
    if spec["kind"] != "rmat":
        raise ValueError(f"unknown graph kind {spec['kind']!r}")
    if config["placement"] != "memory" or config["weights_dtype"] != "float32":
        raise ValueError("only the in-HBM placement with float32 weights is built so far")
    t = time.perf_counter()
    draws = graphgen.rmat_draws(
        jax.random.PRNGKey(int(spec["structure_seed"])), jax.random.fold_in(key, 1),
        scale=int(spec["scale"]), edge_factor=int(spec["edge_factor"]),
        a=float(spec["a"]), b=float(spec["b"]), c=float(spec["c"]),
    )
    jax.block_until_ready(draws)
    t_draws = time.perf_counter()
    g = graphgen.csr_from_draws(*draws, 1 << int(spec["scale"]))
    del draws
    jax.block_until_ready(g)
    t_csr = time.perf_counter()
    max_deg, live = (int(x) for x in graphgen.degree_summary(g.indptr))
    order = graphgen.live_permutation(jax.random.fold_in(key, 2), g.indptr, count=live)
    order = np.asarray(order)
    info = {
        "draws_s": t_draws - t, "csr_s": t_csr - t_draws,
        "order_s": time.perf_counter() - t_csr,
        "vertices": int(g.indptr.shape[0] - 1), "entries": int(g.indices.shape[0]),
        "max_degree": max_deg, "non_isolated": live,
        "hubs_over_512": int(np.sum(np.diff(np.asarray(g.indptr)) > 512)),
        "bytes": int(g.indptr.nbytes + g.indices.nbytes + g.weights.nbytes),
    }
    return g, order, info


# ---------------------------------------------------------------------------
# Measured windows
# ---------------------------------------------------------------------------


class Tracer:
    """The profiler over part of the window (``--trace 1``), or nothing."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = tempfile.mkdtemp(prefix="bench-trace-") if on else None
        self.started = self.stopped = None

    def start(self):
        import jax

        if self.on and self.started is None:
            jax.profiler.start_trace(self.dir)
            self.started = time.perf_counter()

    def stop(self):
        import jax

        if self.on and self.started is not None and self.stopped is None:
            self.stopped = time.perf_counter()
            jax.profiler.stop_trace()

    def summary(self):
        if self.stopped is None:
            return None, None
        path = tracefile.find_xplane(self.dir)
        if path is None:
            return None, None
        chips, spans = tracefile.read_xplane(path)
        return tracefile.summarize(chips, spans), self.stopped - self.started

    def close(self):
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(f"bench.{name}")


def closed_window(ctx, seconds: float, tracer: Tracer) -> dict:
    """Launch after launch, each blocked on, until ``seconds`` have passed."""
    import jax

    from repro.core import random_walk

    mix, graph = ctx.mix, ctx.graph
    depth, walkers = int(mix["depth"]), int(mix["walkers"])
    base = ctx.key

    def launch(i: int, starts: np.ndarray):
        with span("launch"):
            res = random_walk(
                graph, jax.device_put(starts.astype(np.int32)),
                jax.random.fold_in(base, 1000 + i), depth=depth, spec=ctx.spec,
                max_degree=ctx.info["max_degree"], backend=ctx.config["backend"],
            )
        with span("block"):
            res.walks.block_until_ready()
            return res.walks, int(res.sampled_edges)

    # warm-up: the window's own shapes, with a key the window never uses
    t = time.perf_counter()
    launch(-1, load.closed_starts(ctx.order, walkers, 0))
    print(f"warm launch: {time.perf_counter() - t:.3f} s", file=sys.stderr)
    setup_s = time.perf_counter() - T0

    outputs, hops, traced_hops, launch_s = [], 0, 0, []
    t_start = time.perf_counter()
    t_end = t_start
    i = 0
    while time.perf_counter() - t_start < seconds:
        if i == 1:
            tracer.start()
        starts = load.closed_starts(ctx.order, walkers, i)
        t_launch = time.perf_counter()
        walks, n = launch(i, starts)
        t_end = time.perf_counter()
        launch_s.append(t_end - t_launch)
        outputs.append((starts, walks))
        hops += n
        if tracer.started is not None and tracer.stopped is None:
            traced_hops += n
            if t_end - tracer.started >= TRACE_SECONDS:
                tracer.stop()
        i += 1
    tracer.stop()
    return {
        "setup_s": setup_s, "launches": i, "hops": hops,
        "elapsed_s": t_end - t_start, "traced_hops": traced_hops, "outputs": outputs,
        "launch_s": launch_s,
    }


def open_window(ctx, seconds: float, tracer: Tracer) -> dict:
    """Queries on a fixed schedule; each timed from its due time to its answer."""
    from repro.serve import AdmissionError, SamplingService
    from repro.serve.stream import StreamConfig, StreamingSamplingService

    mix = ctx.mix
    depth, walkers = int(mix["depth"]), int(mix["walkers"])
    svc = SamplingService(
        ctx.graph, max_degree=ctx.info["max_degree"], backend=ctx.config["backend"],
        key=ctx.key,
    )
    cap = svc.config.max_requests_per_launch
    r = 1
    while r <= cap:
        t = time.perf_counter()
        svc.prewarm(ctx.spec, depth=depth, width=walkers, requests=r)
        print(f"prewarm requests={r}: {time.perf_counter() - t:.3f} s", file=sys.stderr)
        r *= 2
    stream = StreamingSamplingService(svc, StreamConfig(**ctx.config.get("stream", {})))
    rng = np.random.default_rng([ctx.seed, 7])
    due, vertices = load.open_arrivals(mix, seconds, ctx.order, rng)
    done_at = np.full(due.shape, np.nan)
    futures: list = [None] * due.shape[0]
    lateness = np.zeros(due.shape)
    refused = 0
    setup_s = time.perf_counter() - T0

    def finished(i):
        def cb(_fut):
            done_at[i] = time.perf_counter()
        return cb

    t_start = time.perf_counter()
    try:
        for i, (d, v) in enumerate(zip(due, vertices)):
            if tracer.started is None and d >= 1.0:
                tracer.start()
            wait = t_start + d - time.perf_counter()
            if wait > 0:
                with span("sleep"):
                    time.sleep(wait)
            lateness[i] = time.perf_counter() - (t_start + d)
            try:
                with span("submit"):
                    fut = stream.submit(np.full((walkers,), v, np.int32), depth=depth, spec=ctx.spec)
            except AdmissionError:
                refused += 1
                continue
            futures[i] = fut
            fut.add_done_callback(finished(i))
        close_at = t_start + seconds
        while time.perf_counter() < close_at:
            time.sleep(min(0.05, max(close_at - time.perf_counter(), 0.0)))
        tracer.stop()
        answered_in_window = int(np.sum(done_at - t_start <= seconds))
        limit = close_at + ANSWER_WAIT_S
        with span("wait"):
            for fut in futures:
                if fut is not None:
                    try:
                        fut.exception(max(limit - time.perf_counter(), 0.0))
                    except TimeoutError:
                        pass
    finally:
        stream.close(flush=True)
    latency_ms = (done_at - (t_start + due)) * 1e3
    results = {}
    for i, fut in enumerate(futures):
        if fut is not None and fut.done() and fut.exception(0) is None:
            results[i] = fut.result(0)
        else:
            latency_ms[i] = np.inf
    return {
        "setup_s": setup_s, "queries": int(due.shape[0]), "refused": refused,
        "answered": len(results), "answered_in_window": answered_in_window,
        "latency_ms": latency_ms, "lateness_s": lateness, "vertices": vertices,
        "results": results, "stats": svc.stats,
    }


# ---------------------------------------------------------------------------
# The comparison
# ---------------------------------------------------------------------------


def closed_compare(ctx, out: dict, host: reference.HostGraph) -> dict:
    """A seeded sample of the window's walks against the reference."""
    rng = np.random.default_rng([ctx.seed, 11])
    depth = int(ctx.mix["depth"])
    budget = int(ctx.mix["compare"]["hops"])
    outputs = out["outputs"]
    per_launch = max(1, -(-budget // (depth * max(len(outputs), 1))))
    walks, starts = [], []
    for launch_starts, dev_walks in outputs:
        w = dev_walks.shape[0]
        rows = np.sort(rng.choice(w, size=min(per_launch, w), replace=False))
        got = np.asarray(dev_walks[rows])
        if got.shape != (rows.size, depth + 1):
            got = np.full((rows.size, depth + 1), -1)
        walks.append(got)
        starts.append(launch_starts[rows])
    return reference.compare(host, ctx.law, np.concatenate(walks), np.concatenate(starts), rng)


def open_compare(ctx, out: dict, host: reference.HostGraph) -> dict:
    """A seeded sample of the answered queries, each whole, against the
    reference; an unanswered query counts its rows as bad."""
    rng = np.random.default_rng([ctx.seed, 13])
    depth, walkers = int(ctx.mix["depth"]), int(ctx.mix["walkers"])
    n = int(ctx.mix["compare"]["queries"])
    answered = sorted(out["results"])
    pick = rng.choice(len(answered), size=min(n, len(answered)), replace=False) if answered else []
    walks, starts, bad = [], [], 0
    for j in sorted(pick):
        i = answered[j]
        res = out["results"][i]
        if res.walks.shape != (walkers, depth + 1):
            bad += walkers
            continue
        walks.append(res.walks)
        starts.append(np.full((walkers,), out["vertices"][i]))
    if not walks:
        return {"bad_rows": bad + walkers, "bad_hops": 0, "max_abs_z": 0.0, "hops": 0, "z": {}}
    numbers = reference.compare(host, ctx.law, np.concatenate(walks), np.concatenate(starts), rng)
    numbers["bad_rows"] += bad + walkers * (out["queries"] - out["refused"] - out["answered"])
    return numbers


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------


def percentile(values: np.ndarray, q: float) -> float:
    """Nearest-rank percentile; a missing answer is an infinite sample."""
    return float(np.percentile(np.asarray(values, np.float64), q, method="higher"))


def end_to_end(cell: dict, bench: dict, out: dict, seconds: float) -> dict:
    values = {"setup_s": out["setup_s"]}
    if "hops" in out:
        values["sampled_edges_per_s"] = out["hops"] / out["elapsed_s"]
    if "latency_ms" in out:
        values["query_p50_ms"] = percentile(out["latency_ms"], 50)
        values["queries_per_s"] = out["answered_in_window"] / seconds
    return _pick(bench["end_to_end"], cell["name"], values)


def _pick(metrics: list, cell: str, values: dict) -> dict:
    out = {}
    for m in metrics:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        v = values.get(m["name"])
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def per_layer(cell: dict, bench: dict, run: SimpleNamespace) -> dict:
    values = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        path = BENCH / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(f"bench_metric_{len(values)}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        values[m["name"]] = module.read(run)
    return _pick(bench["per_layer"], cell["name"], values)


def device_info(devices, peaks: dict) -> dict:
    d = devices[0]
    stats = [dev.memory_stats() or {} for dev in devices]
    return {
        "platform": d.platform, "kind": d.device_kind, "count": len(devices),
        "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0)) for s in stats),
    }


def load_peaks(kind: str, allow_missing: bool) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        if allow_missing:
            return {}
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        allow_cpu: bool = False, root: Path = ROOT, overrides: dict | None = None,
        log=print) -> dict:
    """One run of one cell; returns the result object (the last line).

    ``allow_cpu`` and ``overrides`` (keys merged into the configuration and
    the mix: ``{"config": {...}, "mix": {...}}``) exist for the tests, which
    drive a run at a small size without the chip.
    """
    bench, cell, config, mix = load_spec(cell_name, root)
    if overrides:
        config = {**config, **overrides.get("config", {})}
        mix = {**mix, **overrides.get("mix", {})}
    import_program()
    devices = check_device(int(cell["chips"]), allow_cpu)
    peaks = load_peaks(devices[0].device_kind, allow_missing=allow_cpu)
    enable_compile_cache(root)
    import jax

    key = run_key(seed)
    t = time.perf_counter()
    g, order, info = build_graph(config, key)
    info["build_s"] = time.perf_counter() - t
    info["start_s"] = t - T0
    from repro.graph.csr import CSRGraph

    ctx = SimpleNamespace(
        seed=seed, key=jax.random.fold_in(key, 3), config=config, mix=mix,
        graph=CSRGraph(g.indptr, g.indices, g.weights), order=order, info=info,
        spec=make_spec(mix["program"]), law=reference.Law.of(mix["program"]),
    )
    log(f"graph: {json.dumps(info)}", file=sys.stderr)
    tracer = Tracer(trace)
    try:
        if mix["kind"] == "closed":
            out = closed_window(ctx, seconds, tracer)
            attempted, failed = out["launches"], 0
        else:
            out = open_window(ctx, seconds, tracer)
            attempted = out["queries"]
            failed = out["queries"] - out["answered"]
        summary, window_s = tracer.summary()
    finally:
        tracer.close()
    device = device_info(devices, peaks)
    if trace:
        if summary is None:
            raise RuntimeError("the traced run recorded no trace")
        device["busy_s"] = summary.busy_s
        device["window_s"] = window_s
        layer_run = SimpleNamespace(
            trace=summary, window_s=window_s, hops=out.get("traced_hops"),
            stats=out.get("stats"), latencies=getattr(out.get("stats"), "stream_latencies", None),
            peaks=peaks,
        )
        metrics = per_layer(cell, bench, layer_run)
    else:
        metrics = end_to_end(cell, bench, out, seconds)
    log(f"window: {json.dumps(_window_note(out))}", file=sys.stderr)

    # the reference runs once the window has closed and the peak is read
    t_ref = time.perf_counter()
    host = reference.HostGraph(*graphgen.host_csr(g))
    del g, ctx.graph
    numbers = (closed_compare if mix["kind"] == "closed" else open_compare)(ctx, out, host)
    ok, rows = reference.verdict(numbers, mix["correct"])
    log(f"reference: {time.perf_counter() - t_ref:.3f} s, {numbers['hops']} hops, "
        f"z {json.dumps(numbers['z'])}", file=sys.stderr)
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": [list(x) for x in summary.ops],
                               "idle_gaps": [list(x) for x in summary.gaps]}
    result["compared"] = {name: {"value": v, "limit": lim} for name, v, lim in rows}
    for name, v, lim in rows:
        log(f"compared {name} {v!r} limit {lim!r}", file=sys.stderr)
    return result


def _window_note(out: dict) -> dict:
    note = {k: out[k] for k in ("setup_s", "launches", "hops", "elapsed_s", "traced_hops",
                                "queries", "refused", "answered", "answered_in_window")
            if k in out}
    if out.get("launch_s"):
        note.update(launch_s_median=float(np.median(out["launch_s"])),
                    launch_s_max=max(out["launch_s"]))
    if "latency_ms" in out:
        # too wide from run to run to hold to a bound at this window's ~490
        # queries; printed for the record
        note.update(query_p90_ms=percentile(out["latency_ms"], 90),
                    query_p99_ms=percentile(out["latency_ms"], 99))
    if "lateness_s" in out and out["lateness_s"].size:
        note["generator_late_p99_ms"] = float(np.percentile(out["lateness_s"], 99) * 1e3)
    if "stats" in out:
        s = out["stats"]
        note.update(launches=s.stream_launches, walkers=s.walkers_served,
                    padded_slots=s.padded_walker_slots)
    return note


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return NO_CHIP
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

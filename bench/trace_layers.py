#!/usr/bin/env python3
"""Traced runs of one cell, split by the program's own scopes and spans.

    python3 bench/trace_layers.py --workload lj-walks.node2vec --seconds 32 \\
        --seeds 11 12 13 [--hlo DIR] [--keep DIR]

Each seed is one ``run.run(..., trace=True)`` in this process, as
``--trace 1`` makes it, with the trace also reduced by ``scopes.py``: device
self time per ``csaw.*`` scope (``""`` for ops under none), count and time
per ``csaw.*`` host span, and the longest idle gaps named by the program's
spans.  It prints one JSON line per seed: the run's metrics, its ``window:``
note, that reduction, and the layer metrics read from it (``layers``).
``--keep DIR`` copies the first seed's trace file there.  ``--hlo DIR``
writes, after the last seed, the optimized HLO of the first three walk
launch programs the runs called, without its metadata, one file per
program: the HLO that two versions of the program compile to can then be
compared.  A closed cell needs ``--seconds`` longer than one launch, so
that a second, traced one starts.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import re
import shutil
import sys
from pathlib import Path

import numpy as np

import run as R  # bench/ is this script's own directory
import scopes
import tracefile

METADATA = re.compile(r",? metadata=\{[^}]*\}")
#: debug tables of compiled HLO text: source files, functions, stack frames
DEBUG_TABLE = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(.+\n)*\n?",
                         re.MULTILINE)


def without_metadata(hlo: str) -> str:
    return DEBUG_TABLE.sub("", METADATA.sub("", hlo))


def scoped_tracer(into: dict, keep=None):
    """A ``run.Tracer`` that also reduces its trace by program scope, into
    ``into["scoped"]`` (a ``scopes.Scoped``, or the reduction's error), and
    copies the first trace file under ``keep``."""

    class ScopedTracer(R.Tracer):
        def summary(self):
            out = super().summary()
            path = tracefile.find_xplane(self.dir) if self.stopped is not None else None
            if path is not None:
                try:
                    into["scoped"] = scopes.reduce(path)
                except Exception as e:  # noqa: BLE001 - the run's own numbers still count
                    into["scoped"] = f"{type(e).__name__}: {e}"
                if keep and not into.get("kept"):
                    Path(keep).mkdir(parents=True, exist_ok=True)
                    into["kept"] = shutil.copy(path, Path(keep) / path.name)
            return out

    return ScopedTracer


def layers(s: scopes.Scoped, window: dict, device: dict, latencies) -> dict:
    """The layer metrics a trace split by scope and span gives."""
    total = sum(s.scope_s.values())
    hops = window.get("traced_hops")
    out = {"scoped_share": 100.0 * (1 - s.scope_s.get("", 0.0) / total) if total else None}
    if hops:
        for name in ("window_hook", "hub_tail", "select", "epilogue", "graph_prep"):
            out[f"{name}_ns_per_hop"] = s.scope_s.get(f"csaw.walk.{name}", 0.0) * 1e9 / hops
    busy = device.get("busy_s")
    if busy:
        out["graph_prep_share"] = 100.0 * s.scope_s.get("csaw.walk.graph_prep", 0.0) / busy
    launches = s.span_s.get("csaw.serve.launch", [0, 0.0])[0]
    if launches:
        host = sum(s.span_s.get(f"csaw.serve.{k}", [0, 0.0])[1]
                   for k in ("pack", "dispatch", "slice"))
        out["launch_host_ms"] = host * 1e3 / launches
    blocked = [getattr(lat, "blocked_ms", None) for lat in latencies or ()]
    if blocked and None not in blocked:
        out["blocked_ms_p50"] = float(np.median(blocked))
    return out


#: launch programs whose HLO ``--hlo`` writes: the first ones called (the
#: open cell compiles one per request bucket, all alike but for that axis)
HLO_PROGRAMS = 3


class LaunchRecorder:
    """Shapes and static arguments of the first ``HLO_PROGRAMS`` distinct
    walk launch programs called."""

    def __init__(self, module, names):
        self.calls = {}
        for name in names:
            setattr(module, name, self._wrap(name, getattr(module, name)))

    def _wrap(self, name, fn):
        import jax

        def recorded(*args, **kwargs):
            leaves = jax.tree_util.tree_leaves(args)
            if not any(isinstance(x, jax.core.Tracer) for x in leaves):
                shapes = jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), args)
                sig = (name, str([(x.shape, str(x.dtype)) for x in leaves]),
                       str(sorted((k, repr(v)) for k, v in kwargs.items())))
                if len(self.calls) < HLO_PROGRAMS:
                    self.calls.setdefault(sig, (fn, shapes, kwargs))
            return fn(*args, **kwargs)

        return recorded

    def write(self, out: Path) -> list:
        """One file per distinct program text, named by its first call."""
        out.mkdir(parents=True, exist_ok=True)
        written = {}
        for i, ((name, _, _), (fn, shapes, kwargs)) in enumerate(sorted(
                self.calls.items(), key=lambda kv: kv[0])):
            text = without_metadata(fn.lower(*shapes, **kwargs).compile().as_text())
            sha = hashlib.sha1(text.encode()).hexdigest()
            if sha not in written:
                path = out / f"{i:02d}{name}.hlo.txt"
                path.write_text(text)
                written[sha] = {"file": path.name, "sha1": sha, "lines": text.count("\n")}
        return list(written.values())


def trace(cell: str, seeds: list, seconds: float, *, hlo=None, keep=None, **run_kwargs):
    """One line per seed (see the module's docstring), then the HLO files
    written under ``hlo``; ``run_kwargs`` go to ``run.run``.  What it
    patches in ``run`` and the engine is put back when it ends."""
    R.import_program()
    from repro.core import engine

    launches = ("_random_walk_impl", "_random_walk_segments")
    real = {n: getattr(engine, n) for n in launches}
    real_open, real_tracer = R.open_window, R.Tracer
    recorder = LaunchRecorder(engine, launches)
    reduced = {}
    R.Tracer = scoped_tracer(reduced, keep)
    try:
        for seed in seeds:
            notes = {}

            def log(*a, **k):
                print(*a, file=sys.stderr)
                text = " ".join(map(str, a))
                if text.startswith("window: "):
                    notes["window"] = json.loads(text[len("window: "):])

            def open_window(ctx, seconds, tracer):
                out = real_open(ctx, seconds, tracer)
                notes["latencies"] = out["stats"].stream_latencies
                return out

            reduced["scoped"] = None
            R.open_window = open_window
            result = R.run(cell, seed, seconds, True, log=log, **run_kwargs)
            s = reduced["scoped"]
            line = {"seed": seed, "correct": result["correct"], "metrics": result["metrics"],
                    "device": result["device"], "window": notes.get("window"),
                    "breakdown": result.get("breakdown")}
            if isinstance(s, str):
                line["reduce_error"] = s
            elif s is not None:
                line.update(scope_s=s.scope_s, span_s=s.span_s, gaps=s.gaps, found=s.found,
                            layers=layers(s, notes.get("window", {}), result["device"],
                                          notes.get("latencies")))
            yield line
        if hlo:
            yield {"hlo": recorder.write(Path(hlo))}
    finally:
        R.open_window, R.Tracer = real_open, real_tracer
        for n, fn in real.items():
            setattr(engine, n, fn)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--hlo", help="directory for the launch programs' optimized HLO")
    ap.add_argument("--keep", help="directory for the first seed's trace file")
    args = ap.parse_args(argv)
    try:
        for line in trace(args.workload, args.seeds, args.seconds, hlo=args.hlo,
                          keep=args.keep):
            print(json.dumps(line), flush=True)
    except R.NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return R.NO_CHIP
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The control: the reference with one stated guarantee broken, put in the
program's place at a cell's own size, judged by the cell's own comparison.

    python3 bench/control.py --workload <cell> --seed <n> [--seed <n> ...]

For each seed it builds the cell's graph on the device, draws the walks the
comparison would sample from the window — the same rows, start vertices and
lengths — from the mix's ``control`` law, and prints one JSON line with the
compared numbers.  A sound limit makes every one of them ``correct: false``.
The benchmark's own runs never run this.

Controls (``reference.Law.broken``): ``hub_rows`` draws a hub's next vertex
from its first 512 entries only; ``membership`` skips node2vec's
prev-neighbour test (every non-return neighbour weighs 1/q); ``restart``
never restarts.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import run as R  # bench/ is this script's own directory
import graphgen
import load
import reference


def control_walks(mix: dict, order: np.ndarray, host: reference.HostGraph,
                  law: reference.Law, seed: int, seconds: float) -> tuple[np.ndarray, np.ndarray]:
    """(walks, starts) in the shape the cell's comparison samples."""
    rng = np.random.default_rng([seed, 17])
    depth, walkers = int(mix["depth"]), int(mix["walkers"])
    if mix["kind"] == "closed":
        rows = max(1, int(mix["compare"]["hops"]) // depth)
        launches = -(-rows // walkers)
        starts = np.concatenate([load.closed_starts(order, walkers, i) for i in range(launches)])
        starts = starts[np.sort(rng.choice(starts.size, rows, replace=False))]
    else:
        _, vertices = load.open_arrivals(mix, seconds, order, np.random.default_rng([seed, 7]))
        pick = rng.choice(vertices.size, min(int(mix["compare"]["queries"]), vertices.size),
                          replace=False)
        starts = np.repeat(vertices[np.sort(pick)], walkers)
    return reference.walks_from_law(host, law, starts, depth, rng), starts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length for open mixes (default: run_seconds)")
    args = ap.parse_args(argv)
    bench, cell, config, mix = R.load_spec(args.workload)
    seconds = args.seconds or float(bench["run_seconds"])
    R.check_device(int(cell["chips"]), allow_cpu=False)
    R.enable_compile_cache(R.ROOT)
    law = reference.Law.of(mix["program"], broken=mix["control"])
    for seed in args.seed:
        t = time.perf_counter()
        g, order, info = R.build_graph(config, R.run_key(seed))
        host = reference.HostGraph(*graphgen.host_csr(g))
        del g
        walks, starts = control_walks(mix, order, host, law, seed, seconds)
        numbers = reference.compare(host, law, walks, starts,
                                    np.random.default_rng([seed, 11]))
        ok, rows = reference.verdict(numbers, mix["correct"])
        print(json.dumps({"workload": args.workload, "seed": seed, "control": law.broken,
                          "correct": ok, "numbers": numbers,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except R.NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        sys.exit(R.NO_CHIP)

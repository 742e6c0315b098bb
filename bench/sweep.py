#!/usr/bin/env python3
"""Sweep the offered rate of an open cell once, to find its knee.

    python3 bench/sweep.py --workload lj-ppr.steady --seed 5 --seconds 20 --rates 10 20 40

For each rate it runs the cell's open window (one graph, one process) and
prints one JSON line: p50 and p99 from due time to answer, answers inside
the window per second, and the p99 of the window's first and last quarters
of arrivals (a last quarter far above the first means the backlog grows).
The knee is the highest rate whose p99 meets the configuration's
``service_limit_ms`` with no growing backlog; the cell's mix then offers a
fixed share of it.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run as R  # bench/ is this script's own directory


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench, cell, config, mix = R.load_spec(args.workload)
    if mix["kind"] != "open":
        raise SystemExit(f"{args.workload} is not an open cell")
    placement = R.placement_of(config, mix)
    R.import_program()
    devices = R.check_device(int(cell["chips"]), allow_cpu=False)
    R.enable_compile_cache(R.ROOT)
    ctx = R.context(placement, config, mix, args.seed, devices)
    for rate in args.rates:
        ctx.mix = {**mix, "rate": rate}
        out = R.open_window(ctx, args.seconds, R.Tracer(False))
        lat = out["latency_ms"]
        q = max(1, lat.size // 4)
        print(json.dumps({
            "rate": rate, "queries": out["queries"], "refused": out["refused"],
            "answered": out["answered"],
            "answered_per_s": out["answered_in_window"] / args.seconds,
            "p50_ms": R.percentile(lat, 50), "p99_ms": R.percentile(lat, 99),
            "meets_limit": R.percentile(lat, 99) <= float(config["service_limit_ms"]),
            "p99_first_quarter_ms": R.percentile(lat[:q], 99),
            "p99_last_quarter_ms": R.percentile(lat[-q:], 99),
            "launches": out["stats"].stream_launches,
            "generator_late_p99_ms": float(np.percentile(out["lateness_s"], 99) * 1e3),
        }), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except R.NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        sys.exit(R.NO_CHIP)

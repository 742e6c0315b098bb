"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (see DESIGN.md §9 for the
figure-to-module index).  ``python -m benchmarks.run [--only fig09,...]``.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated module keys")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache  # noqa: PLC0415

    enable_compile_cache(Path(__file__).resolve().parents[1])
    from benchmarks import (  # noqa: PLC0415
        bench_serve,
        bench_walk,
        fig09_seps,
        fig10_inmem,
        fig13_oom,
        fig16_sweep,
        fig17_scaling,
        roofline,
    )

    modules = {
        "fig09": fig09_seps,
        "fig10": fig10_inmem,  # also emits fig11/fig12 rows
        "fig13": fig13_oom,  # also emits fig14/fig15 columns
        "fig16": fig16_sweep,
        "fig17": fig17_scaling,
        "roofline": roofline,
        "walk": bench_walk,  # transition programs; writes BENCH_walk.json
        "serve": bench_serve,  # batched request serving; writes BENCH_serve.json
    }
    keys = args.only.split(",") if args.only else list(modules)
    print("name,us_per_call,derived")
    ok = True
    for k in keys:
        try:
            for r in modules[k].run():
                print(r, flush=True)
        except Exception as e:  # noqa: BLE001
            ok = False
            print(f"{k},0.0,ERROR={type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()

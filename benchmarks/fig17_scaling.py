"""Paper Fig. 17: multi-device scaling — instance-parallel AND graph-sharded.

Off the TPU each point runs in a subprocess with
``--xla_force_host_platform_device_count=N`` so the parent process keeps
its single-device view; on the TPU it runs in this process on the chips it
holds, since a child could not reach a chip this process has taken.
Wall-clock on shared host cores is not a throughput claim — the
host devices time-slice the same physical cores — so the reported figures
are the *work and memory distribution*: instances per device for the
zero-comm instance-parallel mode, and per-device CSR bytes (∝ 1/D) plus
drain wall time for the owner-routed sharded mode (``repro.shard``,
DESIGN.md §12).  The sharded sweep is written to ``BENCH_shard.json`` so
the mesh-scaling trajectory is tracked across PRs: per-device graph bytes
must fall with D while the drain keeps walking the full pl50k edge set.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import jax

from benchmarks.common import row

ROOT = pathlib.Path(__file__).resolve().parents[1]
OUT_PATH = ROOT / "BENCH_shard.json"

def _instance_parallel(n: int) -> dict:
    """Zero-collective instance-parallel walk on an ``n``-device mesh."""
    import time

    from repro.core import algorithms as alg
    from repro.core.distributed import instance_parallel_walk
    from repro.graph import powerlaw_graph

    g = powerlaw_graph(20000, exponent=2.1, seed=7, weighted=True)
    mesh = jax.make_mesh((n,), ("data",), devices=jax.devices()[:n])
    key = jax.random.PRNGKey(0)
    seeds = jax.random.randint(key, (4096,), 0, g.num_vertices)
    md = min(g.max_degree(), 512)
    run = lambda: instance_parallel_walk(mesh, g, seeds, key, depth=32,  # noqa: E731
                                         spec=alg.biased_random_walk(), max_degree=md)
    jax.block_until_ready(run().walks)
    t0 = time.perf_counter()
    res = run()
    jax.block_until_ready(res.walks)
    secs = time.perf_counter() - t0
    return {"devices": n, "secs": secs, "edges": int(res.sampled_edges)}


def _sharded(n: int, v: int, hub_bytes) -> dict:
    """Owner-routed sharded walk on an ``n``-device mesh over a ``v``-vertex
    power-law graph (``hub_bytes``: None = default hub budget, 0 = off)."""
    import time

    from repro.core import algorithms as alg
    from repro.core import backend as bk
    from repro.graph import powerlaw_graph
    from repro.graph.partition import PartitionMap, partition_by_vertex_range
    from repro.shard import sharded_random_walk

    g = powerlaw_graph(v, exponent=2.1, seed=7, weighted=True)  # 50000 = BENCH_GRAPHS["pl50k"]
    mesh = jax.make_mesh((n,), ("data",), devices=jax.devices()[:n])
    key = jax.random.PRNGKey(0)
    seeds = jax.random.randint(key, (2048,), 0, g.num_vertices)
    md = g.max_degree()
    # what one device holds: compact local-id CSR + aligned global-id edge
    # array — same layout arithmetic sharded_random_walk stages
    seg_big = max(bk.walk_bucket_plan(md)[0])
    pm = PartitionMap.create(g.num_vertices, n)
    parts = partition_by_vertex_range(g, n)
    pad_e = max((p.edge_lo % seg_big) + p.num_edges for p in parts)
    # indptr + 4 edge arrays: local ids, global ids, weights, and the sliced
    # flat bias (the benchmarked spec is flat-bias; window mode ships 3)
    bytes_per_device = 4 * ((pm.range_size + 2) + 4 * pad_e)
    run = lambda: sharded_random_walk(mesh, g, seeds, key, depth=32,  # noqa: E731
                                      spec=alg.biased_random_walk(), max_degree=md,
                                      hub_bytes=hub_bytes)
    jax.block_until_ready(run().walks)  # compile + first drain
    t0 = time.perf_counter()
    res = run()
    jax.block_until_ready(res.walks)
    secs = time.perf_counter() - t0
    st = res.stats or {}
    return {"devices": n, "secs": secs, "edges": int(res.sampled_edges),
            "bytes_per_device": int(bytes_per_device),
            "local_edges_max": int(pad_e), "total_edges": int(g.num_edges),
            "exchanged_entries": int(st.get("exchanged_entries", 0)),
            "exchange_bytes": int(st.get("exchange_bytes", 0)),
            "hub_hops": int(st.get("hub_hops", 0)),
            "num_hubs": int(st.get("num_hubs", 0)),
            "hub_replicated_edges": int(st.get("hub_replicated_edges", 0))}


# a child process gets n forced host devices: XLA_FLAGS must be set before
# the child first imports jax
_CHILD = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
from benchmarks.fig17_scaling import %s
print(json.dumps(%s(*%r)))
"""


def _measure(fn, *args, timeout: int = 1800) -> dict:
    """``fn(n, ...)`` on ``n`` devices.  On the TPU it runs in this process
    on the chips it holds (a child could not reach them); elsewhere in a
    child with ``n`` forced host devices, so this process keeps its
    single-device view."""
    n = args[0]
    if jax.default_backend() == "tpu":
        if len(jax.devices()) < n:  # run() asks only for what _device_counts allows
            raise RuntimeError(f"fig17 needs {n} devices, this host has {len(jax.devices())}")
        return fn(*args)
    code = _CHILD % (max(n, 1), fn.__name__, fn.__name__, args)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT), str(ROOT / "src")] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=timeout,
        cwd=ROOT, env=env,
    )
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def _device_counts(wanted) -> tuple:
    """The device counts of ``wanted`` this host can measure: all of them off
    the TPU (forced host devices), only up to the chips it holds on it."""
    if jax.default_backend() != "tpu":
        return tuple(wanted)
    return tuple(n for n in wanted if n <= len(jax.devices()))


def run() -> list[str]:
    rows = []
    for n in _device_counts((1, 2, 4)):
        d = _measure(_instance_parallel, n, timeout=900)
        rows.append(row(
            f"fig17/devices={n}", d["secs"] * 1e6,
            f"SEPS={d['edges']/d['secs']:.3e};inst_per_dev={4096//n}",
        ))

    results = []
    for n in _device_counts((1, 2, 4, 8)):
        d = _measure(_sharded, n, 50000, None)
        rows.append(row(
            f"fig17/sharded_devices={n}", d["secs"] * 1e6,
            f"SEPS={d['edges']/d['secs']:.3e};"
            f"MB_per_dev={d['bytes_per_device']/1e6:.1f};"
            f"exch_MB={d['exchange_bytes']/1e6:.2f};hubs={d['num_hubs']}",
        ))
        results.append({
            "devices": n,
            "seconds": d["secs"],
            "sampled_edges_per_s": d["edges"] / d["secs"],
            "bytes_per_device": d["bytes_per_device"],
            "local_edges_max": d["local_edges_max"],
            "total_edges": d["total_edges"],
            "exchanged_entries": d["exchanged_entries"],
            "exchange_bytes": d["exchange_bytes"],
            "hub_hops": d["hub_hops"],
            "num_hubs": d["num_hubs"],
            "hub_replicated_edges": d["hub_replicated_edges"],
        })

    # the tentpole's transfer-volume claim, isolated: same drain with the hub
    # region disabled (hub_bytes=0) — exchange bytes must be measurably
    # higher without replication at the shard counts where it matters
    hub_replication = []
    for n in _device_counts((4, 8)):
        d0 = _measure(_sharded, n, 50000, 0)
        dh = next(r for r in results if r["devices"] == n)
        rows.append(row(
            f"fig17/hub_ablation D={n}", d0["secs"] * 1e6,
            f"exch_MB_nohubs={d0['exchange_bytes']/1e6:.2f};"
            f"exch_MB_hubs={dh['exchange_bytes']/1e6:.2f}",
        ))
        hub_replication.append({
            "devices": n,
            "exchange_bytes_hubs": dh["exchange_bytes"],
            "exchange_bytes_nohubs": d0["exchange_bytes"],
            "exchanged_entries_hubs": dh["exchanged_entries"],
            "exchanged_entries_nohubs": d0["exchanged_entries"],
            "hub_hops": dh["hub_hops"],
            "num_hubs": dh["num_hubs"],
            "seconds_nohubs": d0["secs"],
        })

    # the distinguishing experiment for "step cost ∝ shard size": hold E/D
    # roughly constant while the FULL graph grows ~10x.  Forced host devices
    # execute the D shards serially on the same cores, so seconds/D is the
    # per-shard drain cost — it must stay flat while total edges explode
    # (the replicated-psum design's per-step cost grows with full V instead).
    const_shard = []
    sweep = ((12500, 1), (25000, 2), (50000, 4), (100000, 8))
    counts = _device_counts(n for _, n in sweep)
    for v, n in (p for p in sweep if p[1] in counts):
        d = _measure(_sharded, n, v, None)
        per_shard = d["secs"] / n
        rows.append(row(
            f"fig17/const_shard V={v} D={n}", d["secs"] * 1e6,
            f"secs_per_shard={per_shard:.3f};edges_per_dev={d['total_edges']//n}",
        ))
        const_shard.append({
            "vertices": v,
            "devices": n,
            "total_edges": d["total_edges"],
            "edges_per_device": d["total_edges"] // n,
            "seconds": d["secs"],
            "seconds_per_shard": per_shard,
        })
    payload = {
        "bench": "owner-routed sharded walk scaling (pl50k, 2048 walkers, depth 32)",
        "device": jax.default_backend(),
        "note": "forced host devices share physical cores (wall time is not a "
                "multi-chip throughput claim): bytes_per_device is the scaling "
                "metric of the device sweep, and seconds_per_shard of the "
                "constant-shard sweep must stay flat from D=2 up while "
                "total_edges grows ~10x (D=1 pays no exchange collective, so "
                "it sits lower) — scan-step cost tracks shard size, not "
                "full-graph size",
        "results": results,
        "hub_replication": hub_replication,
        "constant_shard_scaling": const_shard,
    }
    problems = check(payload)
    if problems:
        raise RuntimeError("scaling gate failed on fresh run:\n" + "\n".join(problems))
    OUT_PATH.write_text(json.dumps(payload, indent=2) + "\n")
    return rows


#: constant-shard seconds/shard may drift this far above the D=2 point before
#: the gate trips (ISSUE: per-shard drain cost must stay flat as the full
#: graph grows ~10x with E/D held constant).  Forced host devices time-slice
#: the same physical cores, so D=8 pays real contention even when the
#: per-shard work is constant — 2.0x tolerates that while still catching a
#: cost term that scales with full-graph size (which would show ~4x here).
CONST_SHARD_TOL = 2.0
#: device-sweep bytes_per_device must fall at least this much per doubling —
#: THE scaling claim of the device sweep (see the payload note: wall time on
#: forced host devices is not a multi-chip throughput claim).  Pure range
#: sharding gives ~0.50x; the replicated hub region rides on top, but its
#: default budget also halves with D, so 0.65x leaves honest headroom.
BYTES_STEP_TOL = 0.65
#: device-sweep sampled_edges/s cliff-guard.  Host devices share cores, so
#: SEPS structurally falls with D (both the pre- and post-hub data sit near
#: 0.35-0.50x per doubling); this bound only catches a collapse — e.g. a
#: per-round cost blowup like an always-on collective — not timing noise.
SEPS_STEP_TOL = 0.25


def check(payload: dict) -> list[str]:
    """The BENCH_shard.json flatness gate (run via ``--check-only`` in CI).

    Returns a list of human-readable violations (empty = pass):

    - constant-shard ``seconds_per_shard`` within ``CONST_SHARD_TOL`` of the
      D=2 point for every D >= 2 (step cost tracks shard size, not full-V);
    - device-sweep ``bytes_per_device`` falls to at most ``BYTES_STEP_TOL``
      per doubling (per-device memory is the sweep's scaling metric);
    - device-sweep ``sampled_edges_per_s`` keeps at least ``SEPS_STEP_TOL``
      per doubling (cliff-guard only — host wall time is noisy by design);
    - hub replication strictly reduces exchange bytes at D=4/8.

    Each gate reads only the device counts the payload holds: a host with
    fewer chips than a gate's D records (and is held to) fewer points.
    """
    problems: list[str] = []
    cs = {r["devices"]: r["seconds_per_shard"] for r in payload["constant_shard_scaling"]}
    base = cs.get(2)
    if base is None:
        if any(dv > 2 for dv in cs):
            problems.append("constant_shard_scaling has no D=2 baseline")
    else:
        for dv in sorted(d for d in cs if d >= 2):
            if cs[dv] > CONST_SHARD_TOL * base:
                problems.append(
                    f"const-shard D={dv}: {cs[dv]:.3f}s/shard exceeds "
                    f"{CONST_SHARD_TOL}x the D=2 baseline ({base:.3f}s)"
                )
    seps = {r["devices"]: r["sampled_edges_per_s"] for r in payload["results"]}
    bpd = {r["devices"]: r["bytes_per_device"] for r in payload["results"]}
    chain = sorted(d for d in seps if d >= 2)
    for lo, hi in zip(chain, chain[1:]):
        if bpd[hi] > BYTES_STEP_TOL * bpd[lo]:
            problems.append(
                f"device sweep D={lo}->{hi}: bytes_per_device fell only "
                f"{bpd[lo]} -> {bpd[hi]} (> {BYTES_STEP_TOL}x retained per "
                f"doubling — shards are not shrinking with the mesh)"
            )
        if seps[hi] < SEPS_STEP_TOL * seps[lo]:
            problems.append(
                f"device sweep D={lo}->{hi}: sampled_edges/s fell "
                f"{seps[lo]:.3e} -> {seps[hi]:.3e} "
                f"(> {1 - SEPS_STEP_TOL:.0%} drop per doubling)"
            )
    for h in payload.get("hub_replication", ()):
        if h["exchange_bytes_hubs"] >= h["exchange_bytes_nohubs"]:
            problems.append(
                f"hub ablation D={h['devices']}: replication did not reduce "
                f"exchange bytes ({h['exchange_bytes_hubs']} >= "
                f"{h['exchange_bytes_nohubs']})"
            )
    return problems


def main() -> None:
    if "--check-only" in sys.argv:
        payload = json.loads(OUT_PATH.read_text())
        problems = check(payload)
        for p in problems:
            print(f"FAIL: {p}")
        if problems:
            sys.exit(1)
        print(f"scaling gate OK ({OUT_PATH.name})")
        return
    for r in run():
        print(r)
    print(f"wrote {OUT_PATH}")


if __name__ == "__main__":
    main()

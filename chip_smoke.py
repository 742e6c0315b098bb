#!/usr/bin/env python3
"""Smoke run of the sampler's main path on a TPU.

    python chip_smoke.py              # one chip: every in-memory/OOM/service phase
    python chip_smoke.py --chips 4    # four chips: the sharded phase only

Builds an R-MAT graph shaped like SNAP soc-LiveJournal1 (scale 22, edge
factor 8, weighted; ~4.2M vertices and ~67M directed entries) from
``--seed``, then drives the public entry points — ``random_walk``,
``traversal_sample``, ``SamplingService``, ``oom_random_walk`` (one chip) or
``sharded_random_walk`` and ``SamplingService(placement="sharded")`` (four
chips) — with ``backend="pallas"`` and checks each result against the
``reference`` backend or the standalone call it must equal, bit for bit.

Every line before the last is informative (sizes, seconds, mismatch
counts).  The last line is ``{"ok": true, "device": {...}}`` and is printed
only when every phase passed.  With no TPU the script exits 2 and prints no
result; any mismatch or exception exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

ROOT = Path(__file__).resolve().parent

#: soc-LiveJournal1 shape: 2^22 vertices, edge factor 8 (~67M directed entries)
SCALE = 22
#: corpus walks: walkers × steps (node2vec's published walk length is 80)
WALKERS, DEPTH = 16384, 80
#: sharded walk length: a power of two, so the sharded service launch of the
#: same width reuses the DeepWalk drain and single-device program compiled
#: for the sharded walk
SHARDED_DEPTH = 64


def log(msg: str) -> None:
    print(msg, flush=True)


class Timer:
    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self.t0
        if exc[0] is None:
            log(f"  [{self.name}] {self.seconds:.3f} s")


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# Graph and shared inputs
# ---------------------------------------------------------------------------


def build_graph(scale: int, seed: int):
    """``rmat_graph(scale, edge_factor=8, weighted=True)``, timed, on device."""
    import jax

    from repro.graph import rmat_graph

    with Timer("graph build (host) + transfer") as t:
        g = rmat_graph(scale, edge_factor=8, seed=seed, weighted=True)
        jax.block_until_ready(g)
    indptr = np.asarray(g.indptr)
    deg = np.diff(indptr)
    nbytes = sum(int(a.nbytes) for a in (g.indptr, g.indices, g.weights))
    log(
        f"graph: rmat scale={scale} edge_factor=8 seed={seed} vertices={g.num_vertices} "
        f"directed_entries={g.num_edges} max_degree={int(deg.max())} "
        f"non_isolated={int((deg > 0).sum())} bytes_on_device={nbytes} "
        f"build_seconds={t.seconds:.3f}"
    )
    return g


def live_seeds(g, n: int, seed: int) -> np.ndarray:
    """``n`` start vertices drawn from the non-isolated ones."""
    deg = np.diff(np.asarray(g.indptr))
    live = np.nonzero(deg > 0)[0]
    return np.random.default_rng(seed).choice(live, n).astype(np.int32)


def bad_hops(g, walks: np.ndarray) -> int:
    """Number of consecutive (a, b) in ``walks`` rows where b ∉ N(a)."""
    indptr = np.asarray(g.indptr).astype(np.int64)
    indices = np.asarray(g.indices).astype(np.int64)
    v = g.num_vertices
    src = np.repeat(np.arange(v, dtype=np.int64), np.diff(indptr))
    edge_keys = src * v + indices  # CSR order: sorted by (src, dst)
    a, b = walks[..., :-1].ravel().astype(np.int64), walks[..., 1:].ravel().astype(np.int64)
    hop = (a >= 0) & (b >= 0)
    q = a[hop] * v + b[hop]
    pos = np.minimum(np.searchsorted(edge_keys, q), len(edge_keys) - 1)
    return int((edge_keys[pos] != q).sum())


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------


def phase_kernels(g, walkers: int, seed: int, interpret: bool | None,
                  its_instances: int = 1024) -> None:
    """Each Pallas kernel, called directly on graph rows, vs its oracle."""
    import jax
    import jax.numpy as jnp

    from repro.core import select as sel
    from repro.kernels import ref as oracles
    from repro.kernels.alias_select import alias_step_pallas
    from repro.kernels.its_select import its_select_pallas
    from repro.kernels.walk_step import (
        pad_csr_for_kernel,
        reject_step_pallas,
        walk_step_pallas,
        walk_step_window_pallas,
    )

    ref = SimpleNamespace(**{
        n: jax.jit(getattr(oracles, n), static_argnames="seg")
        for n in ("walk_step_block_ref", "walk_step_window_block_ref",
                  "reject_step_block_ref", "alias_step_block_ref")
    }, its_select_ref=jax.jit(oracles.its_select_ref))
    rng = np.random.default_rng(seed)
    indptr = np.asarray(g.indptr)
    deg = np.diff(indptr)
    key = jax.random.PRNGKey(seed)
    rand = jax.random.uniform(key, (walkers,))
    rej = sel.rejection_randoms(jax.random.fold_in(key, 1), (walkers,))
    # alias tables in the kernel's format (row-local redirects): random ones
    # exercise the same arithmetic as built ones, without a host Vose pass
    # over every hub row
    row_deg = np.repeat(deg, deg)
    prob = rng.random(g.num_edges, dtype=np.float32)
    alias = (rng.random(g.num_edges) * row_deg).astype(np.int32)
    for seg in (128, 512):
        rows = np.nonzero((deg > 0) & (deg <= seg))[0]
        cur = rng.choice(rows, walkers)
        starts = jnp.asarray(indptr[cur], jnp.int32)
        degs = jnp.asarray(deg[cur], jnp.int32)
        inds_p, w_p = pad_csr_for_kernel(g.indices, g.weights, seg)
        a_p, p_p = pad_csr_for_kernel(jnp.asarray(alias), jnp.asarray(prob), seg)
        row_max = jnp.asarray(sel.build_row_max(indptr, np.asarray(g.weights))[cur])
        bias_win = jax.random.uniform(jax.random.fold_in(key, 2), (walkers, 2 * seg))
        local = (starts % seg)[:, None]
        offs = jnp.arange(2 * seg)
        bias_win = jnp.where((offs >= local) & (offs < local + degs[:, None]), bias_win, 0.0)
        pairs = {
            "walk_step": (
                walk_step_pallas(starts, degs, inds_p, w_p, rand, max_seg=seg, interpret=interpret),
                ref.walk_step_block_ref(starts, degs, inds_p, w_p, rand, seg=seg),
            ),
            "walk_step_window": (
                walk_step_window_pallas(
                    starts, degs, inds_p, bias_win, rand, max_seg=seg, interpret=interpret
                ),
                ref.walk_step_window_block_ref(starts, degs, inds_p, bias_win, rand, seg=seg),
            ),
            "reject_step": (
                reject_step_pallas(
                    starts, degs, inds_p, w_p, row_max, rej, max_seg=seg, interpret=interpret
                ),
                ref.reject_step_block_ref(starts, degs, inds_p, w_p, row_max, rej, seg=seg),
            ),
            "alias_step": (
                alias_step_pallas(
                    starts, degs, inds_p, p_p, a_p, rand, max_seg=seg, interpret=interpret
                ),
                ref.alias_step_block_ref(starts, degs, inds_p, p_p, a_p, rand, seg=seg),
            ),
        }
        for name, (k, o) in pairs.items():
            mism = int(jnp.sum(k != o))
            log(f"kernel {name} max_seg={seg} walkers={walkers} mismatches={mism}")
            check(mism == 0, f"{name} max_seg={seg}: {mism} mismatches vs oracle")
    for p, k in ((128, 1), (2048, 25), (512, 10)):
        shape = (its_instances, p)
        b = jax.random.uniform(jax.random.fold_in(key, p), shape)
        b = jnp.where(jax.random.uniform(jax.random.fold_in(key, p + 1), shape) < 0.2, 0.0, b)
        r = jax.random.uniform(jax.random.fold_in(key, p + 2), (its_instances, 8, k))
        mism = int(jnp.sum(its_select_pallas(b, r, interpret=interpret) != ref.its_select_ref(b, r)))
        log(f"kernel its_select pool={p} k={k} instances={its_instances} mismatches={mism}")
        check(mism == 0, f"its_select pool={p}: {mism} mismatches vs oracle")


def assert_compiled_kernel(g, seeds, key, depth: int, spec, md: int) -> None:
    """The walk program the engine compiles for ``backend="pallas"`` must
    hold a Mosaic kernel (``tpu_custom_call``), not an interpreted one."""
    from repro.core import engine
    from repro.core import transition as tp

    sel_methods, tables = engine.flat_method_plan(g, tp.lower(spec), md)
    with Timer(f"{spec.name} walk program compile"):
        text = engine._random_walk_impl.lower(
            g, seeds, key, tables, depth=depth, spec=spec, max_degree=md,
            backend="pallas", sel_methods=sel_methods,
        ).compile().as_text()
    n = text.count("tpu_custom_call")
    log(f"{spec.name} walk program: methods={sel_methods} tpu_custom_call={n}")
    check(n > 0, f"{spec.name}: compiled walk program holds no tpu_custom_call")


def phase_walk(g, spec, seeds, key, depth: int, md: int) -> None:
    """``random_walk`` on both backends: bit-identical, and every hop an edge."""
    import jax
    import jax.numpy as jnp

    from repro.core import random_walk

    out = {}
    for be in ("pallas", "reference"):
        with Timer(f"{spec.name} {be} (compile + run)") as t:
            res = random_walk(g, jnp.asarray(seeds), key, depth=depth, spec=spec,
                              max_degree=md, backend=be)
            walks = np.asarray(jax.block_until_ready(res.walks))
        out[be] = walks
        log(f"{spec.name} backend={be} walkers={len(seeds)} depth={depth} "
            f"sampled_edges={int(res.sampled_edges)} seconds={t.seconds:.3f}")
    mism = int((out["pallas"] != out["reference"]).sum())
    bad = bad_hops(g, out["pallas"])
    log(f"{spec.name} pallas_vs_reference_mismatches={mism} non_edge_hops={bad}")
    check(mism == 0, f"{spec.name}: {mism} walk entries differ between backends")
    check(bad == 0, f"{spec.name}: {bad} hops are not graph edges")
    check(bool((out["pallas"][:, 1] >= 0).all()), f"{spec.name}: seeds failed to step")


def phase_traversal(g, num_seeds: int, seed: int, fanouts=(25, 10), cap: int = 2048) -> None:
    """Two-hop neighbour sampling (one ``traversal_sample`` call per hop),
    both backends bit-identical.  Neighbourhoods are read through the dense
    gather, which truncates rows to ``cap`` entries."""
    import jax
    import jax.numpy as jnp

    from repro.core import algorithms as alg
    from repro.core import traversal_sample

    key = jax.random.PRNGKey(seed)
    md = min(cap, int(np.diff(np.asarray(g.indptr)).max()))
    out = {}
    for be in ("pallas", "reference"):
        pool = jnp.asarray(live_seeds(g, num_seeds, seed))[:, None]
        hops = []
        with Timer(f"traversal {be} (compile + run)") as t:
            for h, k in enumerate(fanouts):
                spec = alg.unbiased_neighbor_sampling(neighbor_size=k, frontier_size=pool.shape[1])
                res = traversal_sample(
                    g, pool, jax.random.fold_in(key, h), depth=1, spec=spec,
                    max_degree=md, pool_capacity=pool.shape[1], backend=be,
                )
                hops.append((np.asarray(res.edges_src), np.asarray(res.edges_dst)))
                pool = res.edges_dst
        out[be] = hops
        edges = sum(int((d >= 0).sum()) for _, d in hops)
        log(f"traversal backend={be} seeds={num_seeds} fanouts={fanouts} "
            f"sampled_edges={edges} seconds={t.seconds:.3f}")
    mism = sum(int((a != b).sum()) for x, y in zip(out["pallas"], out["reference"])
               for a, b in zip(x, y))
    bad = sum(bad_hops(g, np.stack([s, d], -1).reshape(-1, 2)) for s, d in out["pallas"])
    log(f"traversal pallas_vs_reference_mismatches={mism} non_edge_samples={bad}")
    check(mism == 0, f"traversal: {mism} sampled entries differ between backends")
    check(bad == 0, f"traversal: {bad} sampled pairs are not graph edges")


def phase_service(g, seed: int, md: int) -> None:
    """A mixed-request ``SamplingService`` drain; each request must equal its
    standalone ``random_walk`` at the cohort's padded geometry."""
    import jax
    import jax.numpy as jnp

    from repro.core import algorithms as alg
    from repro.core import random_walk
    from repro.serve import SamplingService
    from repro.serve.queue import _pow2_bucket

    svc = SamplingService(g, backend="pallas", key=jax.random.PRNGKey(seed), max_degree=md)
    rng = np.random.default_rng(seed)
    reqs = {}
    for i, (spec, n, depth) in enumerate([
        (alg.deepwalk(), 300, 12), (alg.deepwalk(), 500, 16),
        (alg.deepwalk(), 400, 10), (alg.node2vec(), 256, 16),
    ]):
        seeds = live_seeds(g, n, seed + 1 + i)
        key = jax.random.fold_in(jax.random.PRNGKey(seed), 100 + i)
        reqs[svc.submit(seeds, depth=depth, spec=spec, key=key)] = (seeds, depth, spec, key)
    with Timer("service drain (compile + run)"):
        results = svc.drain()
    cfg = svc.config
    mism = 0
    with Timer("service standalone calls"):
        for rid, (seeds, depth, spec, key) in reqs.items():
            row = np.full((_pow2_bucket(len(seeds), cfg.min_walker_bucket),), -1, np.int32)
            row[: len(seeds)] = seeds
            solo = random_walk(g, jnp.asarray(row), key, spec=spec, max_degree=md,
                               depth=_pow2_bucket(depth, cfg.min_depth_bucket), backend="pallas")
            expect = np.asarray(solo.walks)[: len(seeds), : depth + 1]
            mism += int((results[rid].walks != expect).sum())
    log(f"service requests={len(reqs)} launches={svc.stats.launches} "
        f"walkers={svc.stats.walkers_served} standalone_mismatches={mism}")
    check(mism == 0, f"service: {mism} walk entries differ from standalone calls")


def phase_oom(g, walkers: int, depth: int, seed: int, md: int) -> None:
    """``oom_random_walk`` over 4 partitions, 2 resident: both backends
    bit-identical, every hop an edge, and the same walk lengths as the
    in-memory ``random_walk`` from the same seeds."""
    import jax
    import jax.numpy as jnp

    from repro.core import algorithms as alg
    from repro.core import random_walk
    from repro.core.oom import oom_random_walk
    from repro.graph.partition import partition_by_vertex_range

    with Timer("partition (host)"):
        parts = partition_by_vertex_range(g, 4)
    seeds = live_seeds(g, walkers, seed + 7)
    key = jax.random.PRNGKey(seed + 7)
    spec = alg.deepwalk()
    out = {}
    for be in ("pallas", "reference"):
        with Timer(f"oom {be} (compile + run)") as t:
            walks, st = oom_random_walk(parts, g.num_vertices, seeds, key, depth=depth,
                                        spec=spec, max_degree=md, memory_capacity=2,
                                        backend=be, strict=True)
        out[be] = walks
        log(f"oom backend={be} partitions=4 resident=2 walkers={walkers} depth={depth} "
            f"transfers={st.partition_transfers} kernel_launches={st.kernel_launches} "
            f"seconds={t.seconds:.3f}")
    inmem = random_walk(g, jnp.asarray(seeds), key, depth=depth, spec=spec,
                        max_degree=md, backend="pallas")
    len_oom = (out["pallas"] >= 0).sum(-1)
    len_mem = np.asarray(inmem.lengths)
    mism = int((out["pallas"] != out["reference"]).sum())
    bad = bad_hops(g, out["pallas"])
    len_diff = int((len_oom != len_mem).sum())
    log(f"oom pallas_vs_reference_mismatches={mism} non_edge_hops={bad} "
        f"seed_column_equal={bool((out['pallas'][:, 0] == seeds).all())} "
        f"length_mismatches_vs_random_walk={len_diff}")
    check(mism == 0, f"oom: {mism} walk entries differ between backends")
    check(bad == 0, f"oom: {bad} hops are not graph edges")
    check(bool((out["pallas"][:, 0] == seeds).all()), "oom: seed column altered")
    check(len_diff == 0, f"oom: {len_diff} walk lengths differ from random_walk")


# ---------------------------------------------------------------------------
# Four-chip phase
# ---------------------------------------------------------------------------


def phase_sharded(g, walkers: int, depth: int, seed: int, md: int, devices) -> None:
    """``sharded_random_walk`` on a 1-D mesh vs single-device ``random_walk``
    (DeepWalk and node2vec), then a sharded service drain vs single-device
    ``random_walk``; bytes per device show each shard on its own chip."""
    import jax
    import jax.numpy as jnp

    from repro.core import algorithms as alg
    from repro.core import random_walk
    from repro.serve import SamplingService
    from repro.serve.queue import _pow2_bucket
    from repro.shard import sharded_random_walk

    mesh = jax.make_mesh((len(devices),), ("data",), devices=devices)
    key = jax.random.PRNGKey(seed + 11)
    dw = alg.deepwalk()
    seeds = live_seeds(g, walkers, seed + 11)
    for spec in (dw, alg.node2vec()):
        with Timer(f"{spec.name} sharded (compile + run)") as t:
            res = sharded_random_walk(mesh, g, seeds, key, depth=depth, spec=spec,
                                      max_degree=md, backend="pallas")
            sw = np.asarray(jax.block_until_ready(res.walks))
        with Timer(f"{spec.name} single device (compile + run)"):
            one = np.asarray(random_walk(g, jnp.asarray(seeds), key, depth=depth, spec=spec,
                                         max_degree=md, backend="pallas").walks)
        mism = int((sw != one).sum())
        st = res.stats or {}
        log(f"{spec.name} sharded devices={len(devices)} walkers={walkers} depth={depth} "
            f"exchanged_entries={st.get('exchanged_entries')} hub_hops={st.get('hub_hops')} "
            f"seconds={t.seconds:.3f} mismatches_vs_single_device={mism}")
        check(mism == 0, f"{spec.name}: sharded walks differ from single-device ({mism})")
    # the drain packs the request alone at row 0, padded to its instance
    # bucket, under the launch key: the result must equal single-device
    # random_walk over that packed row (at walkers and depth that are powers
    # of two, the launch reuses both programs compiled above)
    svc = SamplingService(g, mesh=mesh, placement="sharded", backend="pallas",
                          key=jax.random.PRNGKey(seed), max_degree=md)
    seeds = live_seeds(g, walkers, seed + 20)
    with Timer("sharded service drain + single-device call"):
        rid = svc.submit(seeds, depth=depth, spec=dw)
        got = svc.drain()[rid].walks
        row = np.full((_pow2_bucket(walkers, 128),), -1, np.int32)
        row[:walkers] = seeds
        launch_key = jax.random.fold_in(svc._oom_key, svc._oom_launch)
        one = random_walk(g, jnp.asarray(row), launch_key, spec=dw, max_degree=md,
                          depth=_pow2_bucket(depth, svc.config.min_depth_bucket),
                          backend="pallas")
        mism = int((got != np.asarray(one.walks)[:walkers, : depth + 1]).sum())
    bad = bad_hops(g, got)
    log(f"sharded service walkers={walkers} depth={depth} "
        f"launches={svc.stats.sharded_launches} single_device_mismatches={mism} "
        f"non_edge_hops={bad}")
    check(mism == 0, f"sharded service: {mism} walk entries differ from a single-device call")
    check(bad == 0, f"sharded service: {bad} hops are not graph edges")
    per_dev = [d.memory_stats() or {} for d in devices]
    peaks = [int(s.get("peak_bytes_in_use", 0)) for s in per_dev]
    log("bytes_per_device " + json.dumps({
        str(d.id): {"peak_bytes_in_use": p, "bytes_in_use": int(s.get("bytes_in_use", 0))}
        for d, p, s in zip(devices, peaks, per_dev)
    }))
    if devices[0].platform == "tpu":  # host CPU devices keep no memory stats
        shard_floor = 4 * g.num_edges // (2 * len(devices))
        check(all(p >= shard_floor for p in peaks),
              f"a device held less than half a shard's edge ids: {peaks}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="graph, seed and key stream")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase on a four-chip mesh")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); refusing to run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)} devices",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache

    t_all = time.perf_counter()
    log(f"device: {devices[0].device_kind} x{len(devices)} "
        f"compile_cache={enable_compile_cache(ROOT)}")
    g = build_graph(SCALE, args.seed)
    md = int(np.diff(np.asarray(g.indptr)).max())
    if args.chips == 4:
        phase_sharded(g, WALKERS, SHARDED_DEPTH, args.seed, md, devices[:4])
    else:
        from repro.core import algorithms as alg

        key = jax.random.PRNGKey(args.seed)
        with Timer("kernels vs oracles"):
            phase_kernels(g, WALKERS, args.seed, interpret=False)

        def walk(spec, n, depth):
            seeds = live_seeds(g, n, args.seed)
            assert_compiled_kernel(g, seeds, key, depth, spec, md)
            phase_walk(g, spec, seeds, key, depth, md)

        walk(alg.deepwalk(), WALKERS, DEPTH)
        phase_traversal(g, 1024, args.seed)
        phase_service(g, args.seed, md)
        phase_oom(g, 4096, 20, args.seed, md)
        walk(alg.node2vec(), WALKERS, DEPTH)
    log(f"total_seconds={time.perf_counter() - t_all:.3f}")
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

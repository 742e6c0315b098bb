"""fig17's device sweep on a TPU host measures in-process and only at the
device counts that host holds (the chip belongs to the one process)."""
import jax
import pytest

fig17 = pytest.importorskip("benchmarks.fig17_scaling")


def _fake_sharded(n, v, hub_bytes):
    return {"devices": n, "secs": 1.0, "edges": 1000, "bytes_per_device": 4_000_000 // n,
            "local_edges_max": 1, "total_edges": v * 4, "exchanged_entries": 0,
            "exchange_bytes": 0 if hub_bytes is None else 10, "hub_hops": 0,
            "num_hubs": 0, "hub_replicated_edges": 0}


@pytest.mark.parametrize("chips", [1, 4])
def test_tpu_host_sweeps_only_its_chips(monkeypatch, tmp_path, chips):
    calls = []

    def instance_parallel(n):
        calls.append(("instance", n))
        return {"devices": n, "secs": 1.0, "edges": 1000}

    def sharded(n, v, hub_bytes):
        calls.append(("sharded", n))
        return _fake_sharded(n, v, hub_bytes)

    devices = jax.devices()[:1] * chips
    monkeypatch.setattr(fig17.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fig17.jax, "devices", lambda: devices)
    monkeypatch.setattr(fig17, "_instance_parallel", instance_parallel)
    monkeypatch.setattr(fig17, "_sharded", sharded)
    monkeypatch.setattr(fig17, "OUT_PATH", tmp_path / "BENCH_shard.json")
    rows = fig17.run()
    assert rows and max(n for _, n in calls) == chips
    assert (tmp_path / "BENCH_shard.json").exists()


def test_gate_needs_d2_baseline_only_with_multichip_points():
    one = {"constant_shard_scaling": [{"devices": 1, "seconds_per_shard": 1.0}],
           "results": [], "hub_replication": []}
    assert fig17.check(one) == []
    four = {"constant_shard_scaling": [{"devices": 1, "seconds_per_shard": 1.0},
                                       {"devices": 4, "seconds_per_shard": 1.0}],
            "results": [], "hub_replication": []}
    assert fig17.check(four) == ["constant_shard_scaling has no D=2 baseline"]

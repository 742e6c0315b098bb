"""``chip_smoke.py`` phases at a tiny size on the CPU (kernels interpreted).

The script itself refuses to run without a TPU; these tests drive the same
phase functions on a small R-MAT graph so a broken path is found before any
chip time is spent.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke as cs  # noqa: E402
from repro.core import algorithms as alg  # noqa: E402


@pytest.fixture(scope="module")
def graph():
    return cs.build_graph(9, seed=3)


@pytest.fixture(scope="module")
def md(graph):
    return int(np.diff(np.asarray(graph.indptr)).max())


def test_kernels_match_oracles(graph):
    cs.phase_kernels(graph, 48, seed=1, interpret=True, its_instances=16)


@pytest.mark.parametrize("name", ["deepwalk", "node2vec"])
def test_walk_backends_agree(graph, md, name):
    spec = getattr(alg, name)()
    seeds = cs.live_seeds(graph, 64, seed=2)
    cs.phase_walk(graph, spec, seeds, jax.random.PRNGKey(2), 6, md)


def test_traversal_backends_agree(graph):
    cs.phase_traversal(graph, 16, seed=4, fanouts=(5, 3), cap=256)


def test_service_matches_standalone(graph, md):
    cs.phase_service(graph, seed=5, md=md)


def test_oom_matches_in_memory(graph, md):
    cs.phase_oom(graph, 128, 6, seed=6, md=md)


def test_sharded_phase_on_host_devices():
    """The ``--chips 4`` phase on four forced host devices (a child process,
    so this one keeps its single-device view)."""
    code = f"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {str(ROOT)!r})
import jax, numpy as np
import chip_smoke as cs
g = cs.build_graph(9, seed=3)
md = int(np.diff(np.asarray(g.indptr)).max())
cs.phase_sharded(g, 64, 8, seed=7, md=md, devices=jax.devices()[:4])
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "single_device_mismatches=0" in out.stdout, out.stdout[-2000:]


def test_bad_hops_counts_non_edges(graph):
    ip = np.asarray(graph.indptr)
    v = int(np.nonzero(np.diff(ip) > 0)[0][0])
    u = int(np.asarray(graph.indices)[ip[v]])
    non = next(x for x in range(graph.num_vertices)
               if x not in set(np.asarray(graph.indices)[ip[v]:ip[v + 1]]))
    assert cs.bad_hops(graph, np.array([[v, u, -1]])) == 0
    assert cs.bad_hops(graph, np.array([[v, non]])) == 1


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 2, out.stderr[-2000:]
    assert '"ok"' not in out.stdout

"""Shared test helpers.

Multi-device suites (``test_multidevice.py``, ``test_shard.py``) run each
case in a subprocess so the main test process keeps its single-device view
(the dry-run isolation rule): the child sets
``--xla_force_host_platform_device_count=8`` before importing jax, asserts
inside, and prints one JSON line the parent parses.

Every test process also drops JAX's compiled programs when it holds too many
memory maps (``_release_compiled_programs``).
"""
import json
import subprocess
import sys

import pytest

MULTIDEVICE_HEADER = """
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
"""


def run_multidevice_child(code: str, timeout: int = 420) -> dict:
    """Run ``code`` in a fresh interpreter; return its last stdout line as JSON."""
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=timeout
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _maps_held() -> int | None:
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:  # not Linux
        return None


def _map_limit() -> int | None:
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            return int(f.read())
    except (OSError, ValueError):
        return None


_MAP_LIMIT = _map_limit()


@pytest.fixture(autouse=True)
def _release_compiled_programs():
    """Each program XLA compiles for the CPU keeps a few hundred memory maps
    for its code until JAX's caches let it go, and a test file compiles
    hundreds of programs in one process.  Past the kernel's limit on maps
    (``vm.max_map_count``) the compiler aborts the process, so after a test
    that leaves the process above three quarters of the limit, drop the
    caches."""
    yield
    held = _maps_held() if _MAP_LIMIT else None
    if held is not None and held > _MAP_LIMIT * 3 // 4:
        import jax

        jax.clear_caches()

"""The five Pallas kernels compile for a described TPU v5e at real widths.

Nothing runs: the TPU compiler that ships with jaxlib lowers each kernel
for a chip that is described, not attached, and raises what Mosaic would
raise on the chip (tiling, VMEM, unsupported primitives).  ``interpret``
is pinned to ``False`` so the CPU-side interpret fallback cannot hide a
kernel that would not lower.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.alias_select import alias_step_pallas
from repro.kernels.its_select import its_select_pallas
from repro.kernels.walk_step import (
    reject_step_pallas,
    walk_step_pallas,
    walk_step_window_pallas,
)

W = 1024  # walkers per cohort
E = 1 << 21  # padded CSR edge entries


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables are written to the persistent cache but
    # cannot be read back without a chip: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes, one_chip):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


I32, F32 = jnp.int32, jnp.float32
WALKERS = [((W,), I32), ((W,), I32)]


def _walk_kernel(kernel: str, seg: int) -> tuple:
    """(function, argument shapes) of one per-walker kernel at ``seg``."""
    if kernel == "walk":
        fn = lambda s, d, i, w, r: walk_step_pallas(s, d, i, w, r, max_seg=seg, interpret=False)
        shapes = WALKERS + [((E,), I32), ((E,), F32), ((W,), F32)]
    elif kernel == "window":
        fn = lambda s, d, i, b, r: walk_step_window_pallas(
            s, d, i, b, r, max_seg=seg, interpret=False)
        shapes = WALKERS + [((E,), I32), ((W, 2 * seg), F32), ((W,), F32)]
    elif kernel == "reject":
        fn = lambda s, d, i, w, m, r: reject_step_pallas(
            s, d, i, w, m, r, max_seg=seg, interpret=False)
        shapes = WALKERS + [((E,), I32), ((E,), F32), ((W,), F32), ((W, 8, 2), F32)]
    else:
        fn = lambda s, d, i, p, a, r: alias_step_pallas(
            s, d, i, p, a, r, max_seg=seg, interpret=False)
        shapes = WALKERS + [((E,), I32), ((E,), F32), ((E,), I32), ((W,), F32)]
    return fn, shapes


@pytest.mark.parametrize("seg", [128, 512])
@pytest.mark.parametrize("kernel", ["walk", "window", "reject", "alias"])
def test_walk_kernels_compile(one_chip, kernel, seg):
    fn, shapes = _walk_kernel(kernel, seg)
    assert "tpu_custom_call" in _compile(fn, *shapes, one_chip=one_chip)


def test_walk_kernel_compiles_under_vmap(one_chip):
    """The fused service launches vmap the walk step over requests."""
    fn = jax.vmap(
        lambda s, d, r, i, w: walk_step_pallas(s, d, i, w, r, max_seg=128, interpret=False),
        in_axes=(0, 0, 0, None, None),
    )
    shapes = [((4, W), I32), ((4, W), I32), ((4, W), F32), ((E,), I32), ((E,), F32)]
    assert "tpu_custom_call" in _compile(fn, *shapes, one_chip=one_chip)


@pytest.mark.parametrize("pool,k", [(128, 1), (512, 10), (2048, 25)])
def test_its_select_compiles(one_chip, pool, k):
    fn = lambda b, r: its_select_pallas(b, r, interpret=False, with_stats=True)
    shapes = [((W, pool), F32), ((W, 32, k), F32)]
    assert "tpu_custom_call" in _compile(fn, *shapes, one_chip=one_chip)


@pytest.mark.parametrize("kernel,name", [
    ("walk", "csaw_walk_step"), ("window", "csaw_walk_step_window"),
    ("reject", "csaw_reject_step"), ("alias", "csaw_alias_step"), ("its", "csaw_its_select"),
])
def test_kernels_carry_stable_names(one_chip, kernel, name):
    """Each kernel's custom call is named by the kernel, not by the Python
    function around it, so a device trace finds it after a refactor."""
    if kernel == "its":
        fn = lambda b, r: its_select_pallas(b, r, interpret=False)
        shapes = [((W, 128), F32), ((W, 32, 1), F32)]
    else:
        fn, shapes = _walk_kernel(kernel, 128)
    calls = [line for line in _compile(fn, *shapes, one_chip=one_chip).splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls and all(re.match(rf"\s*(ROOT )?%{name}(\.\d+)? = ", c) for c in calls), calls


@pytest.mark.parametrize("rejection", [False, True])
def test_window_step_compiles(one_chip, rejection):
    """The window-bias step as the node2vec cell runs it: with a rejection
    stage its cohort rounds are ``TAIL_SCAN_ROUND`` walkers wide, without
    one ``WINDOW_TILE`` wide; both lower, kernel included."""
    from repro.core import backend as bk

    w, v = 2 * W, 1 << 16
    buckets = bk.WALK_BUCKETS

    def step(indptr, indices, weights, cur, key):
        padded = bk.pad_walk_csr(indices, weights, buckets)
        return bk.walk_step_bucketed_window(
            key, indptr, indices, weights, padded, cur,
            lambda u, wt, m, e=None, rows=None: 2.0 * wt,
            buckets=buckets, use_chunked=True, backend="pallas", interpret=False,
            envelope=2.0 * jnp.max(weights) if rejection else None,
        )

    shapes = [((v + 1,), I32), ((E,), I32), ((E,), F32), ((w,), I32), ((2,), jnp.uint32)]
    assert "tpu_custom_call" in _compile(step, *shapes, one_chip=one_chip)

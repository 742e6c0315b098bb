"""Adaptive selection runtime (core.methods, DESIGN.md §13).

Four layers:

- Alias-table construction properties (deterministic random trials, plus
  hypothesis versions when the plugin is installed): the (prob, alias)
  pair reconstructs the normalized bias exactly, including degenerate rows
  (zero bias, single edge, all-equal).
- The cost model: per-cohort picks and overrides.
- Draw-level and walk-level cross-backend bit-parity for the alias and
  rejection methods (forced via ``SamplingSpec.selection_method``),
  in-memory and out-of-memory; the sharded mesh parity runs in a
  subprocess (same harness as ``test_shard.py``).
- The explicit reference-fallback flag on ``select_without_replacement``
  and the serving ``prewarm()`` hook.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import MULTIDEVICE_HEADER as HEADER, run_multidevice_child as run_child
from repro.core import algorithms as alg
from repro.core import backend as bk
from repro.core import methods as mt
from repro.core import select as sel
from repro.core.engine import flat_method_plan, random_walk
from repro.core.oom import oom_random_walk
from repro.core.transition import lower
from repro.graph import powerlaw_graph
from repro.graph.partition import partition_by_vertex_range
from repro.kernels import ref
from repro.kernels.alias_select import alias_step_pallas
from repro.kernels.walk_step import pad_csr_for_kernel, reject_step_pallas
from repro.serve.service import SamplingService

KEY = jax.random.PRNGKey(0)


def _csr_from_rows(rows):
    """rows: list of per-row bias lists -> (indptr, bias) numpy."""
    indptr = np.zeros(len(rows) + 1, np.int64)
    for i, r in enumerate(rows):
        indptr[i + 1] = indptr[i] + len(r)
    bias = np.concatenate([np.asarray(r, np.float64) for r in rows]) if indptr[-1] \
        else np.zeros((0,), np.float64)
    return indptr, bias


def _reconstruct_pmf(indptr, bias, prob, alias):
    """The distribution an alias draw realizes, per edge (host float64)."""
    pmf = np.zeros_like(bias, dtype=np.float64)
    for v in range(len(indptr) - 1):
        s, e = int(indptr[v]), int(indptr[v + 1])
        d = e - s
        if d == 0:
            continue
        for j in range(d):
            pj = float(prob[s + j])
            pmf[s + j] += pj
            a = int(alias[s + j])
            if a >= 0:
                pmf[s + a] += 1.0 - pj
        pmf[s:e] /= d
    return pmf


def _check_rows(rows):
    indptr, bias = _csr_from_rows(rows)
    prob, alias = sel.build_alias(indptr, bias)
    assert prob.shape == bias.shape and alias.shape == bias.shape
    pmf = _reconstruct_pmf(indptr, bias, prob, alias)
    for v in range(len(rows)):
        s, e = int(indptr[v]), int(indptr[v + 1])
        tot = bias[s:e].sum()
        if e == s:
            continue
        if tot <= 0:
            # dead row: zero acceptance, every alias a -1 sentinel
            np.testing.assert_array_equal(prob[s:e], 0.0)
            np.testing.assert_array_equal(alias[s:e], -1)
        else:
            np.testing.assert_allclose(
                pmf[s:e], bias[s:e] / tot, rtol=1e-5, atol=1e-7
            )
            # redirects stay row-local
            assert alias[s:e].min() >= 0 and alias[s:e].max() < e - s


class TestAliasBuild:
    def test_reconstructs_normalized_bias_random_trials(self):
        rng = np.random.default_rng(0)
        for trial in range(25):
            rows = [
                list(rng.gamma(0.5, 2.0, size=rng.integers(0, 14)))
                for _ in range(rng.integers(1, 10))
            ]
            _check_rows(rows)

    def test_degenerate_rows(self):
        _check_rows([
            [0.0, 0.0, 0.0],     # zero-bias row -> dead
            [3.5],               # single edge -> prob 1, self alias
            [2.0, 2.0, 2.0, 2.0],  # all-equal -> prob 1 everywhere
            [],                  # empty row
            [0.0, 5.0, 0.0],     # zero-bias edges inside a live row
            [1e-12, 1e12],       # extreme skew
        ])
        indptr, bias = _csr_from_rows([[2.0, 2.0], [0.0, 7.0, 0.0]])
        prob, alias = sel.build_alias(indptr, bias)
        np.testing.assert_allclose(prob[:2], 1.0)  # all-equal: never redirect
        pmf = _reconstruct_pmf(indptr, bias, prob, alias)
        np.testing.assert_allclose(pmf[2:], [0.0, 1.0, 0.0], atol=1e-7)

    def test_subnormal_row_total(self):
        """Regression: a row whose total is subnormal (``deg / total``
        overflows float64) still builds a finite, exact table."""
        rows = [[1e-310, 2e-310, 0.0], [1e-320, 3e-320, 2e-320], [2.0, 6.0]]
        prob, _ = sel.build_alias(*_csr_from_rows(rows))
        assert np.isfinite(prob).all()
        _check_rows(rows)

    def test_reconstruction_hypothesis(self):
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        weight = st.floats(0.0, 1e6, allow_nan=False, allow_infinity=False)

        @settings(max_examples=60, deadline=None)
        @given(st.lists(st.lists(weight, max_size=12), min_size=1, max_size=8))
        def prop(rows):
            _check_rows(rows)

        prop()

    def test_row_max_hypothesis(self):
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        weight = st.floats(0.0, 1e6, allow_nan=False, allow_infinity=False)

        @settings(max_examples=60, deadline=None)
        @given(st.lists(st.lists(weight, max_size=10), min_size=1, max_size=8))
        def prop(rows):
            indptr, bias = _csr_from_rows(rows)
            rm = sel.build_row_max(indptr, bias)
            # the envelope is float32 by contract: compare with the f32 max
            expect = np.asarray([max(r) if r else 0.0 for r in rows], np.float32)
            np.testing.assert_array_equal(rm, expect)

        prop()

    def test_row_max_before_trailing_empty_row(self):
        """Regression: the row before a trailing empty row reads its own whole
        segment (a clamped start used to cut it to one edge)."""
        indptr, bias = _csr_from_rows([[1.0], [2.0, 5.0], []])
        np.testing.assert_array_equal(sel.build_row_max(indptr, bias), [1.0, 5.0, 0.0])


class TestCostModel:
    BUCKETS = (4, 16)

    def _stats(self, rows):
        indptr, bias = _csr_from_rows(rows)
        deg = np.diff(indptr)
        return deg, mt.row_stats(indptr, bias, deg)

    def test_uniform_rows_pick_rejection(self):
        deg, stats = self._stats([[1.0] * 3, [2.0] * 2, [5.0] * 8])
        methods = mt.plan_methods(deg, stats, buckets=self.BUCKETS, use_chunked=False)
        assert methods == ("rejection", "rejection")

    def test_skewed_rows_pick_alias(self):
        deg, stats = self._stats([[100.0, 1.0, 1.0], [50.0, 1.0] * 4])
        methods = mt.plan_methods(deg, stats, buckets=self.BUCKETS, use_chunked=False)
        assert methods == ("alias", "alias")

    def test_zero_bias_edge_forces_alias_even_when_uniform(self):
        # rejection would burn budget proposing the dead edge
        deg, stats = self._stats([[1.0, 1.0, 0.0]])
        methods = mt.plan_methods(deg, stats, buckets=self.BUCKETS, use_chunked=False)
        assert methods[0] == "alias"

    def test_empty_cohort_stays_its(self):
        deg, stats = self._stats([[1.0, 1.0]])  # nothing above the first bucket
        methods = mt.plan_methods(deg, stats, buckets=self.BUCKETS, use_chunked=True)
        assert methods == ("rejection", "its", "its")

    def test_override_pins_every_cohort(self):
        deg, stats = self._stats([[1.0] * 3, [9.0, 1.0] * 10])
        for o in ("its", "alias", "rejection"):
            methods = mt.plan_methods(
                deg, stats, buckets=self.BUCKETS, use_chunked=True, override=o
            )
            assert methods == (o,) * 3

    def test_plan_for_graph_caches_tables(self):
        g = powerlaw_graph(300, seed=1, weighted=True)
        mt.clear_plan_cache()
        fn = lower(alg.weighted_random_walk()).bias.fn
        m1, t1 = mt.plan_for_graph(g, fn, buckets=(128,), use_chunked=True)
        m2, t2 = mt.plan_for_graph(g, fn, buckets=(128,), use_chunked=True)
        assert m1 == m2 and not mt.is_trivial(m1)
        for a, b in zip(t1, t2):
            assert a is b  # cache hit: the very same arrays, no rebuild

    def test_deepwalk_auto_plan_is_rejection(self):
        g = powerlaw_graph(300, seed=1)
        methods, tables = flat_method_plan(g, lower(alg.deepwalk()), int(g.max_degree()))
        assert set(methods) <= {"rejection", "its"} and "rejection" in methods
        assert tables.row_max is not None and tables.prob is None

    def test_spec_override_reaches_plan(self):
        g = powerlaw_graph(300, seed=1)
        pinned = dataclasses.replace(alg.deepwalk(), selection_method="alias")
        methods, tables = flat_method_plan(g, lower(pinned), int(g.max_degree()))
        assert set(methods) == {"alias"} and tables.alias is not None


class TestDrawParity:
    """Kernel vs pure-jnp flat draw, same tables, same counted uniforms."""

    SEG = 128

    def _graph_tables(self):
        g = powerlaw_graph(600, seed=2, weighted=True)
        indptr = np.asarray(g.indptr)
        bias = np.maximum(np.asarray(g.weights, np.float64), 0.0)
        prob, alias = sel.build_alias(indptr, bias)
        rmax = sel.build_row_max(indptr, bias)
        return g, jnp.asarray(prob), jnp.asarray(alias), jnp.asarray(rmax)

    def test_alias_kernel_bit_identical(self):
        g, prob, alias, _ = self._graph_tables()
        deg_all = np.diff(np.asarray(g.indptr))
        rows = np.nonzero(deg_all > 0)[0][:256].astype(np.int32)
        starts = jnp.asarray(np.asarray(g.indptr)[rows])
        degs = jnp.asarray(deg_all[rows].astype(np.int32))
        rand = jax.random.uniform(KEY, rows.shape, dtype=jnp.float32)
        flat = sel.alias_draw_flat(
            starts, degs, prob, alias, g.indices, rand, cap=self.SEG
        )
        inds_p, _ = pad_csr_for_kernel(g.indices, g.weights, self.SEG)
        a_pad, p_pad = pad_csr_for_kernel(alias, prob, self.SEG)
        kern = alias_step_pallas(
            starts, degs, inds_p, p_pad, a_pad, rand, max_seg=self.SEG
        )
        np.testing.assert_array_equal(np.asarray(flat), np.asarray(kern))
        oracle = ref.alias_step_block_ref(
            starts, degs, inds_p, p_pad, a_pad, rand, seg=self.SEG
        )
        np.testing.assert_array_equal(np.asarray(flat), np.asarray(oracle))

    def test_rejection_kernel_bit_identical(self):
        g, _, _, rmax = self._graph_tables()
        deg_all = np.diff(np.asarray(g.indptr))
        rows = np.nonzero(deg_all > 0)[0][:256].astype(np.int32)
        starts = jnp.asarray(np.asarray(g.indptr)[rows])
        degs = jnp.asarray(deg_all[rows].astype(np.int32))
        rmv = rmax[jnp.asarray(rows)]
        rej = sel.rejection_randoms(KEY, rows.shape)
        flat = sel.rejection_draw_flat(
            starts, degs, g.weights, rmv, g.indices, rej, cap=self.SEG
        )
        inds_p, bias_p = pad_csr_for_kernel(g.indices, g.weights, self.SEG)
        kern = reject_step_pallas(
            starts, degs, inds_p, bias_p, rmv, rej, max_seg=self.SEG
        )
        np.testing.assert_array_equal(np.asarray(flat), np.asarray(kern))
        oracle = ref.reject_step_block_ref(
            starts, degs, inds_p, bias_p, rmv, rej, seg=self.SEG
        )
        np.testing.assert_array_equal(np.asarray(flat), np.asarray(oracle))
        # draws are a pure function of the counted budget: replay == replay
        again = sel.rejection_draw_flat(
            starts, degs, g.weights, rmv, g.indices, rej, cap=self.SEG
        )
        np.testing.assert_array_equal(np.asarray(flat), np.asarray(again))

    def test_dead_rows_stay_dead(self):
        indptr = jnp.asarray(np.array([0, 0, 2], np.int32))
        bias = jnp.asarray(np.array([0.0, 0.0], np.float32))
        prob, alias = sel.build_alias(np.array([0, 0, 2]), np.zeros(2))
        starts = indptr[:2]
        degs = jnp.asarray(np.array([0, 2], np.int32))
        indices = jnp.asarray(np.array([5, 6], np.int32))
        rand = jnp.asarray(np.array([0.3, 0.9], np.float32))
        out = sel.alias_draw_flat(
            starts, degs, jnp.asarray(prob), jnp.asarray(alias), indices, rand
        )
        np.testing.assert_array_equal(np.asarray(out), [-1, -1])
        rej = sel.rejection_randoms(KEY, (2,))
        out = sel.rejection_draw_flat(
            starts, degs, bias, jnp.zeros(2), indices, rej
        )
        np.testing.assert_array_equal(np.asarray(out), [-1, -1])


@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("method", ["alias", "rejection"])
class TestWalkParity:
    def test_forced_method_bitwise_inmem(self, method, backend):
        g = powerlaw_graph(500, seed=4, weighted=True)
        spec = dataclasses.replace(
            alg.weighted_random_walk(), selection_method=method
        )
        seeds = jnp.arange(128) % 500
        md = int(g.max_degree())
        res = random_walk(g, seeds, KEY, depth=6, spec=spec, max_degree=md,
                          backend=backend)
        ref = random_walk(g, seeds, KEY, depth=6, spec=spec, max_degree=md,
                          backend="reference")
        assert jnp.array_equal(res.walks, ref.walks)
        # walks end at real neighbors of their predecessors
        walks = np.asarray(res.walks)
        indptr, indices = np.asarray(g.indptr), np.asarray(g.indices)
        for r in range(0, 128, 17):
            for t in range(6):
                v, u = walks[r, t], walks[r, t + 1]
                if v < 0 or u < 0:
                    break
                assert u in indices[indptr[v]:indptr[v + 1]]

    def test_forced_method_bitwise_oom(self, method, backend):
        g = powerlaw_graph(300, seed=6, weighted=True)
        parts = partition_by_vertex_range(g, 3)
        spec = dataclasses.replace(
            alg.weighted_random_walk(), selection_method=method
        )
        seeds = np.arange(48) % 300
        w, _ = oom_random_walk(parts, 300, seeds, KEY, depth=4, spec=spec,
                               max_degree=int(g.max_degree()), backend=backend)
        wr, _ = oom_random_walk(parts, 300, seeds, KEY, depth=4, spec=spec,
                                max_degree=int(g.max_degree()), backend="reference")
        assert np.array_equal(w, wr)
        assert (w[:, 1] >= 0).any()


def test_sharded_forced_methods_bit_identical_to_inmem():
    """Forced alias/rejection under the mesh drain == in-memory engine,
    bit for bit (the §12 parity contract extended to the new methods)."""
    out = run_child(HEADER + """
import dataclasses
from jax.sharding import Mesh
from repro.core import algorithms as alg
from repro.core.engine import random_walk
from repro.graph import powerlaw_graph
from repro.shard.walk import sharded_random_walk

g = powerlaw_graph(300, seed=3, weighted=True)
mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
seeds = jnp.arange(64) % 300
key = jax.random.PRNGKey(11)
md = int(g.max_degree())
ok = {}
for m, be in (("alias", "reference"), ("alias", "pallas"), ("rejection", "reference")):
    spec = dataclasses.replace(alg.weighted_random_walk(), selection_method=m)
    ref = random_walk(g, seeds, key, depth=5, spec=spec, max_degree=md, backend=be)
    res = sharded_random_walk(mesh, g, seeds, key, depth=5, spec=spec,
                              max_degree=md, backend=be)
    ok[m + "_" + be] = bool(jnp.array_equal(ref.walks, res.walks))
print(json.dumps(ok))
""")
    assert all(out.values()), out


def test_select_fallback_flag_is_explicit():
    b = jax.random.uniform(KEY, (8, 32))
    ref = bk.select_without_replacement(KEY, b, None, 2, method="gumbel",
                                        backend="reference")
    pal = bk.select_without_replacement(KEY, b, None, 2, method="gumbel",
                                        backend="pallas")
    assert not ref.fell_back and pal.fell_back
    np.testing.assert_array_equal(np.asarray(ref.indices), np.asarray(pal.indices))
    kern = bk.select_without_replacement(KEY, b, None, 2, method="its_brs",
                                         backend="pallas")
    assert not kern.fell_back


def test_service_prewarm_builds_and_reuses_plan():
    g = powerlaw_graph(400, seed=8, weighted=True)
    svc = SamplingService(g, backend="reference")
    spec = dataclasses.replace(alg.weighted_random_walk(), selection_method="alias")
    mt.clear_plan_cache()
    methods = svc.prewarm(spec)
    assert set(methods) == {"alias"}
    assert svc.stats.plans_prewarmed == 1
    _, t1 = mt.plan_for_graph(
        g, lower(spec).bias.fn, buckets=bk.walk_bucket_plan(int(g.max_degree()))[0],
        use_chunked=bk.walk_bucket_plan(int(g.max_degree()))[1], override="alias"
    )
    rid = svc.submit(np.arange(32) % 400, depth=4, spec=spec)
    out = svc.drain()
    assert out[rid].walks.shape == (32, 5)
    # the drain reused the prewarmed cache entry (same array objects)
    _, t2 = mt.plan_for_graph(
        g, lower(spec).bias.fn, buckets=bk.walk_bucket_plan(int(g.max_degree()))[0],
        use_chunked=bk.walk_bucket_plan(int(g.max_degree()))[1], override="alias"
    )
    assert t1.prob is t2.prob
    # non-flat specs have nothing to prebuild
    assert svc.prewarm(alg.node2vec()) == ()

"""Selection backend dispatcher: reference vs Pallas parity, engine routing.

The dispatcher's counted-RNG contract (``select.retry_randoms``) makes the
kernel path bit-identical to the reference retry loop, so most assertions
here are exact array equality — not statistical.
"""
import jax
import jax.numpy as jnp
from jax.extend import core as jex_core
import numpy as np
import pytest

from repro.core import algorithms as alg
from repro.core import backend as bk
from repro.core import select as sel
from repro.core.engine import random_walk, traversal_sample
from repro.graph import powerlaw_graph
from repro.graph.csr import csr_from_edges

KEY = jax.random.PRNGKey(0)


def _biases(key, i_dim, p, zero_frac=0.25):
    b = jax.random.uniform(key, (i_dim, p))
    keep = jax.random.uniform(jax.random.fold_in(key, 1), (i_dim, p)) > zero_frac
    return b * keep


class TestResolve:
    def test_auto_resolves_by_device(self):
        expect = "pallas" if jax.default_backend() == "tpu" else "reference"
        assert bk.resolve_backend("auto") == expect

    def test_explicit_passthrough(self):
        assert bk.resolve_backend("reference") == "reference"
        assert bk.resolve_backend("pallas") == "pallas"

    def test_unknown_rejected(self):
        with pytest.raises(ValueError):
            bk.resolve_backend("cuda")


class TestWithoutReplacementParity:
    # non-aligned P (lane padding) and non-aligned I (blk_i padding) included
    @pytest.mark.parametrize("i_dim,p,k", [(8, 128, 4), (13, 100, 3), (5, 37, 2), (32, 256, 8)])
    def test_its_brs_bitwise(self, i_dim, p, k):
        key = jax.random.PRNGKey(i_dim * p + k)
        b = _biases(key, i_dim, p)
        mask = jax.random.uniform(jax.random.fold_in(key, 2), (i_dim, p)) > 0.1
        ref = bk.select_without_replacement(
            key, b, mask, k, method="its_brs", backend="reference", max_iters=8
        )
        pal = bk.select_without_replacement(
            key, b, mask, k, method="its_brs", backend="pallas", max_iters=8
        )
        np.testing.assert_array_equal(np.asarray(ref.indices), np.asarray(pal.indices))
        np.testing.assert_array_equal(np.asarray(ref.valid), np.asarray(pal.valid))
        np.testing.assert_array_equal(np.asarray(ref.iters), np.asarray(pal.iters))
        np.testing.assert_array_equal(np.asarray(ref.searches), np.asarray(pal.searches))

    def test_gumbel_bitwise(self):
        b = _biases(KEY, 16, 64)
        ref = bk.select_without_replacement(KEY, b, None, 4, method="gumbel", backend="reference")
        pal = bk.select_without_replacement(KEY, b, None, 4, method="gumbel", backend="pallas")
        np.testing.assert_array_equal(np.asarray(ref.indices), np.asarray(pal.indices))
        np.testing.assert_array_equal(np.asarray(ref.valid), np.asarray(pal.valid))

    def test_batched_leading_dims(self):
        """(I, fs, P) pools — the per-vertex neighbor-selection shape."""
        b = jax.random.uniform(KEY, (6, 3, 40))
        ref = bk.select_without_replacement(
            KEY, b, None, 2, method="its_brs", backend="reference", max_iters=6
        )
        pal = bk.select_without_replacement(
            KEY, b, None, 2, method="its_brs", backend="pallas", max_iters=6
        )
        assert pal.indices.shape == (6, 3, 2)
        np.testing.assert_array_equal(np.asarray(ref.indices), np.asarray(pal.indices))

    def test_insufficient_candidates(self):
        b = jnp.tile(jnp.array([1.0, 2.0, 0.0, 0.0]), (10, 1))
        pal = bk.select_without_replacement(
            KEY, b, None, 4, method="its_brs", backend="pallas", max_iters=8
        )
        assert int(pal.valid.sum(-1).max()) <= 2
        assert not np.isin(np.asarray(pal.indices), [2, 3]).any()


class TestWithReplacementParity:
    @pytest.mark.parametrize("i_dim,p", [(16, 64), (11, 100)])
    def test_k1_bitwise(self, i_dim, p):
        key = jax.random.PRNGKey(i_dim + p)
        b = _biases(key, i_dim, p)
        ref = sel.select_with_replacement(key, b, None, 1)
        pal = bk.select_with_replacement(key, b, None, 1, backend="pallas")
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))

    def test_dead_rows_match_reference_degenerate_index(self):
        b = jnp.zeros((4, 16))
        ref = sel.select_with_replacement(KEY, b, None, 1)
        pal = bk.select_with_replacement(KEY, b, None, 1, backend="pallas")
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))


class TestEngineBackends:
    @pytest.fixture(scope="class")
    def graph(self):
        return powerlaw_graph(256, seed=1, weighted=True)

    def test_walk_fast_path_edges_exist(self, graph):
        seeds = jax.random.randint(KEY, (48,), 0, graph.num_vertices)
        res = random_walk(graph, seeds, KEY, depth=8, spec=alg.weighted_random_walk(),
                          max_degree=graph.max_degree(), backend="pallas")
        ip, ind = np.asarray(graph.indptr), np.asarray(graph.indices)
        for row in np.asarray(res.walks):
            for a, b in zip(row[:-1], row[1:]):
                if a < 0 or b < 0:
                    break
                assert b in ind[ip[a]: ip[a + 1]]

    def test_walk_fast_path_stationary_distribution(self, graph):
        """Same distributional bar as the reference path (deepwalk ∝ degree)."""
        seeds = jax.random.randint(KEY, (1024,), 0, graph.num_vertices)
        res = random_walk(graph, seeds, KEY, depth=30, spec=alg.deepwalk(),
                          max_degree=graph.max_degree(), backend="pallas")
        last = np.asarray(res.walks)[:, -1]
        last = last[last >= 0]
        deg = np.asarray(graph.indptr[1:] - graph.indptr[:-1]).astype(float)
        visit = np.bincount(last, minlength=graph.num_vertices).astype(float)
        assert np.corrcoef(visit, deg)[0, 1] > 0.7

    def test_walk_window_bias_bitwise(self, graph):
        """State-dependent bias (node2vec) runs the bucketed WINDOW path on
        both backends (transition programs): the dynamic hook is evaluated
        once in shared jnp, the pick dispatches kernel vs mirror —
        bit-identical."""
        seeds = jax.random.randint(KEY, (32,), 0, graph.num_vertices)
        kw = dict(depth=5, spec=alg.node2vec(), max_degree=graph.max_degree())
        ref = random_walk(graph, seeds, KEY, backend="reference", **kw)
        pal = random_walk(graph, seeds, KEY, backend="pallas", **kw)
        np.testing.assert_array_equal(np.asarray(ref.walks), np.asarray(pal.walks))

    def test_walk_chunked_huge_degree_cohort(self):
        """A hub above the last bucket segment routes through the two-pass
        chunked scan and still yields real neighbors."""
        hub_deg = bk.WALK_BUCKETS[-1] + 37
        src = np.concatenate([np.zeros(hub_deg, int), np.arange(1, hub_deg + 1)])
        dst = np.concatenate([np.arange(1, hub_deg + 1), np.zeros(hub_deg, int)])
        g = csr_from_edges(hub_deg + 1, src, dst)
        assert g.max_degree() > bk.WALK_BUCKETS[-1]
        seeds = jnp.zeros((8,), jnp.int32)
        res = random_walk(g, seeds, KEY, depth=2, spec=alg.deepwalk(),
                          max_degree=g.max_degree(), backend="pallas")
        walks = np.asarray(res.walks)
        assert (walks[:, 1] >= 1).all() and (walks[:, 1] <= hub_deg).all()
        assert (walks[:, 2] == 0).all()  # spokes all point back at the hub

    @staticmethod
    def _hub_tail_setup(num_walkers):
        """Every walker on one hub above the top bucket, with a hook whose
        bias is ``weight * (0.5, 1, 2)[u % 3]`` (so ``max_ratio`` is 2)."""
        hub_deg = bk.WALK_BUCKETS[-1] + 88
        nb = np.arange(1, hub_deg + 1)
        wts = (0.1 + (nb % 7) / 7.0).astype(np.float32)
        g = csr_from_edges(hub_deg + 1, np.zeros(hub_deg, int), nb, weights=wts)
        factor = jnp.asarray([0.5, 1.0, 2.0], jnp.float32)

        def bias_of(u, w, mask, eidx=None, rows=None):
            return jnp.where(mask, w * factor[jnp.maximum(u, 0) % 3], 0.0)

        cur = jnp.zeros((num_walkers,), jnp.int32)
        start = jnp.zeros_like(cur)
        deg = jnp.full_like(cur, hub_deg)
        envelope = 2.0 * jnp.max(g.weights)
        exact = np.asarray(g.weights) * np.asarray(factor)[np.asarray(g.indices) % 3]
        return g, bias_of, cur, start, deg, envelope, exact / exact.sum()

    @staticmethod
    def _window_step(g, cur, bias_of, backend="reference", **kw):
        """``walk_step_bucketed_window`` on ``g`` with its exact window plan."""
        buckets, use_chunked = bk.walk_bucket_plan_window(g.max_degree())
        padded = bk.pad_walk_csr(g.indices, g.weights, buckets)
        return bk.walk_step_bucketed_window(
            KEY, g.indptr, g.indices, g.weights, padded, cur, bias_of,
            buckets=buckets, use_chunked=use_chunked, backend=backend, **kw,
        )

    def test_window_tail_rejection_matches_bias(self):
        """Walkers on a hub row draw by rejection, then the exact scan: each
        neighbour is picked in proportion to its dynamic bias (grouped by
        the bias's 21 distinct values)."""
        n = 40000
        g, bias_of, cur, start, deg, envelope, prob = self._hub_tail_setup(n)
        nxt = self._window_step(g, cur, bias_of, envelope=envelope)
        picks = np.asarray(nxt)
        assert (picks >= 1).all()
        nb = np.asarray(g.indices)
        group = (nb % 3) * 7 + nb % 7
        seen = np.bincount(group[picks - 1], minlength=21) / n
        expect = np.bincount(group, weights=prob, minlength=21)
        sigma = np.sqrt(expect * (1 - expect) / n)
        assert np.all(np.abs(seen - expect) < 5 * sigma + 1e-9), (seen, expect)

    def test_window_tail_exhausted_budget_takes_exact_scan(self):
        """Hub walkers whose rejection budget runs out fall back to the
        chunked scan, compacted into rounds: the picks equal the scan's own
        picks for the same uniforms."""
        n = 37
        g, bias_of, cur, start, deg, envelope, _ = self._hub_tail_setup(n)
        rand = jax.random.uniform(KEY, (n,))
        # every trial proposes slot 0 (bias 1 * (0.1 + 1/7)) and draws an
        # accept uniform of 0.99, far above bias / envelope: all rejected
        rej = jnp.zeros((n, bk.TAIL_REJECT_ITERS, 2), jnp.float32).at[..., 1].set(0.99)
        via_fallback = self._window_step(
            g, cur, bias_of, envelope=envelope, rej_rand=rej, tail_rand=rand
        )
        off = sel.walk_transition_chunked_window(
            None, start, deg, g.indices, g.weights, bias_of, chunk=bk.CHUNK, rand=rand
        )
        expect = np.asarray(g.indices)[np.asarray(off)]
        np.testing.assert_array_equal(np.asarray(via_fallback), expect)

    @staticmethod
    def _cohort_setup(per_state):
        """Three rows, one per route — degree 100 (the 128 cohort), 300 (the
        512 cohort) and 600 (the hub tail) — over shared leaves, with
        ``per_state`` walkers on each row in each of three walker states
        ``s``, and a hook whose bias ``weight * (0.5, 1, 2)[(u + s) % 3]``
        reads the walker's state (so ``max_ratio`` is 2).  Also returns the
        walkers' rows and states, and the bias of ``(row, state, neighbour)``."""
        degs = (100, 300, 600)
        leaves = np.arange(len(degs), len(degs) + max(degs))
        src = np.concatenate([np.full(d, c) for c, d in enumerate(degs)])
        dst = np.concatenate([leaves[:d] for d in degs])
        wts = (0.1 + (dst % 7) / 7.0).astype(np.float32)
        g = csr_from_edges(len(degs) + max(degs), src, dst, weights=wts)
        factor = np.asarray([0.5, 1.0, 2.0], np.float32)
        row, state = np.meshgrid(np.arange(3), np.arange(3), indexing="ij")
        row = np.repeat(row.ravel(), per_state)
        state = np.repeat(state.ravel(), per_state)
        s = jnp.asarray(state, jnp.int32)
        fj = jnp.asarray(factor)

        def bias_of(u, w, mask, eidx=None, rows=None):
            sw = s if rows is None else s[rows]
            return jnp.where(mask, w * fj[(jnp.maximum(u, 0) + sw[..., None]) % 3], 0.0)

        def exact(c, st):
            ip = np.asarray(g.indptr)
            nb = np.asarray(g.indices)[ip[c]: ip[c + 1]]
            return nb, np.asarray(g.weights)[ip[c]: ip[c + 1]] * factor[(nb + st) % 3]

        envelope = 2.0 * jnp.max(g.weights)
        return g, bias_of, jnp.asarray(row, jnp.int32), row, state, envelope, exact

    @pytest.mark.parametrize("backend", ["reference", "pallas"])
    def test_window_rejection_route_matches_bias(self, backend):
        """Every cohort draws by rejection first, then the exact draw of its
        route: from each (row, state), the picks follow the exact normalised
        bias (chi-square over the bias's 21 distinct values a state)."""
        per = 3000
        g, bias_of, cur, row, state, envelope, exact = self._cohort_setup(per)
        buckets, use_chunked = bk.walk_bucket_plan_window(g.max_degree())
        assert buckets == (128, 512) and use_chunked  # one row per route
        picks = np.asarray(self._window_step(g, cur, bias_of, backend, envelope=envelope))
        chi2, df = 0.0, 0
        for c in range(3):
            for st in range(3):
                nb, b = exact(c, st)
                got = picks[(row == c) & (state == st)]
                assert np.isin(got, nb).all()
                group = ((nb + st) % 3) * 7 + nb % 7
                expect = per * np.bincount(group, weights=b / b.sum(), minlength=21)
                seen = np.bincount(group[np.searchsorted(nb, got)], minlength=21)
                chi2 += float(np.sum((seen - expect) ** 2 / expect))
                df += 20
        # Wilson-Hilferty quantile of chi2(df) at z = 5 (about 3e-7)
        bound = df * (1 - 2 / (9 * df) + 5 * np.sqrt(2 / (9 * df))) ** 3
        assert chi2 < bound, (chi2, bound)

    @pytest.mark.parametrize("backend", ["reference", "pallas"])
    def test_window_rejection_exhausted_budget_is_exact_route(self, backend):
        """A budget that accepts nothing (accept uniform 1) leaves every
        live walker pending, and the step then returns exactly what the
        route without a rejection stage returns, in every cohort."""
        g, bias_of, cur, *_, envelope, _ = self._cohort_setup(5)
        cur = cur.at[::7].set(-1)  # finished walkers stay finished
        n = cur.shape[0]
        rej = jnp.ones((n, bk.TAIL_REJECT_ITERS, 2), jnp.float32)
        live = np.asarray(cur) >= 0
        starts = g.indptr[jnp.maximum(cur, 0)]
        deg = jnp.where(cur >= 0, g.indptr[jnp.maximum(cur, 0) + 1] - starts, 0)
        _, pending = bk.window_rejection_stage(
            KEY, g.indices, g.weights, starts, deg, bias_of, envelope, rej
        )
        np.testing.assert_array_equal(np.asarray(pending), live)
        via_fallback = self._window_step(g, cur, bias_of, backend, envelope=envelope, rej_rand=rej)
        exact = self._window_step(g, cur, bias_of, backend)
        np.testing.assert_array_equal(np.asarray(via_fallback), np.asarray(exact))
        assert (np.asarray(exact)[live] >= 0).all()

    def test_window_rejection_stage_accepting_budget_leaves_none_pending(self):
        """A budget that accepts every proposal (accept uniform 0, every
        bias positive) leaves no walker pending, each pick is the neighbour
        its first proposal names, and the step keeps those picks: no
        cohort round or hub scan runs over them."""
        g, bias_of, cur, *_, envelope, _ = self._cohort_setup(5)
        n = cur.shape[0]
        rej = jnp.zeros((n, bk.TAIL_REJECT_ITERS, 2), jnp.float32).at[:, 0, 0].set(0.5)
        starts = g.indptr[cur]
        deg = g.indptr[cur + 1] - starts
        nxt, pending = bk.window_rejection_stage(
            KEY, g.indices, g.weights, starts, deg, bias_of, envelope, rej
        )
        assert not np.asarray(pending).any()
        expect = np.asarray(g.indices)[np.asarray(starts + deg // 2)]
        np.testing.assert_array_equal(np.asarray(nxt), expect)
        step = self._window_step(g, cur, bias_of, envelope=envelope, rej_rand=rej)
        np.testing.assert_array_equal(np.asarray(step), expect)

    def test_walk_understated_max_degree_keeps_hub_walkers(self):
        """Regression: the bucket plan must not shrink its top segment on the
        caller's (possibly understated) max_degree — a deg-400 hub with
        declared max_degree=300 must still walk on the pallas fast path.
        (Only exact=True callers, like the OOM drain planning from the true
        max row degree, opt into the shrink.)"""
        hub_deg = 400
        src = np.concatenate([np.zeros(hub_deg, int), np.arange(1, hub_deg + 1)])
        dst = np.concatenate([np.arange(1, hub_deg + 1), np.zeros(hub_deg, int)])
        g = csr_from_edges(hub_deg + 1, src, dst)
        assert bk.walk_bucket_plan(300) == ((128, 512), False)
        assert bk.walk_bucket_plan(300, exact=True) == ((128, 384), False)
        seeds = jnp.zeros((8,), jnp.int32)
        res = random_walk(g, seeds, KEY, depth=2, spec=alg.deepwalk(),
                          max_degree=300, backend="pallas")
        assert (np.asarray(res.walks)[:, 1] >= 1).all()

    @pytest.mark.parametrize("name", ["neighbor_unbiased", "layer", "mdrw"])
    def test_traversal_bitwise(self, graph, name):
        pools = jax.random.randint(KEY, (8, 2), 0, graph.num_vertices)
        kw = dict(depth=2, spec=alg.ALGORITHMS[name](), max_degree=graph.max_degree(),
                  pool_capacity=64, max_vertices=graph.num_vertices)
        ref = traversal_sample(graph, pools, KEY, backend="reference", **kw)
        pal = traversal_sample(graph, pools, KEY, backend="pallas", **kw)
        for a, b, field in zip(ref, pal, ref._fields):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=field)


def _rank2_trailing_dims(jaxpr, dims):
    """Collect the trailing dim of every rank>=2 aval, recursing into nested
    jaxprs (pjit/scan/cond/pallas_call bodies).  A flat array viewed as one
    ``(1, E)`` row (how the walk kernels take CSR edge arrays) is still a
    flat array, like the rank-1 ones, and is skipped."""
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            shape = getattr(getattr(v, "aval", None), "shape", ())
            if len(shape) >= 2 and any(d != 1 for d in shape[:-1]):
                dims.append(int(shape[-1]))
        for val in eqn.params.values():
            for sub in _subjaxprs(val):
                _rank2_trailing_dims(sub, dims)
    return dims


def _subjaxprs(val):
    if isinstance(val, jex_core.ClosedJaxpr):
        return [val.jaxpr]
    if isinstance(val, jex_core.Jaxpr):
        return [val]
    if isinstance(val, (list, tuple)):
        return [j for v in val for j in _subjaxprs(v)]
    return []


class TestTransitionPrograms:
    """The tentpole contract (DESIGN.md §10): node2vec/MH/jump/restart run
    the degree-bucketed fast path on BOTH backends, bit-identically, with no
    dense full-context gather in their jaxpr."""

    @pytest.fixture(scope="class")
    def graph(self):
        return powerlaw_graph(256, seed=1, weighted=True)

    def _specs(self, graph):
        return {
            "node2vec": alg.node2vec(),
            "mhrw": alg.metropolis_hastings_walk(),
            "rw_jump": alg.random_walk_with_jump(0.3, graph.num_vertices),
            "rw_restart": alg.random_walk_with_restart(0.3, home=5),
            "rw_restart_home": alg.random_walk_with_restart(0.3),
        }

    @pytest.mark.parametrize(
        "name", ["node2vec", "mhrw", "rw_jump", "rw_restart", "rw_restart_home"]
    )
    def test_cross_backend_bitwise(self, graph, name):
        spec = self._specs(graph)[name]
        seeds = jax.random.randint(KEY, (48,), 0, graph.num_vertices)
        kw = dict(depth=8, spec=spec, max_degree=graph.max_degree())
        ref = random_walk(graph, seeds, KEY, backend="reference", **kw)
        pal = random_walk(graph, seeds, KEY, backend="pallas", **kw)
        np.testing.assert_array_equal(np.asarray(ref.walks), np.asarray(pal.walks))
        np.testing.assert_array_equal(np.asarray(ref.lengths), np.asarray(pal.lengths))
        assert int(ref.lengths.min()) == 9  # nobody silently died

    @pytest.mark.parametrize(
        "name", ["node2vec", "mhrw", "rw_jump", "rw_restart", "rw_restart_home"]
    )
    @pytest.mark.parametrize("backend", ["reference", "pallas"])
    def test_no_dense_gather_in_jaxpr(self, graph, name, backend):
        """With a declared max_degree far above the bucket windows, the
        bucketed paths must not materialize any (..., max_degree)-wide
        tensor; the widest allowed is the top bucket's 2·512 window.  The
        forced-opaque fallback (transition stripped) does materialize one —
        proof the probe can tell the difference."""
        import dataclasses

        declared = 4096
        spec = self._specs(graph)[name]
        seeds = jax.random.randint(KEY, (16,), 0, graph.num_vertices)

        def dims_of(s):
            jx = jax.make_jaxpr(
                lambda g, sd, k: random_walk(
                    g, sd, k, depth=2, spec=s, max_degree=declared, backend=backend
                )
            )(graph, seeds, KEY)
            return _rank2_trailing_dims(jx.jaxpr, [])

        assert max(dims_of(spec)) <= 2 * bk.WALK_BUCKETS[-1]
        if name != "rw_restart_home":  # restart-to-seed has no legacy hook
            opaque = dataclasses.replace(spec, transition=None, flat_edge_bias=None)
            assert max(dims_of(opaque)) >= declared

    @pytest.mark.parametrize("backend", ["reference", "pallas"])
    def test_flat_understated_max_degree_truncates_not_kills(self, backend):
        """Regression: with the bucketed flat path now the default on BOTH
        backends, a hub whose degree exceeds the declared plan entirely
        (deg 600 vs max_degree=256 → buckets (128,512), no chunked tail)
        must truncate its neighborhood to the top cohort's window like the
        dense gather did — not silently die at step 1."""
        from repro.graph.csr import csr_from_edges

        hub_deg = 600
        src = np.concatenate([np.zeros(hub_deg, int), np.arange(1, hub_deg + 1)])
        dst = np.concatenate([np.arange(1, hub_deg + 1), np.zeros(hub_deg, int)])
        g = csr_from_edges(hub_deg + 1, src, dst)
        seeds = jnp.zeros((8,), jnp.int32)
        res = random_walk(g, seeds, KEY, depth=2, spec=alg.deepwalk(),
                          max_degree=256, backend=backend)
        walks = np.asarray(res.walks)
        assert (walks[:, 1] >= 1).all() and (walks[:, 1] <= 512).all()
        assert (walks[:, 2] == 0).all()
        ref = random_walk(g, seeds, KEY, depth=2, spec=alg.deepwalk(),
                          max_degree=256, backend="reference")
        np.testing.assert_array_equal(walks, np.asarray(ref.walks))

    def test_window_understated_max_degree_truncates_not_kills(self):
        """In-memory the window path trusts the caller's max_degree for its
        exact bucket plan; an UNDERSTATED bound must degrade like the dense
        gather it replaced — hub neighborhoods truncate to the top cohort's
        window — never silently kill walkers.  Both backends, bit-identical."""
        from repro.graph.csr import csr_from_edges

        hub_deg = 300  # true degree above the declared 256 plan
        src = np.concatenate([np.zeros(hub_deg, int), np.arange(1, hub_deg + 1)])
        dst = np.concatenate([np.arange(1, hub_deg + 1), np.zeros(hub_deg, int)])
        g = csr_from_edges(hub_deg + 1, src, dst)
        seeds = jnp.zeros((8,), jnp.int32)
        kw = dict(depth=2, spec=alg.node2vec(), max_degree=256)
        ref = random_walk(g, seeds, KEY, backend="reference", **kw)
        pal = random_walk(g, seeds, KEY, backend="pallas", **kw)
        walks = np.asarray(ref.walks)
        assert (walks[:, 1] >= 1).all() and (walks[:, 1] <= 256).all()
        assert (walks[:, 2] == 0).all()  # spokes point back at the hub
        np.testing.assert_array_equal(walks, np.asarray(pal.walks))

    def test_restart_home_returns_to_seed(self, graph):
        """target="home" teleports to each walk's own seed (carried state)."""
        spec = alg.random_walk_with_restart(1.0)
        seeds = jax.random.randint(KEY, (16,), 0, graph.num_vertices)
        res = random_walk(graph, seeds, KEY, depth=4, spec=spec,
                          max_degree=graph.max_degree())
        walks = np.asarray(res.walks)
        for i in range(16):
            alive = walks[i, 1:][walks[i, 1:] >= 0]
            assert (alive == walks[i, 0]).all()

    def test_lowering_infers_legacy_flags(self):
        from repro.core import transition as tp
        from repro.core.api import SamplingSpec

        legacy_flat = SamplingSpec(flat_edge_bias=lambda g: g.weights)
        prog = tp.lower(legacy_flat)
        assert isinstance(prog.bias, tp.FlatBias)
        assert isinstance(prog.epilogue, tp.IdentityEpilogue)

        legacy_opaque = SamplingSpec(update=lambda k, c, u: u)
        prog = tp.lower(legacy_opaque)
        assert isinstance(prog.bias, tp.OpaqueBias)
        assert isinstance(prog.epilogue, tp.OpaqueEpilogue)

    def test_declared_program_wins(self):
        from repro.core import transition as tp

        spec = alg.metropolis_hastings_walk()
        prog = tp.lower(spec)
        assert isinstance(prog.bias, tp.FlatBias)
        assert isinstance(prog.epilogue, tp.MHAcceptEpilogue)
        assert not prog.carries_home
        assert alg.random_walk_with_restart(0.5).transition.carries_home


class TestScanTrace:
    def test_traversal_trace_is_depth_independent(self):
        g = powerlaw_graph(64, seed=2, weighted=True)
        pools = jax.random.randint(KEY, (4, 1), 0, g.num_vertices)

        def hlo_len(depth):
            lo = traversal_sample.lower(
                g, pools, KEY, depth=depth, spec=alg.layer_sampling(2, 2),
                max_degree=g.max_degree(), pool_capacity=16,
                max_vertices=g.num_vertices, backend="reference",
            )
            return len(lo.as_text())

        s2, s8 = hlo_len(2), hlo_len(8)
        assert s8 < 1.2 * s2, (s2, s8)


class TestInsertIntoPool:
    def test_compaction_semantics(self):
        from repro.core.engine import _insert_into_pool
        pool = jnp.array([[5, -1, 3, -1], [-1, -1, -1, -1]])
        new = jnp.array([[7, -1, 9], [1, 2, -1]])
        out = np.asarray(_insert_into_pool(pool, new))
        np.testing.assert_array_equal(out[0], [5, 3, 7, 9])
        np.testing.assert_array_equal(out[1], [1, 2, -1, -1])

    def test_overflow_dropped(self):
        from repro.core.engine import _insert_into_pool
        pool = jnp.array([[1, 2, 3]])
        new = jnp.array([[4, 5]])
        out = np.asarray(_insert_into_pool(pool, new))
        np.testing.assert_array_equal(out[0], [1, 2, 3])

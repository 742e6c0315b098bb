"""Bias-based selection: ITS, BRS (Theorem 2), collision handling."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # property tests are skipped without the [test] extra
    HAVE_HYPOTHESIS = False

from repro.core import select as sel


def chi2_stat(counts, probs):
    n = counts.sum()
    expected = probs * n
    keep = expected > 1e-9
    return float(np.sum((counts[keep] - expected[keep]) ** 2 / expected[keep]))


class TestWithReplacement:
    def test_matches_transition_probabilities(self):
        """Theorem 1: selection frequency ∝ bias."""
        biases = jnp.array([3.0, 6.0, 2.0, 2.0, 2.0])
        n = 40000
        idx = sel.select_with_replacement(
            jax.random.PRNGKey(0), jnp.tile(biases, (n, 1)), None, 1
        )[:, 0]
        counts = np.bincount(np.asarray(idx), minlength=5)
        probs = np.asarray(biases) / float(biases.sum())
        # chi-square with 4 dof: 99.9th percentile ~ 18.5
        assert chi2_stat(counts, probs) < 18.5

    def test_zero_bias_never_selected(self):
        biases = jnp.array([1.0, 0.0, 2.0, 0.0])
        idx = sel.select_with_replacement(
            jax.random.PRNGKey(1), jnp.tile(biases, (5000, 1)), None, 1
        )[:, 0]
        assert not np.isin(np.asarray(idx), [1, 3]).any()

    def test_masked_entries_never_selected(self):
        biases = jnp.ones((2000, 6))
        mask = jnp.tile(jnp.array([True, False, True, True, False, True]), (2000, 1))
        idx = sel.select_with_replacement(jax.random.PRNGKey(2), biases, mask, 2)
        assert not np.isin(np.asarray(idx), [1, 4]).any()


@pytest.mark.parametrize("method", ["its_brs", "repeated", "updated", "gumbel"])
class TestWithoutReplacement:
    def test_no_duplicates(self, method):
        key = jax.random.PRNGKey(3)
        biases = jax.random.uniform(key, (500, 16)) + 0.05
        res = sel.select_without_replacement(key, biases, None, 8, method=method)
        arr = np.asarray(res.indices)
        for row in arr:
            chosen = row[row >= 0]
            assert len(set(chosen.tolist())) == len(chosen)

    def test_all_valid_when_enough_candidates(self, method):
        key = jax.random.PRNGKey(4)
        biases = jax.random.uniform(key, (200, 32)) + 0.1
        res = sel.select_without_replacement(key, biases, None, 4, method=method)
        assert bool(res.valid.all())

    def test_insufficient_candidates_marked_invalid(self, method):
        biases = jnp.tile(jnp.array([1.0, 2.0, 0.0, 0.0]), (50, 1))
        res = sel.select_without_replacement(jax.random.PRNGKey(5), biases, None, 4, method=method)
        assert int(res.valid.sum(-1).max()) <= 2
        arr = np.asarray(res.indices)
        assert not np.isin(arr, [2, 3]).any()

    def test_first_draw_distribution(self, method):
        """First selection must follow the unmodified transition probs."""
        biases = jnp.array([5.0, 1.0, 1.0, 1.0, 2.0])
        n = 20000
        res = sel.select_without_replacement(
            jax.random.PRNGKey(6), jnp.tile(biases, (n, 1)), None, 3, method=method
        )
        first = np.asarray(res.indices[:, 0])
        counts = np.bincount(first[first >= 0], minlength=5)
        probs = np.asarray(biases) / float(biases.sum())
        assert chi2_stat(counts, probs) < 18.5


class TestBipartiteRegionSearch:
    @staticmethod
    def _set_counts(method, seed, biases, n=30000, k=2):
        res = sel.select_without_replacement(
            jax.random.PRNGKey(seed), jnp.tile(biases, (n, 1)), None, k, method=method
        )
        arr = np.sort(np.asarray(res.indices), axis=1)
        keys = arr[:, 0] * len(biases) + arr[:, 1]
        return np.bincount(keys, minlength=len(biases) ** 2)

    def test_repeated_equals_sequential_updated(self):
        """Identity: parallel draw + rejection-retry == sequential
        renormalized (Plackett-Luce) sampling.  (2·p_a·p_b + collision
        resolution mass algebraically equals p_a·p_b·(1/(1-p_a)+1/(1-p_b)).)"""
        biases = jnp.array([4.0, 3.0, 2.0, 1.0])
        rep = self._set_counts("repeated", 8, biases)
        upd = self._set_counts("updated", 9, biases)
        tot = rep + upd
        keep = tot > 0
        stat = np.sum((rep[keep] - upd[keep]) ** 2 / np.maximum(tot[keep], 1))
        assert stat < 25.0, (rep, upd)

    def test_brs_joint_bias_is_present_and_bounded(self):
        """FIDELITY FINDING (EXPERIMENTS.md §Fidelity): the paper's BRS
        reuses the *colliding* r, whose conditional law is uniform on the
        removed region — the transformed draw therefore concentrates on
        CTPS-adjacent candidates.  First-draw marginals stay exact (tested
        above), but the joint k-subset law deviates from Plackett-Luce.
        This test pins the deviation: present (so we notice if the
        implementation changes) and bounded (< 5pp on this pool)."""
        biases = jnp.array([4.0, 3.0, 2.0, 1.0])
        n = 30000
        brs = self._set_counts("its_brs", 7, biases, n) / n
        upd = self._set_counts("updated", 9, biases, n) / n
        dev = np.abs(brs - upd).max()
        assert 0.005 < dev < 0.05, dev

    def test_brs_fewer_iterations_than_repeated(self):
        """The paper's headline: BRS cuts retry iterations (Fig. 11)."""
        key = jax.random.PRNGKey(9)
        # skewed biases → high collision rate
        biases = jnp.tile(jnp.array([50.0, 1.0, 1.0, 1.0, 1.0, 1.0]), (2000, 1))
        brs = sel.select_without_replacement(key, biases, None, 4, method="its_brs")
        rep = sel.select_without_replacement(key, biases, None, 4, method="repeated")
        assert float(brs.iters.mean()) < float(rep.iters.mean())

    @staticmethod
    def _check_theorem2_transform(bias_list, seed):
        """Property check of the paper's Theorem 2: transforming a uniform r
        through BRS around a pre-selected region reproduces the *updated*
        CTPS distribution over the remaining candidates."""
        b = np.asarray(bias_list, dtype=np.float64)
        s = seed % len(b)  # pre-selected vertex
        rng = np.random.default_rng(seed)
        n = 4000
        r1 = rng.random(n)
        cum = np.cumsum(b) / b.sum()
        lower = np.concatenate([[0.0], cum[:-1]])
        l, h = lower[s], cum[s]
        delta = h - l
        r2 = r1 * (1.0 - delta)
        r2 = np.where(r2 < l, r2, r2 + delta)
        idx = np.searchsorted(cum, r2, side="right")
        idx = np.clip(idx, 0, len(b) - 1)
        assert not (idx == s).any()  # never re-selects the removed vertex
        # distribution over remaining == renormalized biases
        b2 = b.copy()
        b2[s] = 0.0
        probs = b2 / b2.sum()
        counts = np.bincount(idx, minlength=len(b)).astype(float)
        stat = chi2_stat(counts, probs)
        # generous bound: dof ≈ len(b)-2, 99.99th pct < 30 for <=12 bins
        assert stat < 40.0

    if HAVE_HYPOTHESIS:

        @settings(max_examples=30, deadline=None)
        @given(
            st.lists(st.floats(0.1, 20.0), min_size=3, max_size=12),
            st.integers(0, 2**31 - 1),
        )
        def test_theorem2_transform(self, bias_list, seed):
            self._check_theorem2_transform(bias_list, seed)

    else:

        def test_theorem2_transform(self):
            # single fixed example so the theorem still gets exercised
            # when the [test] extra (hypothesis) is absent
            self._check_theorem2_transform([4.0, 3.0, 2.0, 1.0, 0.5], 1234)


class TestChunkedTransition:
    def test_matches_padded_selection(self):
        from repro.graph import powerlaw_graph

        g = powerlaw_graph(256, seed=11, weighted=True)
        key = jax.random.PRNGKey(12)
        cur = jax.random.randint(key, (2000,), 0, 256)
        off = sel.walk_transition_chunked(key, g.indptr, g.weights, cur, chunk=8)
        off = np.asarray(off)
        deg = np.asarray(g.indptr[cur + 1] - g.indptr[cur])
        assert ((off >= 0) == (deg > 0)).all()
        assert (off[deg > 0] < deg[deg > 0]).all()

    def test_distribution(self):
        indptr = jnp.array([0, 4], dtype=jnp.int32)
        weights = jnp.array([1.0, 2.0, 3.0, 4.0])
        key = jax.random.PRNGKey(13)
        n = 20000
        off = sel.walk_transition_chunked(
            key, indptr, weights, jnp.zeros((n,), jnp.int32), chunk=2
        )
        counts = np.bincount(np.asarray(off), minlength=4)
        assert chi2_stat(counts, np.array([0.1, 0.2, 0.3, 0.4])) < 16.3


@pytest.mark.parametrize("batch_shape,iters", [((37,), 16), ((3, 64), 8), ((5,), 1)])
def test_rejection_randoms_keep_the_counted_layout(batch_shape, iters):
    """Round ``t`` reads ``uniform(fold_in(key, 2t))`` for its slot and
    ``uniform(fold_in(key, 2t + 1))`` for its accept test, bit for bit: the
    layout the sharded drain rebuilds one column at a time."""
    key = jax.random.PRNGKey(11)
    rej = np.asarray(sel.rejection_randoms(key, batch_shape, iters=iters))
    assert rej.shape == batch_shape + (iters, 2)
    for t in range(iters):
        for j in range(2):
            col = jax.random.uniform(jax.random.fold_in(key, 2 * t + j), batch_shape)
            np.testing.assert_array_equal(rej[..., t, j], np.asarray(col))

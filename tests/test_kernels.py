"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Per instructions: sweep shapes/dtypes and assert_allclose against ref.py.
Integer outputs must match the oracle EXACTLY (same random budget).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graph import powerlaw_graph
from repro.kernels import ops
from repro.kernels.its_select import its_select_pallas
from repro.kernels.common import pick_lane, prefix_sum
from repro.kernels.ref import its_select_ref, walk_step_block_ref
from repro.kernels.walk_step import pad_csr_for_kernel, walk_step_pallas


class TestItsSelectKernel:
    @pytest.mark.parametrize("i_dim,p,k,iters", [
        (8, 64, 2, 4),
        (16, 128, 4, 8),
        (8, 256, 8, 8),
        (32, 100, 3, 6),   # non-lane-aligned pool
        (8, 2048, 4, 8),   # max pool tile
    ])
    def test_matches_ref(self, i_dim, p, k, iters):
        key = jax.random.PRNGKey(i_dim * p + k)
        b = jax.random.uniform(key, (i_dim, p))
        b = b * (jax.random.uniform(jax.random.fold_in(key, 1), (i_dim, p)) > 0.2)
        r = jax.random.uniform(jax.random.fold_in(key, 2), (i_dim, iters, k))
        out_k = its_select_pallas(b, r, blk_i=8)
        out_r = its_select_ref(b, r)
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
    def test_dtypes(self, dtype):
        key = jax.random.PRNGKey(5)
        b = jax.random.uniform(key, (16, 128)).astype(dtype)
        r = jax.random.uniform(jax.random.fold_in(key, 1), (16, 8, 4))
        out_k = its_select_pallas(b, r, blk_i=8)
        out_r = its_select_ref(b, r)
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))

    def test_no_duplicates_and_valid(self):
        key = jax.random.PRNGKey(6)
        b = jax.random.uniform(key, (64, 256)) + 0.01
        idx = ops.its_select(key, b, 8, iters=12)
        arr = np.asarray(idx)
        assert (arr >= 0).all()
        for row in arr:
            assert len(set(row.tolist())) == len(row)

    def test_skewed_bias_distribution(self):
        """Kernel selections follow transition probabilities (first draw)."""
        key = jax.random.PRNGKey(7)
        b = jnp.tile(jnp.array([8.0, 4.0, 2.0, 1.0, 1.0] + [0.0] * 59), (4096, 1))
        idx = ops.its_select(key, b, 2)
        first = np.asarray(idx[:, 0])
        counts = np.bincount(first, minlength=5)[:5].astype(float)
        probs = np.array([8, 4, 2, 1, 1]) / 16.0
        n = counts.sum()
        chi2 = np.sum((counts - probs * n) ** 2 / (probs * n))
        assert chi2 < 18.5


class TestWalkStepKernel:
    @pytest.mark.parametrize("max_seg,nv", [(64, 128), (128, 256), (256, 512)])
    def test_matches_ref(self, max_seg, nv):
        g = powerlaw_graph(nv, seed=max_seg, weighted=True)
        assert g.max_degree() <= max_seg, "test graph exceeds segment cap"
        key = jax.random.PRNGKey(max_seg)
        cur = jax.random.randint(key, (64,), 0, nv)
        starts = g.indptr[cur]
        degs = g.indptr[cur + 1] - starts
        inds, wts = pad_csr_for_kernel(g.indices, g.weights, max_seg)
        rand = jax.random.uniform(jax.random.fold_in(key, 1), (64,))
        out_k = walk_step_pallas(starts, degs, inds, wts, rand, max_seg=max_seg)
        out_r = walk_step_block_ref(starts, degs, inds, wts, rand, seg=max_seg)
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))

    @pytest.mark.parametrize("wdtype", [jnp.float32, jnp.bfloat16])
    def test_weight_dtypes(self, wdtype):
        g = powerlaw_graph(128, seed=3, weighted=True)
        key = jax.random.PRNGKey(9)
        cur = jax.random.randint(key, (32,), 0, 128)
        starts = g.indptr[cur]
        degs = g.indptr[cur + 1] - starts
        inds, wts = pad_csr_for_kernel(g.indices, g.weights.astype(wdtype), 64)
        rand = jax.random.uniform(jax.random.fold_in(key, 1), (32,))
        out_k = walk_step_pallas(starts, degs, inds, wts.astype(jnp.float32), rand, max_seg=64)
        out_r = walk_step_block_ref(starts, degs, inds, wts.astype(jnp.float32), rand, seg=64)
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))

    def test_next_vertices_are_neighbors(self):
        g = powerlaw_graph(256, seed=4, weighted=True)
        key = jax.random.PRNGKey(11)
        cur = jax.random.randint(key, (128,), 0, 256)
        nxt = np.asarray(ops.walk_step(key, g, cur, max_seg=64))
        ip, ind = np.asarray(g.indptr), np.asarray(g.indices)
        for c, n in zip(np.asarray(cur), nxt):
            if n >= 0:
                assert n in ind[ip[c] : ip[c + 1]]

    def test_dead_end_returns_minus_one(self):
        import repro.graph.csr as csr
        import numpy as onp
        # vertex 0 has no out edges
        g = csr.csr_from_edges(4, onp.array([1, 2, 3]), onp.array([2, 3, 1]))
        key = jax.random.PRNGKey(12)
        nxt = ops.walk_step(key, g, jnp.array([0, 1], jnp.int32), max_seg=64)
        assert int(nxt[0]) == -1 and int(nxt[1]) >= 0

    def test_oracle_follows_kernel_sum_order(self):
        """Regression: the oracle must sum in the kernel's order, not
        ``jnp.cumsum``'s.  One heavy edge followed by 127 edges each below
        half an ulp of it: a running sum drops every light edge, the
        log-step tree keeps their pairwise sums, so the two orders pick
        different neighbours for the same uniform."""
        seg = 128
        wts = jnp.concatenate([jnp.ones(1), jnp.full(seg - 1, 4e-8)]).astype(jnp.float32)
        inds = jnp.arange(seg, dtype=jnp.int32) + 1000
        inds_p, wts_p = pad_csr_for_kernel(inds, wts, seg)

        def old_pick(r):
            cum = jnp.cumsum(wts)
            return int(jnp.sum(cum <= r * cum[-1]))

        def new_pick(r):
            cum = prefix_sum(wts)
            return int(jnp.sum(cum <= r * pick_lane(cum, seg - 1)[0]))

        rands = jnp.linspace(0.999990, 0.9999999, 64, dtype=jnp.float32)
        split = [r for r in rands if old_pick(r) != new_pick(r)]
        assert split, "constructed row no longer separates the two orders"
        rand = jnp.full((8,), split[0], jnp.float32)
        starts = jnp.zeros((8,), jnp.int32)
        degs = jnp.full((8,), seg, jnp.int32)
        out_k = walk_step_pallas(starts, degs, inds_p, wts_p, rand, max_seg=seg)
        out_r = walk_step_block_ref(starts, degs, inds_p, wts_p, rand, seg=seg)
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
        assert int(out_k[0]) == 1000 + min(new_pick(split[0]), seg - 1)
        assert int(out_k[0]) != 1000 + min(old_pick(split[0]), seg - 1)

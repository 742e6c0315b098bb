"""Data plane: walk corpus (C-SAW as the LM data pipeline) + graph substrate."""
import jax
from jax.sharding import AxisType
import numpy as np
import pytest

from repro.data.walk_corpus import build_walk_corpus
from repro.graph import (
    csr_from_edges,
    erdos_renyi_graph,
    neighbors_padded,
    powerlaw_graph,
    rmat_graph,
)


class TestGenerators:
    def test_powerlaw_degree_distribution(self):
        g = powerlaw_graph(2048, exponent=2.2, seed=0)
        deg = np.asarray(g.indptr[1:] - g.indptr[:-1])
        # heavy tail: max degree well above mean
        assert deg.max() > 5 * deg.mean()

    def test_rmat_structure(self):
        g = rmat_graph(8, edge_factor=8, seed=1)
        assert g.num_vertices == 256
        assert g.num_edges > 0
        deg = np.asarray(g.indptr[1:] - g.indptr[:-1])
        assert deg.max() > 3 * max(deg.mean(), 1)  # skewed (community bias)

    def test_er_uniformish(self):
        g = erdos_renyi_graph(1024, avg_degree=16, seed=2)
        deg = np.asarray(g.indptr[1:] - g.indptr[:-1])
        assert abs(deg.mean() - 16) < 4

    def test_csr_sorted_and_deduped(self):
        src = np.array([0, 0, 0, 1, 1])
        dst = np.array([2, 2, 1, 0, 0])
        g = csr_from_edges(3, src, dst)
        ind = np.asarray(g.indices)
        ip = np.asarray(g.indptr)
        assert list(ind[ip[0] : ip[1]]) == [1, 2]
        assert list(ind[ip[1] : ip[2]]) == [0]

    def test_neighbors_padded(self):
        g = csr_from_edges(4, np.array([0, 0, 1]), np.array([1, 2, 3]))
        import jax.numpy as jnp
        nbrs, wts, mask = neighbors_padded(g, jnp.array([0, 1, 3]), 4)
        assert nbrs.shape == (3, 4)
        np.testing.assert_array_equal(np.asarray(mask).sum(-1), [2, 1, 0])
        assert set(np.asarray(nbrs[0][:2]).tolist()) == {1, 2}


class TestWalkCorpus:
    def test_sequences_are_graph_paths(self):
        g = powerlaw_graph(200, seed=5)
        corpus = build_walk_corpus(g, num_walks=64, walk_length=10, seed=1)
        assert corpus.shape == (64, 11)
        assert (corpus >= 0).all()
        ip, ind = np.asarray(g.indptr), np.asarray(g.indices)
        for row in corpus[:16]:
            for a, b in zip(row[:-1], row[1:]):
                if a == b:  # dead-end padding repeats last vertex
                    continue
                assert b in ind[ip[a] : ip[a + 1]]

    def test_vocab_bound(self):
        g = powerlaw_graph(200, seed=5)
        corpus = build_walk_corpus(g, num_walks=16, walk_length=5, vocab_size=256)
        assert corpus.max() < 256

    def test_node2vec_corpus(self):
        g = powerlaw_graph(128, seed=6, weighted=True)
        corpus = build_walk_corpus(
            g, num_walks=16, walk_length=8, algorithm="node2vec", p=4.0, q=0.25
        )
        assert corpus.shape == (16, 9)

    def test_feeds_lm_training(self):
        """End-to-end integration: C-SAW walks -> pipeline -> LM loss drops."""
        import jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.data.pipeline import TokenPipeline
        from repro.models import init_params
        from repro.train.optimizer import OptConfig, opt_init
        from repro.train.train_step import make_train_step

        g = powerlaw_graph(200, seed=7)
        # a memorizable corpus: 8 fixed walks — every batch is the same 8
        # sequences, so the LM must drive the loss down within a few dozen
        # steps (a 128-walk corpus is genuinely high-entropy: next-vertex
        # conditional entropy ≈ E[log deg], unreachable in a smoke run)
        corpus = build_walk_corpus(g, num_walks=8, walk_length=16, seed=2, vocab_size=256)
        cfg = get_smoke_config("xlstm_350m")  # vocab 256
        pipe = TokenPipeline(cfg.vocab_size, 8, 16, corpus=corpus)
        mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        ocfg = OptConfig(kind="adamw", lr=3e-3, warmup_steps=2)
        params = init_params(jax.random.PRNGKey(0), cfg)
        opt_state = opt_init(ocfg, params)
        step_fn, _ = make_train_step(cfg, ocfg, mesh)
        step = jnp.zeros((), jnp.int32)
        losses = []
        for _ in range(30):
            b = pipe.next()
            batch = {k: jnp.asarray(v) for k, v in b.items()}
            params, opt_state, step, metrics = step_fn(params, opt_state, step, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0] - 0.2, losses

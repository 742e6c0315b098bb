"""Training substrate: optimizers, checkpointing, fault tolerance, pipeline."""
import os

import jax
from jax.sharding import AxisType
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data.pipeline import TokenPipeline
from repro.models import init_params
from repro.train.checkpoint import CheckpointManager
from repro.train.fault import StepMonitor, largest_mesh_shape, run_with_recovery
from repro.train.optimizer import OptConfig, opt_init, opt_update
from repro.train.train_step import make_train_step

KEY = jax.random.PRNGKey(0)


class TestOptimizers:
    @pytest.mark.parametrize("kind", ["adamw", "adafactor"])
    def test_quadratic_convergence(self, kind):
        """Optimizer drives a quadratic toward its minimum."""
        target = {"w": jnp.array([1.0, -2.0, 3.0]), "b": jnp.ones((4, 200)) * 0.5}
        params = {"w": jnp.zeros(3), "b": jnp.zeros((4, 200))}
        cfg = OptConfig(kind=kind, lr=0.05, weight_decay=0.0, warmup_steps=1,
                        min_dim_factored=4)
        state = opt_init(cfg, params)
        loss = lambda p: sum(jnp.sum((p[k] - target[k]) ** 2) for k in p)
        l0 = float(loss(params))
        for i in range(200):
            grads = jax.grad(loss)(params)
            params, state, _ = opt_update(cfg, grads, state, params, jnp.array(i))
        assert float(loss(params)) < 0.05 * l0

    def test_adafactor_state_is_factored(self):
        params = {"big": jnp.zeros((256, 512)), "small": jnp.zeros((8,))}
        cfg = OptConfig(kind="adafactor")
        state = opt_init(cfg, params)
        assert set(state["v"]["big"].keys()) == {"vr", "vc"}
        assert state["v"]["big"]["vr"].shape == (256,)
        assert state["v"]["big"]["vc"].shape == (512,)
        assert set(state["v"]["small"].keys()) == {"v"}

    def test_grad_clip(self):
        params = {"w": jnp.zeros(4)}
        cfg = OptConfig(kind="adamw", grad_clip=1.0, lr=1.0, warmup_steps=1)
        state = opt_init(cfg, params)
        huge = {"w": jnp.full(4, 1e6)}
        new, _, gnorm = opt_update(cfg, huge, state, params, jnp.array(0))
        assert float(gnorm) > 1e5
        assert np.abs(np.asarray(new["w"])).max() < 10.0


class TestTrainingLoop:
    def test_loss_decreases_on_learnable_data(self):
        cfg = get_smoke_config("internlm2-1.8b")
        mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        ocfg = OptConfig(kind="adamw", lr=3e-3, warmup_steps=2)
        params = init_params(KEY, cfg)
        opt_state = opt_init(ocfg, params)
        step_fn, _ = make_train_step(cfg, ocfg, mesh)
        # learnable corpus: fixed repeating pattern
        base = np.arange(33) % 7 + 1
        batch = {
            "tokens": jnp.asarray(np.tile(base[:-1], (4, 1)), jnp.int32),
            "labels": jnp.asarray(np.tile(base[1:], (4, 1)), jnp.int32),
        }
        step = jnp.zeros((), jnp.int32)
        losses = []
        for _ in range(20):
            params, opt_state, step, metrics = step_fn(params, opt_state, step, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < 0.5 * losses[0], losses


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=3, fingerprint="test")
        tree = {"a": jnp.arange(6.0).reshape(2, 3), "b": {"c": jnp.ones(4, jnp.int32)}}
        mgr.save(10, tree)
        restored, manifest = mgr.restore(tree)
        assert manifest["step"] == 10
        for x, y in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(restored)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_keep_k_gc(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        tree = {"a": jnp.zeros(3)}
        for s in (1, 2, 3, 4):
            mgr.save(s, tree)
        assert mgr.all_steps() == [3, 4]

    def test_async_save(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        tree = {"a": jnp.arange(1000.0)}
        mgr.save_async(5, tree)
        mgr.wait()
        restored, m = mgr.restore(tree)
        assert m["step"] == 5

    def test_atomicity_no_tmp_visible(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=3)
        mgr.save(1, {"a": jnp.zeros(2)})
        names = os.listdir(tmp_path)
        assert all(not n.endswith(".tmp") for n in names)

    def test_fingerprint_mismatch_rejected(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=3, fingerprint="cfgA")
        mgr.save(1, {"a": jnp.zeros(2)})
        mgr2 = CheckpointManager(str(tmp_path), keep=3, fingerprint="cfgB")
        with pytest.raises(ValueError):
            mgr2.restore({"a": jnp.zeros(2)})

    def test_restore_latest(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=3)
        for s in (3, 7, 11):
            mgr.save(s, {"a": jnp.full(2, float(s))})
        restored, m = mgr.restore({"a": jnp.zeros(2)})
        assert m["step"] == 11
        assert float(restored["a"][0]) == 11.0


class TestFaultTolerance:
    def test_straggler_detection(self):
        mon = StepMonitor(deadline_factor=3.0)
        for i in range(10):
            assert not mon.observe(i, 1.0)
        assert mon.observe(10, 10.0)  # 10x median
        assert mon.straggler_steps == [10]

    def test_recovery_replays_from_checkpoint(self):
        calls = {"n": 0}

        def step_fn(a, b, batch):
            return a + batch, b, {"loss": 0.0}

        def fail_first(attempt):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("injected node failure")

        state, metrics, attempts = run_with_recovery(
            step_fn, (1, 2), 10,
            restore_fn=lambda: (100, 200),
            fail_injector=fail_first,
        )
        assert attempts == 1
        assert state == (110, 200)  # restored state was used

    def test_recovery_gives_up(self):
        def always_fail(attempt):
            raise RuntimeError("down")
        with pytest.raises(RuntimeError):
            run_with_recovery(lambda *a: a, (1,), 2,
                              restore_fn=lambda: (1,), max_retries=1,
                              fail_injector=always_fail)

    def test_largest_mesh_shape(self):
        assert largest_mesh_shape(512, 16) == (32, 16)
        assert largest_mesh_shape(496, 16) == (31, 16)  # 496 = 31×16
        assert largest_mesh_shape(508, 16) == (127, 4)  # lost nodes: shrink TP
        assert largest_mesh_shape(13, 4) == (13, 1)


class TestPipeline:
    def test_deterministic_by_cursor(self):
        p1 = TokenPipeline(100, 4, 16, seed=3)
        p2 = TokenPipeline(100, 4, 16, seed=3)
        b1, b2 = p1.next(), p2.next()
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])

    def test_state_restore_resumes_stream(self):
        p1 = TokenPipeline(100, 4, 16, seed=3)
        for _ in range(5):
            p1.next()
        state = p1.state_dict()
        expected = p1.next()
        p2 = TokenPipeline(100, 4, 16, seed=3)
        p2.load_state_dict(state)
        got = p2.next()
        np.testing.assert_array_equal(expected["tokens"], got["tokens"])

    def test_labels_shifted(self):
        corpus = np.tile(np.arange(17)[None], (8, 1))
        p = TokenPipeline(100, 4, 16, corpus=corpus)
        b = p.next()
        np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])

    def test_host_sharding(self):
        c = np.arange(8 * 17).reshape(8, 17) % 97
        p0 = TokenPipeline(100, 4, 16, corpus=c, host_index=0, host_count=2)
        p1 = TokenPipeline(100, 4, 16, corpus=c, host_index=1, host_count=2)
        b0, b1 = p0.next(), p1.next()
        assert b0["tokens"].shape == (2, 16)
        assert not np.array_equal(b0["tokens"], b1["tokens"])

"""Sharding rules: divisibility fallback, cache specs, mesh construction."""
import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.distributed.sharding import cache_spec, default_rules, spec_for
from repro.train.fault import largest_mesh_shape


@pytest.fixture(scope="module")
def mesh():
    # abstract 16x16 mesh over 1 real device is fine for spec logic tests
    return AbstractMesh(axis_sizes=(16, 16), axis_names=("data", "model"))


class TestSpecFor:
    def test_basic_2d(self, mesh):
        s = spec_for((2048, 8192), ("embed", "mlp"), mesh)
        assert s == P("data", "model")

    def test_nondivisible_axis_dropped(self, mesh):
        # kv_heads=1 can't shard over model=16
        s = spec_for((2048, 1, 128), ("embed", "kv_heads", None), mesh)
        assert s == P("data")

    def test_axis_used_once(self, mesh):
        # two logical axes both wanting "model": second gets dropped
        s = spec_for((4096, 4096), ("mlp", "rnn"), mesh)
        assert s == P("model")

    def test_layers_never_sharded(self, mesh):
        s = spec_for((24, 2048, 8192), ("layers", "embed", "mlp"), mesh)
        assert s == P(None, "data", "model")


class TestCacheSpec:
    def test_kv_heads_preferred(self, mesh):
        # gemma-7b decode: kv=16 divisible -> heads on model, batch on data
        s = cache_spec((128, 32768, 16, 256), "kv", mesh)
        assert s == P("data", None, "model", None)

    def test_split_kv_when_heads_dont_divide(self, mesh):
        # internlm2 kv=8: sequence takes the model axis (flash-decoding)
        s = cache_spec((128, 32768, 8, 128), "kv", mesh)
        assert s == P("data", "model", None, None)

    def test_long_context_batch1_shards_sequence_everywhere(self, mesh):
        s = cache_spec((1, 524288, 1, 256), "kv", mesh)
        assert s == P(None, ("data", "model"), None, None)

    def test_recurrent_state(self, mesh):
        s = cache_spec((128, 4096), "state", mesh)
        assert s == P("data", "model")


class TestMesh:
    def test_production_mesh_shapes(self):
        # can't build 256-device mesh on 1 CPU; validate the spec instead
        from repro.launch import mesh as mesh_mod
        import inspect
        src = inspect.getsource(mesh_mod.make_production_mesh)
        assert "(2, 16, 16)" in src and "(16, 16)" in src
        assert '("pod", "data", "model")' in src

    def test_elastic_shrink_keeps_model_axis(self):
        assert largest_mesh_shape(512, 16) == (32, 16)
        assert largest_mesh_shape(511, 16) == (511, 1)
        assert largest_mesh_shape(508, 16) == (127, 4)

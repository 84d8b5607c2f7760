"""Unit tests for the logical-sharding machinery (no heavy compiles)."""
import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.models.base import logical_to_pspec
from repro.parallel.sharding import (WorkloadKind, rules_for, fit_pspec,
                                     cache_pspecs, batch_pspec)
from repro.models.layers import KVCache
from repro.models.ssd import SSMCache


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


MESH = FakeMesh({"data": 16, "model": 16})


class TestLogicalMapping:
    def test_basic(self):
        rules = rules_for(WorkloadKind.TRAIN)
        assert logical_to_pspec(("embed", "heads", "head_dim"), rules) \
            == P(("data",), "model", None)

    def test_duplicate_axis_dropped(self):
        rules = rules_for(WorkloadKind.TRAIN, seq_shard=True)
        # seq takes `model` first; heads must fall back to replication
        assert logical_to_pspec(("batch", "seq", "heads"), rules) \
            == P(("data",), "model", None)

    def test_multipod_batch(self):
        rules = rules_for(WorkloadKind.TRAIN, multi_pod=True)
        assert batch_pspec(rules, 2) == P(("pod", "data"), None)

    def test_decode_rules_shard_head_dim(self):
        rules = rules_for(WorkloadKind.DECODE)
        assert rules["head_dim"] == "model"
        assert rules["kv_heads"] is None

    def test_long_decode_shards_cache_seq(self):
        rules = rules_for(WorkloadKind.LONG_DECODE)
        assert rules["batch"] is None
        assert rules["cache_seq"] == ("data",)


class TestFitPspec:
    def test_drops_indivisible(self):
        # kv=2 cannot shard over model=16
        got = fit_pspec(P(None, "model", None), (28, 2, 128), MESH)
        assert got == P(None, None, None)

    def test_keeps_divisible(self):
        got = fit_pspec(P(("data",), "model"), (4096, 32), MESH)
        assert got == P(("data",), "model")

    def test_tuple_axis_size(self):
        mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})
        got = fit_pspec(P(("pod", "data"), None), (64, 8), mesh)
        assert got == P(("pod", "data"), None)
        got = fit_pspec(P(("pod", "data"), None), (48, 8), mesh)
        assert got == P(None, None)   # 48 % 32 != 0

    def test_pads_short_spec(self):
        got = fit_pspec(P("model"), (32, 4, 4), MESH)
        assert got == P("model", None, None)


class TestCachePspecs:
    def test_kv_cache_decode(self):
        rules = rules_for(WorkloadKind.DECODE)
        kv = KVCache(
            k=jax.ShapeDtypeStruct((8, 128, 8, 32896, 128), jnp.bfloat16),
            v=jax.ShapeDtypeStruct((8, 128, 8, 32896, 128), jnp.bfloat16),
            length=jax.ShapeDtypeStruct((8,), jnp.int32))
        spec = cache_pspecs(None, {"l0": kv}, rules)["l0"]
        assert spec.k == P(None, ("data",), None, None, "model")
        assert spec.length == P(None)

    def test_ssm_cache(self):
        rules = rules_for(WorkloadKind.DECODE)
        c = SSMCache(
            conv=jax.ShapeDtypeStruct((48, 128, 3, 3328), jnp.bfloat16),
            state=jax.ShapeDtypeStruct((48, 128, 48, 64, 128), jnp.float32))
        spec = cache_pspecs(None, {"l0": c}, rules)["l0"]
        assert spec.conv == P(None, ("data",), None, None)
        assert spec.state == P(None, ("data",), None, None, None)

    def test_long_decode_seq_sharded(self):
        rules = rules_for(WorkloadKind.LONG_DECODE)
        kv = KVCache(
            k=jax.ShapeDtypeStruct((9, 1, 8, 524416, 128), jnp.bfloat16),
            v=jax.ShapeDtypeStruct((9, 1, 8, 524416, 128), jnp.bfloat16),
            length=jax.ShapeDtypeStruct((9,), jnp.int32))
        spec = cache_pspecs(None, {"l0": kv}, rules)["l0"]
        assert spec.k == P(None, None, None, ("data",), "model")


class TestOverlapPrimitives:
    """core.overlap on a single device (axis size 1: a2a == identity)."""

    def _mesh1(self):
        return jax.make_mesh((1,), ("model",))

    def test_pipelined_a2a_identity(self):
        from repro.core.overlap import pipelined_all_to_all
        mesh = self._mesh1()
        x = jnp.arange(32.0).reshape(8, 4)

        def f(x):
            return pipelined_all_to_all(x, "model", n_chunks=4)

        out = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=jax.sharding.PartitionSpec(),
            out_specs=jax.sharding.PartitionSpec(), check_vma=False))(x)
        assert jnp.allclose(out, x)

    def test_warmup_a2a_identity_and_compute(self):
        from repro.core.overlap import warmup_all_to_all
        mesh = self._mesh1()
        x = jnp.arange(32.0).reshape(8, 4)
        w = jnp.eye(4)

        def f(x, w):
            out, y = warmup_all_to_all(x, "model", warmup_rows=2,
                                       compute_fn=lambda a: a @ w,
                                       compute_arg=x)
            return out, y

        out, y = jax.jit(jax.shard_map(
            f, mesh=mesh,
            in_specs=(jax.sharding.PartitionSpec(),) * 2,
            out_specs=(jax.sharding.PartitionSpec(),) * 2,
            check_vma=False))(x, w)
        assert jnp.allclose(out, x)
        assert jnp.allclose(y, x)

    def test_moe_block_ep_single_shard(self):
        from repro.models.moe import moe_block_ep, init_moe
        from repro.models.base import ParamBuilder
        from repro import configs
        cfg = configs.get_smoke_config("granite-moe-1b-a400m")
        b = ParamBuilder(jax.random.PRNGKey(0))
        init_moe(b, cfg, "moe")
        p = b.params["moe"]
        x = jax.random.normal(jax.random.PRNGKey(1), (32, cfg.d_model))
        mesh = self._mesh1()

        def f(x, wg, wu, wo, r):
            pp = {"wi_gate": wg, "wi_up": wu, "wo": wo, "router": r}
            y, aux = moe_block_ep(pp, cfg, x, "model")
            return y

        y = jax.jit(jax.shard_map(
            f, mesh=mesh,
            in_specs=(jax.sharding.PartitionSpec(),) * 5,
            out_specs=jax.sharding.PartitionSpec(), check_vma=False))(
                x, p["wi_gate"], p["wi_up"], p["wo"], p["router"])
        assert y.shape == x.shape
        assert jnp.isfinite(y).all()

"""Integration tests for the sharded step builders on a local 1x1 mesh.

The 512-device production meshes are exercised by launch/dryrun.py (cached
results in results/dryrun); here we verify the same builders produce
numerically working steps end-to-end on whatever devices exist.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.shapes import ShapeSpec
from repro.kernels.compat import make_mesh
from repro.launch.steps import make_train_step, make_serve_step, make_prefill_step
from repro.optim import adamw, with_master, cosine_with_warmup


def local_mesh():
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))


def smoke_shape(kind, seq, batch):
    return ShapeSpec(name=f"t_{kind}", kind=kind, seq_len=seq,
                     global_batch=batch)


@pytest.fixture(scope="module")
def cfg():
    return configs.get_smoke_config("qwen3-1.7b").replace(n_layers=2)


class TestTrainStep:
    def test_loss_decreases_and_state_shards(self, cfg):
        mesh = local_mesh()
        opt = with_master(adamw(cosine_with_warmup(1e-2, 2, 50)))
        with mesh:
            step, in_sh, _, (params_s, opt_s) = make_train_step(
                cfg, opt, mesh, microbatches=2)
            train_cfg = cfg.replace(param_dtype=cfg.dtype)
            from repro.models import api
            params, _ = api.init(train_cfg, jax.random.PRNGKey(0))
            opt_state = opt.init(params)
            k = jax.random.PRNGKey(1)
            batch = {
                "inputs": jax.random.randint(k, (4, 32), 0, cfg.vocab_size),
                "targets": jax.random.randint(
                    jax.random.fold_in(k, 1), (4, 32), 0, cfg.vocab_size),
            }
            losses = []
            for _ in range(5):
                params, opt_state, metrics = step(params, opt_state, batch)
                losses.append(float(metrics["loss"]))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]          # memorizes a fixed batch
        assert params["tok_embed"].dtype == jnp.bfloat16
        assert opt_state["master"]["tok_embed"].dtype == jnp.float32

    def test_grad_norm_finite(self, cfg):
        mesh = local_mesh()
        opt = with_master(adamw(cosine_with_warmup(1e-3, 2, 50)))
        with mesh:
            step, *_ , (params_s, opt_s) = make_train_step(cfg, opt, mesh)
            from repro.models import api
            params, _ = api.init(cfg.replace(param_dtype=cfg.dtype),
                                 jax.random.PRNGKey(0))
            opt_state = opt.init(params)
            k = jax.random.PRNGKey(2)
            batch = {
                "inputs": jax.random.randint(k, (2, 16), 0, cfg.vocab_size),
                "targets": jax.random.randint(k, (2, 16), 0, cfg.vocab_size),
            }
            _, _, metrics = step(params, opt_state, batch)
            assert np.isfinite(float(metrics["grad_norm"]))


class TestServeSteps:
    def test_prefill_then_serve_runs(self, cfg):
        mesh = local_mesh()
        shape = smoke_shape("decode", seq=64, batch=2)
        with mesh:
            pre, *_ = make_prefill_step(cfg, mesh, shape)
            srv, *_ = make_serve_step(cfg, mesh, shape)
            from repro.models import api
            serve_cfg = cfg.replace(param_dtype=cfg.dtype)
            params, _ = api.init(serve_cfg, jax.random.PRNGKey(0))
            batch = {"inputs": jax.random.randint(
                jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab_size)}
            logits, caches = pre(params, batch)
            assert logits.shape == (2, cfg.vocab_size)
            tok = jnp.argmax(logits, axis=-1)
            logits2, caches = srv(params, tok, caches)
            assert logits2.shape == (2, cfg.vocab_size)
            assert np.isfinite(np.asarray(logits2, np.float32)).all()

    def test_long_decode_rules_apply(self, cfg):
        # global_batch=1 selects LONG_DECODE (cache_seq sharded over data)
        mesh = local_mesh()
        shape = smoke_shape("decode", seq=64, batch=1)
        with mesh:
            srv, in_sh, _, (params_s, cache_s) = make_serve_step(
                cfg, mesh, shape)
            # lowering compiles without allocation
            from repro.launch import specs as sp
            lowered = srv.lower(params_s, sp.token_specs(shape), cache_s)
            compiled = lowered.compile()
            assert compiled.memory_analysis() is not None

"""``moe_block_ep``'s local experts: sorted rows and grouped matmuls against
the one-hot formulation they replaced, which lives on here as the oracle.

The oracle runs every local expert over every received slot through a
one-hot buffer and folds the result back, so it is right by construction
and costs E_loc times the work.  Routing, capacity and the all-to-alls are
shared; only the expert section differs between the two runs.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CASES = ("seeded", "one_expert", "idle_expert", "over_capacity")
DTYPES = ("float32", "bfloat16")
# Largest |program - oracle| over the oracle's largest |y|, by dtype.
TOL = {"float32": 1e-5, "bfloat16": 2e-2}


def onehot_experts(p, rows, recv_meta, n_local):
    """The one-hot expert section: [n_local, S, D] buffer, every expert's
    SwiGLU over every slot, folded back by the same one-hot."""
    sel = jax.nn.one_hot(recv_meta.reshape(-1) - 1, n_local, dtype=rows.dtype)
    buf = jnp.einsum("te,td->etd", sel, rows)
    g = jnp.einsum("etd,edf->etf", buf, p["wi_gate"].astype(rows.dtype))
    u = jnp.einsum("etd,edf->etf", buf, p["wi_up"].astype(rows.dtype))
    out = jnp.einsum("etf,efd->etd", jax.nn.silu(g) * u,
                     p["wo"].astype(rows.dtype))
    return jnp.einsum("etd,te->td", out, sel)


EP_CODE = """
import json, sys
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro import configs
from repro.kernels.compat import make_mesh
from repro.models import moe
from repro.models.base import ParamBuilder
from test_moe_ep import CASES, DTYPES, onehot_experts

N = 4
T = 16                                  # tokens per shard
base = configs.get_smoke_config("qwen3-moe-235b-a22b")
mesh = make_mesh((N,), ("model",))
e = P("model", None, None)
spec = {"router": P(), "wi_gate": e, "wi_up": e, "wo": e}


def inputs(case, cfg):
    b = ParamBuilder(jax.random.PRNGKey(0), cfg.dtype)
    moe.init_moe(b, cfg, "moe")
    p = dict(b.params["moe"])
    D, E = cfg.d_model, cfg.n_experts
    x = jax.random.normal(jax.random.PRNGKey(1), (N * T, D), cfg.dtype)
    if case != "seeded":
        # Logits x . router: all-positive tokens, so a column of ones is
        # every token's largest logit and a column of minus ones its least.
        x = jnp.abs(x) + 0.1
        r = np.zeros((D, E), np.float32)
        if case == "one_expert":              # local expert 0 of shards 0, 1
            r[:, 0], r[:, 2] = 1.0, 0.9
        elif case == "over_capacity":         # both rows of a token to shard 0
            r[:, 0], r[:, 1] = 1.0, 0.9
        else:                                 # idle_expert: 1 never chosen
            r = np.array(p["router"], np.float32)
            r[:, 1] = -1.0
        p["router"] = jnp.asarray(r, cfg.dtype)
    return p, x


def run(p, x, cfg):
    def f(p, x):
        y, _ = moe.moe_block_ep(p, cfg, x, "model")
        return y
    return np.asarray(jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(spec, P("model", None)),
        out_specs=P("model", None), check_vma=False))(p, x), np.float32)


grouped = moe._local_experts
for case in CASES:
    for dtype in DTYPES:
        cfg = base.replace(dtype=dtype)
        p, x = inputs(case, cfg)
        got = run(p, x, cfg)
        moe._local_experts = onehot_experts
        want = run(p, x, cfg)
        moe._local_experts = grouped
        idx, _, _ = moe.route(p, cfg, x.reshape(N, T, -1)[0])
        print(json.dumps({
            "case": case, "dtype": dtype,
            "err": float(np.abs(got - want).max() / np.abs(want).max()),
            "finite": bool(np.isfinite(got).all()),
            "zero_rows": int((np.abs(want).max(axis=1) == 0).sum()),
            "experts_shard0": sorted(set(np.asarray(idx).reshape(-1).tolist()))}))
"""


@pytest.fixture(scope="module")
def ep_runs():
    """Every (case, dtype) of ``moe_block_ep`` on four virtual devices, the
    program and the oracle, in one process."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"),
                                           os.path.join(ROOT, "tests")]),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", EP_CODE], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    rows = [json.loads(ln) for ln in r.stdout.splitlines()
            if ln.startswith("{")]
    return {(d["case"], d["dtype"]): d for d in rows}


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("case", CASES)
def test_moe_block_ep_matches_onehot_oracle(ep_runs, case, dtype):
    got = ep_runs[(case, dtype)]
    assert got["finite"]
    assert got["err"] <= TOL[dtype], got
    routed = got["experts_shard0"]
    if case == "one_expert":
        assert routed == [0, 2]
    elif case == "over_capacity":
        # Both rows of every token go to shard 0, twice the 16 slots each
        # source has there: the last 8 tokens of each source are dropped.
        assert routed == [0, 1]
        assert got["zero_rows"] == 4 * 8
    elif case == "idle_expert":
        assert 1 not in routed


def _meta(pattern, n_local, shape=(4, 24), seed=0):
    rng = np.random.default_rng(seed)
    if pattern == "random":
        return rng.integers(0, n_local + 1, shape)
    if pattern == "empty":
        return np.zeros(shape, np.int64)
    if pattern == "one_expert":
        return np.where(rng.random(shape) < 0.7, 3, 0)
    if pattern == "full":                        # no empty slot
        return rng.integers(1, n_local + 1, shape)
    # "idle": expert 2 (meta 3) receives nothing
    m = rng.integers(0, n_local + 1, shape)
    return np.where(m == 3, 1, m)


PATTERNS = ("random", "empty", "one_expert", "full", "idle")


@pytest.mark.parametrize("pattern", PATTERNS)
def test_local_groups_sort_by_expert_and_count_its_rows(pattern):
    n_local = 4
    meta = _meta(pattern, n_local).astype(np.int32)
    order, sizes = moe._local_groups(jnp.asarray(meta), n_local)
    flat = meta.reshape(-1)
    want = np.bincount(flat, minlength=n_local + 1)[1:]
    assert sizes.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(sizes), want)
    key = np.where(flat == 0, n_local, flat - 1)
    np.testing.assert_array_equal(np.asarray(order),
                                  np.argsort(key, kind="stable"))


@pytest.mark.parametrize("pattern", PATTERNS)
def test_local_experts_match_onehot_and_zero_empty_slots(pattern):
    n_local, D, F = 4, 32, 48
    meta = jnp.asarray(_meta(pattern, n_local), jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    rows = jax.random.normal(ks[0], (meta.size, D), jnp.float32)
    rows = jnp.where(meta.reshape(-1, 1) > 0, rows, 0)
    p = {"wi_gate": jax.random.normal(ks[1], (n_local, D, F)) / 6,
         "wi_up": jax.random.normal(ks[2], (n_local, D, F)) / 6,
         "wo": jax.random.normal(ks[3], (n_local, F, D)) / 7}
    got = np.asarray(moe._local_experts(p, rows, meta, n_local))
    want = np.asarray(onehot_experts(p, rows, meta, n_local))
    empty = np.asarray(meta).reshape(-1) == 0
    assert (got[empty] == 0).all()                 # exact, never NaN
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=TOL["float32"] * max(
                                   np.abs(want).max(), 1.0))

"""Named scopes reach the compiled program, and the compile counter counts.

The scopes (``repro.models.scopes``) are only ``op_name`` metadata; the
chip benchmark reads them back from the compiled text
(``benchmarks/chip/scopes.py``) to split device time by sub-layer.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from benchmarks.chip.scopes import UNSCOPED, scope_map, strip_metadata
from repro import configs
from repro.launch.compile_cache import CompileCounter
from repro.launch.serve import init_params, make_steps, serving_config
from repro.models.scopes import SCOPES, scope

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def granite_steps():
    """Compiled smoke-size granite prefill and decode steps."""
    cfg = serving_config(configs.get_smoke_config("granite-moe-1b-a400m"))
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    prefill, decode = make_steps(cfg, 16)
    batch = {"inputs": jax.ShapeDtypeStruct((2, 8), jnp.int32)}
    _, caches = jax.eval_shape(prefill, params, batch)
    tok = jax.ShapeDtypeStruct((2,), jnp.int32)
    return (prefill.lower(params, batch).compile(),
            decode.lower(params, tok, caches).compile())


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_every_sublayer_is_the_innermost_scope_of_some_op(granite_steps,
                                                          step):
    compiled = granite_steps[step == "decode"]
    got = set(scope_map(compiled, SCOPES).values())
    want = {"embed", "layer_scan", "attn", "moe.router", "moe.dispatch",
            "moe.experts", "moe.combine", "unembed"}
    assert want <= got, want - got


def test_prefill_program_is_named(granite_steps):
    head = granite_steps[0].as_text().split("\n", 1)[0]
    assert head.startswith("HloModule jit_prefill,"), head


def test_scopes_change_only_metadata():
    def body(x):
        return jnp.tanh(x @ x.T).sum()

    def scoped(x):
        with scope("attn"):
            return body(x)

    x = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    a = jax.jit(body).lower(x).compile().as_text()
    b = jax.jit(scoped).lower(x).compile().as_text()
    assert 'scope="attn"' in b and a != b
    assert strip_metadata(a) == strip_metadata(b)


CACHE_CODE = """
import sys
import jax, jax.numpy as jnp
from repro.models.scopes import scope

jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
hits = []
jax.monitoring.register_event_listener(
    lambda e, **_: hits.append(e) if e.endswith("cache_hits") else None)

def program(name):
    def step(x):
        if name is None:
            return jnp.tanh(x @ x.T).sum()
        with scope(name):
            return jnp.tanh(x @ x.T).sum()
    return step

x = jax.ShapeDtypeStruct((8, 8), jnp.float32)
for name in sys.argv[2:]:
    n = len(hits)
    text = jax.jit(program(None if name == "-" else name)).lower(
        x).compile().as_text()
    print(name, len(hits) > n, f"/{name}/" in text)
"""


def test_a_cached_program_keeps_its_own_scopes(tmp_path):
    """JAX's persistent cache keys leave metadata out; the scope attribute
    keeps a program without scopes, or with others, from being handed back
    in place of the scoped one."""
    r = subprocess.run(
        [sys.executable, "-c", CACHE_CODE, str(tmp_path), "-", "attn",
         "ffn", "attn"], cwd=ROOT, capture_output=True, text=True,
        timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu",
                              PYTHONPATH=os.path.join(ROOT, "src")))
    assert r.returncode == 0, r.stderr[-3000:]
    # (scope, cache hit, its name in the compiled op_names)
    assert r.stdout.split() == ["-", "False", "False",
                                "attn", "False", "True",
                                "ffn", "False", "True",
                                "attn", "True", "True"]


EP_CODE = """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from benchmarks.chip.scopes import scope_map
from repro import configs
from repro.kernels.compat import make_mesh
from repro.models import moe
from repro.models.base import ParamBuilder
from repro.models.scopes import SCOPES, scope

cfg = configs.get_smoke_config("qwen3-moe-235b-a22b")
mesh = make_mesh((4,), ("model",))
b = ParamBuilder(jax.random.PRNGKey(0), cfg.dtype)
moe.init_moe(b, cfg, "moe")
p = jax.eval_shape(lambda: b.params["moe"])
e = P("model", None, None)
spec = {"router": P(), "wi_gate": e, "wi_up": e, "wo": e}
step = jax.jit(jax.shard_map(
    lambda p, x: moe.moe_block_ep(p, cfg, x, "model")[0], mesh=mesh,
    in_specs=(spec, P("model", None)), out_specs=P("model", None),
    check_vma=False))
x = jax.ShapeDtypeStruct((4 * 16, cfg.d_model), jnp.dtype(cfg.dtype))
text = step.lower(p, x).compile().as_text()
m = scope_map(text, SCOPES)
for line in text.splitlines():
    op = line.split(" = ", 1)[0].strip().removeprefix("ROOT ").strip()
    if " all-to-all(" in line:
        print("A2A", op, m[op])
"""


def test_every_all_to_all_has_its_ep_scope():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", EP_CODE], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    got = [ln.split()[2] for ln in r.stdout.splitlines()
           if ln.startswith("A2A ")]
    assert sorted(got) == ["ep.combine", "ep.dispatch", "ep.meta"], got
    assert UNSCOPED not in got


def test_compile_counter_counts_a_new_program_once():
    x = jnp.arange(3.0)
    f = jax.jit(lambda v: lax.sin(v))
    with CompileCounter() as first:
        f(x).block_until_ready()
    with CompileCounter() as again:
        f(x).block_until_ready()
    assert (first.traces, first.compiles) == (1, 1)
    assert first.compile_s > 0
    assert (again.traces, again.compiles, again.cache_hits) == (0, 0, 0)


def test_compile_counter_stops_counting_on_exit():
    with CompileCounter() as c:
        pass
    jax.jit(lambda v: lax.cos(v))(jnp.arange(2.0)).block_until_ready()
    assert (c.traces, c.compiles) == (0, 0)

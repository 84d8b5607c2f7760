"""Compiles for a described TPU v5e: what the chip's compiler refuses fails
here, with no chip attached.

Covers the four Pallas kernels at the shapes ``workloads/calibrate.py``
measures (interpret mode off), the serving steps of
granite-moe-1b-a400m at its published widths, and the expert-parallel
step of the ``qwen3moe_ep4`` benchmark cell over the described 2x2
host.  Nothing runs, so nothing here says anything about results or
times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every pytest-xdist worker
imports this file.  The persistent compile cache is off around these
compiles (an entry compiled for a described chip cannot be read back).
"""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import configs
from repro.workloads.calibrate import (_CAP_EXPERTS, _CAP_FF, _CAP_HEADS,
                                       _CAP_SEQ, _CAP_TOKENS)

V5E_HBM_BYTES = 16 * 2**30
GRANITE = "granite-moe-1b-a400m"
BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks", "chip")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """One described v5e chip, with the persistent compile cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _on(chip, tree):
    """ShapeDtypeStructs of ``tree`` placed on the described chip."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)


def _kernel_case(name, dtype):
    """(kernel call with interpret off, argument shapes) as calibrate.py
    measures them: granite's attention and MoE phases, mamba2's SSD."""
    from repro.kernels.flash_attention import flash_attention_kernel
    from repro.kernels.grouped_matmul import grouped_matmul_kernel
    from repro.kernels.rmsnorm import rmsnorm_kernel
    from repro.kernels.ssd_scan import ssd_chunk_kernel

    g = configs.get_config(GRANITE)
    S = jax.ShapeDtypeStruct
    T, D = _CAP_TOKENS, g.d_model
    if name == "rmsnorm":
        return (lambda x, w: rmsnorm_kernel(x, w, interpret=False),
                (S((T, D), dtype), S((D,), dtype)))
    if name == "flash_attention":
        H = min(g.n_heads, _CAP_HEADS)
        KV = max(1, min(g.n_kv_heads, H))
        q = S((1, _CAP_SEQ, H, g.d_head), dtype)
        kv = S((1, _CAP_SEQ, KV, g.d_head), dtype)
        return (lambda q, k, v: flash_attention_kernel(
            q, k, v, causal=True, interpret=False), (q, kv, kv))
    if name == "grouped_matmul":
        E = min(g.n_experts, _CAP_EXPERTS)
        return (lambda l, r, o: grouped_matmul_kernel(l, r, o,
                                                      interpret=False),
                (S((T, D), dtype), S((E, D, _CAP_FF), dtype),
                 S((E + 1,), jnp.int32)))
    m = configs.get_config("mamba2-780m")        # ssd_scan: calibrate's slice
    H = min(max(1, m.d_model * m.ssm_expand // m.ssm_head_dim), 2)
    P, N = max(m.ssm_head_dim, 8), min(max(m.ssm_state, 16), 64)
    Q = 64
    G = (_CAP_SEQ // Q) * H
    return (lambda x, dt, a, B, C: ssd_chunk_kernel(x, dt, a, B, C,
                                                    interpret=False),
            (S((G, Q, P), dtype), S((G, Q), dtype), S((G, Q), dtype),
             S((G, Q, N), dtype), S((G, Q, N), dtype)))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", ["rmsnorm", "flash_attention",
                                  "grouped_matmul", "ssd_chunk_kernel"])
def test_kernel_compiles_for_v5e(chip, name, dtype):
    fn, shapes = _kernel_case(name, jnp.dtype(dtype))
    compiled = jax.jit(fn).lower(*_on(chip, shapes)).compile()
    assert "tpu_custom_call" in compiled.as_text()   # Mosaic, not XLA


def _granite_steps(chip, batch, prompt_len, s_max):
    from repro.launch.serve import make_batch, make_steps, serving_config
    from repro.models import api

    cfg = serving_config(configs.get_config(GRANITE))
    key = jax.random.PRNGKey(0)
    params = _on(chip, jax.eval_shape(lambda k: api.init(cfg, k)[0], key))
    prompts = _on(chip, jax.eval_shape(
        lambda k: make_batch(cfg, k, batch, prompt_len), key))
    prefill, decode = make_steps(cfg, s_max)
    return cfg, params, prompts, prefill, decode


def _fits_one_chip(compiled):
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
    return ma


def test_granite_full_width_prefill_compiles_for_v5e(chip):
    cfg, params, prompts, prefill, _ = _granite_steps(chip, 1, 128, 160)
    assert (cfg.n_layers, cfg.d_model, cfg.n_experts) == (24, 1024, 32)
    ma = _fits_one_chip(prefill.lower(params, prompts).compile())
    # bf16 weights: ~1.4 B parameters at two bytes each.
    assert 2.5e9 < ma.argument_size_in_bytes < 3.0e9


def test_granite_full_width_decode_compiles_for_v5e(chip):
    _, params, prompts, prefill, decode = _granite_steps(chip, 8, 512, 544)
    _, caches = jax.eval_shape(prefill, params, prompts)
    tok = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=chip)
    ma = _fits_one_chip(decode.lower(params, tok, _on(chip, caches))
                        .compile())
    assert ma.alias_size_in_bytes > 0                # the cache is donated


def _cache_stays_in_place(compiled, caches):
    """The decode step writes into its donated stacked cache where it lies:
    no copy has the shape of a whole stacked K or V, and the step's
    scratch stays under a quarter of the cache's bytes."""
    ma = compiled.memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(caches))
    stacks = {"[" + ",".join(map(str, a.shape)) + "]"
              for a in jax.tree.leaves(caches) if a.ndim == 5}
    copies = [ln for ln in compiled.as_text().splitlines()
              if re.search(r"\[[0-9,]+\]\{[^}]*\} copy(-start)?\(", ln)
              and re.search(r"\[[0-9,]+\]", ln).group(0) in stacks]
    assert not copies, copies
    assert ma.temp_size_in_bytes < 0.25 * cache_bytes, (
        ma.temp_size_in_bytes, cache_bytes)
    assert ma.alias_size_in_bytes > 0                # the cache is donated


def _cell(config, traffic):
    """A benchmark cell's configuration and traffic files."""
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        c = json.load(f)
    with open(os.path.join(BENCH, "traffic", traffic + ".json")) as f:
        return c, json.load(f)


def _expert_dots(text, cfg):
    """The dots (``dot`` or ``convolution``) of a compiled program that
    read a layer's expert weights: an operand of E x D x F elements."""
    shapes = {}
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?(%\S+)\s+=\s+\w+\[([0-9,]*)\]", ln)
        if m:
            shapes[m.group(1)] = np.prod([int(d) for d in m.group(2).split(",")
                                          if d])
    n = cfg.n_experts * cfg.d_model * cfg.d_ff_expert
    dots = []
    for ln in text.splitlines():
        m = re.search(r" (?:dot|convolution)\(([^)]*)\)", ln)
        if m and any(shapes.get(a.strip()) == n
                     for a in m.group(1).split(",")):
            dots.append(ln.split(" = ", 1)[0].split()[-1])
    return dots


@pytest.fixture(scope="module")
def granite_decode_cell(chip):
    """The ``granite_decode`` cell's decode step (64 sequences, caches of
    256 + 512 positions over 24 layers, 2.4 GB), compiled once."""
    c, t = _cell(GRANITE, "decode_rounds")
    B, P = t["batch"], t["prompt_len"]
    cfg, params, prompts, prefill, decode = _granite_steps(
        chip, B, P, P + t["gen_tokens"])
    _, caches = jax.eval_shape(prefill, params, prompts)
    tok = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=chip)
    compiled = decode.lower(params, tok, _on(chip, caches)).compile()
    return c, t, cfg, caches, compiled


def test_granite_decode_writes_its_cache_in_place_for_v5e(granite_decode_cell):
    *_, caches, compiled = granite_decode_cell
    _fits_one_chip(compiled)
    _cache_stays_in_place(compiled, caches)


def test_granite_decode_experts_run_over_the_tokens_for_v5e(
        granite_decode_cell):
    """At decode (one token a sequence, capacity 8) ``moe_gather`` builds
    no capacity buffer: no ``[64, 32, 8, *]`` array is left, a layer
    compiles to at most 4x the work it needs, and every dot that reads
    the experts' weights is timed under ``moe.experts``."""
    from benchmarks.chip.scopes import scope_map
    from benchmarks.chip.work import decode_step_work
    from repro.models.scopes import MOE_EXPERTS, SCOPES

    c, t, cfg, _, compiled = granite_decode_cell
    text = compiled.as_text()
    B, E = t["batch"], cfg.n_experts
    for width in (cfg.d_model, cfg.d_ff_expert):
        assert f"[{B},{E},8,{width}]" not in text
    # The cost analysis counts the scanned layer's body once: take out the
    # head (the work of no layers) on both sides and compare one layer at
    # a round's mean fill.  Each token runs through all E experts, E/k = 4
    # times the routed work; the experts are ~75% of a layer's flops, so a
    # layer compiles to ~3.3x.  The capacity buffer (8 slots a token in
    # every expert) made it ~24x.
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    fill = t["prompt_len"] + t["gen_tokens"] / 2

    def work(layers):
        return decode_step_work(dict(c, num_hidden_layers=layers), B,
                                fill)["flops"]

    assert cost["flops"] - work(0) <= 4 * (work(1) - work(0))
    scopes = scope_map(text, SCOPES)
    dots = _expert_dots(text, cfg)
    assert len(dots) == 3                              # gate, up, down
    assert {scopes[d] for d in dots} == {MOE_EXPERTS}


def test_granite_prefill_keeps_its_capacity_buffer_for_v5e(chip):
    """The ``granite_prefill`` cell's prefill (one 2048-token prompt,
    capacity 640 < 2048) still scatters into the ``[1, 32, 640, *]``
    capacity buffer."""
    from repro.models.moe import _capacity

    _, t = _cell(GRANITE, "prefill_rounds")
    B, P = t["batch"], t["prompt_len"]
    cfg, params, prompts, prefill, _ = _granite_steps(
        chip, B, P, P + t["gen_tokens"])
    C = _capacity(cfg, P)
    assert C == 640
    compiled = prefill.lower(params, prompts).compile()
    _fits_one_chip(compiled)
    assert f"[{B},{cfg.n_experts},{C},{cfg.d_model}]" in compiled.as_text()


def test_qwen3_ep_step_runs_each_row_through_its_own_expert(topo, chip,
                                                            monkeypatch):
    """The 4-layer expert-parallel step at the ``qwen3moe_ep4`` cell's
    widths (32 experts and 256 tokens per chip): no one-hot expert buffer
    is left, the compiled work stays within twice what the model needs,
    and every grouped-matmul kernel is timed under ``ep.experts``."""
    from benchmarks.chip.scopes import scope_map
    from benchmarks.chip.work import ep_step_work
    from repro.kernels import ops
    from repro.models import moe
    from repro.models.base import ParamBuilder
    from repro.models.scopes import EP_EXPERTS, SCOPES

    c, t = _cell("qwen3-moe-235b-a22b-ep4", "ep_decode256")
    tokens = t["tokens_per_chip"]
    monkeypatch.setattr(ops, "on_tpu", lambda: True)   # compile, not interpret
    cfg = configs.get_config(c["arch"]).replace(**c["overrides"])
    n, L = len(topo.devices), cfg.n_layers
    mesh = Mesh(np.array(topo.devices), ("model",))
    e = P("model", None, None)
    spec = {"router": P(), "wi_gate": e, "wi_up": e, "wo": e}

    def one_layer(key):
        b = ParamBuilder(key, cfg.dtype)
        moe.init_moe(b, cfg, "moe")
        return b.params["moe"]

    layer = jax.eval_shape(one_layer, jax.random.PRNGKey(0))
    params = {f"l{i}": {k: jax.ShapeDtypeStruct(
        v.shape, v.dtype, sharding=NamedSharding(mesh, spec[k]))
        for k, v in layer.items()} for i in range(L)}
    x = jax.ShapeDtypeStruct((n * tokens, cfg.d_model), jnp.dtype(cfg.dtype),
                             sharding=NamedSharding(mesh, P("model", None)))

    def stack(p, x):
        for i in range(L):
            x = x + moe.moe_block_ep(p[f"l{i}"], cfg, x, "model")[0]
        return x

    compiled = jax.jit(jax.shard_map(
        stack, mesh=mesh, in_specs=({f"l{i}": spec for i in range(L)},
                                    P("model", None)),
        out_specs=P("model", None), check_vma=False)).lower(
            params, x).compile()
    text = compiled.as_text()
    e_loc = cfg.n_experts // n
    slots = n * moe._capacity(cfg, tokens) * e_loc
    assert f"[{e_loc},{slots},{cfg.d_model}]" not in text   # [32,2560,4096]
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["flops"] <= 2 * ep_step_work(c, tokens, n)["flops"]
    scopes = scope_map(text, SCOPES)
    kernels = [ln.split(" = ", 1)[0].split()[-1] for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(kernels) == 3 * L                      # gate, up, down
    assert {scopes[k] for k in kernels} == {EP_EXPERTS}


def test_qwen3_share_serving_steps_compile_for_v5e(chip, monkeypatch):
    """The ``qwen3_share_decode`` cell's prefill (64 prompts of 2048) and
    decode step (caches of 2304) at published widths, 8 layers and 8 held
    experts: each fits one chip, the decode step writes its cache in
    place, and the held experts' grouped matmuls (gate, up, down) are the
    only kernels, all timed under ``moe.experts``."""
    from benchmarks.chip.scopes import scope_map
    from repro.kernels import ops
    from repro.launch.serve import make_steps, serving_config
    from repro.models import api
    from repro.models.scopes import MOE_EXPERTS, SCOPES

    c, t = _cell("qwen3-moe-235b-a22b-ep16", "long_prompt_rounds")
    monkeypatch.setattr(ops, "on_tpu", lambda: True)   # compile, not interpret
    cfg = serving_config(configs.get_config(c["arch"]).replace(
        **c["overrides"]))
    assert (cfg.n_experts, cfg.experts_held, cfg.d_model) == (128, 8, 4096)
    B, P = t["batch"], t["prompt_len"]
    params = _on(chip, jax.eval_shape(lambda k: api.init(cfg, k)[0],
                                      jax.random.PRNGKey(0)))
    batch = {"inputs": jax.ShapeDtypeStruct((B, P), jnp.int32,
                                            sharding=chip)}
    prefill, decode = make_steps(cfg, P + t["gen_tokens"])
    _, caches = jax.eval_shape(prefill, params, batch)
    tok = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=chip)
    step = decode.lower(params, tok, _on(chip, caches)).compile()
    _cache_stays_in_place(step, caches)
    for compiled in (prefill.lower(params, batch).compile(), step):
        _fits_one_chip(compiled)
        text = compiled.as_text()
        scopes = scope_map(text, SCOPES)
        kernels = [ln.split(" = ", 1)[0].split()[-1]
                   for ln in text.splitlines()
                   if 'custom_call_target="tpu_custom_call"' in ln]
        assert len(kernels) == 3                   # in the scanned layer
        assert {scopes[k] for k in kernels} == {MOE_EXPERTS}

"""Architecture specification shared by every model family — jax-free.

:class:`ModelConfig` is the single config object the whole repo keys on:
the JAX model zoo (:mod:`repro.models`), the launch/runtime layers, and the
pure-Python workload derivation (:mod:`repro.workloads`) and serving layers
(:mod:`repro.serving`).  The latter two must resolve registry architectures
*without* importing jax (the serving CLI runs offline), so the config lives
here as a plain dataclass: ``dtype``/``param_dtype`` default to dtype
*names* ("bfloat16"/"float32"), which every jnp call site (``astype``,
``jnp.zeros``, ``ShapeDtypeStruct``...) accepts interchangeably with the
jnp dtype objects the defaults used to be.

:mod:`repro.models.base` re-exports :class:`ModelConfig` for the JAX tier,
so existing ``from repro.models.base import ModelConfig`` imports keep
working (but pull in jax); jax-free callers import from here or from
:mod:`repro.configs`.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Tuple


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab_size: int
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    # MoE
    n_experts: int = 0
    top_k: int = 0
    d_ff_expert: int = 0
    moe_every: int = 1               # MoE FFN on layers where idx % every == r
    capacity_factor: float = 1.25
    moe_impl: str = "gather"         # "gather" (pjit auto) | "ep" (shard_map)
    # One expert-parallel rank's share, served on one chip: the experts
    # [expert_offset, expert_offset + experts_held) of the router's
    # n_experts are held here (0 = all of them, through moe_gather).
    experts_held: int = 0
    expert_offset: int = 0
    # SSM / hybrid
    layer_pattern: Tuple[str, ...] = ()   # repeating pattern, e.g. 7x mamba + attn
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # encoder-decoder (whisper-style)
    is_encoder_decoder: bool = False
    n_enc_layers: int = 0
    enc_frames: int = 1500
    # VLM (stub frontend provides patch embeddings)
    n_img_tokens: int = 0
    # attention extras
    sliding_window: int = 0          # 0 = full causal
    # execution — dtype *names*, accepted verbatim by every jnp call site;
    # kept as strings so this module (and hence repro.configs) never needs
    # jax.
    dtype: Any = "bfloat16"
    param_dtype: Any = "float32"
    remat: bool = True
    scan_layers: bool = True
    # Chunk FFN weights over the hidden dim inside a lax.scan: bounds the
    # number of simultaneously-gathered FSDP weight shards (XLA cannot hoist
    # an all-gather out of a loop).  1 = unchunked.
    ffn_chunks: int = 1
    # Same idea for SSM layers: scan over head groups so z/x/out projection
    # weights are gathered one group at a time.  1 = unchunked.
    ssm_scan_groups: int = 1

    def kv_bytes_per_token(self, dtype_bytes: int = 2) -> int:
        """KV-cache footprint of one token across all layers, in bytes.

        ``n_kv_heads * d_head`` per K and per V (the factor 2) per layer
        that runs attention — SSM/hybrid patterns only cache KV on their
        ``attn`` layers (Mamba state is step-local, not a growing cache).
        This is the quantity the disaggregated serving handoff transfers
        per prompt token (DESIGN.md §16).
        """
        if self.layer_pattern:
            attn_per_block = sum(1 for kind in self.layer_pattern
                                 if kind == "attn")
            attn_layers = self.n_blocks * attn_per_block
        else:
            attn_layers = self.n_layers
        return self.n_kv_heads * self.d_head * 2 * dtype_bytes * attn_layers

    @property
    def pattern(self) -> Tuple[str, ...]:
        if self.layer_pattern:
            return self.layer_pattern
        return ("attn",)

    @property
    def block_size(self) -> int:
        return len(self.pattern)

    @property
    def n_blocks(self) -> int:
        assert self.n_layers % self.block_size == 0, (
            f"{self.name}: n_layers {self.n_layers} not divisible by "
            f"pattern period {self.block_size}")
        return self.n_layers // self.block_size

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

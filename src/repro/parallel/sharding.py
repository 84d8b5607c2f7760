"""Logical->physical sharding rules per workload (train / prefill / decode).

Mesh axes: ``("pod", "data", "model")`` multi-pod or ``("data", "model")``
single-pod.  Parallelism mapping:

  * ``pod``+``data`` — data parallel over the global batch, and FSDP: weight
    matrices are *also* sharded on their row (embed/mlp input) axis over the
    data axis, so parameters + optimizer state are fully sharded 2-D
    (data x model) like MaxText FSDP+TP.  GSPMD inserts the per-layer
    all-gathers / reduce-scatters.
  * ``model`` — tensor parallel (attention heads, MLP columns, vocab) and
    expert parallel (the MoE "experts" axis) — the collective the paper
    studies rides this axis.
  * decode shapes re-map: KV-cache head_dim shards over ``model`` (kv_heads
    can be < 16) and ``long_500k`` (batch=1) shards the cache sequence over
    ``data`` instead of the batch.

A logical name maps to at most one mesh axis per array; duplicate physical
axes within one array resolve to replication for the later name
(``logical_to_pspec`` drops them), which is what makes a single rule table
serve parameters and activations at once.
"""
from __future__ import annotations

import enum
from typing import Any, Dict

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.base import logical_to_pspec


class WorkloadKind(str, enum.Enum):
    TRAIN = "train"
    PREFILL = "prefill"
    DECODE = "decode"
    LONG_DECODE = "long_decode"


def rules_for(kind: WorkloadKind, multi_pod: bool = False,
              fsdp: bool = True, seq_shard: bool = False) -> Dict[str, Any]:
    data = ("pod", "data") if multi_pod else ("data",)
    rules: Dict[str, Any] = {
        "batch": data,
        "embed": (data if fsdp else None),   # FSDP row-shard of weights
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "mlp": "model",
        "vocab": "model",
        "experts": "model",                  # expert parallelism
        "expert_embed": data,                # FSDP rows of expert weights
        "expert_mlp": None,
        "ssm_inner": "model",
        "cache_seq": None,
        # flattened [batch*seq, d] token tensors (MoE dispatch path)
        "tokens": data + ("model",),
        # Sequence parallelism: sharding activations' seq dim over `model`
        # bounds residual/attention memory when heads don't divide the TP
        # axis and shrinks the saved scan carries of deep stacks.
        "seq": ("model" if seq_shard else None),
        "layers": None,
    }
    if kind in (WorkloadKind.DECODE, WorkloadKind.LONG_DECODE):
        rules["tokens"] = data
        # (A weight-stationary expert layout — expert_embed=None,
        # expert_mlp=data — was measured in the Perf hillclimb and refuted:
        # GSPMD still gathers the weights; see EXPERIMENTS.md Perf cell 3.)
        # Serving keeps FSDP rows (`embed` over data): the big archs
        # (jamba-398B, qwen3-moe-235B) exceed per-pod HBM under TP-only even
        # at bf16, so weights are gathered per layer during decode (the
        # standard capacity/latency trade at this scale).
        rules["kv_heads"] = None
        rules["head_dim"] = "model"          # shards any GQA cache (kv>=1)
    if kind == WorkloadKind.LONG_DECODE:
        rules["batch"] = None                # global_batch=1
        rules["cache_seq"] = data            # sequence-sharded cache
    return rules


def param_pspecs(specs, rules) -> Any:
    """Map a logical-axes pytree to PartitionSpecs."""
    return jax.tree.map(lambda ax: logical_to_pspec(ax, rules), specs,
                        is_leaf=lambda x: isinstance(x, tuple))


def _axis_size(mesh: Mesh, part) -> int:
    if part is None:
        return 1
    parts = part if isinstance(part, (tuple, list)) else (part,)
    n = 1
    for p in parts:
        n *= mesh.shape[p]
    return n


def fit_pspec(spec: P, shape, mesh: Mesh) -> P:
    """Drop partitions whose mesh-axis size does not divide the dim size
    (e.g. kv_heads=2 cannot shard over model=16 -> replicate that dim)."""
    parts = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, part in zip(shape, parts[:len(shape)]):
        out.append(part if part is None or dim % _axis_size(mesh, part) == 0
                   else None)
    return P(*out)


def fit_tree(spec_tree, shape_tree, mesh: Mesh):
    """fit_pspec over parallel (specs, shapes) pytrees."""
    return jax.tree.map(
        lambda s, x: fit_pspec(s, x.shape, mesh), spec_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, P))


def batch_pspec(rules, ndim: int = 2) -> P:
    """[B, S, ...] batches: shard batch dim, replicate the rest."""
    return P(rules.get("batch"), *([None] * (ndim - 1)))


def make_shardings(mesh: Mesh, spec_tree) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


# -------------------------------------------------------------- cache specs
def cache_pspecs(cfg, cache_shapes, rules) -> Any:
    """PartitionSpecs for a decode-cache pytree (by leaf shape pattern).

    Caches are built by ``api.prefill``: KVCache leaves are
    [blocks, B, KV, S, Dh], SSM conv [blocks, B, K-1, C], SSM state
    [blocks, B, H, P, N], lengths [blocks]; enc-dec cross-KV are
    [blocks, B, F, KV, Dh].  We map axes by position.
    """
    data = rules.get("batch")
    cseq = rules.get("cache_seq")
    hd = rules.get("head_dim")
    kv = rules.get("kv_heads")

    def spec_for(leaf):
        nd = len(leaf.shape)
        if nd == 5:                      # enc-dec cross-KV [L, B, F, KV, Dh]
            return P(None, data, cseq, kv, hd)
        if nd == 4:                      # [L, B, K-1, x|B|C] conv cache
            # channel dim replicated: it concatenates a sharded (x) and two
            # replicated (B, C) streams, so boundaries are shard-misaligned
            # (and the cache is tiny: [K-1, d_inner+2N] per sequence).
            return P(None, data, None, None)
        if nd == 3:
            return P(None, data, None)
        if nd == 1 or nd == 0:           # lengths
            return P(*([None] * nd))
        if nd == 2:
            return P(None, data)
        return P(*([None] * nd))

    def spec_for_state(leaf):
        # SSM state [L, B, H, P, N]
        return P(None, data, None, None, None)

    from ..models.layers import KVCache
    from ..models.ssd import SSMCache

    def map_cache(c):
        if isinstance(c, KVCache):
            kv_spec = P(None, data, kv, cseq, hd)    # [L, B, KV, S, Dh]
            return KVCache(k=kv_spec, v=kv_spec, length=P(None))
        if isinstance(c, SSMCache):
            return SSMCache(conv=spec_for(c.conv),
                            state=spec_for_state(c.state))
        return spec_for(c)   # raw leaves (e.g. enc-dec cross-attention KV)

    return jax.tree.map(
        map_cache, cache_shapes,
        is_leaf=lambda x: isinstance(x, (KVCache, SSMCache)))

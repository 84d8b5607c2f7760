"""Work one decode step of an expert-parallel rank's share of a llama-style
MoE model needs, and the held experts' grouped-matmul share of it.

Counted from a configuration file's published sizes and the traffic's
shapes, never from a compiled program (as ``work.py``).  The router
chooses ``num_experts_per_tok`` of ``router_experts`` for every token;
this chip holds ``num_experts`` of them and computes only the choices
that land there.  Bytes are bf16 (2 per element).
"""
from __future__ import annotations

from benchmarks.chip.work import BYTES, experts_hit


def held_load(c: dict, batch: int) -> dict:
    """What the held experts see in a step of ``batch`` tokens routed
    uniformly: ``rows``, the expected choices that land on them, and
    ``experts``, the expected number of them that get at least one."""
    E, n, k = c["router_experts"], c["num_experts"], c["num_experts_per_tok"]
    return {"rows": batch * k * n / E,
            "experts": n * experts_hit(E, k, batch) / E}


def gmm_work(c: dict, batch: int) -> dict:
    """The held experts' SwiGLU in one decode step, all layers: each
    touched expert's three matrices read once, the rows in and out of each
    grouped matmul, and the filled rows' flops."""
    D, F, L = c["hidden_size"], c["moe_intermediate_size"], c["num_hidden_layers"]
    h = held_load(c, batch)
    # gate and up read [rows, D] and write [rows, F]; down reads [rows, F]
    # and writes [rows, D].
    rows_io = h["rows"] * (2 * (D + F) + (F + D))
    nbytes = BYTES * L * (h["experts"] * 3 * D * F + rows_io)
    return {"flops": float(L * h["rows"] * 3 * 2 * D * F),
            "bytes": float(nbytes)}


def share_decode_work(c: dict, batch: int, fill: float) -> dict:
    """One decode step of ``batch`` tokens, each attending ``fill`` cached
    positions (its own included).

    Bytes: every weight the step needs read once (attention, norms, the
    router, the held experts that the batch hits, the head, the embedding
    rows of its tokens), the filled part of the KV cache read and the new
    entries written.  FLOPs: projections, attention over the fill, the
    router over all its experts, the held choices' SwiGLUs and the head.
    """
    D, H, KV = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    Dh, F, V = c["head_dim"], c["moe_intermediate_size"], c["vocab_size"]
    E, L = c["router_experts"], c["num_hidden_layers"]
    attn_w = D * H * Dh * 2 + D * KV * Dh * 2
    h = held_load(c, batch)
    layer_w = attn_w + 2 * D + 2 * Dh + D * E + h["experts"] * 3 * D * F
    weights = L * layer_w + D * V + D + batch * D
    kv_token = L * 2 * KV * Dh
    nbytes = BYTES * (weights + batch * fill * kv_token + batch * kv_token)
    flops = (batch * (L * (2 * attn_w + 4 * H * Dh * fill + 2 * D * E)
                      + 2 * D * V)
             + gmm_work(c, batch)["flops"])
    return {"flops": float(flops), "bytes": float(nbytes)}

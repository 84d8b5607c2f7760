"""Work a step needs by the model's semantics, and the chip's peaks.

Every count here comes from a configuration file's published sizes and
the traffic's shapes, never from a compiled program: padded capacity rows,
masked attention blocks or cache positions past the fill are not work the
model needs, so a program that stops doing them raises its share of the
peak without that share passing 100%.  Bytes are bf16 (2 per element).
"""
from __future__ import annotations

import json
from pathlib import Path

BYTES = 2
PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The peak table's row for ``device_kind``; an unknown chip raises."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise ValueError(f"no peaks for device kind {device_kind!r} in {path}; "
                         f"known: {sorted(table)}")
    return table[device_kind]


def least_time_s(flops: float, nbytes: float, peaks: dict) -> float:
    """The larger of the compute bound and the memory bound."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def experts_hit(n_experts: int, top_k: int, n_tokens: int) -> float:
    """Expected number of distinct experts that ``n_tokens`` tokens, each
    routed to ``top_k`` of ``n_experts`` uniformly, ask for."""
    return n_experts * (1.0 - (1.0 - top_k / n_experts) ** n_tokens)


def decode_step_work(c: dict, batch: int, fill: float) -> dict:
    """One decode step of ``batch`` tokens, each attending ``fill`` cached
    positions (its own included), through a llama-style MoE model.

    Bytes: every weight the step needs read once (the experts that the
    batch hits, the embedding rows of its tokens), the filled part of the
    KV cache read and the new entries written.  FLOPs: projections, causal
    attention over the fill, the router, each token's top-k expert SwiGLUs
    and the unembedding.
    """
    D, H, KV = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    Dh, F, V = c["head_dim"], c["intermediate_size"], c["vocab_size"]
    E, k, L = c["num_local_experts"], c["num_experts_per_tok"], c["num_hidden_layers"]
    attn_w = D * H * Dh * 2 + D * KV * Dh * 2
    expert_w = 3 * D * F
    layer_w = attn_w + 2 * D + D * E + experts_hit(E, k, batch) * expert_w
    weights = L * layer_w + D * V + D + batch * D
    kv_token = L * 2 * KV * Dh
    nbytes = BYTES * (weights + batch * fill * kv_token + batch * kv_token)
    per_token = L * (2 * attn_w + 4 * H * Dh * fill + 2 * D * E
                     + k * 2 * expert_w) + 2 * D * V
    return {"flops": float(batch * per_token), "bytes": float(nbytes)}


def ep_step_work(c: dict, tokens_per_chip: int, n_chips: int) -> dict:
    """One step of the expert-parallel MoE stack, per chip.

    Each chip holds ``num_experts / n_chips`` experts of every layer.
    Bytes: those experts (as many as the step's tokens hit) and the
    replicated router read once per layer, and the chip's tokens read and
    written once per layer.  FLOPs: the router over the chip's tokens and
    the SwiGLU of every routed (token, expert) pair that lands here, which
    is ``tokens_per_chip * top_k`` pairs on average.
    """
    D, F = c["hidden_size"], c["moe_intermediate_size"]
    E, k, L = c["num_experts"], c["num_experts_per_tok"], c["num_hidden_layers"]
    e_loc = E / n_chips
    hit = e_loc * experts_hit(E, k, tokens_per_chip * n_chips) / E
    per_layer_bytes = hit * 3 * D * F + D * E + 2 * tokens_per_chip * D
    per_layer_flops = (tokens_per_chip * 2 * D * E
                       + tokens_per_chip * k * 3 * 2 * D * F)
    return {"flops": float(L * per_layer_flops),
            "bytes": float(BYTES * L * per_layer_bytes)}

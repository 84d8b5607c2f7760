"""Work one prefill of a llama-style MoE model needs (the model's whole
expert set, as ``work.py`` counts a decode step).

Counted from a configuration file's published sizes and the traffic's
shapes, never from a compiled program: capacity rows past the model's
choices, masked attention blocks and cache positions past the prompt are
not work the model needs.  Bytes are bf16 (2 per element).
"""
from __future__ import annotations

from benchmarks.chip.work import BYTES, experts_hit


def prefill_work(c: dict, batch: int, prompt_len: int) -> dict:
    """One prefill of ``batch`` prompts of ``prompt_len`` tokens, with the
    logits of each prompt's last position.

    Bytes: every weight read once (the experts the prompts hit, the
    embedding rows of their tokens), the new KV cache written.  FLOPs:
    projections, causal attention (position i attends i + 1 positions),
    the router, each token's top-k expert SwiGLUs, and the head at the
    last position of each prompt.
    """
    D, H, KV = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    Dh, F, V = c["head_dim"], c["intermediate_size"], c["vocab_size"]
    E, k, L = c["num_local_experts"], c["num_experts_per_tok"], c["num_hidden_layers"]
    T = batch * prompt_len
    attn_w = D * H * Dh * 2 + D * KV * Dh * 2
    layer_w = attn_w + 2 * D + D * E + experts_hit(E, k, T) * 3 * D * F
    weights = L * layer_w + D * V + D + T * D
    nbytes = BYTES * (weights + T * L * 2 * KV * Dh)
    mean_fill = (prompt_len + 1) / 2
    per_token = L * (2 * attn_w + 4 * H * Dh * mean_fill + 2 * D * E
                     + k * 3 * 2 * D * F)
    return {"flops": float(T * per_token + batch * 2 * D * V),
            "bytes": float(nbytes)}

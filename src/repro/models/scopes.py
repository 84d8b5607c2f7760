"""Names of the program's sub-layers, and ``scope`` that applies them.

A scope sets the ``op_name`` metadata of the HLO ops made inside it, so a
profiler trace can attribute each device op to the innermost of these
names on its path.  It adds no op and changes no result.
"""
import contextlib

import jax
from jax.experimental.xla_metadata import set_xla_metadata


@contextlib.contextmanager
def scope(name: str):
    """``jax.named_scope(name)``, with the name also in each op's frontend
    attributes.  JAX's persistent compilation cache leaves metadata out of
    its key, so without the attribute a program cached before its scopes
    changed would be handed back with the old names; the attribute is
    part of the key.  Neither changes the optimized program."""
    with jax.named_scope(name), set_xla_metadata(scope=name):
        yield


EMBED = "embed"                # token embedding lookup (and image prefix)
LAYER_SCAN = "layer_scan"      # loop over blocks: per-layer weight slices
ATTN = "attn"                  # ln1, attention with its in-place cache write,
#                                residual
SSM = "ssm"                    # ln1, SSM mixer with its state update, residual
FFN = "ffn"                    # ln2, dense or MoE FFN, residual
UNEMBED = "unembed"            # final norm, logits, greedy next token
MOE_ROUTER = "moe.router"      # router logits, softmax, top-k, aux loss
MOE_DISPATCH = "moe.dispatch"  # slot positions, scatter into the expert buffer
MOE_EXPERTS = "moe.experts"    # the experts' SwiGLU over the buffer
MOE_COMBINE = "moe.combine"    # gather back from the buffer, weighted sum
EP_ROUTE = "ep.route"          # expert parallel: routing, destination slots
EP_DISPATCH = "ep.dispatch"    # send buffer and the dispatch all-to-all
EP_META = "ep.meta"            # slot metadata and its all-to-all
EP_EXPERTS = "ep.experts"      # rows sorted by local expert, grouped-matmul
#                                SwiGLU, back to slot order
EP_COMBINE = "ep.combine"      # combine all-to-all, gather, weighted sum

SCOPES = (EMBED, LAYER_SCAN, ATTN, SSM, FFN, UNEMBED,
          MOE_ROUTER, MOE_DISPATCH, MOE_EXPERTS, MOE_COMBINE,
          EP_ROUTE, EP_DISPATCH, EP_META, EP_EXPERTS, EP_COMBINE)

"""JAX-native latency-hiding collectives (TPU adaptation of paper §6).

The paper proposes (1) fused pre-translation kernels and (2) software TLB
prefetching to hide destination-side cold-start latency.  On TPU there is no
Link MMU, but collectives still pay a cold-start/latency term that dominates
small transfers.  The same two ideas map to (DESIGN.md §6):

  * :func:`warmup_all_to_all` — issue a tiny head chunk of the all-to-all
    *before* (and data-dependency-free of) the producing compute, so XLA's
    latency-hiding scheduler overlaps the cold-start with compute.  This is
    the "fused pre-translation kernel": the warm-up chunk touches one
    translation-working-set unit per peer.
  * :func:`pipelined_all_to_all` — chunk the transfer and software-pipeline
    it against per-chunk consumer compute inside ``lax.scan``
    (double-buffering = "prefetch depth" in the paper's terms).

Both are pure ``jax.lax`` programs: under ``shard_map`` they lower to real
``all-to-all`` HLO collectives that the dry-run roofline accounts for.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .scheduler import CollectivePlan


def _a2a(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """All-to-all along the leading (peer) dimension of ``x``."""
    return lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)


def _a2a_blocks(xb: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """All-to-all of peer blocks ``xb`` [n, r, ...] -> [n, r, ...].

    Chunks are cut along the rows *within* each peer block (axis 1): a
    slice of the flat leading dim would re-deal rows to the wrong peers."""
    n, r = xb.shape[:2]
    out = _a2a(xb.reshape(n * r, *xb.shape[2:]), axis_name)
    return out.reshape(xb.shape)


def warmup_all_to_all(x: jnp.ndarray, axis_name: str, *,
                      warmup_rows: int,
                      compute_fn: Callable[[jnp.ndarray], jnp.ndarray],
                      compute_arg: jnp.ndarray):
    """All-to-all of ``x`` with a warm-up head chunk overlapped with compute.

    ``x``: [rows, ...], rows divisible among the peers (the leading dim is
    the peer-partitioned dim, as for a tiled ``lax.all_to_all``).  The head
    chunk takes the first rows of every peer's block.
    ``compute_fn(compute_arg)`` is the producing compute the transfer tail
    depends on; the warm-up chunk has no data dependency on it, so XLA
    schedules the small collective concurrently (hiding the fabric
    cold-start exactly as a fused pre-translation kernel hides Link-TLB
    walks).

    Returns ``(a2a(x), compute_fn(compute_arg))``.
    """
    n = lax.psum(1, axis_name)
    rows = x.shape[0]
    xb = x.reshape(n, rows // n, *x.shape[1:])
    # Round the warm-up to a whole number of rows per peer.
    head_rows = min(max(1, warmup_rows // n), rows // n)
    head = _a2a_blocks(xb[:, :head_rows], axis_name)   # no dep on compute_fn
    y = compute_fn(compute_arg)                        # overlaps with `head`
    if head_rows < rows // n:
        tail = _a2a_blocks(xb[:, head_rows:], axis_name)
        head = jnp.concatenate([head, tail], axis=1)
    return head.reshape(x.shape), y


def pipelined_all_to_all(x: jnp.ndarray, axis_name: str, *, n_chunks: int,
                         per_chunk_fn: Optional[Callable] = None):
    """Chunked all-to-all software-pipelined against per-chunk compute.

    Splits every peer block of ``x`` into ``n_chunks`` equal chunks of
    rows; chunk ``k+1``'s transfer is issued while ``per_chunk_fn``
    consumes chunk ``k`` (XLA overlaps the independent collective with the
    compute inside the scan).  ``per_chunk_fn`` maps a received chunk
    [n, r / n_chunks, ...] to the same shape.  With ``per_chunk_fn=None``
    this degenerates to a chunked transfer whose chunks can still overlap
    each other's latency.
    """
    n = lax.psum(1, axis_name)
    r = x.shape[0] // n
    n_chunks = max(1, min(n_chunks, r))
    while r % n_chunks:
        n_chunks -= 1
    xs = jnp.moveaxis(
        x.reshape(n, n_chunks, r // n_chunks, *x.shape[1:]), 1, 0)

    def step(carry, xc):
        yc = _a2a_blocks(xc, axis_name)
        if per_chunk_fn is not None:
            yc = per_chunk_fn(yc)
        return carry, yc

    _, ys = lax.scan(step, 0, xs)
    return jnp.moveaxis(ys, 0, 1).reshape(x.shape)


def scheduled_all_to_all(x: jnp.ndarray, axis_name: str,
                         plan: CollectivePlan, *,
                         compute_fn: Optional[Callable] = None,
                         compute_arg=None):
    """Execute an all-to-all under a :class:`CollectivePlan`.

    Applies the warm-up chunk when the plan requested one (and compute is
    available to hide it in), then pipelines the remainder.
    """
    itemsize = x.dtype.itemsize
    row_bytes = max(1, int(x.size // max(1, x.shape[0])) * itemsize)
    if plan.warmup_chunk_bytes and compute_fn is not None:
        warmup_rows = max(1, plan.warmup_chunk_bytes // row_bytes)
        out, y = warmup_all_to_all(x, axis_name, warmup_rows=warmup_rows,
                                   compute_fn=compute_fn,
                                   compute_arg=compute_arg)
        return out, y
    out = pipelined_all_to_all(x, axis_name, n_chunks=plan.n_chunks)
    y = compute_fn(compute_arg) if compute_fn is not None else None
    return out, y

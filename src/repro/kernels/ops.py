"""Jit'd public wrappers around the Pallas kernels.

``interpret`` defaults to True off-TPU (this container is CPU-only; TPU v5e
is the *target*): the kernel body executes in Python for correctness
validation, while ``interpret=False`` on real hardware compiles to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm as megablox_gmm

from .flash_attention import flash_attention_kernel
from .grouped_matmul import grouped_matmul_kernel
from .ssd_scan import ssd_chunk_kernel
from .rmsnorm import rmsnorm_kernel


def on_tpu() -> bool:
    """Kernels compile for the chip here; elsewhere they interpret."""
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128):
    return flash_attention_kernel(q, k, v, causal=causal, block_q=block_q,
                                  block_k=block_k, interpret=not on_tpu())


@functools.partial(jax.jit, static_argnames=("block_t", "block_f"))
def grouped_matmul(lhs, rhs, group_offsets, *, block_t: int = 128,
                   block_f: int = 128):
    return grouped_matmul_kernel(lhs, rhs, group_offsets, block_t=block_t,
                                 block_f=block_f, interpret=not on_tpu())


GMM_ROWS = 128              # rows per tile: beat 256 and 512 on a v5e
GMM_VMEM_BYTES = 15 << 20   # a kernel may hold 16 MiB of VMEM on a v5e


def gmm_tiling(m: int, k: int, n: int, itemsize: int):
    """``gmm``'s (rows, depth, columns) tile for [m, k] x [G, k, n].

    The depth is all of ``k``, so a weight block's index changes only with
    its group or column tile, and each group's weights stream in once per
    column tile.  The columns are the widest 128-multiple dividing ``n``
    whose double-buffered blocks and float32 accumulator fit
    ``GMM_VMEM_BYTES`` (all of ``n`` when it is no 128-multiple)."""
    tm = min(GMM_ROWS, -(-m // 16) * 16)

    def vmem(tn):
        return 2 * itemsize * (tm * k + k * tn + tm * tn) + 4 * tm * tn

    if n % 128:
        return tm, k, n
    fits = [c for c in range(128, n + 1, 128)
            if n % c == 0 and vmem(c) <= GMM_VMEM_BYTES]
    return tm, k, max(fits, default=128)


@jax.jit
def gmm(lhs, rhs, group_sizes):
    """Grouped matmul: ``lhs`` rows in consecutive groups, group ``g``
    (``group_sizes[g]`` rows) times ``rhs[g]``; [m, k] x [G, k, n] ->
    [m, n] in ``lhs``'s dtype, accumulated in float32.  Rows past
    ``sum(group_sizes)`` are left undefined."""
    m, k = lhs.shape
    tiling = gmm_tiling(m, k, rhs.shape[2], lhs.dtype.itemsize)
    pad = -m % tiling[0]
    out = megablox_gmm(jnp.pad(lhs, ((0, pad), (0, 0))), rhs, group_sizes,
                       preferred_element_type=lhs.dtype, tiling=tiling,
                       interpret=not on_tpu())
    return out[:m]


@functools.partial(jax.jit, static_argnames=("eps", "block_rows"))
def rmsnorm(x, w, *, eps: float = 1e-6, block_rows: int = 256):
    return rmsnorm_kernel(x, w, eps=eps, block_rows=block_rows,
                          interpret=not on_tpu())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A_log, B, C, *, chunk: int = 256):
    """Full SSD scan built on the intra-chunk Pallas kernel.

    x: [b,S,H,P]; dt: [b,S,H] (post-softplus); A_log: [H]; B,C: [b,S,N].
    Returns (y [b,S,H,P] f32, final_state [b,H,P,N] f32).  Mirrors
    repro.models.ssd.ssd_chunked (the jnp oracle path).
    """
    b, S, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, S)
    while S % Q:
        Q -= 1
    nc = S // Q
    f32 = jnp.float32

    a = (dt.astype(f32) * (-jnp.exp(A_log.astype(f32))))          # [b,S,H]
    # Flatten (b, chunk, head) into the kernel grid.
    xg = (x.reshape(b, nc, Q, H, P).transpose(0, 1, 3, 2, 4)
          .reshape(b * nc * H, Q, P))
    dtg = (dt.reshape(b, nc, Q, H).transpose(0, 1, 3, 2)
           .reshape(b * nc * H, Q))
    ag = (a.reshape(b, nc, Q, H).transpose(0, 1, 3, 2)
          .reshape(b * nc * H, Q))
    Bg = jnp.broadcast_to(B.reshape(b, nc, 1, Q, N),
                          (b, nc, H, Q, N)).reshape(b * nc * H, Q, N)
    Cg = jnp.broadcast_to(C.reshape(b, nc, 1, Q, N),
                          (b, nc, H, Q, N)).reshape(b * nc * H, Q, N)

    y_diag, states = ssd_chunk_kernel(xg, dtg, ag, Bg, Cg,
                                      interpret=not on_tpu())
    y_diag = (y_diag.reshape(b, nc, H, Q, P).transpose(0, 1, 3, 2, 4))
    states = states.reshape(b, nc, H, P, N)

    # Cross-chunk recurrence (cheap): S_{c} = g_c S_{c-1} + states_c.
    a_cum = jnp.cumsum(ag.reshape(b, nc, H, Q), axis=-1)          # [b,nc,H,Q]
    g = jnp.exp(a_cum[..., -1])                                    # [b,nc,H]

    def combine(c1, c2):
        g1, s1 = c1
        g2, s2 = c2
        return g1 * g2, s2 + g2[..., None, None] * s1

    _, ss = jax.lax.associative_scan(combine, (g, states), axis=1)
    prev = jnp.concatenate([jnp.zeros_like(ss[:, :1]), ss[:, :-1]], axis=1)

    # Off-diagonal: y += C_t exp(a_cum_t) S_prev.
    Cc = C.reshape(b, nc, Q, N).astype(f32)
    y_off = jnp.einsum("bcqn,bchq,bchpn->bcqhp",
                       Cc, jnp.exp(a_cum), prev)
    y = (y_diag + y_off).reshape(b, S, H, P)
    return y, ss[:, -1]

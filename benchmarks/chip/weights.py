"""Random weights from the seed, made by the benchmark for both sides.

Each leaf is drawn from its own key, ``fold_in(key, crc32(path))``, so the
value of a leaf depends only on the seed, its path, shape and dtype.  The
harness fills the program's parameter tree in one jitted call on the
device; the plain references draw the leaves they need by the same paths
and cast them to float32.  Neither side takes weights the other made.

Scales follow the usual initialisation: norms are ones, biases zeros, the
token embedding has unit variance, and every other matrix has standard
deviation ``1/sqrt(fan_in)``.  Values are uniform: integers drawn from the
key, converted exactly to float32, times one constant, rounded once to the
leaf's dtype.  A normal draw in bf16 is not: its intermediate roundings
change with how the compiler fuses the program that draws it, so two
programs drawing the same leaf disagreed in the last bit.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

ONES = ("ln1", "ln2", "final_norm", "q_norm", "k_norm")
ZEROS = ("bq", "bk", "bv")
HALF_RANGE = 2 ** 15


def base_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(seed)


def fan_in(path: str, shape) -> int:
    """Input width of a matrix leaf, from its name: attention's q/k/v take
    ``[..., D, heads, head_dim]`` and its output ``[..., heads, head_dim,
    D]``; every other matrix is ``[..., in, out]``."""
    parts = path.split("/")
    name, parent = parts[-1], (parts[-2] if len(parts) > 1 else "")
    if parent == "attn" and name in ("wq", "wk", "wv"):
        return shape[-3]
    if parent == "attn" and name == "wo":
        return shape[-3] * shape[-2]
    return shape[-2]


def leaf(key: jax.Array, path: str, shape, dtype) -> jax.Array:
    name = path.split("/")[-1]
    if name in ONES:
        return jnp.ones(shape, dtype)
    if name in ZEROS:
        return jnp.zeros(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    std = 1.0 if name == "tok_embed" else 1.0 / math.sqrt(fan_in(path, shape))
    ints = jax.random.randint(k, shape, -HALF_RANGE, HALF_RANGE, jnp.int32)
    # Uniform on [-a, a] has variance a^2 / 3.
    step = std * math.sqrt(3.0) / HALF_RANGE
    return (ints.astype(jnp.float32) * jnp.float32(step)).astype(dtype)


def path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def fill(shapes, key: jax.Array):
    """A tree like ``shapes`` (``ShapeDtypeStruct`` leaves) of weights."""
    return jax.tree_util.tree_map_with_path(
        lambda p, s: leaf(key, path_str(p), s.shape, s.dtype), shapes)

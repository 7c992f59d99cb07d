"""32-bit words of 64-bit integers, for the Pallas kernels.

Mosaic (the TPU's kernel compiler) has no 64-bit types, and XLA passes
no 64-bit operand to a TPU kernel. So a kernel over int64 keys, labels
or bit-views takes each as two int32 words and returns words that the
caller joins again. Vectors enter a kernel as ``(1, n)`` rows: a row
block needs only a multiple of 128 lanes, where a 1-D int32 block
would have to match the 1024-element tiling XLA gives the operand.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LO_BIAS = -(1 << 31)
"""XOR with this makes signed order of a low word its unsigned order."""


def split64(a: jnp.ndarray) -> tuple:
    """(hi, lo) int32 words of an int64/uint64 array: ``hi`` signed,
    ``lo`` the raw low 32 bits."""
    a = jax.lax.bitcast_convert_type(a, jnp.int64) \
        if a.dtype == jnp.uint64 else a.astype(jnp.int64)
    hi = (a >> 32).astype(jnp.int32)
    lo = jax.lax.bitcast_convert_type(
        (a & 0xFFFFFFFF).astype(jnp.uint32), jnp.int32)
    return hi, lo


def join64(hi: jnp.ndarray, lo: jnp.ndarray) -> jnp.ndarray:
    """Inverse of ``split64`` (int64)."""
    low = jax.lax.bitcast_convert_type(lo, jnp.uint32).astype(jnp.int64)
    return (hi.astype(jnp.int64) << 32) | low


def row(a: jnp.ndarray, multiple: int, fill=0) -> jnp.ndarray:
    """``a`` (n,) padded with ``fill`` to a multiple of ``multiple`` and
    shaped ``(1, n_padded)``."""
    pad = (-a.shape[0]) % multiple
    if pad:
        a = jnp.pad(a, (0, pad), constant_values=fill)
    return a.reshape(1, -1)


def lanes_for(n: int, block: int) -> int:
    """The block width for a row of ``n`` elements: ``block`` (a
    multiple of 128), or the whole row when it is shorter."""
    return block if n > block else max(-(-n // 128) * 128, 128)

"""Jit'd public wrappers for the Pallas kernels.

Each call compiles its kernel with Mosaic when JAX's backend is the TPU
and runs it in the Pallas interpreter otherwise (``_interpret``): a
kernel the TPU compiler refuses raises there, it never falls back to
the interpreter. Each wrapper falls back to the jnp oracle when
``USE_REF`` is set — the knob benchmarks use to compare.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .decode import (bitunpack_pallas, delta_unpack_pallas,
                     dict_gather_pallas, rle_expand_pallas)
from .flash_attention import flash_attention_pallas
from .gather_join import gather_rows_pallas, merge_positions_pallas
from .rwkv6_scan import rwkv6_pallas
from .segment_fused import segment_sum_first_pallas
from .segment_reduce import segment_reduce_pallas
from .shuffle_pack import (member_mask_pallas, pack_rows_pallas,
                           replicate_scatter_pallas, unpack_cols_pallas)

USE_REF = False


def _interpret() -> bool:
    """Interpret mode exactly when the backend is not a TPU."""
    return jax.default_backend() != "tpu"


def segment_reduce(values: jnp.ndarray, seg_ids: jnp.ndarray,
                   num_segments: int) -> jnp.ndarray:
    """Sorted-segment sum. values (n,) or (n, d)."""
    squeeze = values.ndim == 1
    if squeeze:
        values = values[:, None]
    dtype = values.dtype
    if USE_REF:
        out = ref.segment_reduce_ref(values.astype(jnp.float32),
                                     seg_ids, num_segments)
    else:
        out = segment_reduce_pallas(values.astype(jnp.float32),
                                    seg_ids, num_segments,
                                    interpret=_interpret())
    out = out.astype(dtype)
    return out[:, 0] if squeeze else out


def segment_sum_first(values: jnp.ndarray, keys: jnp.ndarray,
                      seg_ids: jnp.ndarray, num_segments: int) -> tuple:
    """Fused Gamma tail: (segment sums f32, first-row index i32,
    first-row key values i64) in one pass. values (n, d); keys (n, k)
    int64 bit-views."""
    if USE_REF:
        return ref.segment_sum_first_ref(values, keys, seg_ids,
                                         num_segments)
    return segment_sum_first_pallas(values, keys, seg_ids, num_segments,
                                    interpret=_interpret())


def merge_positions(sorted_keys: jnp.ndarray, queries: jnp.ndarray) -> tuple:
    """(lo, hi) = searchsorted(sorted_keys, queries, left/right) — the
    blocked sorted-merge position kernel of the join inner loop."""
    if USE_REF:
        return ref.merge_positions_ref(sorted_keys, queries)
    return merge_positions_pallas(sorted_keys, queries,
                                  interpret=_interpret())


def gather_rows(values: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Blocked one-hot row gather (int64 bit-views); out-of-range
    indices gather 0."""
    if USE_REF:
        return ref.gather_rows_ref(values, idx)
    return gather_rows_pallas(values, idx, interpret=_interpret())


def pack_rows(values: jnp.ndarray, idx: jnp.ndarray,
              ok: jnp.ndarray) -> jnp.ndarray:
    """Packed-shuffle dest-scatter: out[j] = values[idx[j]] where ok[j]
    (else 0). values (n, d) int64 bit-view lanes."""
    if USE_REF:
        return ref.pack_rows_ref(values, idx, ok)
    return pack_rows_pallas(values, idx, ok, interpret=_interpret())


def replicate_scatter(values: jnp.ndarray, vidx: jnp.ndarray,
                      ok: jnp.ndarray, repl: int) -> jnp.ndarray:
    """Hypercube replicating dest-scatter: out[j] = values[vidx[j] //
    repl] where ok[j] (else 0) — the virtual-row generalization of
    pack_rows for the one-round multiway-join exchange."""
    if USE_REF:
        return ref.replicate_scatter_ref(values, vidx, ok, repl)
    return replicate_scatter_pallas(values, vidx, ok, repl,
                                    interpret=_interpret())


def unpack_cols(buf: jnp.ndarray) -> jnp.ndarray:
    """Packed-shuffle unpack: (rows, lanes) -> (lanes, rows)."""
    if USE_REF:
        return ref.unpack_cols_ref(buf)
    return unpack_cols_pallas(buf, interpret=_interpret())


def member_mask(keys: jnp.ndarray, heavy: jnp.ndarray) -> jnp.ndarray:
    """Heavy-key membership (skew-triple probe split): out[i] = keys[i]
    in the padded sorted heavy set."""
    if USE_REF:
        return ref.member_mask_ref(keys, heavy)
    return member_mask_pallas(keys, heavy, interpret=_interpret())


def rle_expand(values: jnp.ndarray, starts: jnp.ndarray,
               ends: jnp.ndarray, n: int) -> jnp.ndarray:
    """Run-length expand: out[i] = values[j] for the run j covering row
    i ([starts[j], ends[j]) tile [0, n)). int64 bit-views."""
    if USE_REF:
        return ref.rle_expand_ref(values, starts, ends, n)
    return rle_expand_pallas(values, starts, ends, n, interpret=_interpret())


def delta_unpack(z: jnp.ndarray, first: jnp.ndarray) -> jnp.ndarray:
    """Zigzag-delta decode: first + inclusive modular-uint64 prefix sum
    of the decoded deltas. z (n,) uint64, first (1,) uint64 -> int64."""
    if USE_REF:
        return ref.delta_unpack_ref(z, first)
    return delta_unpack_pallas(z, first, interpret=_interpret())


def bitunpack(words: jnp.ndarray, k: int, vpw: int, n: int,
              lo: int) -> jnp.ndarray:
    """Frame-of-reference unpack: k-bit values, vpw per uint32 word,
    + lo -> int64, trimmed to n rows."""
    if USE_REF:
        return ref.bitunpack_ref(words, k, vpw, n, lo)
    return bitunpack_pallas(words, k, vpw, n, lo, interpret=_interpret())


def dict_gather(values: jnp.ndarray, codes: jnp.ndarray) -> jnp.ndarray:
    """Dictionary decode: out[i] = values[codes[i]] (int64 bit-views;
    out-of-range codes gather 0)."""
    if USE_REF:
        return ref.dict_gather_ref(values, codes)
    return dict_gather_pallas(values, codes, interpret=_interpret())


def flash_attention(q, k, v, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128):
    if USE_REF:
        return ref.attention_ref(q, k, v, causal=causal, window=window,
                                 softcap=softcap, scale=scale)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  softcap=softcap, scale=scale,
                                  block_q=block_q, block_k=block_k,
                                  interpret=_interpret())


def rwkv6_scan(r, k, v, w, u, chunk: int = 64):
    if USE_REF:
        return ref.rwkv6_ref(r, k, v, w, u)
    return rwkv6_pallas(r, k, v, w, u, chunk=chunk, interpret=_interpret())

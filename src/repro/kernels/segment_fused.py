"""Pallas TPU kernel: fused sorted-segment sum + first-row gather.

``sum_by`` and ``nest_level`` share a tail: per segment they need (a)
the sum of the value columns, (b) the index of the segment's first row
and (c) that row's key-column values. The jnp path issues a
``segment_min`` plus one random gather per key column on top of the
segment sums; this kernel produces all three in ONE pass over the rows:

  grid (segment-block, row-block), row axis fastest/accumulating:
    sums     += one_hot(seg)^T @ values          (MXU, f32)
    firstidx  = min(firstidx, first row index of seg in this block)
    firstvals = key rows where a new minimum was found (masked integer
                sum — key columns are int64 bit-views, so no f32 pass
                may touch them)

Empty segments report firstidx == INT32_MAX and zero firstvals, exactly
like ``ref.segment_sum_first_ref``. Sums accumulate in f32 block order;
the property tests use integer-valued floats so the bit-for-bit check
against the ref holds (DESIGN.md records the trade-off).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import words as W


DEF_BLOCK_ROWS = 512      # rows per grid step
DEF_BLOCK_SEGS = 128      # segments per grid step (one MXU tile side)

I32_MAX = jnp.iinfo(jnp.int32).max


def _kernel(seg_ref, val_ref, key_ref, sum_ref, fidx_ref, fval_ref, *,
            block_rows, block_segs, n_words):
    sb = pl.program_id(0)           # segment-block index
    rb = pl.program_id(1)           # row-block index (fastest; accumulates)

    @pl.when(rb == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        fidx_ref[...] = jnp.full_like(fidx_ref, I32_MAX)
        fval_ref[...] = jnp.zeros_like(fval_ref)

    segs = seg_ref[0, :]            # (block_rows,)
    vals = val_ref[...]             # (block_rows, d) f32
    local = segs[:, None] - sb * block_segs
    onehot = local == jax.lax.broadcasted_iota(
        jnp.int32, (block_rows, block_segs), 1)

    # (block_segs, block_rows) @ (block_rows, d) on the MXU
    sum_ref[...] += jax.lax.dot_general(
        onehot.astype(vals.dtype), vals, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(sum_ref.dtype)

    rows = rb * block_rows + jax.lax.broadcasted_iota(
        jnp.int32, (block_rows, block_segs), 0)
    cand = jnp.min(jnp.where(onehot, rows, jnp.int32(I32_MAX)), axis=0)
    cur = fidx_ref[0, :]
    upd = cand < cur
    hit = onehot & (rows == cand[None, :])    # the first row of each seg
    for j in range(n_words):        # masked integer sum per key word
        fv = jnp.sum(jnp.where(hit, key_ref[j, :][:, None], jnp.int32(0)),
                     axis=0, dtype=jnp.int32)
        fval_ref[j, :] = jnp.where(upd, fv, fval_ref[j, :])
    fidx_ref[0, :] = jnp.where(upd, cand, cur)


def segment_sum_first_pallas(values: jnp.ndarray, keys: jnp.ndarray,
                             seg_ids: jnp.ndarray, num_segments: int,
                             block_rows: int = DEF_BLOCK_ROWS,
                             block_segs: int = DEF_BLOCK_SEGS,
                             interpret: bool = True) -> tuple:
    """(sums (S, d) f32, firstidx (S,) i32, firstvals (S, k) i64) over
    sorted ``seg_ids``. Rows with seg_id outside [0, num_segments) are
    dropped (the invalid-row sentinel convention). The int64 key
    columns cross as rows of 32-bit words (``kernels.words``)."""
    n, d = values.shape
    k = keys.shape[1]
    br = W.lanes_for(n, block_rows)
    bs = W.lanes_for(num_segments, block_segs)
    n_pad = (-n) % br
    if n_pad:
        values = jnp.pad(values, ((0, n_pad), (0, 0)))
    seg = W.row(seg_ids.astype(jnp.int32), br, -1)
    hi, lo = W.split64(keys)
    words = jnp.pad(jnp.concatenate([hi, lo], axis=1).T,
                    ((0, 0), (0, n_pad)))             # (2k, rows)
    S = num_segments + (-num_segments) % bs
    sums, fidx, fvals = pl.pallas_call(
        functools.partial(_kernel, block_rows=br, block_segs=bs,
                          n_words=2 * k),
        grid=(S // bs, seg.shape[1] // br),
        in_specs=[
            pl.BlockSpec((1, br), lambda sb, rb: (jnp.int32(0), rb)),
            pl.BlockSpec((br, d), lambda sb, rb: (rb, jnp.int32(0))),
            pl.BlockSpec((2 * k, br), lambda sb, rb: (jnp.int32(0), rb)),
        ],
        out_specs=[
            pl.BlockSpec((bs, d), lambda sb, rb: (sb, jnp.int32(0))),
            pl.BlockSpec((1, bs), lambda sb, rb: (jnp.int32(0), sb)),
            pl.BlockSpec((2 * k, bs), lambda sb, rb: (jnp.int32(0), sb)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, d), values.dtype),
            jax.ShapeDtypeStruct((1, S), jnp.int32),
            jax.ShapeDtypeStruct((2 * k, S), jnp.int32),
        ],
        interpret=interpret,
    )(seg, values, words)
    fvals = W.join64(fvals[:k].T, fvals[k:].T)
    return sums[:num_segments], fidx[0, :num_segments], \
        fvals[:num_segments]

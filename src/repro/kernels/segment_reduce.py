"""Pallas TPU kernel: sorted-segment sum (the Gamma+ hot spot).

The paper's sumBy/groupBy reduce is a segment reduction over sorted
keys. On TPU we turn it into MXU work: each (segment-block, row-block)
grid cell builds a one-hot matrix of local segment offsets and
accumulates ``one_hot(seg)^T @ values`` into the output block. Grid
iteration on TPU is sequential with the last axis fastest, so the
row-block axis accumulates safely into the same output block.

Trade-off (recorded in EXPERIMENTS.md §Perf): this does rows x segments
MAC work — wasteful in FLOPs but it runs on the 128x128 systolic array
instead of the scalar unit; for the segment counts produced by the
query engine's capacity discipline the MXU wins. The jnp fallback
(`ref.segment_reduce_ref`) remains available via ExecSettings.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEF_BLOCK_ROWS = 1024     # rows per grid step (XLA tiles 1-D int32 by 1024)
DEF_BLOCK_SEGS = 128      # segments per grid step (one MXU tile side)
DEF_BLOCK_D = 128         # value lanes


def _kernel(seg_ref, val_ref, out_ref, *, block_rows, block_segs):
    sb = pl.program_id(0)           # segment-block index
    rb = pl.program_id(1)           # row-block index (fastest; accumulates)

    @pl.when(rb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    segs = seg_ref[...]             # (block_rows,)
    vals = val_ref[...]             # (block_rows, d)
    base = sb * block_segs
    local = segs - base             # local segment offset for this block
    onehot = (local[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (block_rows, block_segs), 1))
    onehot = onehot.astype(vals.dtype)
    # (block_segs, block_rows) @ (block_rows, d) on the MXU
    out_ref[...] += jax.lax.dot_general(
        onehot, vals, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


def segment_reduce_pallas(values: jnp.ndarray, seg_ids: jnp.ndarray,
                          num_segments: int,
                          block_rows: int = DEF_BLOCK_ROWS,
                          block_segs: int = DEF_BLOCK_SEGS,
                          interpret: bool = True) -> jnp.ndarray:
    """Sum ``values`` (n, d) into ``num_segments`` buckets by sorted
    ``seg_ids`` (n,). Rows with seg_id outside [0, num_segments) are
    dropped (used for invalid-row sentinels)."""
    n, d = values.shape
    block_rows = min(block_rows, n)
    block_segs = min(block_segs, num_segments)
    n_pad = (-n) % block_rows
    s_pad = (-num_segments) % block_segs
    if n_pad:
        values = jnp.pad(values, ((0, n_pad), (0, 0)))
        seg_ids = jnp.pad(seg_ids, (0, n_pad), constant_values=-1)
    S = num_segments + s_pad
    n_tot = n + n_pad

    grid = (S // block_segs, n_tot // block_rows)
    out = pl.pallas_call(
        functools.partial(_kernel, block_rows=block_rows,
                          block_segs=block_segs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows,), lambda sb, rb: (rb,)),
            pl.BlockSpec((block_rows, d),
                         lambda sb, rb: (rb, jnp.int32(0))),
        ],
        out_specs=pl.BlockSpec((block_segs, d),
                               lambda sb, rb: (sb, jnp.int32(0))),
        out_shape=jax.ShapeDtypeStruct((S, d), values.dtype),
        interpret=interpret,
    )(seg_ids.astype(jnp.int32), values)
    return out[:num_segments]

"""Pallas TPU kernels for the join hot loop (fk_join / general_join).

On the jnp path, ``fk_join`` ranks its probes by one co-sort
(``exec.ops._merge_rank_left``) and ``general_join`` by a double
``searchsorted``; both then make several random gathers, scalar-unit
work on TPU. These kernels turn positions and gathers into blocked
vector/matrix work:

* ``merge_positions_pallas`` — the sorted-merge position computation:
  for each probe key, its left/right insertion points into the sorted
  build keys, computed as blocked compare-and-count over (probe-block x
  build-block) tiles. rank(q) = #{k : k < q} needs no binary search, so
  the random-access pattern becomes a streaming reduction on the VPU.
* ``gather_rows_pallas`` — blocked one-hot row gather: out[i] =
  vals[idx[i]] accumulated over build blocks. Values travel as int64
  bit-views and are combined with a masked integer sum (NOT an f32
  one-hot matmul: labels are full-width 64-bit, an MXU pass would
  truncate them). Out-of-range indices gather 0.

Trade-off (DESIGN.md "Physical properties and fusion"): both kernels do
O(n·r / block) wasted comparisons versus O(n log r) binary search, but
the work is dense, regular and block-local — the same FLOPs-for-
locality trade the segment_reduce kernel makes. Exactness is bitwise:
comparisons and masked integer sums have no rounding, so the property
tests assert bit-for-bit equality against ``ref.merge_positions_ref`` /
``ref.gather_rows_ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import words as W


DEF_BLOCK_Q = 256      # probe rows per grid step
DEF_BLOCK_R = 256      # build rows per grid step (accumulation axis)
DEF_BLOCK_N = 128      # gather output rows per grid step
DEF_BLOCK_SRC = 128    # gather source rows per grid step


def _merge_kernel(sh_ref, sl_ref, qh_ref, ql_ref, lo_ref, hi_ref, *,
                  block_r, n_build):
    rb = pl.program_id(1)           # build-block index (fastest; accumulates)

    @pl.when(rb == 0)
    def _init():
        lo_ref[...] = jnp.zeros_like(lo_ref)
        hi_ref[...] = jnp.zeros_like(hi_ref)

    # 64-bit order from 32-bit words: high words signed, low words
    # biased so that signed order is their unsigned order
    qh = qh_ref[0, :][:, None]      # (block_q, 1)
    ql = ql_ref[0, :][:, None]
    sh = sh_ref[0, :][None, :]      # (1, block_r)
    sl = sl_ref[0, :][None, :]
    col = rb * block_r + jax.lax.broadcasted_iota(
        jnp.int32, (qh.shape[0], block_r), 1)
    inb = col < n_build             # padded build slots count as +inf
    lt = ((sh < qh) | ((sh == qh) & (sl < ql))) & inb
    le = (lt | ((sh == qh) & (sl == ql))) & inb
    lo_ref[0, :] += jnp.sum(lt.astype(jnp.int32), axis=1, dtype=jnp.int32)
    hi_ref[0, :] += jnp.sum(le.astype(jnp.int32), axis=1, dtype=jnp.int32)


def merge_positions_pallas(sorted_keys: jnp.ndarray, queries: jnp.ndarray,
                           block_q: int = DEF_BLOCK_Q,
                           block_r: int = DEF_BLOCK_R,
                           interpret: bool = True
                           ) -> tuple:
    """(lo, hi) insertion points of ``queries`` into ``sorted_keys`` —
    bitwise identical to jnp.searchsorted(side=left/right). Keys enter
    as int32 word rows (``kernels.words``)."""
    r = sorted_keys.shape[0]
    n = queries.shape[0]
    bq = W.lanes_for(n, block_q)
    br = W.lanes_for(r, block_r)

    def rows(a, width):
        hi, lo = W.split64(a)
        return W.row(hi, width), W.row(lo ^ W.LO_BIAS, width)

    sh, sl = rows(sorted_keys, br)
    qh, ql = rows(queries, bq)
    q_spec = pl.BlockSpec((1, bq), lambda qb, rb: (jnp.int32(0), qb))
    r_spec = pl.BlockSpec((1, br), lambda qb, rb: (jnp.int32(0), rb))
    lo, hi = pl.pallas_call(
        functools.partial(_merge_kernel, block_r=br, n_build=r),
        grid=(qh.shape[1] // bq, sh.shape[1] // br),
        in_specs=[r_spec, r_spec, q_spec, q_spec],
        out_specs=[q_spec, q_spec],
        out_shape=[jax.ShapeDtypeStruct(qh.shape, jnp.int32)] * 2,
        interpret=interpret,
    )(sh, sl, qh, ql)
    return lo[0, :n], hi[0, :n]


def _gather_kernel(idx_ref, val_ref, out_ref, *, block_n, block_src):
    rb = pl.program_id(1)           # source-block index (accumulates)

    @pl.when(rb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    idx = idx_ref[...]              # (block_n,)
    vals = val_ref[...]             # (block_src, d) int64 bit-views
    local = idx - rb * block_src
    onehot = (local[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (block_n, block_src), 1))
    # masked integer sum: exactly one (or zero) contribution per row
    out_ref[...] += jnp.sum(
        jnp.where(onehot[:, :, None], vals[None, :, :], 0), axis=1)


def gather_rows_pallas(values: jnp.ndarray, idx: jnp.ndarray,
                       block_n: int = DEF_BLOCK_N,
                       block_src: int = DEF_BLOCK_SRC,
                       interpret: bool = True) -> jnp.ndarray:
    """out[i, :] = values[idx[i], :] (int64 bit-views); rows with idx
    outside [0, len(values)) come back 0."""
    r, d = values.shape
    n = idx.shape[0]
    block_n = min(block_n, n)
    block_src = min(block_src, r)
    n_pad = (-n) % block_n
    r_pad = (-r) % block_src
    if n_pad:
        idx = jnp.pad(idx, (0, n_pad), constant_values=-1)
    if r_pad:
        values = jnp.pad(values, ((0, r_pad), (0, 0)))

    grid = ((n + n_pad) // block_n, (r + r_pad) // block_src)
    out = pl.pallas_call(
        functools.partial(_gather_kernel, block_n=block_n,
                          block_src=block_src),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n,), lambda nb, rb: (nb,)),
            pl.BlockSpec((block_src, d), lambda nb, rb: (rb, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda nb, rb: (nb, 0)),
        out_shape=jax.ShapeDtypeStruct((n + n_pad, d), values.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), values)
    return out[:n]

"""Pallas TPU kernels for the compressed-chunk scan path (DESIGN.md
"Compressed chunks and morsel streaming").

Each lightweight codec in ``storage.encodings`` gets a blocked decode
kernel so decompression runs post-transfer at memory-bandwidth speed —
the encoded members are what crosses the wire; the expansion to row
vectors happens on-device:

* ``rle_expand_pallas``   — run-length expand. Runs tile ``[0, n)`` as
  half-open intervals ``[starts[j], ends[j])``; each output block
  accumulates a masked integer one-hot sum over run blocks (exactly one
  run covers each row, so the sum IS the gather — same dense-compare
  accumulation as ``shuffle_pack.pack_rows_pallas``, exact for int64
  bit-views).
* ``delta_unpack_pallas`` — zigzag decode + inclusive prefix sum from
  ``first``. Arithmetic is modular uint64 (two's complement bits), so
  the round trip is exact even across int64 extremes. The running total
  is carried across the sequential TPU grid in a scratch cell — the
  ``rwkv6_scan`` state-carry idiom, one value instead of a K x V tile.
* ``bitunpack_pallas``    — frame-of-reference unpack: ``vpw = 32 // k``
  values per uint32 word (values never straddle words), so each word
  block expands to an aligned output block with one shift+mask.
* ``dict_gather_pallas``  — dictionary gather: blocked masked one-hot
  integer sum of the (tiny) sorted dictionary against per-row codes.

All four are bit-for-bit equal to their jnp oracles in ``kernels.ref``
(comparisons, integer sums, shifts and modular adds have no rounding);
``tests/test_kernels.py`` holds the hypothesis parity sweeps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import words as W

DEF_BLOCK_N = 256     # output rows per grid step
DEF_BLOCK_R = 256     # runs / dictionary entries per grid step
DEF_BLOCK_W = 512     # packed words per grid step


# ---------------------------------------------------------------------------
# rle_expand
# ---------------------------------------------------------------------------

def _rle_kernel(vh_ref, vl_ref, s_ref, e_ref, oh_ref, ol_ref, *,
                block_n):
    nb = pl.program_id(0)
    rb = pl.program_id(1)

    @pl.when(rb == 0)
    def _init():
        oh_ref[...] = jnp.zeros_like(oh_ref)
        ol_ref[...] = jnp.zeros_like(ol_ref)

    i = nb * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, 1), 0)
    hit = (s_ref[0, :][None, :] <= i) & (i < e_ref[0, :][None, :])
    # exactly one run covers each row: the masked sum IS the gather
    oh_ref[0, :] += jnp.sum(jnp.where(hit, vh_ref[0, :][None, :],
                                      jnp.int32(0)),
                            axis=1, dtype=jnp.int32)
    ol_ref[0, :] += jnp.sum(jnp.where(hit, vl_ref[0, :][None, :],
                                      jnp.int32(0)),
                            axis=1, dtype=jnp.int32)


def rle_expand_pallas(values: jnp.ndarray, starts: jnp.ndarray,
                      ends: jnp.ndarray, n: int,
                      block_n: int = DEF_BLOCK_N,
                      block_r: int = DEF_BLOCK_R,
                      interpret: bool = True) -> jnp.ndarray:
    """out[i] = values[j] for the run j with starts[j] <= i < ends[j].
    values/starts/ends (r,) int64, runs sorted and tiling [0, n) (row
    positions fit in int32; values cross as word rows)."""
    r = values.shape[0]
    bn = W.lanes_for(n, block_n)
    br = W.lanes_for(r, block_r)
    # empty interval [0, 0): padding runs never cover a row
    vh, vl = (W.row(w, br) for w in W.split64(values))
    st = W.row(starts.astype(jnp.int32), br)
    en = W.row(ends.astype(jnp.int32), br)
    width = W.row(jnp.zeros((max(n, 1),), jnp.int32), bn).shape[1]
    r_spec = pl.BlockSpec((1, br), lambda nb, rb: (jnp.int32(0), rb))
    o_spec = pl.BlockSpec((1, bn), lambda nb, rb: (jnp.int32(0), nb))
    oh, ol = pl.pallas_call(
        functools.partial(_rle_kernel, block_n=bn),
        grid=(width // bn, vh.shape[1] // br),
        in_specs=[r_spec] * 4,
        out_specs=[o_spec, o_spec],
        out_shape=[jax.ShapeDtypeStruct((1, width), jnp.int32)] * 2,
        interpret=interpret,
    )(vh, vl, st, en)
    return W.join64(oh[0, :n], ol[0, :n])


# ---------------------------------------------------------------------------
# delta_unpack
# ---------------------------------------------------------------------------

def _delta_kernel(first_ref, z_ref, out_ref, carry_ref):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        carry_ref[0] = first_ref[0]

    z = z_ref[...]
    d = (z >> jnp.uint64(1)) ^ (jnp.uint64(0) - (z & jnp.uint64(1)))
    tot = carry_ref[0] + jnp.cumsum(d, dtype=jnp.uint64)
    out_ref[...] = jax.lax.bitcast_convert_type(tot, jnp.int64)
    carry_ref[0] = tot[-1]


def delta_unpack_pallas(z: jnp.ndarray, first: jnp.ndarray,
                        block_n: int = DEF_BLOCK_N,
                        interpret: bool = True) -> jnp.ndarray:
    """Inclusive zigzag-delta prefix sum: out[i] = first + sum of the
    decoded deltas z[0..i] in modular uint64 (delta[0] == 0 by the
    encoder's convention, so out[0] == first). z (n,) uint64, first
    (1,) uint64; returns int64 bit patterns."""
    n = z.shape[0]
    block_n = max(1, min(block_n, max(n, 1)))
    n_pad = (-n) % block_n if n else block_n
    if n_pad:
        z = jnp.pad(z, (0, n_pad))        # zero delta: repeats last value
    grid = ((n + n_pad) // block_n,)
    out = pl.pallas_call(
        _delta_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b: (0,)),
            pl.BlockSpec((block_n,), lambda b: (b,)),
        ],
        out_specs=pl.BlockSpec((block_n,), lambda b: (b,)),
        out_shape=jax.ShapeDtypeStruct((n + n_pad,), jnp.int64),
        scratch_shapes=[pltpu.VMEM((1,), jnp.uint64)],
        interpret=interpret,
    )(first.astype(jnp.uint64), z.astype(jnp.uint64))
    return out[:n]


# ---------------------------------------------------------------------------
# bitunpack
# ---------------------------------------------------------------------------

def _bitunpack_kernel(words_ref, out_ref, *, k, vpw):
    w = words_ref[0, :][None, :]                        # (1, block_w)
    shift = jax.lax.broadcasted_iota(
        jnp.uint32, (vpw, w.shape[1]), 0) * jnp.uint32(k)
    out_ref[...] = (w >> shift) & jnp.uint32((1 << k) - 1)


def bitunpack_pallas(words: jnp.ndarray, k: int, vpw: int, n: int,
                     lo: int, block_w: int = DEF_BLOCK_W,
                     interpret: bool = True) -> jnp.ndarray:
    """Frame-of-reference unpack: word i holds values [i*vpw, i*vpw+vpw)
    at k bits each; out = unpacked + lo as int64, trimmed to n rows.
    The kernel unpacks value j of every word into row j of a
    ``(vpw, words)`` uint32 block; the 64-bit ``+ lo`` runs after it."""
    nw = words.shape[0]
    bw = W.lanes_for(nw, block_w)
    wr = W.row(words.astype(jnp.uint32), bw)
    out = pl.pallas_call(
        functools.partial(_bitunpack_kernel, k=k, vpw=vpw),
        grid=(wr.shape[1] // bw,),
        in_specs=[pl.BlockSpec((1, bw), lambda b: (jnp.int32(0), b))],
        out_specs=pl.BlockSpec((vpw, bw), lambda b: (jnp.int32(0), b)),
        out_shape=jax.ShapeDtypeStruct((vpw, wr.shape[1]), jnp.uint32),
        interpret=interpret,
    )(wr)
    return out.T.reshape(-1)[:n].astype(jnp.int64) + jnp.int64(lo)


# ---------------------------------------------------------------------------
# dict_gather
# ---------------------------------------------------------------------------

def _dict_kernel(codes_ref, vh_ref, vl_ref, oh_ref, ol_ref, *, block_v):
    vb = pl.program_id(1)

    @pl.when(vb == 0)
    def _init():
        oh_ref[...] = jnp.zeros_like(oh_ref)
        ol_ref[...] = jnp.zeros_like(ol_ref)

    local = codes_ref[0, :][:, None] - vb * block_v
    onehot = local == jax.lax.broadcasted_iota(
        jnp.int32, (local.shape[0], block_v), 1)
    oh_ref[0, :] += jnp.sum(jnp.where(onehot, vh_ref[0, :][None, :],
                                      jnp.int32(0)),
                            axis=1, dtype=jnp.int32)
    ol_ref[0, :] += jnp.sum(jnp.where(onehot, vl_ref[0, :][None, :],
                                      jnp.int32(0)),
                            axis=1, dtype=jnp.int32)


def dict_gather_pallas(values: jnp.ndarray, codes: jnp.ndarray,
                       block_n: int = DEF_BLOCK_N,
                       block_v: int = DEF_BLOCK_R,
                       interpret: bool = True) -> jnp.ndarray:
    """out[i] = values[codes[i]] — the dictionary decode as a blocked
    masked one-hot integer sum over the values' 32-bit words
    (out-of-range codes gather 0)."""
    r = values.shape[0]
    n = codes.shape[0]
    bn = W.lanes_for(n, block_n)
    bv = W.lanes_for(r, block_v)
    cr = W.row(codes.astype(jnp.int32), bn, -1)
    vh, vl = (W.row(w, bv) for w in W.split64(values))
    c_spec = pl.BlockSpec((1, bn), lambda nb, vb: (jnp.int32(0), nb))
    v_spec = pl.BlockSpec((1, bv), lambda nb, vb: (jnp.int32(0), vb))
    oh, ol = pl.pallas_call(
        functools.partial(_dict_kernel, block_v=bv),
        grid=(cr.shape[1] // bn, vh.shape[1] // bv),
        in_specs=[c_spec, v_spec, v_spec],
        out_specs=[c_spec, c_spec],
        out_shape=[jax.ShapeDtypeStruct(cr.shape, jnp.int32)] * 2,
        interpret=interpret,
    )(cr, vh, vl)
    return W.join64(oh[0, :n], ol[0, :n])

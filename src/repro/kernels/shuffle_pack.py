"""Pallas TPU kernels for the packed single-collective shuffle
(``dist.DistContext.exchange``, DESIGN.md "Partitioning-aware shuffle").

The packed exchange routes rows by a destination sort, then ships every
column of the bag in ONE ``all_to_all`` as a ``(P, bucket, n_lanes)``
int64 buffer (narrow dtypes bit-cast to int64 lanes). Two kernels turn
the pack/unpack around that collective into blocked vector work:

* ``pack_rows_pallas`` — the dest-scatter: build the send buffer from
  the routing. The routing precomputes, per send-buffer slot ``j``,
  which source row lands there (``idx[j]``) and whether the slot is
  real (``ok[j]``), so the scatter becomes a slot-major blocked masked
  one-hot gather — dense (block_m x block_src) compare tiles with
  masked *integer* accumulation, exact for int64 bit-views (an f32
  one-hot matmul would truncate 64-bit labels and float64 payloads).
* ``unpack_cols_pallas`` — the receiving side: blocked transpose of the
  ``(rows, lanes)`` wire buffer into ``(lanes, rows)`` so each lane
  unpacks into a contiguous column before its dtype bit-cast.

A third kernel serves the skew triple built on the same wire format:

* ``member_mask_pallas`` — heavy-key membership: for each packed key,
  whether it appears in the (tiny, padded) heavy-key set. The compare
  is a dense ``(block_n, max_heavy)`` equality tile reduced along the
  heavy axis — the light/heavy probe split of a planned ``SkewJoinP``
  as one blocked VPU pass instead of a searchsorted gather chain.

All are bit-for-bit equal to their jnp oracles (``ref.pack_rows_ref``,
``ref.unpack_cols_ref``, ``ref.member_mask_ref``): comparisons, masked
integer sums and transposes have no rounding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import words as W


DEF_BLOCK_M = 128      # send-buffer slots per grid step
DEF_BLOCK_SRC = 128    # source rows per grid step (accumulation axis)
DEF_BLOCK_T = 256      # wire-buffer rows per transpose grid step
DEF_BLOCK_N = 512      # keys per membership grid step (a lane multiple)

_I32_MAX = jnp.iinfo(jnp.int32).max


def _pack_kernel(idx_ref, ok_ref, val_ref, out_ref, *, block_m, block_src):
    rb = pl.program_id(1)           # source-block index (accumulates)

    @pl.when(rb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    idx = idx_ref[...]              # (block_m,) i32 source row per slot
    ok = ok_ref[...]                # (block_m,) i32 slot is real
    vals = val_ref[...]             # (block_src, d) int64 lanes
    local = idx - rb * block_src
    onehot = (local[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (block_m, block_src), 1)) & (ok[:, None] != 0)
    # masked integer sum: exactly one (or zero) contribution per slot
    out_ref[...] += jnp.sum(
        jnp.where(onehot[:, :, None], vals[None, :, :], 0), axis=1)


def pack_rows_pallas(values: jnp.ndarray, idx: jnp.ndarray,
                     ok: jnp.ndarray,
                     block_m: int = DEF_BLOCK_M,
                     block_src: int = DEF_BLOCK_SRC,
                     interpret: bool = True) -> jnp.ndarray:
    """out[j, :] = values[idx[j], :] where ``ok[j]`` and idx in range,
    else 0 — the dest-scatter that fills the packed send buffer."""
    r, d = values.shape
    m = idx.shape[0]
    block_m = min(block_m, m)
    block_src = min(block_src, r)
    m_pad = (-m) % block_m
    r_pad = (-r) % block_src
    if m_pad:
        idx = jnp.pad(idx, (0, m_pad), constant_values=-1)
        ok = jnp.pad(ok, (0, m_pad))
    if r_pad:
        values = jnp.pad(values, ((0, r_pad), (0, 0)))

    grid = ((m + m_pad) // block_m, (r + r_pad) // block_src)
    out = pl.pallas_call(
        functools.partial(_pack_kernel, block_m=block_m,
                          block_src=block_src),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m,), lambda mb, rb: (mb,)),
            pl.BlockSpec((block_m,), lambda mb, rb: (mb,)),
            pl.BlockSpec((block_src, d), lambda mb, rb: (rb, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, d), lambda mb, rb: (mb, 0)),
        out_shape=jax.ShapeDtypeStruct((m + m_pad, d), values.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), ok.astype(jnp.int32), values)
    return out[:m]


def _repscatter_kernel(idx_ref, ok_ref, val_ref, out_ref, *, block_m,
                       block_src, repl):
    rb = pl.program_id(1)           # source-block index (accumulates)

    @pl.when(rb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    vidx = idx_ref[...]             # (block_m,) i32 VIRTUAL row per slot
    ok = ok_ref[...]                # (block_m,) i32 slot is real
    vals = val_ref[...]             # (block_src, d) int64 lanes
    # virtual -> source row: the replication divide happens IN the
    # kernel, so the routing ships one int per slot, not repl of them
    src = jax.lax.div(vidx, jnp.int32(repl))
    local = src - rb * block_src
    onehot = (local[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (block_m, block_src), 1)) \
        & (ok[:, None] != 0) & (vidx[:, None] >= 0)
    # masked integer sum: exactly one (or zero) contribution per slot
    out_ref[...] += jnp.sum(
        jnp.where(onehot[:, :, None], vals[None, :, :], 0), axis=1)


def replicate_scatter_pallas(values: jnp.ndarray, vidx: jnp.ndarray,
                             ok: jnp.ndarray, repl: int,
                             block_m: int = DEF_BLOCK_M,
                             block_src: int = DEF_BLOCK_SRC,
                             interpret: bool = True) -> jnp.ndarray:
    """out[j, :] = values[vidx[j] // repl, :] where ``ok[j]`` and the
    source row is in range, else 0 — pack_rows generalized to the
    hypercube's replicating exchange, where each source row fans out to
    ``repl`` virtual replicas routed to distinct mesh coordinates."""
    r, d = values.shape
    m = vidx.shape[0]
    block_m = min(block_m, m)
    block_src = min(block_src, r)
    m_pad = (-m) % block_m
    r_pad = (-r) % block_src
    if m_pad:
        vidx = jnp.pad(vidx, (0, m_pad), constant_values=-1)
        ok = jnp.pad(ok, (0, m_pad))
    if r_pad:
        values = jnp.pad(values, ((0, r_pad), (0, 0)))

    grid = ((m + m_pad) // block_m, (r + r_pad) // block_src)
    out = pl.pallas_call(
        functools.partial(_repscatter_kernel, block_m=block_m,
                          block_src=block_src, repl=int(repl)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m,), lambda mb, rb: (mb,)),
            pl.BlockSpec((block_m,), lambda mb, rb: (mb,)),
            pl.BlockSpec((block_src, d), lambda mb, rb: (rb, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, d), lambda mb, rb: (mb, 0)),
        out_shape=jax.ShapeDtypeStruct((m + m_pad, d), values.dtype),
        interpret=interpret,
    )(vidx.astype(jnp.int32), ok.astype(jnp.int32), values)
    return out[:m]


def _member_kernel(kh_ref, kl_ref, hh_ref, hl_ref, out_ref):
    kh = kh_ref[0, :]               # (block_n,) key words
    kl = kl_ref[0, :]
    hh = hh_ref[0, :]               # (m,) heavy-set words
    hl = hl_ref[0, :]
    h_real = ~((hh == _I32_MAX) & (hl == -1))
    k_real = ~((kh == _I32_MAX) & (kl == -1))
    hit = (kh[:, None] == hh[None, :]) & (kl[:, None] == hl[None, :]) \
        & h_real[None, :]
    # int32 accumulation, not bool any: exact, and VPU-friendly
    out_ref[0, :] = ((jnp.sum(hit.astype(jnp.int32), axis=1,
                              dtype=jnp.int32) > 0)
                     & k_real).astype(jnp.int32)


def member_mask_pallas(keys: jnp.ndarray, heavy: jnp.ndarray,
                       block_n: int = DEF_BLOCK_N,
                       interpret: bool = True) -> jnp.ndarray:
    """out[i] = keys[i] in heavy (padding I64_MAX never matches, on
    either side) — the skew-triple probe split. Keys and the heavy set
    enter as int32 word rows (``kernels.words``)."""
    n = keys.shape[0]
    m = heavy.shape[0]
    bn = W.lanes_for(n, block_n)
    pad = (_I32_MAX, -1)            # the words of I64_MAX
    kh, kl = (W.row(w, bn, f) for w, f in zip(W.split64(keys), pad))
    hh, hl = (W.row(w, 128, f) for w, f in zip(W.split64(heavy), pad))
    width = kh.shape[1]
    key_spec = pl.BlockSpec((1, bn), lambda nb: (jnp.int32(0), nb))
    heavy_spec = pl.BlockSpec((1, hh.shape[1]),
                              lambda nb: (jnp.int32(0), jnp.int32(0)))
    out = pl.pallas_call(
        _member_kernel,
        grid=(width // bn,),
        in_specs=[key_spec, key_spec, heavy_spec, heavy_spec],
        out_specs=key_spec,
        out_shape=jax.ShapeDtypeStruct((1, width), jnp.int32),
        interpret=interpret,
    )(kh, kl, hh, hl)
    return out[0, :n] != 0


def _unpack_kernel(buf_ref, out_ref):
    out_ref[...] = buf_ref[...].T


def unpack_cols_pallas(buf: jnp.ndarray,
                       block_t: int = DEF_BLOCK_T,
                       interpret: bool = True) -> jnp.ndarray:
    """(rows, lanes) wire buffer -> (lanes, rows): each lane becomes a
    contiguous column, ready for its dtype bit-cast."""
    m, d = buf.shape
    block_t = min(block_t, m)
    m_pad = (-m) % block_t
    if m_pad:
        buf = jnp.pad(buf, ((0, m_pad), (0, 0)))

    grid = ((m + m_pad) // block_t,)
    out = pl.pallas_call(
        _unpack_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_t, d), lambda mb: (mb, 0))],
        out_specs=pl.BlockSpec((d, block_t), lambda mb: (0, mb)),
        out_shape=jax.ShapeDtypeStruct((d, m + m_pad), buf.dtype),
        interpret=interpret,
    )(buf)
    return out[:, :m]

"""Local (single-partition) columnar operators over FlatBag.

These are the physical counterparts of the paper's plan-language
operators (Fig. 10) under the TPU static-shape discipline:

  sigma      -> select            (mask, no compaction)
  pi         -> project / map     (column arithmetic)
  join       -> fk_join           (build side unique — every benchmark join)
                general_join      (M:N, static output capacity + overflow)
  outer-join -> fk_join(how="left_outer")
  Gamma+     -> sum_by            (sort + segmented scan + compaction sort)
  Gamma_u    -> nest_level        (CSR regroup; labels = dense group ids)
  dedup      -> dedup
  mu / mu-bar-> flatten_child / outer_unnest (wide flattening, standard route)

All ops are shape-static and jit-safe.

Order-awareness (DESIGN.md "Physical properties and fusion"): every
operator consults and propagates ``FlatBag.props`` instead of
re-deriving physical work. Grouping ops sort *lexicographically by the
raw key columns* (not by a packed hash), so a bag sorted by (G, A) is
also grouped by every prefix — sum_by(G+A) feeding nest_level(G) costs
one sort total, and a ``join -> sum_by -> nest_level`` pipeline sorts
the probe side exactly once. ``SORT_STATS`` counts the sorts actually
performed (the hook the fusion tests assert on); ``ORDER_AWARE`` is the
global knob benchmarks flip to measure the unfused executor.

Aggregation and join gathers can route through the Pallas kernels
(interpret mode on CPU) or the jnp fallbacks.
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.columnar.props import PhysicalProps
from repro.columnar.table import FlatBag

from .hashing import combine64

I64_MAX = jnp.iinfo(jnp.int64).max


# ---------------------------------------------------------------------------
# physical-property plumbing: knob + sort accounting
# ---------------------------------------------------------------------------

ORDER_AWARE = True   # False => recompute everything per operator (seed mode)

from repro.obs.metrics import REGISTRY as _METRICS  # noqa: E402

SORT_STATS = _METRICS.view("sort")
"""Sort/key-cache accounting — a live view onto the unified metrics
registry (``repro.obs``) under the ``sort.`` domain. Behaves like the
historical dict (item get/set, ``.get``, ``.clear()``)."""


def reset_sort_stats() -> None:
    SORT_STATS.clear()


def _count(name: str) -> None:
    _METRICS.inc("sort." + name)


@contextmanager
def order_awareness(enabled: bool):
    """Scoped ORDER_AWARE toggle (benchmarks compare fused vs unfused)."""
    global ORDER_AWARE
    prev = ORDER_AWARE
    ORDER_AWARE = enabled
    try:
        yield
    finally:
        ORDER_AWARE = prev


def _cache_ok(bag: FlatBag, arr) -> bool:
    """Refuse to store a traced array on a concrete bag's props: a
    closure-captured bag would hand the tracer to eager code after the
    trace ends. (Bags passed as jit arguments rebuild with props=None,
    so same-trace caching is always safe.)"""
    from jax.core import Tracer
    return isinstance(bag.valid, Tracer) or not isinstance(arr, Tracer)


# ---------------------------------------------------------------------------
# key packing
# ---------------------------------------------------------------------------

def pack_keys(bag: FlatBag, cols: Sequence[str]) -> jnp.ndarray:
    """Composite equality key as int64 (see hashing.combine64), cached
    per column tuple on the bag's physical props. Values at invalid
    rows are unspecified — consumers mask by validity."""
    cols = tuple(cols)
    assert cols, "empty key"
    if ORDER_AWARE:
        cached = bag.props.key_cache.get(cols)
        if cached is not None:
            _count("key_reuse")
            return cached
    key = combine64([bag.col(c) for c in cols])
    if ORDER_AWARE and _cache_ok(bag, key):
        bag.props.key_cache[cols] = key
    return key


def _part_if(bag: FlatBag, cols) -> Optional[Tuple[str, ...]]:
    """The bag's hash-partitioning, propagated to an output whose
    columns ``cols`` keep their values: survives iff every partitioning
    column is among them (local ops never move rows across partitions)."""
    part = bag.props.partitioning if ORDER_AWARE else None
    if part is not None and set(part) <= set(cols):
        return part
    return None


def _key_arrays(bag: FlatBag, cols: Sequence[str]) -> List[jnp.ndarray]:
    """Sortable int64 views of key columns. Floats sort by BIT pattern,
    not by truncated value: grouping only needs equal values adjacent,
    and bit-equality is exact where an int cast would merge 2.1 and
    2.9 into one sort key (their raw-value boundaries then depend on
    sort stability)."""
    return [_to_i64_bits(bag.col(c)) for c in cols]


# ---------------------------------------------------------------------------
# sorting / grouping (the shared physical work)
# ---------------------------------------------------------------------------

def _lexsort(bag: FlatBag, cols: Tuple[str, ...]) -> FlatBag:
    """Sort rows by (invalid-last, cols lexicographic), stably. One
    ``lax.sort`` moves every column as an operand, so no row is
    gathered by a permutation; the key columns come back from their
    sorted int64 views. The result delivers ``sorted_by = cols`` with
    ``invalid_last``."""
    _count("lexsort")
    with jax.named_scope("sort"):
        keys = _key_arrays(bag, cols)
        rest = [n for n in bag.data if n not in cols]
        out = jax.lax.sort(
            (~bag.valid, *keys, *[bag.data[n] for n in rest]),
            num_keys=1 + len(keys), is_stable=True)
        valid = ~out[0]
        moved = dict(zip(rest, out[1 + len(keys):]))
        moved.update((c, _from_i64_bits(k, bag.col(c).dtype))
                     for c, k in zip(cols, out[1:1 + len(keys)]))
        data = {n: moved[n] for n in bag.data}
    props = PhysicalProps(sorted_by=cols, invalid_last=True,
                          partitioning=_part_if(bag, bag.data)) \
        if ORDER_AWARE else None
    return FlatBag(data, valid, props)


def _presorted_seg_ids(bag: FlatBag, cols: Tuple[str, ...],
                       invalid_last: bool) -> jnp.ndarray:
    """Dense group ids for a bag whose VALID rows are already clustered
    by ``cols``. A valid row starts a new segment iff any key column
    differs from the previous *valid* row; invalid rows fold into the
    running segment (their values are masked out by every consumer).
    With ``invalid_last`` the previous valid row of a valid row is the
    row before it, so each key column is compared with its shift by
    one; interleaved invalid rows need the gather of the last valid
    row."""
    cap = bag.capacity
    # compare the SAME int64 bit-view _lexsort orders by: raw float
    # comparison would split bit-identical NaNs (NaN != NaN) and merge
    # bit-distinct +0.0/-0.0 that the sort left non-adjacent
    keys = [_to_i64_bits(bag.col(c)) for c in cols]
    if invalid_last:
        _count("seg_shift")
        differs = jnp.zeros(cap - 1, bool)
        for a in keys:
            differs = differs | (a[1:] != a[:-1])
        seg_start = bag.valid[1:] & differs
    else:
        idx = jnp.arange(cap)
        last_valid = jax.lax.cummax(jnp.where(bag.valid, idx, -1))
        prev_valid = last_valid[:-1]
        pv = jnp.clip(prev_valid, 0, cap - 1)
        differs = jnp.zeros(cap - 1, bool)
        for a in keys:
            differs = differs | (a[1:] != a[pv])
        seg_start = bag.valid[1:] & ((prev_valid < 0) | differs)
    seg_start = jnp.concatenate([jnp.ones(1, bool), seg_start])
    return jnp.cumsum(seg_start.astype(jnp.int32)) - 1


def _segments(bag: FlatBag, key_cols: Sequence[str]
              ) -> Tuple[FlatBag, jnp.ndarray]:
    """Cluster rows by ``key_cols``; returns (sorted bag, dense group
    ids). Reuses a delivered ordering when ``key_cols`` is a prefix of
    the bag's ``sorted_by`` — the fusion that lets sum_by / dedup /
    nest_level chains on shared keys sort once."""
    cols = tuple(key_cols)
    if ORDER_AWARE and bag.props.sorted_prefix(cols):
        sbag = bag
        cached = sbag.props.seg_cache.get(cols)
        if cached is not None:
            _count("seg_reuse")
            return sbag, cached
        _count("sort_skipped")
    else:
        sbag = _lexsort(bag, cols)
    with jax.named_scope("segment"):
        seg_id = _presorted_seg_ids(
            sbag, cols, sbag is not bag or bag.props.invalid_last)
    if ORDER_AWARE and _cache_ok(sbag, seg_id):
        sbag.props.seg_cache[cols] = seg_id
    return sbag, seg_id


def _segment_firsts(sbag: FlatBag, seg_id: jnp.ndarray, gather_cols,
                    use_kernel: bool, val_cols: Sequence[str] = ()
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, Dict[str, jnp.ndarray],
                               Dict[str, jnp.ndarray]]:
    """Shared Gamma tail: per segment, (exists, first-row validity,
    first-row values of ``gather_cols``, summed ``val_cols``). Row
    ``s`` holds segment ``s``; rows at or past the segment count are
    zero.

    The jnp path moves no row by scatter or gather: a reverse segmented
    scan leaves each segment's sums on its start row, and one sort keyed
    by the start rows' segment ids compacts them to the front (DESIGN.md
    "Grouping without scatter").

    With ``use_kernel`` this is ONE fused Pallas pass (segment-sum +
    first-row gather). The kernel accumulates in f32 (the MXU
    discipline, DESIGN.md), which would silently truncate integer
    sums past 2^24 — so integer value columns keep the exact jnp
    segment_sum path."""
    cap = sbag.capacity
    if use_kernel:
        from repro.kernels import ops as kops
        fval_cols = [v for v in val_cols
                     if not jnp.issubdtype(sbag.col(v).dtype, jnp.integer)]
        vals = [jnp.where(sbag.valid, sbag.col(v), 0).astype(jnp.float32)
                for v in fval_cols]
        packed = [_to_i64_bits(sbag.col(c)) for c in gather_cols]
        packed.append(sbag.valid.astype(jnp.int64))
        sums, fidx, fvals = kops.segment_sum_first(
            jnp.stack(vals, 1) if vals else
            jnp.zeros((cap, 1), jnp.float32),
            jnp.stack(packed, 1), seg_id, cap)
        exists = fidx < cap
        first_valid = exists & (fvals[:, -1] != 0)
        firsts = {c: _from_i64_bits(fvals[:, i], sbag.col(c).dtype)
                  for i, c in enumerate(gather_cols)}
        summed = {v: sums[:, i].astype(sbag.col(v).dtype)
                  for i, v in enumerate(fval_cols)}
        for v in val_cols:
            if v not in summed:
                summed[v] = jax.ops.segment_sum(
                    jnp.where(sbag.valid, sbag.col(v), 0), seg_id,
                    num_segments=cap)
        return exists, first_valid, firsts, summed
    _count("segment_scan")
    # seg_id is dense and nondecreasing: a segment starts where it steps
    start = jnp.concatenate([jnp.ones(1, bool), seg_id[1:] != seg_id[:-1]])
    ends = jnp.concatenate([start[1:], jnp.ones(1, bool)])
    sums = _segment_suffix_sums(
        [jnp.where(sbag.valid, sbag.col(v), 0) for v in val_cols], ends)
    # every row that starts no segment keys ``cap`` and sorts last
    out = jax.lax.sort(
        (jnp.where(start, seg_id, cap).astype(jnp.int32), sbag.valid,
         *[sbag.col(c) for c in gather_cols], *sums), num_keys=1)
    exists = jnp.arange(cap) < seg_id[-1] + 1
    first_valid = exists & out[1]

    def keep(a):
        # rows past the segment count are zero; so is a sum of -0.0s,
        # as the sum of a zero-initialised accumulation would be
        return jnp.where(exists & (a != 0), a, jnp.zeros_like(a))

    firsts = {c: jnp.where(exists, a, jnp.zeros_like(a))
              for c, a in zip(gather_cols, out[2:2 + len(gather_cols)])}
    summed = {v: keep(a)
              for v, a in zip(val_cols, out[2 + len(gather_cols):])}
    return exists, first_valid, firsts, summed


def _segment_suffix_sums(vals: List[jnp.ndarray], ends: jnp.ndarray
                         ) -> List[jnp.ndarray]:
    """Per row, the sum of its segment's values from that row to the
    segment's last row (``ends`` marks last rows), so a segment's start
    row holds the segment's sum. A reverse segmented scan: a float sum
    depends only on its own segment's rows, where a difference of
    prefix sums would carry the rounding of every earlier segment.

    Step ``k`` adds to each row the partial sum ``2^k`` rows on unless
    its span already holds a segment end: ``bit_length(n - 1)`` passes
    over the rows, each a contiguous shift. A loop, so the compiler
    sees one pass and not a chain of them."""
    if not vals:
        return []
    n = ends.shape[0]

    def ahead(a, d, fill):
        # a[i + d], and ``fill`` past the last row
        pad = jnp.concatenate([a, jnp.full((n,), fill, a.dtype)])
        return jax.lax.dynamic_slice(pad, (d,), (n,))

    def step(k, carry):
        *vs, done = carry
        d = jnp.left_shift(1, k)
        vs = [jnp.where(done, v, v + ahead(v, d, 0)) for v in vs]
        return (*vs, done | ahead(done, d, True))

    *sums, _ = jax.lax.fori_loop(0, (n - 1).bit_length(), step,
                                 (*vals, ends))
    return sums


# ---------------------------------------------------------------------------
# sigma / pi
# ---------------------------------------------------------------------------

def select(bag: FlatBag, mask: jnp.ndarray) -> FlatBag:
    return bag.mask(mask)


def project(bag: FlatBag, cols: Dict[str, jnp.ndarray]) -> FlatBag:
    """New bag with computed columns (same validity)."""
    return FlatBag(dict(cols), bag.valid)


# ---------------------------------------------------------------------------
# aggregation: Gamma+ (sum_by) and dedup
# ---------------------------------------------------------------------------

def sum_by(bag: FlatBag, key_cols: Sequence[str], val_cols: Sequence[str],
           use_kernel: bool = False) -> FlatBag:
    """Gamma+: group by key_cols, sum val_cols. NULL-semantics: invalid
    rows contribute nothing; groups of only-invalid rows are invalid.
    Output capacity == input capacity. Output delivers
    ``sorted_by = key_cols`` (lexicographic), so downstream grouping on
    any prefix of the keys reuses this sort."""
    key_cols, val_cols = tuple(key_cols), tuple(val_cols)
    sbag, seg_id = _segments(bag, key_cols)
    with jax.named_scope("segment"):
        exists, out_valid, firsts, summed = _segment_firsts(
            sbag, seg_id, key_cols, use_kernel, val_cols)
    data = dict(firsts)
    data.update(summed)
    props = None
    if ORDER_AWARE:
        props = PhysicalProps(sorted_by=key_cols,
                              invalid_last=sbag.props.invalid_last,
                              partitioning=_part_if(sbag, key_cols))
    return FlatBag(data, out_valid, props)


def dedup(bag: FlatBag, cols: Optional[Sequence[str]] = None) -> FlatBag:
    """Keep one representative row per distinct value of ``cols``."""
    cols = tuple(cols or bag.columns)
    sbag, seg_id = _segments(bag, cols)
    with jax.named_scope("segment"):
        prev = jnp.concatenate([jnp.full((1,), -1, seg_id.dtype),
                                seg_id[:-1]])
        keep = (seg_id != prev) & sbag.valid
    props = None
    if ORDER_AWARE:
        props = PhysicalProps(key_cache=dict(sbag.props.key_cache),
                              sorted_by=sbag.props.sorted_by,
                              invalid_last=False,
                              partitioning=_part_if(sbag, sbag.data))
    return FlatBag(sbag.data, keep, props)


# ---------------------------------------------------------------------------
# joins
# ---------------------------------------------------------------------------

def _build_side(right: FlatBag, right_on: Tuple[str, ...]
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(order, sorted_key) for a join build side, cached on the build
    bag's props so repeated joins against one dictionary argsort once.
    A single-column build side already sorted on its key (e.g. a
    sum_by / dedup output) skips the argsort entirely."""
    if ORDER_AWARE:
        hit = right.props.build_cache.get(right_on)
        if hit is not None:
            _count("build_reuse")
            return hit
    rkey = pack_keys(right, right_on)
    rkey = jnp.where(right.valid, rkey, I64_MAX)
    # sorted_by order == packed-key order only for a single *integer*
    # key column (floats sort by bit pattern, hashes not at all)
    key_is_int = len(right_on) == 1 and jnp.issubdtype(
        right.col(right_on[0]).dtype, jnp.integer)
    if ORDER_AWARE and key_is_int and right.props.invalid_last \
            and right.props.sorted_prefix(right_on):
        _count("build_sort_skipped")
        order_r = jnp.arange(right.capacity)
        srk = rkey
    else:
        _count("build_argsort")
        with jax.named_scope("sort"):
            order_r = jnp.argsort(rkey)
            srk = rkey[order_r]
    if ORDER_AWARE and _cache_ok(right, srk):
        right.props.build_cache[right_on] = (order_r, srk)
    return order_r, srk


def _f64_pair_bits(a: jnp.ndarray) -> jnp.ndarray:
    """float64 -> int64 on the TPU, which refuses a 64-bit bitcast and
    holds a float64 as an unevaluated pair of float32s (hi + lo;
    DESIGN.md "float64 on the TPU"). The pair's two 32-bit patterns
    fill the lane, so the round trip is exact for every value the
    device can hold, and equal values give equal lanes."""
    hi = a.astype(jnp.float32)
    # inf - inf would make the low word NaN and the value NaN
    lo = jnp.where(jnp.isinf(hi), 0.0,
                   a - hi.astype(jnp.float64)).astype(jnp.float32)
    hb = jax.lax.bitcast_convert_type(hi, jnp.int32).astype(jnp.int64)
    lb = jax.lax.bitcast_convert_type(lo, jnp.uint32).astype(jnp.int64)
    return (hb << 32) | lb


def _f64_from_pair_bits(a: jnp.ndarray) -> jnp.ndarray:
    hi = jax.lax.bitcast_convert_type((a >> 32).astype(jnp.int32),
                                      jnp.float32)
    lo = jax.lax.bitcast_convert_type(a.astype(jnp.uint32), jnp.float32)
    return hi.astype(jnp.float64) + lo.astype(jnp.float64)


def _to_i64_bits(a: jnp.ndarray) -> jnp.ndarray:
    """Lossless int64 view of a column (exchange lanes, sort keys,
    kernel gathers). A float64 is its IEEE bit pattern, except when the
    program is lowered for a TPU (``_f64_pair_bits``)."""
    if a.dtype == jnp.int64:
        return a
    if a.dtype == jnp.float64:
        return jax.lax.platform_dependent(
            a, tpu=_f64_pair_bits,
            default=lambda x: jax.lax.bitcast_convert_type(x, jnp.int64))
    if a.dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(a, jnp.int32).astype(jnp.int64)
    return a.astype(jnp.int64)


def _from_i64_bits(a: jnp.ndarray, dtype) -> jnp.ndarray:
    if dtype == jnp.int64:
        return a
    if dtype == jnp.float64:
        return jax.lax.platform_dependent(
            a, tpu=_f64_from_pair_bits,
            default=lambda x: jax.lax.bitcast_convert_type(x, jnp.float64))
    if dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(a.astype(jnp.int32), jnp.float32)
    return a.astype(dtype)


def _gather_columns(arrs: List[jnp.ndarray], idx: jnp.ndarray,
                    use_kernel: bool) -> List[jnp.ndarray]:
    """Gather rows of several columns at ``idx``. Kernel path: one
    blocked one-hot Pallas gather over the int64 bit-views (MXU-shaped
    instead of scalar-unit random access)."""
    if not arrs:
        return []
    if not use_kernel:
        return [a[idx] for a in arrs]
    from repro.kernels import ops as kops
    packed = jnp.stack([_to_i64_bits(a) for a in arrs], axis=1)
    out = kops.gather_rows(packed, idx)
    return [_from_i64_bits(out[:, i], a.dtype) for i, a in enumerate(arrs)]


SORT_ROW_GATHERS = 0.2
"""What one row of a sort costs, in rows gathered by a binary search's
step: the largest ratio of the two per-row costs over the served
cells' probe shapes on a TPU v5e, 0.19, rounded up
(``python -m benchmarks.join_probe``)."""


def _merge_rank_left(sorted_keys: jnp.ndarray,
                     queries: jnp.ndarray) -> jnp.ndarray:
    """``jnp.searchsorted(sorted_keys, queries, side="left")`` by one
    co-sort, bit for bit: no gather and no scatter. The queries go
    first in the concatenation, so the source index, the second sort
    key, puts each query before an equal build key; an exclusive
    cumsum of the build rows then counts the keys strictly below each
    query, and a sort on the source index puts the counts back in query
    order."""
    n = queries.shape[0]
    keys = jnp.concatenate([queries, sorted_keys])
    src = jnp.arange(keys.shape[0], dtype=jnp.int32)
    _, src = jax.lax.sort((keys, src), num_keys=2)
    build = (src >= n).astype(jnp.int32)
    below = jnp.cumsum(build, dtype=jnp.int32) - build
    _, below = jax.lax.sort((src, below), num_keys=1)
    return below[:n]


def _probe_left(sorted_keys: jnp.ndarray,
                queries: jnp.ndarray) -> jnp.ndarray:
    """Left insertion points of ``queries`` in ``sorted_keys``, by the
    cheaper of the two methods at these static sizes: a binary search
    gathers ``n · bit_length(r)`` rows one dependent step at a time,
    the co-sort sorts ``n + r`` rows twice."""
    n, r = queries.shape[0], sorted_keys.shape[0]
    if 2 * (n + r) * SORT_ROW_GATHERS < n * r.bit_length():
        _count("merge_probe")
        return _merge_rank_left(sorted_keys, queries)
    return jnp.searchsorted(sorted_keys, queries)


def fk_join(left: FlatBag, right: FlatBag, left_on: Sequence[str],
            right_on: Sequence[str], how: str = "inner",
            right_prefix: str = "", use_kernel: bool = False) -> FlatBag:
    """Equi-join where the right (build) side is unique on its key — the
    shape of every join in the paper's benchmarks (pk/fk). Output rows
    align with the left side (capacity preserved), so the probe side's
    delivered ordering and key caches carry through.

    how = "inner" | "left_outer". For left_outer, unmatched rows keep
    left validity and get zero-defaults + a ``__matched`` bool column.
    """
    left_on, right_on = tuple(left_on), tuple(right_on)
    cap_r = right.capacity
    order_r, srk = _build_side(right, right_on)
    lkey = pack_keys(left, left_on)

    with jax.named_scope("search"):
        if use_kernel:
            from repro.kernels import ops as kops
            pos, _ = kops.merge_positions(srk, lkey)
        else:
            pos = _probe_left(srk, lkey)
    rnames = [n for n in right.data
              if not (right_prefix + n in left.data and n in right_on)]
    with jax.named_scope("gather"):
        pos_c = jnp.clip(pos, 0, cap_r - 1)
        ridx, srkg = _gather_columns([order_r, srk], pos_c, use_kernel)
        gathered = _gather_columns(
            [right.data[n] for n in rnames] + [right.valid], ridx,
            use_kernel)
    rvalid = gathered[-1]
    matched = (srkg == lkey) & rvalid & left.valid

    data = dict(left.data)
    for n, g in zip(rnames, gathered[:-1]):
        out_name = right_prefix + n
        if out_name in data:
            raise ValueError(f"join column collision: {out_name}")
        data[out_name] = jnp.where(matched, g, jnp.zeros_like(g))
    props = None
    if ORDER_AWARE:
        lp = left.props
        props = PhysicalProps(
            key_cache=dict(lp.key_cache), sorted_by=lp.sorted_by,
            invalid_last=lp.invalid_last if how == "left_outer" else False,
            partitioning=_part_if(left, left.data))
    if how == "inner":
        return FlatBag(data, matched, props)
    assert how == "left_outer", how
    data["__matched"] = matched
    return FlatBag(data, left.valid, props)


def general_join(left: FlatBag, right: FlatBag, left_on: Sequence[str],
                 right_on: Sequence[str], out_capacity: int,
                 how: str = "inner", right_prefix: str = "",
                 matched_col: str = "__matched",
                 rowid_col: Optional[str] = None,
                 use_kernel: bool = False
                 ) -> Tuple[FlatBag, jnp.ndarray]:
    """M:N equi-join with a static output capacity (the TPU analogue of
    the paper's per-partition memory ceiling). Returns (bag, overflow):
    overflow counts result rows that did not fit — the static-shape
    equivalent of Spark's disk-spill/OOM crash region.

    how = "left_outer" keeps unmatched left rows (one output row with
    ``__matched`` False), which is the outer-unnest building block.
    Output rows are left-major, so the probe side's delivered ordering
    carries through (values repeat in place).
    """
    left_on, right_on = tuple(left_on), tuple(right_on)
    cap_r = right.capacity
    order_r, srk = _build_side(right, right_on)
    lkey = pack_keys(left, left_on)
    with jax.named_scope("search"):
        if use_kernel:
            from repro.kernels import ops as kops
            lo, hi = kops.merge_positions(srk, lkey)
        else:
            lo = jnp.searchsorted(srk, lkey, side="left")
            hi = jnp.searchsorted(srk, lkey, side="right")
    # the output's segments: each probe row's run of matches
    with jax.named_scope("segment"):
        cnt = jnp.where(left.valid, hi - lo, 0)
        if how == "left_outer":
            cnt = jnp.where(left.valid & (cnt == 0), 1, cnt)
        offs = jnp.cumsum(cnt)                      # inclusive
        start = offs - cnt
        total = offs[-1]
        j = jnp.arange(out_capacity)
        if use_kernel:
            from repro.kernels import ops as kops
            _, li = kops.merge_positions(offs, j)
        else:
            li = jnp.searchsorted(offs, j, side="right")
    with jax.named_scope("gather"):
        li_c = jnp.clip(li, 0, left.capacity - 1)
        lgather = _gather_columns(
            [left.data[n] for n in left.data] + [start, lo, hi], li_c,
            use_kernel)
        startg, log, hig = lgather[-3:]
        within = j - startg
        has_match = (hig - log) > 0
        ridx_pos = jnp.clip(log + within, 0, cap_r - 1)
        (ridx,) = _gather_columns([order_r], ridx_pos, use_kernel)
        rnames = [n for n in right.data
                  if not (right_prefix + n in left.data
                          and n in right_on)]
        rgather = _gather_columns([right.data[n] for n in rnames], ridx,
                                  use_kernel)
    out_valid = j < total

    data = {n: g for n, g in zip(left.data, lgather)}
    for n, g in zip(rnames, rgather):
        out_name = right_prefix + n
        if out_name in data:
            raise ValueError(f"join column collision: {out_name}")
        data[out_name] = jnp.where(out_valid & has_match, g,
                                   jnp.zeros_like(g))
    if how == "left_outer":
        data[matched_col] = has_match & out_valid
    if rowid_col is not None:
        # the paper's outer-unnest unique ID: one per output tuple
        data[rowid_col] = j.astype(jnp.int64)
    overflow = jnp.maximum(total - out_capacity, 0)
    props = None
    if ORDER_AWARE:
        props = PhysicalProps(sorted_by=left.props.sorted_by,
                              invalid_last=True,
                              partitioning=_part_if(left, left.data))
    return FlatBag(data, out_valid, props), overflow


# ---------------------------------------------------------------------------
# standard-route flattening (mu / outer-unnest) and nesting (Gamma_u)
# ---------------------------------------------------------------------------

def flatten_child(parent: FlatBag, child: FlatBag, parent_label: str,
                  child_label: str, out_capacity: int,
                  outer: bool = True, matched_col: str = "__matched",
                  rowid_col: Optional[str] = None,
                  use_kernel: bool = False
                  ) -> Tuple[FlatBag, jnp.ndarray]:
    """mu / outer-unnest: pair each parent row with its child rows (child
    rows carry ``child_label`` pointing at ``parent_label``), gathering
    ALL parent columns wide onto the result — this is the paper's
    flattening cost, reproduced byte-for-byte."""
    how = "left_outer" if outer else "inner"
    return general_join(parent, child, [parent_label], [child_label],
                        out_capacity, how=how, matched_col=matched_col,
                        rowid_col=rowid_col, use_kernel=use_kernel)


def nest_level(bag: FlatBag, group_cols: Sequence[str],
               child_cols: Sequence[str], label_col: str,
               child_valid_col: Optional[str] = None,
               use_kernel: bool = False) -> Tuple[FlatBag, FlatBag]:
    """Gamma_u: regroup a wide bag into (parents, children):

      parents  — one row per distinct group_cols, plus ``label_col`` with
                 a fresh dense label (the group id);
      children — child_cols of every input row, plus ``label_col``.

    ``child_valid_col`` (from outer joins) marks rows that represent an
    empty bag: the parent row is kept, the child row is dropped — the
    paper's NULL -> empty-bag cast in Gamma.

    When the input already delivers an ordering with ``group_cols`` as a
    prefix (a sum_by on group_cols + agg keys, say), no sort happens —
    the fused group/nest pipeline of the shredded plans."""
    cap = bag.capacity
    group_cols = tuple(group_cols)
    sbag, seg_id = _segments(bag, group_cols)
    with jax.named_scope("segment"):
        exists, parent_valid, firsts, _ = _segment_firsts(
            sbag, seg_id, group_cols, use_kernel)

    pdata = dict(firsts)
    pdata[label_col] = jnp.arange(cap, dtype=jnp.int64)
    pprops = None
    if ORDER_AWARE:
        pprops = PhysicalProps(sorted_by=group_cols,
                               invalid_last=sbag.props.invalid_last,
                               partitioning=_part_if(sbag, group_cols))
    parents = FlatBag(pdata, parent_valid, pprops)

    label = seg_id.astype(jnp.int64)
    cdata = {c: sbag.col(c) for c in child_cols}
    cdata[label_col] = label
    child_valid = sbag.valid
    if child_valid_col is not None:
        child_valid = child_valid & sbag.col(child_valid_col)
    cprops = None
    if ORDER_AWARE:
        cprops = PhysicalProps(key_cache={(label_col,): label},
                               sorted_by=(label_col,),
                               invalid_last=False,
                               partitioning=_part_if(sbag, child_cols))
    children = FlatBag(cdata, child_valid, cprops)
    return parents, children


# ---------------------------------------------------------------------------
# set ops
# ---------------------------------------------------------------------------

def union_all(a: FlatBag, b: FlatBag) -> FlatBag:
    from repro.columnar.table import concat_bags
    return concat_bags(a, b)

"""Distributed execution under shard_map (DESIGN.md §2, §5, and
"Partitioning-aware shuffle").

Spark's shuffle becomes ``jax.lax.all_to_all`` with *fixed-capacity
per-destination buckets* (the MoE-dispatch pattern): skewed keys
overflow their bucket instead of spilling to disk — overflow is counted
and reported, the TPU-native analogue of the paper's crashed bars.

The default (**packed**) exchange is a sort-based packed shuffle:

* rows are routed by a *destination sort* (argsort by ``hash(key) % P``,
  cached per key set in ``PhysicalProps.route_cache``) instead of the
  seed's dense one-hot/cumsum scatter;
* every column ships in ONE collective — the columns are bit-cast to
  int64 lanes and stacked into a single ``(P, bucket, n_lanes)`` wire
  buffer (plus one packed-key lane seeding the receiver's key cache and
  one validity lane), so an exchange costs exactly one ``all_to_all``
  regardless of schema width (``kernels/shuffle_pack.py`` provides the
  Pallas dest-scatter / unpack pair for the TPU path);
* the receiving bag carries ``partitioning = key_cols`` as a physical
  property, and every exchange whose key is a superset of a delivered
  partitioning is **elided** — ``join -> sum_by`` on the same key moves
  rows across the wire exactly once, and co-partitioned joins exchange
  neither side (``SHUFFLE_STATS`` counts executed vs elided exchanges);
* bucket capacities are **adaptive**: each exchange psums its true
  per-destination row counts once (a ``pmax`` metric per exchange
  site); ``run_distributed(adaptive=True)`` re-traces with exact bucket
  sizes whenever a site overflowed, eliminating the overflow-vs-memory
  tradeoff for light keys while keeping metered overflow as the skew
  safety valve.

``shuffle_mode="legacy"`` selects the seed path (one-hot scatter, one
collective per column, no elision) — the benchmarks' baseline.

Broadcast joins use ``all_gather`` of the small side. The skew-aware
join (paper Fig. 6) exchanges only the light component and gathers the
heavy rows of the build side, leaving heavy probe rows in place; the
light+heavy unions compact back to the pre-split capacity
(``concat_compact``) instead of compounding buffer growth.

All operators run *inside* shard_map over a 1-D partition axis; a
``DistContext`` carries the axis name and a metrics accumulator
(shuffle bytes, broadcast bytes, overflow rows) whose values are
psum'd / pmax'd on exit.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.columnar.table import FlatBag, concat_bags, concat_compact
from repro.core import skew as SK
from repro.errors import ExchangeError
from repro.faults import FAULTS
from . import ops as X
from .hashing import mix64


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


# ---------------------------------------------------------------------------
# shuffle accounting (trace-time host counters, the SORT_STATS analogue)
# ---------------------------------------------------------------------------

from repro.obs.metrics import REGISTRY as _METRICS
from repro.obs.trace import span as _span

SHUFFLE_STATS = _METRICS.view("shuffle")
"""Shuffle accounting — live view onto the unified metrics registry
(``repro.obs``) under the ``shuffle.`` domain. Per-site keys
(``size_used_<n>``, ``replication_x100_<n>``) are written as gauges and
wiped by every registry reset, so they can no longer leak across runs
with different mesh sizes (the pytest autouse fixture resets between
tests; ``compile_distributed`` still resets per attempt)."""


def reset_shuffle_stats() -> None:
    SHUFFLE_STATS.clear()


def _scount(name: str, n: int = 1) -> None:
    _METRICS.inc("shuffle." + name, n)


def _roundup8(n: int) -> int:
    return max(-(-int(n) // 8) * 8, 1)


class DistContext:
    """Collective operators + metering for one shard_map region."""

    def __init__(self, axis: str, n_partitions: int,
                 cap_factor: float = 2.0, sample: int = 256,
                 threshold: float = 0.025, skew_default: bool = False,
                 packed: bool = True,
                 size_plan: Optional[Sequence[int]] = None,
                 use_kernel: bool = False):
        self.axis = axis
        self.P = n_partitions
        self.cap_factor = cap_factor
        self.sample = sample
        self.threshold = threshold
        self.skew_default = skew_default
        self.packed = packed
        self.size_plan = size_plan
        self.use_kernel = use_kernel
        self.metrics: Dict[str, jnp.ndarray] = {}
        self.max_metrics: Dict[str, jnp.ndarray] = {}
        self._n_sites = 0

    # -- metering -----------------------------------------------------
    def _add(self, name: str, value):
        self.metrics[name] = self.metrics.get(name, jnp.zeros((), jnp.int64)) \
            + jnp.asarray(value, jnp.int64)

    def _add_max(self, name: str, value):
        v = jnp.asarray(value, jnp.int64)
        cur = self.max_metrics.get(name)
        self.max_metrics[name] = v if cur is None else jnp.maximum(cur, v)

    def finalize_metrics(self) -> Dict[str, jnp.ndarray]:
        out = {k: jax.lax.psum(v, self.axis)
               for k, v in self.metrics.items()}
        # the TPU all-reduces 64-bit integers by sum only; max metrics
        # are per-partition row counts, which fit in 32 bits
        out.update({k: jax.lax.pmax(v.astype(jnp.int32),
                                    self.axis).astype(jnp.int64)
                    for k, v in self.max_metrics.items()})
        return out

    # -- adaptive sizing sites ----------------------------------------
    def _size_site(self, default: int) -> Tuple[int, int]:
        """Claim the next capacity-sizing site (exchange bucket or union
        capacity). Sites are numbered in trace order, which is
        deterministic, so a retry with a ``size_plan`` addresses exactly
        the site that recorded the need."""
        site = self._n_sites
        self._n_sites += 1
        used = int(default)
        if self.size_plan is not None and site < len(self.size_plan):
            used = int(self.size_plan[site])
        _METRICS.set_gauge(f"shuffle.size_used_{site}", used)
        return site, used

    # -- exchange (hash repartition) ------------------------------------
    def exchange(self, bag: FlatBag, key_cols: Sequence[str],
                 keep: Optional[jnp.ndarray] = None,
                 key: Optional[jnp.ndarray] = None) -> FlatBag:
        """Hash-repartition by key (span-traced wrapper; see
        ``_exchange``). The span fires at trace time — host-side only,
        so warm jitted calls are untouched; the ``exchange`` named scope
        labels the compiled ops it emits."""
        with _span("exchange", keys=tuple(key_cols), site=self._n_sites), \
                jax.named_scope("exchange"):
            return self._exchange(bag, key_cols, keep, key)

    def _exchange(self, bag: FlatBag, key_cols: Sequence[str],
                  keep: Optional[jnp.ndarray] = None,
                  key: Optional[jnp.ndarray] = None) -> FlatBag:
        """Hash-repartition rows by key over the partition axis.
        ``keep`` optionally restricts which rows participate (others are
        dropped — used by skew-aware ops to exchange only light rows);
        ``key`` optionally supplies the pre-packed key (the skew path
        packs each key set once and threads it through).

        Elision: when the bag is already hash-partitioned on a subset of
        ``key_cols`` (``PhysicalProps.partitioning``), equal keys are
        already co-located and the exchange is a no-op.

        Wire format (packed mode): every column bit-cast to an int64
        lane, stacked with a packed-key lane (pre-seeding the receiving
        key cache) and a validity lane into one ``(P, bucket, n_lanes)``
        buffer — one ``all_to_all`` total. Within each (sender, dest)
        block rows arrive contiguously in sender order; slots past the
        sender's count arrive zero with validity 0."""
        rule = FAULTS.hit("dist.exchange", keys=tuple(key_cols))
        if rule is not None and rule.kind == "fail":
            raise ExchangeError(
                f"injected exchange failure (keys={tuple(key_cols)})")
        key_cols = tuple(key_cols)
        if not self.packed:
            return self._exchange_legacy(bag, key_cols, keep, key)
        if X.ORDER_AWARE and bag.props.partitioned_for(key_cols):
            _scount("exchange_elided")
            return bag if keep is None else bag.mask(keep)
        _scount("exchanges")
        cap = bag.capacity
        Pn = self.P
        valid = bag.valid if keep is None else (bag.valid & keep)
        if key is None:
            key = X.pack_keys(bag, key_cols)

        # -- destination-sort routing (cached when validity untouched) --
        route = None
        if X.ORDER_AWARE and keep is None:
            route = bag.props.route_cache.get(key_cols)
            if route is not None:
                _scount("route_reuse")
        if route is None:
            _scount("route_argsort")
            dest = (mix64(key) % Pn).astype(jnp.int32)
            destk = jnp.where(valid, dest, Pn)   # invalid rows sort last
            order = jnp.argsort(destk)           # stable: sender order kept
            counts = jax.ops.segment_sum(
                jnp.ones(cap, jnp.int32), destk, num_segments=Pn + 1)[:Pn]
            offsets = jnp.cumsum(counts) - counts
            route = (order, counts, offsets)
            if X.ORDER_AWARE and keep is None and X._cache_ok(bag, order):
                bag.props.route_cache[key_cols] = route
        order, counts, offsets = route

        # -- adaptive bucket sizing -------------------------------------
        site, bucket = self._size_site(
            max(int(cap * self.cap_factor) // Pn, 1))
        self._add_max(f"size_need_{site}", jnp.max(counts))

        # -- partition balance metering ---------------------------------
        # total rows each partition will RECEIVE at this site (psum of
        # the per-sender destination counts); the skew-smoke gate reads
        # max/mean of these as the measured imbalance of the exchange
        recv = jax.lax.psum(counts, self.axis)
        self._add_max(f"part_max_{site}", jnp.max(recv))
        self._add(f"part_rows_{site}", jnp.sum(counts))

        sent = jnp.sum(jnp.minimum(counts, bucket))
        self._add("overflow_rows", jnp.sum(jnp.maximum(counts - bucket, 0)))
        self._add("shuffle_rows", sent)
        # order-aware exchanges ship the packed key as one extra lane
        key_lane = 8 if X.ORDER_AWARE else 0
        self._add("shuffle_bytes", sent * (bag.row_bytes() + key_lane))

        # -- pack: one int64 lane per column + key + validity -----------
        names = bag.columns
        lanes = [X._to_i64_bits(bag.data[n]) for n in names]
        if X.ORDER_AWARE:
            lanes.append(key)
        lanes.append(valid.astype(jnp.int64))
        mat = jnp.stack(lanes, axis=1)                    # (cap, n_lanes)
        slot = jnp.arange(Pn * bucket)
        pdest = slot // bucket
        within = slot % bucket
        slot_ok = within < counts[pdest]
        take = order[jnp.clip(offsets[pdest] + within, 0, cap - 1)]
        if self.use_kernel:
            from repro.kernels import ops as kops
            send = kops.pack_rows(mat, take.astype(jnp.int32), slot_ok)
        else:
            send = jnp.where(slot_ok[:, None], mat[take], 0)

        # -- the single collective --------------------------------------
        _scount("collectives")
        recv = jax.lax.all_to_all(
            send.reshape(Pn, bucket, len(lanes)), self.axis,
            split_axis=0, concat_axis=0, tiled=False
        ).reshape(Pn * bucket, len(lanes))

        # -- unpack ------------------------------------------------------
        if self.use_kernel:
            from repro.kernels import ops as kops
            cols = kops.unpack_cols(recv)

            def lane(i):
                return cols[i]
        else:
            def lane(i):
                return recv[:, i]

        out_data = {n: X._from_i64_bits(lane(i), bag.data[n].dtype)
                    for i, n in enumerate(names)}
        vrecv = lane(len(lanes) - 1) != 0
        props = None
        if X.ORDER_AWARE:
            from repro.columnar.props import PhysicalProps
            props = PhysicalProps(key_cache={key_cols: lane(len(names))},
                                  partitioning=key_cols)
        return FlatBag(out_data, vrecv, props)

    def _exchange_legacy(self, bag: FlatBag, key_cols: Tuple[str, ...],
                         keep: Optional[jnp.ndarray],
                         key: Optional[jnp.ndarray]) -> FlatBag:
        """Seed-era exchange: dense one-hot/cumsum scatter and one
        ``all_to_all`` per column — kept as the benchmarks' baseline
        (``shuffle_mode="legacy"``)."""
        _scount("exchanges")
        cap = bag.capacity
        Pn = self.P
        bucket = max(int(cap * self.cap_factor) // Pn, 1)
        if key is None:
            key = X.pack_keys(bag, key_cols)
        valid = bag.valid if keep is None else (bag.valid & keep)
        dest = (mix64(key) % Pn).astype(jnp.int32)
        dest = jnp.where(valid, dest, 0)
        onehot = (dest[:, None] == jnp.arange(Pn)[None, :]) & valid[:, None]
        pos = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
        pos = jnp.take_along_axis(pos, dest[:, None], axis=1)[:, 0]
        ok = valid & (pos < bucket)
        self._add("overflow_rows", jnp.sum(valid & (pos >= bucket)))
        self._add("shuffle_rows", jnp.sum(ok))
        key_lane = 8 if X.ORDER_AWARE else 0
        self._add("shuffle_bytes", jnp.sum(ok) * (bag.row_bytes() + key_lane))

        pos_safe = jnp.where(ok, pos, bucket)  # out-of-bounds -> dropped

        def scatter(col):
            buf = jnp.zeros((Pn, bucket), col.dtype)
            return buf.at[dest, pos_safe].set(jnp.where(ok, col, 0),
                                              mode="drop")

        def a2a(buf):
            _scount("collectives")
            return jax.lax.all_to_all(buf, self.axis, split_axis=0,
                                      concat_axis=0,
                                      tiled=False).reshape(Pn * bucket)

        out_data = {n: a2a(scatter(a)) for n, a in bag.data.items()}
        vrecv = a2a(jnp.zeros((Pn, bucket), bool).at[dest, pos_safe].set(
            ok, mode="drop"))
        props = None
        if X.ORDER_AWARE:
            from repro.columnar.props import PhysicalProps
            props = PhysicalProps(key_cache={key_cols: a2a(scatter(key))})
        return FlatBag(out_data, vrecv, props)

    # -- broadcast (all_gather) -----------------------------------------
    def gather_all(self, bag: FlatBag,
                   keep: Optional[jnp.ndarray] = None) -> FlatBag:
        with _span("broadcast", cols=bag.columns), \
                jax.named_scope("broadcast"):
            return self._gather_all(bag, keep)

    def _gather_all(self, bag: FlatBag,
                    keep: Optional[jnp.ndarray] = None) -> FlatBag:
        valid = bag.valid if keep is None else (bag.valid & keep)
        self._add("broadcast_bytes",
                  jax.lax.psum(jnp.sum(valid), self.axis)
                  * bag.row_bytes() * (self.P - 1) // self.P)
        if not self.packed:
            _scount("collectives", len(bag.data) + 1)
            data = {n: jax.lax.all_gather(a, self.axis, tiled=True)
                    for n, a in bag.data.items()}
            v = jax.lax.all_gather(valid, self.axis, tiled=True)
            return FlatBag(data, v)
        # packed: same single-collective column batching as exchange
        names = bag.columns
        lanes = [X._to_i64_bits(bag.data[n]) for n in names]
        lanes.append(valid.astype(jnp.int64))
        _scount("collectives")
        allmat = jax.lax.all_gather(jnp.stack(lanes, axis=1), self.axis,
                                    tiled=True)
        data = {n: X._from_i64_bits(allmat[:, i], bag.data[n].dtype)
                for i, n in enumerate(names)}
        return FlatBag(data, allmat[:, -1] != 0)

    # -- joins -----------------------------------------------------------
    def join(self, left: FlatBag, right: FlatBag, left_on, right_on,
             how: str = "inner", unique_right: bool = True,
             broadcast: bool = False, skew_aware: bool = False,
             expansion: float = 4.0,
             heavy_keys: Optional[jnp.ndarray] = None) -> FlatBag:
        """``heavy_keys`` (compiler-planned skew, ``plans.SkewJoinP``)
        supplies the heavy-key set as a runtime value — a padded int64
        array bound per call — instead of the per-call sampling of
        ``skew_aware``. Both route through the same light-exchange +
        heavy-broadcast skew triple."""
        if broadcast:
            rall = self.gather_all(right)
            return self._local_join(left, rall, left_on, right_on, how,
                                    unique_right, expansion)
        if heavy_keys is not None:
            _scount("skew_join_planned")
            return self._skew_join(left, right, left_on, right_on, how,
                                   unique_right, expansion,
                                   heavy=heavy_keys)
        if skew_aware or self.skew_default:
            _scount("skew_join_sampled")
            return self._skew_join(left, right, left_on, right_on, how,
                                   unique_right, expansion)
        lk, rk = self._copartition_keys(left, right, left_on, right_on)
        lex = self._side_exchange(left, lk)
        rex = self._side_exchange(right, rk)
        return self._local_join(lex, rex, left_on, right_on, how,
                                unique_right, expansion)

    def _side_exchange(self, bag: FlatBag, key_cols,
                       keep: Optional[jnp.ndarray] = None,
                       key: Optional[jnp.ndarray] = None) -> FlatBag:
        """Exchange one join side on the co-partition key computed by
        ``_copartition_keys`` (None => already placed: elide)."""
        if key_cols is None:
            _scount("exchange_elided")
            return bag if keep is None else bag.mask(keep)
        return self.exchange(bag, key_cols, keep=keep, key=key)

    def _copartition_keys(self, left: FlatBag, right: FlatBag,
                          left_on, right_on):
        """Pick the exchange key for each join side so the two sides end
        up co-partitioned with as little movement as possible.

        A side already hash-partitioned on a positional sub-tuple of its
        join key can stay put; the OTHER side then exchanges on the
        *corresponding* sub-tuple (matching rows have equal values at
        those positions, hence the same hash). When both sides deliver
        the same positional selection, the join exchanges neither.
        Returns ``(left_key, right_key)`` with ``None`` meaning elide."""
        left_on, right_on = tuple(left_on), tuple(right_on)
        if not (self.packed and X.ORDER_AWARE):
            return left_on, right_on

        def sel(part, on):
            if not part:
                return None
            try:
                return tuple(on.index(c) for c in part)
            except ValueError:
                return None

        li = sel(left.props.partitioning, left_on)
        ri = sel(right.props.partitioning, right_on)
        if li is not None and ri is not None and li == ri:
            return None, None
        if li is not None:
            return None, tuple(right_on[i] for i in li)
        if ri is not None:
            return tuple(left_on[i] for i in ri), None
        return left_on, right_on

    def _local_join(self, left, right, left_on, right_on, how,
                    unique_right, expansion):
        if unique_right:
            return X.fk_join(left, right, left_on, right_on, how=how)
        out_cap = int(max(left.capacity, right.capacity)
                      * max(expansion, 1.0))
        bag, overflow = X.general_join(left, right, left_on, right_on,
                                       out_cap, how=how)
        self._add("overflow_rows", overflow)
        return bag

    def _skew_join(self, left, right, left_on, right_on, how,
                   unique_right, expansion, heavy=None):
        """Paper Fig. 6: split the probe side by heavy keys; exchange the
        light component; leave heavy probe rows in place and broadcast
        the matching build rows. Each key set is packed once and
        threaded through detection, split and exchange. ``heavy``
        (planned skew) supplies the key set directly — sorted here so
        any runtime binding order works with the searchsorted member
        test — replacing the sample + all_gather detection round."""
        left_on, right_on = tuple(left_on), tuple(right_on)
        lkey = X.pack_keys(left, left_on)
        if heavy is not None:
            hk = jnp.sort(heavy.astype(jnp.int64))
        else:
            hk = self.heavy_keys(left, left_on, key=lkey)
        heavy_mask = SK.is_member(lkey, hk,
                                  use_kernel=self.use_kernel) & left.valid
        # light plan: standard exchange join (co-partition aware)
        lk, rk = self._copartition_keys(left, right, left_on, right_on)
        rkey = X.pack_keys(right, right_on)
        lex = self._side_exchange(left, lk, keep=~heavy_mask,
                                  key=lkey if lk == left_on else None)
        rex = self._side_exchange(right, rk,
                                  key=rkey if rk == right_on else None)
        light = self._local_join(lex, rex, left_on, right_on, how,
                                 unique_right, expansion)
        # heavy plan: heavy probe rows stay; broadcast matching build rows
        r_heavy = SK.is_member(rkey, hk, use_kernel=self.use_kernel)
        rall = self.gather_all(right, keep=r_heavy)
        heavy = self._local_join(left.mask(heavy_mask), rall, left_on,
                                 right_on, how, unique_right, expansion)
        return self._union_compact(light, heavy)

    def _union_compact(self, light: FlatBag, heavy: FlatBag) -> FlatBag:
        """Union the light/heavy results of a skew op. Packed mode
        compacts back to the larger of the two capacities (adaptively
        regrown when the valid counts demand more) instead of letting
        every skew op compound ``P*bucket + cap``; the padding that
        remains and any dropped rows are metered."""
        if not self.packed:
            return concat_bags(light, heavy)
        site, target = self._size_site(max(light.capacity, heavy.capacity))
        need = jnp.sum(light.valid.astype(jnp.int64)) \
            + jnp.sum(heavy.valid.astype(jnp.int64))
        self._add_max(f"size_need_{site}", need)
        out, dropped = concat_compact(light, heavy, target)
        self._add("compact_dropped_rows", dropped)
        self._add("union_padding_rows", jnp.maximum(target - need, 0))
        return out

    # -- hypercube multiway join (one replicating round, plans.MultiJoinP)
    def multi_join(self, spine: FlatBag, rights: Sequence[FlatBag],
                   stages, shares: Sequence[int], rel_routes,
                   dim_heavy: Sequence[Optional[jnp.ndarray]],
                   use_kernel: bool = False) -> FlatBag:
        """Span-traced wrapper (compiled ops under the ``hypercube``
        named scope); see ``_multi_join``."""
        with _span("exchange", kind="hypercube", shares=tuple(shares),
                   site=self._n_sites), jax.named_scope("hypercube"):
            return self._multi_join(spine, rights, stages, shares,
                                    rel_routes, dim_heavy, use_kernel)

    def _multi_join(self, spine: FlatBag, rights: Sequence[FlatBag],
                    stages, shares: Sequence[int], rel_routes,
                    dim_heavy: Sequence[Optional[jnp.ndarray]],
                    use_kernel: bool = False) -> FlatBag:
        """One-round multiway equi-join (HyperCube shuffle, DESIGN.md
        "HyperCube exchange"). The mesh is factored into per-dimension
        ``shares``; every relation (``spine`` + ``rights``) is hashed on
        the dimensions it keys (``rel_routes``) and replicated across
        the rest, all relations ship in ONE packed collective, then the
        stages probe locally.

        Replication runs over VIRTUAL rows: source row ``i`` fans out to
        ``repl`` copies, copy ``q`` taking its missing-dimension
        coordinates from the mixed-radix digits of ``q``. Heavy keys
        (``dim_heavy[d]``, the runtime SkewJoinP parameter) spread probe
        rows across their dimension by row index and replicate the
        matching build rows along it — extra copies of light build rows
        are masked invalid, so the wire cost stays proportional to the
        heavy set."""
        rule = FAULTS.hit("dist.exchange", keys=("__hypercube__",))
        if rule is not None and rule.kind == "fail":
            raise ExchangeError("injected hypercube exchange failure")
        Pn = self.P
        n_dims = len(shares)
        shares = [int(s) for s in shares]
        # the plan's shares were chosen for ``skew_partitions`` servers;
        # if the runtime axis is smaller, shrink the largest shares
        # until the coordinate space fits. Exactly-once correctness
        # needs every hypercube coordinate on its OWN server: folding
        # distinct coordinates together would co-locate replicated
        # build copies with one probe row and duplicate join results.
        while _prod(shares) > Pn:
            d = max(range(n_dims), key=lambda i: shares[i])
            shares[d] = max(1, shares[d] - 1)
        strides = [1] * n_dims
        for d in range(n_dims - 2, -1, -1):
            strides[d] = strides[d + 1] * shares[d + 1]
        hsorted = [None if h is None else jnp.sort(h.astype(jnp.int64))
                   for h in dim_heavy]
        bags = [spine] + list(rights)
        use_k = use_kernel or self.use_kernel

        sends, buckets, lane_n = [], [], []
        for r, bag in enumerate(bags):
            route = {int(d): (tuple(cols), role)
                     for d, cols, role in rel_routes[r]}
            miss = [d for d in range(n_dims) if d not in route]
            hrep = [d for d in route
                    if route[d][1] == "build" and hsorted[d] is not None]
            rep_dims = miss + hrep
            repl = 1
            for d in rep_dims:
                repl *= shares[d]
            cap = bag.capacity
            V = cap * repl
            vi = jnp.arange(V, dtype=jnp.int32)
            src = vi // repl
            # mixed-radix replica coordinates for the replicated dims
            qc: Dict[int, jnp.ndarray] = {}
            rem = vi % repl
            for d in reversed(rep_dims):
                qc[d] = rem % shares[d]
                rem = rem // shares[d]
            ok = bag.valid[src]
            dest = jnp.zeros(V, jnp.int32)
            for d in range(n_dims):
                sd = shares[d]
                if d in route:
                    cols, role = route[d]
                    key = X.pack_keys(bag, cols)
                    ch = (mix64(key) % sd).astype(jnp.int32)
                    hv = hsorted[d]
                    if role == "probe":
                        if hv is not None:
                            hm = SK.is_member(key, hv, use_kernel=use_k)
                            spread = jnp.arange(cap, dtype=jnp.int32) % sd
                            ch = jnp.where(hm, spread, ch)
                        coord = ch[src]
                    else:           # build side of dimension d
                        if hv is not None:
                            hm = SK.is_member(key, hv, use_kernel=use_k)
                            coord = qc[d]   # one copy per coordinate...
                            # ...heavy rows keep all of them, light rows
                            # only the hashed one
                            ok = ok & (hm[src] | (qc[d] == ch[src]))
                        else:
                            coord = ch[src]
                else:
                    coord = qc[d]
                dest = dest + coord * strides[d]

            destk = jnp.where(ok, dest, Pn)      # invalid sort last
            order = jnp.argsort(destk)
            counts = jax.ops.segment_sum(
                jnp.ones(V, jnp.int32), destk, num_segments=Pn + 1)[:Pn]
            offsets = jnp.cumsum(counts) - counts
            site, bucket = self._size_site(
                max(int(V * self.cap_factor) // Pn, 1))
            self._add_max(f"size_need_{site}", jnp.max(counts))
            recv_c = jax.lax.psum(counts, self.axis)
            self._add_max(f"part_max_{site}", jnp.max(recv_c))
            self._add(f"part_rows_{site}", jnp.sum(counts))
            sent = jnp.sum(jnp.minimum(counts, bucket))
            self._add("overflow_rows",
                      jnp.sum(jnp.maximum(counts - bucket, 0)))
            self._add("shuffle_rows", sent)
            self._add("shuffle_bytes", sent * bag.row_bytes())
            # replication observability: actual extra copies crossing
            # the wire for this relation (static factor in SHUFFLE_STATS,
            # measured rows/bytes in the device metrics)
            _METRICS.set_gauge(f"shuffle.replication_x100_{site}", repl * 100)
            n_src = jnp.sum(bag.valid.astype(jnp.int64))
            n_virt = jnp.sum(ok.astype(jnp.int64))
            self._add("replicated_rows", n_virt - n_src)
            self._add("bytes_replicated",
                      (n_virt - n_src) * bag.row_bytes())

            names = bag.columns
            mat = jnp.stack(
                [X._to_i64_bits(bag.data[nm]) for nm in names]
                + [jnp.ones(cap, jnp.int64)], axis=1)   # validity lane
            slot = jnp.arange(Pn * bucket)
            pdest = slot // bucket
            within = slot % bucket
            slot_ok = within < counts[pdest]
            take = order[jnp.clip(offsets[pdest] + within, 0, V - 1)]
            if use_k:
                from repro.kernels import ops as kops
                send = kops.replicate_scatter(mat, take.astype(jnp.int32),
                                              slot_ok, repl)
            else:
                send = jnp.where(slot_ok[:, None], mat[take // repl], 0)
            sends.append(send)
            buckets.append(bucket)
            lane_n.append(len(names) + 1)

        # -- ALL relations in ONE collective ---------------------------
        l_max = max(lane_n)
        parts = []
        for r, send in enumerate(sends):
            s3 = send.reshape(Pn, buckets[r], lane_n[r])
            if lane_n[r] < l_max:
                s3 = jnp.pad(s3, ((0, 0), (0, 0), (0, l_max - lane_n[r])))
            parts.append(s3)
        _scount("collectives")
        _scount("hypercube_exchanges")
        recv = jax.lax.all_to_all(
            jnp.concatenate(parts, axis=1), self.axis,
            split_axis=0, concat_axis=0, tiled=False)

        out_bags = []
        off = 0
        for r, bag in enumerate(bags):
            blk = recv[:, off:off + buckets[r], :].reshape(
                Pn * buckets[r], l_max)
            off += buckets[r]
            names = bag.columns
            data = {nm: X._from_i64_bits(blk[:, i], bag.data[nm].dtype)
                    for i, nm in enumerate(names)}
            out_bags.append(FlatBag(data, blk[:, len(names)] != 0))

        # -- local multiway probe (no further exchanges) ----------------
        acc = out_bags[0]
        for st, rb in zip(stages, out_bags[1:]):
            acc = self._local_join(acc, rb, tuple(st.left_on),
                                   tuple(st.right_on), "inner",
                                   st.unique_right, st.expansion)
        return acc

    # -- heavy-key detection (sampled, then gathered) ---------------------
    def heavy_keys(self, bag: FlatBag, key_cols,
                   key: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        if key is None:
            key = X.pack_keys(bag, key_cols)
        local = SK.heavy_keys_local(key, bag.valid, sample=self.sample,
                                    threshold=self.threshold)
        self._add("broadcast_bytes", local.shape[0] * 8 * (self.P - 1))
        _scount("collectives")
        allc = jax.lax.all_gather(local, self.axis, tiled=True)
        return SK.merge_heavy(allc)

    # -- aggregation -------------------------------------------------------
    def sum_by(self, bag: FlatBag, keys, vals, local_preagg: bool = True,
               use_kernel: bool = False,
               exchange_on: Optional[Sequence[str]] = None) -> FlatBag:
        """Gamma+ : optional local pre-aggregation (aggregation pushdown,
        §3.3 — executed "locally at each partition"), exchange by key,
        final local aggregation. Aggregation is inherently skew-resilient
        (paper §5: 'Gamma+ mitigates skew-effects by default').

        ``exchange_on`` (planner hint, ``push_partitioning``) narrows
        the exchange key to a subset of the grouping keys — co-location
        on a subset is sufficient for grouping, and a well-chosen subset
        lets downstream consumers reuse the delivered partitioning."""
        keys = tuple(keys)
        if local_preagg:
            bag = X.sum_by(bag, keys, vals, use_kernel=use_kernel)
        ex_key = tuple(exchange_on) if exchange_on else keys
        assert set(ex_key) <= set(keys), (ex_key, keys)
        ex = self.exchange(bag, ex_key)
        return X.sum_by(ex, keys, vals, use_kernel=use_kernel)

    def dedup(self, bag: FlatBag, cols,
              exchange_on: Optional[Sequence[str]] = None) -> FlatBag:
        cols = tuple(cols)
        local = X.dedup(bag, cols)
        ex_key = tuple(exchange_on) if exchange_on else cols
        assert set(ex_key) <= set(cols), (ex_key, cols)
        ex = self.exchange(local, ex_key)
        return X.dedup(ex, cols)

    # -- BagToDict (skew-aware label repartition, Fig. 6 last row) --------
    def bag_to_dict(self, bag: FlatBag, skew_aware: bool = True) -> FlatBag:
        if not skew_aware:
            return self.exchange(bag, ("label",))
        key = X.pack_keys(bag, ("label",))
        hk = self.heavy_keys(bag, ("label",), key=key)
        heavy_mask = SK.is_member(key, hk,
                                  use_kernel=self.use_kernel) & bag.valid
        light = self.exchange(bag, ("label",), keep=~heavy_mask, key=key)
        heavy = bag.mask(heavy_mask)
        # heavy labels keep their current location (skew resilience);
        # compact the light+heavy union back toward pre-split capacity.
        return self._union_compact(light, heavy)


# ---------------------------------------------------------------------------
# shard_map driver
# ---------------------------------------------------------------------------

def device_mesh_1d(n: int, axis: str = "data") -> Mesh:
    """1-D mesh over the first ``n`` devices; raises when there are
    fewer (a smaller mesh would change every partitioned plan)."""
    import numpy as np
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(
            f"a {n}-partition mesh needs {n} devices; found {len(devs)} "
            f"{devs[0].platform} device(s)")
    return Mesh(np.array(devs[:n]), (axis,))


def _bag_specs(tree, axis: str):
    return jax.tree.map(lambda _: P(axis), tree)


def _merge_host_stats(metrics: Dict[str, int],
                      stats: Dict[str, int]) -> Dict[str, int]:
    """Fold the trace-time SHUFFLE_STATS snapshot into device metrics."""
    metrics = dict(metrics)
    metrics["shuffle_collectives"] = stats.get("collectives", 0)
    metrics["exchanges"] = stats.get("exchanges", 0)
    metrics["exchanges_elided"] = stats.get("exchange_elided", 0)
    metrics["hypercube_exchanges"] = stats.get("hypercube_exchanges", 0)
    repl = [v for k, v in stats.items() if k.startswith("replication_x100_")]
    if repl:
        metrics["replication_factor_x100"] = max(repl)
    return metrics


def receive_imbalance(metrics: Optional[Dict[str, int]], n_partitions: int,
                      floor: int = 1) -> float:
    """Worst receive-load imbalance of one distributed execute: over the
    exchange sites that moved at least ``floor`` rows, the most rows one
    partition received (``part_max_<site>``) over the mean
    (``part_rows_<site>`` / ``n_partitions``). 1.0 with no such site or
    a single partition."""
    worst = 1.0
    if not metrics or n_partitions <= 1:
        return worst
    for k, v in metrics.items():
        if not k.startswith("part_max_"):
            continue
        total = metrics.get("part_rows_" + k[len("part_max_"):], 0)
        if total >= max(floor, 1):
            worst = max(worst, float(v) * n_partitions / float(total))
    return worst


class DistRunner:
    """A compiled distributed program with its capacity plan resolved.

    ``compile_distributed`` returns one of these after the adaptive
    sizing loop converges; calling it re-executes the SAME jitted
    shard_map (warm path — no retrace), which is the steady-state
    serving case the benchmarks time. ``stats`` is the host-side
    SHUFFLE_STATS snapshot of the final trace (collectives, elisions,
    per-site sizes) and is merged into every call's metrics.

    When the program was compiled with runtime parameters
    (``compile_distributed(params=...)``) a warm call may rebind them —
    ``runner(env, params=new_bindings)`` — with zero retracing as long
    as shapes/dtypes match (the skew heavy-key contract)."""

    def __init__(self, sm, stats: Dict[str, int],
                 params: Optional[dict] = None):
        self._sm = sm
        self.stats = stats
        self.params = params        # compile-time bindings (None = none)

    def __call__(self, env, params: Optional[dict] = None
                 ) -> Tuple[dict, Dict[str, int]]:
        if self.params is None:
            assert params is None, (
                "program compiled without runtime parameters")
            with _span("query.dispatch"):
                out, metrics = self._sm(env)
        else:
            p = dict(self.params)
            if params:
                unknown = set(params) - set(p)
                assert not unknown, (
                    f"unknown parameter(s) {sorted(unknown)}; this "
                    f"program binds {sorted(p)}")
                p.update(params)
            p = {k: jnp.asarray(v) for k, v in p.items()}
            with _span("query.dispatch"):
                out, metrics = self._sm(env, p)
        # the meters' host reads wait for the program; the wait is a
        # span of its own, and the reads then cost only their copies
        with _span("dist.device_wait"):
            jax.block_until_ready(metrics)
        with _span("dist.meters"):
            host = {k: int(v) for k, v in metrics.items()}
        return out, _merge_host_stats(host, self.stats)


def shard_program(fn: Callable, mesh: Mesh, axis: str = "data",
                  has_params: bool = False, jit: bool = True,
                  size_plan: Optional[Sequence[int]] = None,
                  cap_factor: float = 2.0, threshold: float = 0.025,
                  skew_default: bool = False,
                  shuffle_mode: str = "packed",
                  use_kernel: bool = False) -> Callable:
    """``fn(env_local, ctx[, params_local])`` as one SPMD program over
    ``mesh[axis]``, returning ``(outputs, metrics)``; nothing runs.
    ``compile_distributed`` calls it once per sizing attempt, and a
    caller may ``.lower(...)`` it for devices that are only described.
    Bags are row-sharded, params (when present) and metrics
    replicated."""
    n = mesh.shape[axis]

    def make_ctx():
        return DistContext(axis, n, cap_factor=cap_factor, sample=256,
                           threshold=threshold, skew_default=skew_default,
                           packed=(shuffle_mode == "packed"),
                           size_plan=size_plan, use_kernel=use_kernel)

    if has_params:
        def inner(env_local, params_local):
            ctx = make_ctx()
            out = fn(env_local, ctx, params_local)
            return out, ctx.finalize_metrics()
    else:
        def inner(env_local):
            ctx = make_ctx()
            out = fn(env_local, ctx)
            return out, ctx.finalize_metrics()

    in_specs = (P(axis), P()) if has_params else (P(axis),)
    sm = jax.shard_map(inner, mesh=mesh, in_specs=in_specs,
                       out_specs=(P(axis), P()), check_vma=False)
    return jax.jit(sm) if jit else sm


def compile_distributed(
        fn: Callable[[Dict[str, FlatBag], DistContext], dict],
        env: Dict[str, FlatBag], mesh: Mesh,
        axis: str = "data", cap_factor: float = 2.0,
        skew_default: bool = False,
        threshold: float = 0.025,
        jit: bool = True,
        shuffle_mode: str = "packed",
        use_kernel: bool = False,
        adaptive: bool = False,
        max_retries: int = 3,
        params: Optional[dict] = None
) -> Tuple[DistRunner, dict, Dict[str, int]]:
    """Compile ``fn(env_local, ctx)`` SPMD over ``mesh[axis]`` and run
    it once. Returns ``(runner, outputs, metrics)`` — call ``runner``
    again for warm executions of the same program.

    Every FlatBag in env is row-sharded over the axis (capacities must
    divide the axis size).

    ``params`` (optional) is a dict of runtime parameter arrays
    replicated into the shard_map region; when given, ``fn`` is called
    as ``fn(env_local, ctx, params_local)`` and warm runner calls may
    rebind new values of the same shapes with zero retracing — the
    mechanism behind parameterized distributed serving and the
    ``SkewJoinP`` heavy-key sets.

    ``adaptive=True`` turns on adaptive capacity: the run records, per
    sizing site (exchange bucket / skew-union capacity), the true
    required size as a pmax metric; if any site was undersized the
    program is re-traced with a ``size_plan`` pinning each such site to
    its exact need (rounded up to a multiple of 8) and re-run, up to
    ``max_retries`` times. Light keys therefore never trade overflow
    against memory; persistent overflow (a site that keeps growing past
    the retry budget) stays metered in ``overflow_rows`` /
    ``compact_dropped_rows``.

    Host-side trace counters (``SHUFFLE_STATS``) from the final attempt
    are merged into the returned metrics: ``shuffle_collectives``,
    ``exchanges``, ``exchanges_elided``.
    """
    n = mesh.shape[axis]
    for k, b in env.items():
        assert b.capacity % n == 0, (
            f"bag {k} capacity {b.capacity} not divisible by {n} partitions")
    assert shuffle_mode in ("packed", "legacy"), shuffle_mode

    has_params = params is not None
    pvals = {k: jnp.asarray(v) for k, v in (params or {}).items()}

    size_plan: Optional[Tuple[int, ...]] = None
    attempt = 0
    while True:
        reset_shuffle_stats()
        sm = shard_program(fn, mesh, axis=axis, has_params=has_params,
                           jit=jit, size_plan=size_plan,
                           cap_factor=cap_factor, threshold=threshold,
                           skew_default=skew_default,
                           shuffle_mode=shuffle_mode,
                           use_kernel=use_kernel)
        out, metrics = sm(env, pvals) if has_params else sm(env)
        host = dict(SHUFFLE_STATS)
        runner = DistRunner(sm, host, pvals if has_params else None)
        metrics = _merge_host_stats({k: int(v) for k, v in metrics.items()},
                                    host)
        if not adaptive or shuffle_mode != "packed" \
                or attempt >= max_retries:
            break
        needs = {int(k.rsplit("_", 1)[1]): v for k, v in metrics.items()
                 if k.startswith("size_need_")}
        used = {int(k.rsplit("_", 1)[1]): v for k, v in host.items()
                if k.startswith("size_used_")}
        grow = {s: v for s, v in needs.items() if v > used.get(s, v)}
        if not grow:
            break
        n_sites = max(used) + 1 if used else 0
        size_plan = tuple(
            _roundup8(grow[s]) if s in grow else used.get(s, 1)
            for s in range(n_sites))
        attempt += 1
    return runner, out, metrics


def run_distributed(fn: Callable[[Dict[str, FlatBag], DistContext], dict],
                    env: Dict[str, FlatBag], mesh: Mesh,
                    **kwargs) -> Tuple[dict, Dict[str, int]]:
    """One-shot ``compile_distributed`` (see there for the knobs)."""
    _, out, metrics = compile_distributed(fn, env, mesh, **kwargs)
    return out, metrics

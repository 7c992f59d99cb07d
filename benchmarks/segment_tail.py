"""Per-row cost of the Gamma family's grouping on the chip, two ways.

The sort: ``jnp.lexsort`` followed by a gather of every column by the
permutation (``gather_lexsort``), against one ``lax.sort`` that moves
the columns as operands (``exec.ops._lexsort``).

The segment tail (segment ids, each segment's first row and sums):
the cummax-and-gather segment ids with ``segment_min`` of the row
index, a gather of every first-row column and ``segment_sum``
(``scatter_tail``), against the shifted compare, a reverse segmented
scan and one compaction sort (``exec.ops._presorted_seg_ids`` with
``invalid_last``, ``exec.ops._segment_firsts``).

    python -m benchmarks.segment_tail            # on a TPU
    python -m benchmarks.segment_tail --smoke    # small shapes, any backend

The rows are the served cells' ``sumBy_{odate}`` input: an int64 key
(``odate``), a float64 value (``total``, integer-valued), three in five
rows valid. Prints one JSON line per (rows, segments) shape, and checks
the new forms against the old ones bit for bit at each. Exits 2 without
a TPU unless ``--smoke``.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

import numpy as np

# the Lineitem class on one chip and a chip's receive buffer on four,
# each with the cells' 2,406 order dates and with 2^16 groups
CELL_SHAPES = [(2**20, 2406), (2**20, 2**16), (2**21, 2406),
               (2**21, 2**16)]
SMOKE_SHAPES = [(2**12, 37), (2**10, 2**10)]


def gather_lexsort(bag, cols):
    """The sort that ``exec.ops._lexsort`` replaced: a stable argsort
    by (invalid-last, cols), then a gather of every column."""
    import jax.numpy as jnp
    from repro.columnar.table import FlatBag
    from repro.exec import ops as X
    keys = X._key_arrays(bag, cols)
    order = jnp.lexsort(tuple(reversed(keys)) + (~bag.valid,))
    return FlatBag({n: a[order] for n, a in bag.data.items()},
                   bag.valid[order])


def scatter_tail(sbag, seg_id, gather_cols, val_cols):
    """The segment tail that ``exec.ops._segment_firsts`` replaced:
    (exists, first_valid, firsts, summed) by a scatter-min of the row
    index, gathers of the first rows and a scatter-add per value
    column. Rows past the segment count hold the last row's values."""
    import jax
    import jax.numpy as jnp
    cap = sbag.capacity
    first = jax.ops.segment_min(jnp.arange(cap), seg_id, num_segments=cap)
    first_c = jnp.clip(first, 0, cap - 1)
    exists = first < cap
    firsts = {c: sbag.col(c)[first_c] for c in gather_cols}
    summed = {v: jax.ops.segment_sum(
        jnp.where(sbag.valid, sbag.col(v), 0), seg_id, num_segments=cap)
        for v in val_cols}
    return exists, exists & sbag.valid[first_c], firsts, summed


def _bag(rng, n: int, segments: int):
    import jax.numpy as jnp
    from repro.columnar.table import FlatBag
    key = rng.integers(0, segments, n).astype(np.int64)
    total = rng.integers(0, 10**7, n).astype(np.float64)
    valid = rng.random(n) < 0.6
    return FlatBag({"odate": jnp.asarray(key), "total": jnp.asarray(total)},
                   jnp.asarray(valid))


def _median_s(fn, *args, reps: int) -> float:
    import jax
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args(argv)

    import jax
    import repro  # noqa: F401  (64-bit mode)
    from repro.columnar.table import FlatBag
    from repro.exec import ops as X

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.smoke:
        print(f"no TPU (found {dev.platform})", file=sys.stderr)
        return 2
    cols = ("odate",)

    def sort_old(data, valid):
        b = gather_lexsort(FlatBag(data, valid), cols)
        return b.data, b.valid

    def sort_new(data, valid):
        b = X._lexsort(FlatBag(data, valid), cols)
        return b.data, b.valid

    def tail_old(data, valid):
        b = FlatBag(data, valid)
        seg = X._presorted_seg_ids(b, cols, invalid_last=False)
        return scatter_tail(b, seg, cols, ("total",))

    def tail_new(data, valid):
        b = FlatBag(data, valid)
        seg = X._presorted_seg_ids(b, cols, invalid_last=True)
        return X._segment_firsts(b, seg, cols, False, ("total",))

    fns = {name: jax.jit(f) for name, f in (
        ("sort_gather", sort_old), ("sort_carry", sort_new),
        ("tail_scatter", tail_old), ("tail_scan", tail_new))}
    rng = np.random.default_rng(0)
    for n, segments in SMOKE_SHAPES if args.smoke else CELL_SHAPES:
        bag = _bag(rng, n, segments)
        data, valid = fns["sort_carry"](bag.data, bag.valid)
        want = fns["sort_gather"](bag.data, bag.valid)
        if not all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b
                   in zip(jax.tree.leaves((data, valid)),
                          jax.tree.leaves(want))):
            print(f"carried sort differs at {n}x{segments}",
                  file=sys.stderr)
            return 1
        old = jax.device_get(fns["tail_scatter"](data, valid))
        new = jax.device_get(fns["tail_scan"](data, valid))
        ex = old[0]
        same = np.array_equal(old[0], new[0]) and \
            np.array_equal(old[1], new[1]) and all(
                np.array_equal(old[i][c][ex], new[i][c][ex])
                for i in (2, 3) for c in old[i])
        if not same:
            print(f"scan tail differs at {n}x{segments}", file=sys.stderr)
            return 1
        ms = {name: _median_s(fn, *((bag.data, bag.valid)
                                    if name.startswith("sort")
                                    else (data, valid)),
                              reps=args.reps) * 1e3
              for name, fn in fns.items()}
        line = {"rows": n, "segments": segments}
        for name, v in ms.items():
            line[f"{name}_ms"] = v
            line[f"{name}_ns_per_row"] = v / n * 1e6
        print(json.dumps(line), flush=True)
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

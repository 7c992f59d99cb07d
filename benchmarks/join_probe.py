"""Per-row cost of the two ways ``exec.ops`` probes a unique build side,
on the chip: the binary search (``jnp.searchsorted``, one dependent
gather of every probe row per step, ``bit_length(r)`` steps) and the
co-sort (``exec.ops._merge_rank_left``, two sorts of ``n + r`` rows).
``exec.ops.SORT_ROW_GATHERS`` is the largest ratio of the two costs
over the shapes of the served cells.

    python -m benchmarks.join_probe            # on a TPU
    python -m benchmarks.join_probe --smoke    # small shapes, any backend

Prints one JSON line per (probe rows, build rows) shape and a last line
with the ratio; every shape also checks the co-sort against the binary
search bit for bit. Exits 2 without a TPU unless ``--smoke``.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

import numpy as np

# the served cells' probes (PERF.md §5): Lineitem's class against
# Orders and Part on one chip, a chip's receive buffers on four
CELL_SHAPES = [(2**20, 2**18), (2**20, 2**15), (2**19, 2**16),
               (2**19, 2**18)]
# where the size rule keeps the binary search, and the tests' size
OTHER_SHAPES = [(2**12, 2**10), (2**10, 2**18), (2**6, 2**18)]
SMOKE_SHAPES = [(2**12, 2**10), (2**6, 2**12)]


def _keys(rng, n: int, r: int):
    """A pk/fk probe: three quarters of the build rows valid (sorted
    unique keys, ``I64_MAX`` padding after them), probes drawn from the
    valid keys."""
    i64 = np.iinfo(np.int64)
    valid = max(1, 3 * r // 4)
    keys = np.unique(rng.integers(0, 2**40, valid, dtype=np.int64))
    build = np.full(r, i64.max, np.int64)
    build[:keys.size] = keys
    return build, rng.choice(keys, n)


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
    import jax.numpy as jnp
    import repro  # noqa: F401  (64-bit mode)
    from repro.exec import ops as X

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.smoke:
        print(f"no TPU (found {dev.platform})", file=sys.stderr)
        return 2
    search = jax.jit(lambda b, q: jnp.searchsorted(b, q))
    cosort = jax.jit(X._merge_rank_left)
    rng = np.random.default_rng(0)
    ratios = []
    for n, r in SMOKE_SHAPES if args.smoke else CELL_SHAPES + OTHER_SHAPES:
        b, q = (jnp.asarray(a) for a in _keys(rng, n, r))
        if not np.array_equal(np.asarray(search(b, q)),
                              np.asarray(cosort(b, q))):
            print(f"co-sort differs from the binary search at {n}x{r}",
                  file=sys.stderr)
            return 1
        t_search = _median_s(search, b, q, reps=args.reps)
        t_cosort = _median_s(cosort, b, q, reps=args.reps)
        gather_ns = t_search / (n * r.bit_length()) * 1e9
        sort_ns = t_cosort / (2 * (n + r)) * 1e9
        cell = (n, r) in CELL_SHAPES
        if cell or args.smoke:
            ratios.append(sort_ns / gather_ns)
        print(json.dumps({"n": n, "r": r, "cell": cell,
                          "search_ms": t_search * 1e3,
                          "cosort_ms": t_cosort * 1e3,
                          "gather_ns_per_row": gather_ns,
                          "sort_ns_per_row": sort_ns,
                          "sort_row_gathers": sort_ns / gather_ns}),
              flush=True)
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "sort_row_gathers_max": max(ratios),
                      "in_use": X.SORT_ROW_GATHERS}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

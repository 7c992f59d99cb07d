"""Fused-executor micro-benchmark: the paper's hot pipeline shape
``join -> sum_by -> nest_level`` on shared keys, executed

  * order-aware (physical props shared: one probe-side sort, cached
    build argsort, cached packed keys), vs
  * unfused (ORDER_AWARE off: every operator re-derives its sort /
    pack, the seed executor's behavior),

plus the Pallas kernel path for the fused variant. The on/off pair is
the before/after number for the sort-order-aware executor; it lands in
BENCH_<timestamp>.json under section "fused_pipeline".

The DISTRIBUTED variant (8 virtual devices, subprocess) runs the same
``join -> sum_by`` chain under shard_map and is the headline number for
the partitioning-aware shuffle: the packed mode ships each side in one
collective and elides the aggregation's re-exchange entirely (the probe
rows cross the wire exactly once — asserted through SHUFFLE_STATS),
vs the legacy per-column exchange of PR 1."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

from repro.columnar.table import FlatBag
from repro.exec import ops as X

from .common import emit, time_fn


def _make_bags(n: int, n_parts: int, seed: int = 0):
    rng = np.random.RandomState(seed)
    lineitem = FlatBag.from_rows(
        [{"pid": int(rng.randint(0, n_parts)),
          "odate": int(rng.randint(0, 365)),
          "qty": float(rng.randint(1, 50))} for _ in range(n)],
        {"pid": "int", "odate": "int", "qty": "real"})
    part = FlatBag.from_rows(
        [{"pid": i, "price": float(rng.randint(1, 100))}
         for i in range(n_parts)],
        {"pid": "int", "price": "real"})
    return lineitem, part


def _pipeline(lineitem: FlatBag, part: FlatBag, use_kernel: bool = False):
    j = X.fk_join(lineitem, part, ("pid",), ("pid",),
                  use_kernel=use_kernel)
    j = j.with_columns(total=j.col("qty") * j.col("price"))
    agg = X.sum_by(j, ("odate", "pid"), ("total",), use_kernel=use_kernel)
    return X.nest_level(agg, ("odate",), ("pid", "total"), "lbl",
                        use_kernel=use_kernel)


_DIST_CHILD = r"""
import os
# a CPU rehearsal on 8 virtual devices: never the accelerator the
# parent process may hold
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json, time
sys.path.insert(0, r"%(src)s")
import numpy as np
import jax
import repro
from repro.columnar.table import FlatBag
from repro.exec.dist import device_mesh_1d, compile_distributed

n = %(n)d
n_parts = 512
rng = np.random.RandomState(0)
lineitem = FlatBag.from_rows(
    [{"pid": int(rng.randint(0, n_parts)),
      "odate": int(rng.randint(0, 365)),
      "qty": float(rng.randint(1, 50))} for _ in range(n)],
    {"pid": "int", "odate": "int", "qty": "real"})
part = FlatBag.from_rows(
    [{"pid": i, "price": float(rng.randint(1, 100))}
     for i in range(n_parts)],
    {"pid": "int", "price": "real"})
PN = 8
env = {"L": lineitem.resize(((n + PN - 1)//PN)*PN),
       "R": part.resize(((n_parts + PN - 1)//PN)*PN)}
mesh = device_mesh_1d(PN)

def fn(env_local, ctx):
    j = ctx.join(env_local["L"], env_local["R"], ("pid",), ("pid",))
    j = j.with_columns(total=j.col("qty") * j.col("price"))
    # same key as the join: the packed shuffle elides this exchange
    s = ctx.sum_by(j, ("pid", "odate"), ("total",), local_preagg=True)
    return {"out": s}

out = []
results = {}
for mode, kw in (("legacy", dict(shuffle_mode="legacy", cap_factor=8.0)),
                 ("packed", dict(shuffle_mode="packed", cap_factor=2.0,
                                 adaptive=True))):
    t0 = time.perf_counter()
    runner, res, metrics = compile_distributed(fn, env, mesh, **kw)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    iters = 5
    for _ in range(iters):
        res, _m = runner(env)
        jax.block_until_ready(res)
    warm = (time.perf_counter() - t0) / iters
    ob = res["out"]
    agg = {}
    for r in ob.to_rows():
        agg[(r["pid"], r["odate"])] = agg.get((r["pid"], r["odate"]), 0.0) \
            + r["total"]
    results[mode] = agg
    out.append(dict(mode=mode, seconds=warm, cold_seconds=cold,
                    exchanges=metrics["exchanges"],
                    elided=metrics["exchanges_elided"],
                    collectives=metrics["shuffle_collectives"],
                    overflow=metrics.get("overflow_rows", 0)))
# correctness: both modes agree with the single-device oracle
oracle = {}
for i in range(n):
    pid = int(np.asarray(lineitem.col("pid"))[i])
    od = int(np.asarray(lineitem.col("odate"))[i])
    qty = float(np.asarray(lineitem.col("qty"))[i])
    price = float(np.asarray(part.col("price"))[pid])
    oracle[(pid, od)] = oracle.get((pid, od), 0.0) + qty * price
for mode, agg in results.items():
    assert set(agg) == set(oracle), mode
    for k in oracle:
        assert abs(agg[k] - oracle[k]) < 1e-6 * max(1.0, abs(oracle[k])), \
            (mode, k)
# the packed join->sum_by pipeline exchanges the probe rows exactly once:
# one exchange per join side, the aggregation's re-shuffle elided
pk = {r["mode"]: r for r in out}
assert pk["packed"]["exchanges"] == 2 and pk["packed"]["elided"] == 1, pk
assert pk["legacy"]["exchanges"] == 3 and pk["legacy"]["elided"] == 0, pk
print("JSON" + json.dumps(out))
"""


def run_dist(n: int = 4000):
    """Distributed join->sum_by on the same key: packed vs legacy."""
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                       "src")
    script = _DIST_CHILD % {"src": os.path.abspath(src), "n": n}
    res = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=1800)
    if res.returncode != 0:
        print(res.stdout[-2000:])
        print(res.stderr[-2000:])
        raise RuntimeError("fused_pipeline dist child failed")
    payload = [l for l in res.stdout.splitlines() if l.startswith("JSON")][0]
    rows = json.loads(payload[4:])
    by_mode = {}
    for r in rows:
        by_mode[r["mode"]] = r
        emit(f"dist_join_sum_by_{r['mode']}", r["seconds"] * 1e6,
             f"n={n};exchanges={r['exchanges']};elided={r['elided']};"
             f"collectives={r['collectives']};overflow={r['overflow']}",
             compile_ms=r["cold_seconds"] * 1e3,
             warm_ms=r["seconds"] * 1e3)
    speed = by_mode["legacy"]["seconds"] / max(by_mode["packed"]["seconds"],
                                               1e-9)
    emit("dist_join_sum_by_packed_speedup", 0.0,
         f"x{speed:.2f};collectives {by_mode['legacy']['collectives']}->"
         f"{by_mode['packed']['collectives']}")


def run(n: int = 20000, n_parts: int = 512, pallas_n: int = 1000,
        dist_n: int = 4000):
    # pallas variant runs tiny: off the TPU, interpret mode executes the
    # grid as a Python loop, so it only demonstrates wiring. On a TPU its
    # fk_join reaches gather_rows, which Mosaic refuses, so the variant
    # raises there (ROADMAP Speed 2)
    for label, order_aware, use_kernel, nn, iters in (
            ("fused", True, False, n, 3),
            ("unfused", False, False, n, 3),
            ("fused_pallas", True, True, pallas_n, 1)):
        # fresh bags per variant: caches must not leak across variants
        lineitem, part = _make_bags(nn, n_parts)
        with X.order_awareness(order_aware):
            us = time_fn(lambda: _pipeline(lineitem, part,
                                           use_kernel=use_kernel),
                         iters=iters)
            X.reset_sort_stats()
            _pipeline(lineitem, part, use_kernel=use_kernel)
            sorts = X.SORT_STATS.get("lexsort", 0) \
                + X.SORT_STATS.get("build_argsort", 0)
        emit(f"pipeline_{label}", us, f"n={nn} sorts_per_call={sorts}")

    # correctness tie: fused == unfused on the same data
    lineitem, part = _make_bags(2000, 64, seed=1)
    fused = _pipeline(lineitem, part)
    with X.order_awareness(False):
        li2, p2 = _make_bags(2000, 64, seed=1)
        unfused = _pipeline(li2, p2)

    def _freeze(out):
        parents, children = out
        lbl = {r["lbl"]: r["odate"] for r in parents.to_rows()}
        return sorted((lbl[r["lbl"]], r["pid"], r["total"])
                      for r in children.to_rows())

    assert _freeze(fused) == _freeze(unfused), "fused executor mismatch"

    # distributed variant (8 virtual devices, own subprocess)
    if dist_n:
        run_dist(n=dist_n)


if __name__ == "__main__":
    run()

#!/usr/bin/env python3
"""Bring-up smoke of the nested-query engine on a TPU.

    python chip_smoke.py                   # one chip, TPC-H SF1 orders
    python chip_smoke.py --scale 375000    # a cut of the data scale
    python chip_smoke.py --chips 4         # the distributed path only

One chip (the default) drives the served main path once, in this one
process. It generates TPC-H-shaped data (``gen_tpch``, seeded),
persists it through the storage writer, reopens it, and serves two
query families from the stored dataset:

  (a) flat-to-nested at depth 2: Customer -> orders -> lineitems,
      through ``QueryService.execute_stored``;
  (b) the Lineitem x Part x Orders revenue per order date with a
      ``Part.price >= threshold`` predicate, at several thresholds,
      through ``QueryService.execute_stored`` and
      ``ServingRuntime.submit``.

The answers must equal a plain NumPy reference at full scale (per-level
row counts and every row of every shredded part, resolved by key) and
the interpreter oracle on a ``scale=200`` copy; warm calls must not
retrace; no runtime response may be degraded.

``--chips 4`` runs only what exists across chips: query (b) over Zipf
2.0 part keys through ``QueryService(mesh=...)`` on a 4-chip mesh, as
one-round HyperCube (``MultiJoinP``) and as the binary cascade with
``SkewJoinP``, both over the packed exchange. Both must agree bit for
bit with the same query served on one of those chips.

Every phase raises on failure and the script exits non-zero. Without a
TPU it exits non-zero before any work. The last line of a passing run
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.

The persistent compilation cache is ``JAX_COMPILATION_CACHE_DIR`` when
set, else ``<checkout>/.jax_cache``. The stored dataset goes to
``<checkout>/.smoke_store`` and is removed at the end.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SF1_ORDERS = 1_500_000       # TPC-H scale factor 1
CHUNK_ROWS = 1 << 16         # rows per stored chunk
THRESHOLDS = (25.0, 50.0, 75.0)
ORACLE_SCALE = 200
WARM_CALLS = 5


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


# ---------------------------------------------------------------------------
# the two query families
# ---------------------------------------------------------------------------

def query_a():
    """(a) flat-to-nested, depth 2 (benchmarks.common)."""
    from benchmarks.common import flat_to_nested_query
    from repro.core import nrc as N
    return N.Program([N.Assignment("Q", flat_to_nested_query(2))])


def query_b(threshold: float):
    """(b) revenue per order date over Lineitem x Part x Orders, parts
    priced at least ``threshold`` (the constant lifts to a parameter:
    every threshold is one query family)."""
    from repro.core import nrc as N
    from repro.data.generators import TPCH_TYPES
    L = N.Var("Lineitem", TPCH_TYPES["Lineitem"])
    P = N.Var("Part", TPCH_TYPES["Part"])
    O = N.Var("Orders", TPCH_TYPES["Orders"])

    def per_item(l):
        return N.for_in("p", P, lambda p: N.IfThen(
            N.BoolOp("&&", l.pid.eq(p.pid),
                     p.price.ge(N.Const(float(threshold), N.REAL))),
            N.for_in("o", O, lambda o: N.IfThen(
                l.oid.eq(o.oid),
                N.Singleton(N.record(odate=o.odate,
                                     total=l.qty * p.price))))))

    q = N.SumBy(N.for_in("l", L, per_item), keys=("odate",),
                values=("total",))
    return N.Program([N.Assignment("Q", q)])


# ---------------------------------------------------------------------------
# the NumPy reference (independent of repro)
# ---------------------------------------------------------------------------

def columns(db: dict) -> dict:
    """Column arrays of the generated rows."""
    def col(rows, name, dtype):
        return np.fromiter((r[name] for r in rows), dtype, len(rows))
    return {
        "c_cid": col(db["Customer"], "cid", np.int64),
        "c_cname": col(db["Customer"], "cname", np.int64),
        "o_oid": col(db["Orders"], "oid", np.int64),
        "o_cid": col(db["Orders"], "cid", np.int64),
        "o_odate": col(db["Orders"], "odate", np.int64),
        "l_oid": col(db["Lineitem"], "oid", np.int64),
        "l_pid": col(db["Lineitem"], "pid", np.int64),
        "l_qty": col(db["Lineitem"], "qty", np.float64),
        "p_pid": col(db["Part"], "pid", np.int64),
        "p_price": col(db["Part"], "price", np.float64),
    }


def lookup(keys: np.ndarray, values: list, probe: np.ndarray) -> list:
    """values[i] where keys[i] == probe, for unique ``keys``; every
    probe must match."""
    order = np.argsort(keys, kind="stable")
    sk = keys[order]
    if sk.size > 1 and not np.all(sk[1:] != sk[:-1]):
        raise AssertionError("lookup keys are not unique")
    pos = np.clip(np.searchsorted(sk, probe), 0, max(sk.size - 1, 0))
    if probe.size and not np.array_equal(sk[pos], probe):
        raise AssertionError("a probe key has no match")
    return [v[order][pos] for v in values]


def sorted_rows(*cols) -> tuple:
    order = np.lexsort(tuple(reversed(cols)))
    return tuple(np.asarray(c)[order] for c in cols)


def ref_a(c: dict) -> dict:
    """Rows of each level of (a), by key: top (cname), orders
    (cname, odate), line items (cname, odate, pid, qty)."""
    (o_cname,) = lookup(c["c_cid"], [c["c_cname"]], c["o_cid"])
    l_cname, l_odate = lookup(c["o_oid"], [o_cname, c["o_odate"]],
                              c["l_oid"])
    return {"top": sorted_rows(c["c_cname"]),
            "orders": sorted_rows(o_cname, c["o_odate"]),
            "items": sorted_rows(l_cname, l_odate, c["l_pid"],
                                 c["l_qty"])}


def ref_b(c: dict, threshold: float) -> tuple:
    """(odate, total) of (b), sorted by odate."""
    (price,) = lookup(c["p_pid"], [c["p_price"]], c["l_pid"])
    (odate,) = lookup(c["o_oid"], [c["o_odate"]], c["l_oid"])
    keep = price >= threshold
    dates, inv = np.unique(odate[keep], return_inverse=True)
    totals = np.bincount(inv, weights=(c["l_qty"] * price)[keep],
                         minlength=dates.size)
    return dates, totals


def host_rows(bag) -> dict:
    valid = np.asarray(bag.valid)
    return {k: np.asarray(a)[valid] for k, a in bag.data.items()}


def got_a(out: dict, man) -> dict:
    """The same per-level rows, read off the shredded output parts by
    resolving each label to its parent row."""
    top = host_rows(out[man.top])
    orders = host_rows(out[man.dicts[("corders",)]])
    items = host_rows(out[man.dicts[("corders", "oparts")]])
    (o_cname,) = lookup(top["corders"], [top["cname"]], orders["label"])
    i_cname, i_odate = lookup(orders["oparts"], [o_cname, orders["odate"]],
                              items["label"])
    return {"top": sorted_rows(top["cname"]),
            "orders": sorted_rows(o_cname, orders["odate"]),
            "items": sorted_rows(i_cname, i_odate, items["pid"],
                                 items["qty"])}


def got_b(out: dict, man) -> tuple:
    rows = host_rows(out[man.top])
    return sorted_rows(rows["odate"], rows["total"])


def same(what: str, got, want) -> None:
    """Exact equality of two tuples of row columns."""
    if len(got) != len(want) or not all(
            g.shape == w.shape and np.array_equal(g, w)
            for g, w in zip(got, want)):
        raise AssertionError(
            f"{what}: answer differs from the reference "
            f"({[g.shape for g in got]} vs {[w.shape for w in want]} "
            f"rows)")


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def timed(fn):
    import jax
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, (time.perf_counter() - t0) * 1e3


def traces() -> int:
    from repro.core import codegen as CG
    return int(CG.TRACE_STATS.get("traces", 0))


def cache_events() -> tuple:
    """(hits, misses) of the persistent compile cache so far."""
    from repro.obs.metrics import REGISTRY
    return (int(REGISTRY.get("compile_cache.hits")),
            int(REGISTRY.get("compile_cache.misses")))


def cache_since(before: tuple) -> str:
    hits, misses = cache_events()
    return (f"compile cache {hits - before[0]} hits, "
            f"{misses - before[1]} misses")


def manifest(program, types):
    from repro.core import materialization as M
    return M.shred_program(program, types,
                           domain_elimination=True).manifests["Q"]


def write_dataset(root: str, name: str, db: dict, types: dict,
                  chunk_rows: int):
    from repro.storage import StorageCatalog
    cat = StorageCatalog(root)
    cat.writer(name, types, chunk_rows=chunk_rows).append(
        {k: db[k] for k in types})
    return cat.open(name)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def run_one_chip(args, store: str, chunk_rows: int = CHUNK_ROWS) -> None:
    import jax
    from repro.core import interpreter as I
    from repro.data.generators import TPCH_TYPES, gen_tpch
    from repro.serve import QueryService
    from repro.serve.runtime import QueryRequest, ServingRuntime
    from benchmarks.common import CATALOG

    types = {k: TPCH_TYPES[k]
             for k in ("Lineitem", "Orders", "Customer", "Part")}
    log(f"scale: {args.scale} orders, seed {args.seed}, skew 0.0")
    if args.scale < SF1_ORDERS:
        log(f"cut: {args.scale} of TPC-H SF1's {SF1_ORDERS} orders "
            f"(x{args.scale / SF1_ORDERS:g})")
    t0 = time.perf_counter()
    db = gen_tpch(scale=args.scale, skew=0.0, seed=args.seed)
    t1 = time.perf_counter()
    ds = write_dataset(store, "tpch", db, types, chunk_rows)
    t2 = time.perf_counter()
    ref = columns(db)
    del db
    log(f"host: generate {t1 - t0:.1f} s, shred+write {t2 - t1:.1f} s")
    log("rows per input part: " + json.dumps(
        {n: p.rows for n, p in sorted(ds.parts.items())}))
    log(f"bytes on disk: {ds.bytes_on_disk()}")

    svc = QueryService(types, catalog=CATALOG)
    rt = ServingRuntime(svc)

    # (a) flat-to-nested, depth 2
    prog_a = query_a()
    man_a = manifest(prog_a, types)
    cache0 = cache_events()
    out, cold = timed(lambda: svc.execute_stored(prog_a, ds))
    want = ref_a(ref)
    got = got_a(out, man_a)
    for level in ("top", "orders", "items"):
        same(f"(a) {level}", got[level], want[level])
    log("(a) rows per level: " + json.dumps(
        {k: int(v[0].size) for k, v in got.items()}) + " == reference")
    t_before = traces()
    warm = []
    for _ in range(WARM_CALLS):
        out, ms = timed(lambda: svc.execute_stored(prog_a, ds))
        warm.append(ms)
    if traces() != t_before:
        raise AssertionError(f"(a) retraced {traces() - t_before} times "
                             f"on warm calls")
    same("(a) warm", got_a(out, man_a)["items"], want["items"])
    log(f"(a) cold_ms {cold:.3f} warm_ms median "
        f"{statistics.median(warm):.3f} over {len(warm)} calls "
        f"{[round(w, 3) for w in warm]}, warm retraces 0; "
        f"{cache_since(cache0)}")

    # (b) the join chain, several thresholds
    man_b = manifest(query_b(THRESHOLDS[0]), types)
    wants = {t: ref_b(ref, t) for t in THRESHOLDS}
    cache0 = cache_events()
    out, cold = timed(lambda: svc.execute_stored(query_b(THRESHOLDS[0]),
                                                 ds))
    same(f"(b) t={THRESHOLDS[0]}", got_b(out, man_b), wants[THRESHOLDS[0]])
    t_before = traces()
    warm = []
    for _ in range(2):
        for t in THRESHOLDS:
            out, ms = timed(lambda: svc.execute_stored(query_b(t), ds))
            same(f"(b) t={t}", got_b(out, man_b), wants[t])
            warm.append(ms)
    served = []
    for t in THRESHOLDS:
        t_sub = time.perf_counter()
        r = rt.submit(QueryRequest(query_b(t), ds))
        if not r.ok or r.degraded != ():
            raise AssertionError(
                f"(b) runtime response ok={r.ok} degraded={r.degraded} "
                f"error={r.error!r}")
        jax.block_until_ready(r.outputs)
        served.append((time.perf_counter() - t_sub) * 1e3)
        same(f"(b) runtime t={t}", got_b(r.outputs, man_b), wants[t])
    if traces() != t_before:
        raise AssertionError(f"(b) retraced {traces() - t_before} times "
                             f"on warm calls")
    log(f"(b) cold_ms {cold:.3f} warm_ms median "
        f"{statistics.median(warm):.3f} over {len(warm)} calls "
        f"{[round(w, 3) for w in warm]} at thresholds "
        f"{list(THRESHOLDS)}; runtime submit ms median "
        f"{statistics.median(served):.3f} over {len(served)} (ok, not "
        f"degraded); warm retraces 0; {cache_since(cache0)}")
    log("(b) groups per threshold: " + json.dumps(
        {str(t): int(wants[t][0].size) for t in THRESHOLDS})
        + " == reference")
    del ref, out

    # the interpreter oracle, on a small copy
    cache0 = cache_events()
    small = gen_tpch(scale=ORACLE_SCALE, skew=0.0, seed=args.seed)
    ds_s = write_dataset(store, "tpch_small", small, types, 64)
    inputs = {k: small[k] for k in types}
    cols_s = columns(small)
    svc_s = QueryService(types, catalog=CATALOG)
    for name, prog in [("(a)", prog_a)] + [
            (f"(b) t={t}", query_b(t)) for t in THRESHOLDS]:
        out = svc_s.execute_stored(prog, ds_s)
        rows = svc_s.unshred_stored(prog, ds_s, out, "Q")
        oracle = I.eval_expr(prog.assignments[0].expr, inputs)
        if not I.bags_equal(rows, oracle):
            raise AssertionError(f"{name} differs from the interpreter "
                                 f"oracle at scale {ORACLE_SCALE}")
    same("(a) small reference", got_a(svc_s.execute_stored(prog_a, ds_s),
                                      man_a)["items"],
         ref_a(cols_s)["items"])
    log(f"oracle: (a) and (b) at {len(THRESHOLDS)} thresholds equal the "
        f"interpreter at scale {ORACLE_SCALE}; {cache_since(cache0)}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def run_four_chips(args, store: str, devices,
                   chunk_rows: int = CHUNK_ROWS) -> None:
    import jax
    from repro.core import skew as SKM
    from repro.core.plans import MultiJoinP, SkewJoinP, _walk_plan
    from repro.data.generators import TPCH_TYPES, gen_tpch
    from repro.exec.dist import device_mesh_1d, receive_imbalance
    from repro.serve import QueryService
    from repro.storage import table_stats
    from benchmarks.common import CATALOG

    n = 4
    mesh = device_mesh_1d(n)
    types = {k: TPCH_TYPES[k] for k in ("Lineitem", "Part", "Orders")}
    log(f"scale: {args.scale} orders, seed {args.seed}, skew 2.0, "
        f"{n}-chip mesh; cut: x{args.scale / SF1_ORDERS:g} of TPC-H SF1")
    t0 = time.perf_counter()
    db = gen_tpch(scale=args.scale, skew=2.0, seed=args.seed)
    ds = write_dataset(store, "tpch_zipf2", db, types, chunk_rows)
    ref = columns(db)
    del db
    log(f"host: generate+write {time.perf_counter() - t0:.1f} s")
    log("rows per input part: " + json.dumps(
        {p: ds.parts[p].rows for p in sorted(ds.parts)}))
    stats = table_stats(ds)
    heavy = SKM.decide_heavy_keys(stats["Lineitem__F"], "pid", n)
    hints = {"Lineitem__F": {"pid": heavy}}
    env = ds.load_env()
    env = {k: b.resize(-(-b.capacity // n) * n) for k, b in env.items()}
    man = manifest(query_b(THRESHOLDS[0]), types)

    # the same program on one of those chips: the local served path
    local = QueryService(types, catalog=CATALOG)
    with jax.default_device(devices[0]):
        ones = {t: got_b(local.execute(query_b(t), env), man)
                for t in THRESHOLDS}
    for t in THRESHOLDS:
        same(f"one chip t={t}", ones[t], ref_b(ref, t))
    log(f"one chip: {len(THRESHOLDS)} thresholds equal the NumPy "
        f"reference")

    for mode, node in (("auto", MultiJoinP), ("off", SkewJoinP)):
        svc = QueryService(types, catalog=CATALOG, mesh=mesh,
                           hypercube_mode=mode,
                           dist_kwargs=dict(cap_factor=2.0,
                                            adaptive=True))
        out, cold = timed(lambda: svc.execute(query_b(THRESHOLDS[0]), env,
                                              skew_hints=hints))
        lowered = sum(1 for e in svc._cache.values()
                      for _, p in e.cp.plans for s in _walk_plan(p)
                      if isinstance(s, node))
        if lowered < 1:
            raise AssertionError(f"hypercube_mode={mode}: no "
                                 f"{node.__name__} lowered")
        t_before = traces()
        warm = []
        for t in THRESHOLDS:
            out, ms = timed(lambda: svc.execute(query_b(t), env,
                                                skew_hints=hints))
            warm.append(ms)
            got = got_b(out, man)
            same(f"{n} chips {node.__name__} t={t}", got, ones[t])
        if traces() != t_before:
            raise AssertionError(f"{node.__name__}: warm calls retraced")
        m = svc.last_metrics
        worst = receive_imbalance(m, n, floor=64)
        log(f"{node.__name__} x{lowered} on {n} chips: bit-for-bit equal "
            f"to one chip at {len(THRESHOLDS)} thresholds; collectives "
            f"{m['shuffle_collectives']}, exchanges {m['exchanges']} "
            f"(elided {m['exchanges_elided']}), shipped rows "
            f"{m['shuffle_rows']}, overflow {m['overflow_rows']}, worst "
            f"receive imbalance {worst:.3f}; cold_ms {cold:.3f}, warm_ms "
            f"median {statistics.median(warm):.3f}")


# ---------------------------------------------------------------------------

def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--scale", type=int, default=None,
                    help="orders to generate (default 1,500,000 = TPC-H "
                         "SF1 on one chip, 375,000 on four)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.scale is None:
        args.scale = SF1_ORDERS if args.chips == 1 else SF1_ORDERS // 4

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"no engine next to this script ({src}/repro is missing)")
    sys.path[:0] = [src, HERE]

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        fail(f"JAX found no TPU: {len(devices)} {platform} device(s) "
             f"({devices[0].device_kind})")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} TPU chips; JAX "
             f"found {len(devices)}")

    import repro  # noqa: F401  (64-bit mode)
    from repro import compile_cache
    cache_dir = compile_cache.enable(HERE)
    log(f"device: {platform} {devices[0].device_kind} x{len(devices)}; "
        f"compile cache {cache_dir}")

    store = os.path.join(HERE, ".smoke_store")
    shutil.rmtree(store, ignore_errors=True)
    try:
        if args.chips == 1:
            run_one_chip(args, store)
        else:
            run_four_chips(args, store, devices)
    finally:
        shutil.rmtree(store, ignore_errors=True)

    stats = devices[0].memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    log(f"{cache_since((0, 0))} in all")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()

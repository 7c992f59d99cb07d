"""Cost-based whole-program optimizer benchmark (DESIGN.md
"Cost-based planning"): a Zipf-2.0 3-relation equi-join chain
(Lineitem x Orders x Part) on 8 virtual devices where the PROGRAM
order is the worst order — the foreign-key Orders passthrough runs
before the highly selective Part join (Part covers only the cold tail
of the pid domain, so the Zipf hot key dies at that join). Compared:

  * **auto** — ``compile_program(..., cost_mode="auto")``: the
    estimator (``repro.core.cost``) prices each join's output from
    distinct counts + heavy-key sketches and reorders the chain so the
    selective join runs first;
  * **off**  — the program-written order, everything else identical
    (``hypercube_mode="off"`` for both, so the comparison is cascade
    vs cascade and the only difference is the join order).

The ``--smoke`` gate asserts the deterministic facts: bit-for-bit
parity for both modes vs the interpreter oracle; the costed plan ships
STRICTLY fewer rows over the wire; a warm ``QueryService`` call (the
cost estimates live in the plan-cache entry) re-serves with ZERO
retraces; and one EXPLAIN ANALYZE feedback round
(``StatsFeedback.record_explain`` -> ``observed_rows=``) lands the
max per-operator Q-error at <= 4.

Runs in a subprocess so the virtual-device XLA flag never leaks into
the parent (single-device) process.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from .common import emit

_CHILD = r"""
import os
# a CPU rehearsal on 8 virtual devices: never the accelerator the
# parent process may hold
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json, tempfile, time
sys.path.insert(0, r"%(src)s")
sys.path.insert(0, r"%(bench)s")
import numpy as np
import jax
import repro
from repro.core import codegen as CG
from repro.core import interpreter as I
from repro.core import materialization as M
from repro.core import nrc as N
from repro.data.generators import TPCH_TYPES, zipf_choice
from repro.exec.dist import device_mesh_1d
from repro.obs import explain_analyze, StatsFeedback
from repro.storage import StorageCatalog, table_stats
from benchmarks.common import CATALOG

SMOKE = %(smoke)d
PN = 8
WARM_ITERS = 3 if SMOKE else 8
mesh = device_mesh_1d(PN)

# Zipf-2.0 Lineitem over a WIDE pid domain; Part covers only the cold
# tail (pids 2..41), so the Part join is highly selective (the hot key
# pid=1 never matches) while the Orders join is a pure foreign-key
# passthrough. The program joins Orders FIRST — the worst order.
rng = np.random.RandomState(7)
N_L = 4000 if SMOKE else 16000
N_PID = 200
N_PART = 40
N_ORD = 400 if SMOKE else 1600
lineitem = [{"oid": int(rng.randint(1, N_ORD + 1)),
             "pid": int(zipf_choice(rng, N_PID, 2.0, 1)[0]),
             "qty": float(rng.randint(1, 50))} for _ in range(N_L)]
parts = [{"pid": i, "pname": 10000 + i,
          "price": float(rng.randint(1, 100))}
         for i in range(2, N_PART + 2)]
orders = [{"oid": i, "cid": 1, "odate": 20200000 + (i * 7) %% 365}
          for i in range(1, N_ORD + 1)]
types = {k: TPCH_TYPES[k] for k in ("Lineitem", "Part", "Orders")}
inputs = {"Lineitem": lineitem, "Part": parts, "Orders": orders}

L = N.Var("Lineitem", types["Lineitem"])
P = N.Var("Part", types["Part"])
O = N.Var("Orders", types["Orders"])
inner = N.for_in("l", L, lambda l:
    N.for_in("o", O, lambda o:
        N.IfThen(l.oid.eq(o.oid),
            N.for_in("p", P, lambda p:
                N.IfThen(l.pid.eq(p.pid),
                    N.Singleton(N.record(odate=o.odate,
                                         total=l.qty * p.price)))))))
q = N.SumBy(inner, keys=("odate",), values=("total",))
prog = N.Program([N.Assignment("Q", q)])
sp = M.shred_program(prog, types, domain_elimination=True)
man = sp.manifests["Q"]
direct = I.eval_expr(q, inputs)

# persist through the streaming writer so distinct counts and the
# heavy-key sketch reach the estimator exactly as in production
td = tempfile.mkdtemp()
cat = StorageCatalog(td)
cat.writer("costbench", types, chunk_rows=512).append(inputs)
ds = cat.open("costbench")
stats = table_stats(ds)
env = ds.load_env()
env = {k: b.resize(((b.capacity + PN - 1) // PN) * PN)
       for k, b in env.items()}


def rows_of(res):
    parts_ = {(): res[man.top],
              **{p_: res[n] for p_, n in man.dicts.items()}}
    return CG.parts_to_rows(parts_, q.ty)


out = []
for mode in ("off", "auto"):
    cp = CG.compile_program(sp, CATALOG, skew_stats=stats,
                            skew_partitions=PN, hypercube_mode="off",
                            cost_mode=mode)
    t0 = time.perf_counter()
    runner, res, m = CG.compile_program_distributed(
        cp, env, mesh, cap_factor=2.0, adaptive=True)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(WARM_ITERS):
        res, m = runner(env)
        jax.block_until_ready(res)
    warm = (time.perf_counter() - t0) / WARM_ITERS
    out.append(dict(kind="mode", mode=mode, seconds=warm,
                    cold_seconds=cold,
                    ok=I.bags_equal(direct, rows_of(res)),
                    shuffle_rows=int(m["shuffle_rows"]),
                    collectives=int(m["shuffle_collectives"]),
                    estimated=sum(1 for v in cp.estimates.values()
                                  if v is not None)))

# warm serving: the estimates ride in the plan-cache entry, so the
# second call must hit the cache and re-serve with ZERO retraces
from repro.serve import QueryService
svc = QueryService(types, catalog=CATALOG, skew_partitions=PN,
                   cost_mode="auto", mesh=mesh,
                   dist_kwargs=dict(cap_factor=2.0, adaptive=True))
res1 = svc.execute(prog, env)
t0 = CG.TRACE_STATS.get("traces", 0)
res2 = svc.execute(prog, env)
ests = [len(e.estimates) for e in svc._cache.values()]
out.append(dict(kind="service",
                ok=I.bags_equal(direct, rows_of(res2)),
                retraces=CG.TRACE_STATS.get("traces", 0) - t0,
                hits=svc.stats["hits"], misses=svc.stats["misses"],
                cached_estimates=max(ests) if ests else 0))

# EXPLAIN ANALYZE feedback: estimate -> measure -> re-estimate from
# the observed per-operator rows; one round lands max Q-error <= 4
env0 = ds.load_env()
r1 = explain_analyze(prog, env0, types, catalog=CATALOG,
                     skew_stats=stats, skew_partitions=PN,
                     hypercube_mode="off", cost_mode="auto")
fb = StatsFeedback()
harvested = fb.record_explain(r1)
r2 = explain_analyze(prog, env0, types, catalog=CATALOG,
                     skew_stats=stats, skew_partitions=PN,
                     hypercube_mode="off", cost_mode="auto",
                     observed_rows=fb.node_rows)
s1, s2 = r1.qerror_summary(), r2.qerror_summary()
out.append(dict(kind="qerror", harvested=harvested,
                round1_p50=s1["qerr_p50"], round1_max=s1["qerr_max"],
                round2_p50=s2["qerr_p50"], round2_max=s2["qerr_max"]))
print("JSON" + json.dumps(out))
"""


def run(smoke: bool = False):
    """The cost-auto-vs-off scenario (and `make cost-smoke`)."""
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                       "src")
    bench = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    script = _CHILD % {"src": os.path.abspath(src),
                       "bench": os.path.abspath(bench),
                       "smoke": int(smoke)}
    res = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=3000)
    if res.returncode != 0:
        print(res.stdout[-2000:])
        print(res.stderr[-2000:])
        raise RuntimeError("cost benchmark child failed")
    payload = [l for l in res.stdout.splitlines()
               if l.startswith("JSON")][0]
    rows = json.loads(payload[4:])
    by_mode = {r["mode"]: r for r in rows if r["kind"] == "mode"}
    for mode, r in by_mode.items():
        assert r["ok"], f"cost_mode={mode} produced wrong results"
        emit(f"cost3_zipf2.0_{mode}", r["seconds"] * 1e6,
             f"shuffle_rows={r['shuffle_rows']};"
             f"collectives={r['collectives']};"
             f"est_nodes={r['estimated']};"
             f"coldS={r['cold_seconds']:.2f}")
    auto, off = by_mode["auto"], by_mode["off"]
    # gate 1: annotation only under "auto"
    assert auto["estimated"] >= 1, auto
    assert off["estimated"] == 0, off
    # gate 2: the costed join order ships STRICTLY fewer rows than the
    # program-written order
    assert auto["shuffle_rows"] < off["shuffle_rows"], (auto, off)
    ratio = off["shuffle_rows"] / max(auto["shuffle_rows"], 1)
    emit("cost3_reorder_shipped_rows", 0.0,
         f"{off['shuffle_rows']}->{auto['shuffle_rows']};"
         f"x{ratio:.2f} fewer")
    for r in rows:
        if r["kind"] == "service":
            # gate 3: warm rebind stays zero-retrace with estimates in
            # the plan-cache entry
            assert r["ok"] and r["retraces"] == 0, r
            assert r["hits"] >= 1 and r["cached_estimates"] >= 1, r
            emit("cost3_warm_service", 0.0,
                 f"retraces={r['retraces']};hits={r['hits']};"
                 f"misses={r['misses']};"
                 f"cached_estimates={r['cached_estimates']}")
        elif r["kind"] == "qerror":
            # gate 4: one feedback round pins the estimates
            assert r["harvested"] >= 1, r
            assert r["round2_max"] is not None, r
            assert r["round2_max"] <= 4.0, r
            emit("cost3_qerror_feedback", 0.0,
                 f"p50 {r['round1_p50']:.2f}->{r['round2_p50']:.2f};"
                 f"max {r['round1_max']:.2f}->{r['round2_max']:.2f};"
                 f"ops={r['harvested']}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI gate: parity both modes + strictly "
                         "fewer shipped rows under cost auto + zero "
                         "warm retraces + max Q-error <= 4 after one "
                         "feedback round")
    args = ap.parse_args()
    run(smoke=args.smoke)
    if args.smoke:
        print("COST-SMOKE OK")

"""HyperCube multiway-join benchmark (paper §5.2 skew discussion +
Beame/Koutris/Suciu one-round joins): a 3-relation equi-join chain
(Lineitem x Part x Orders, Zipf-skewed part keys) on 8 virtual
devices, comparing

  * **hypercube** — ``compile_program(..., hypercube_mode="auto")``:
    the join chain collapses into one MultiJoinP whose relations ship
    in a SINGLE replicating collective, then probe locally; heavy part
    keys (from the storage sketch) spread along their dimension;
  * **cascade**  — ``hypercube_mode="off"``: the binary join cascade,
    one exchange round per join (the pre-PR-8 plan).

Reported per plan: warm runtime, collective count, receive-load
imbalance over the exchange sites, and for the hypercube plan the
replication factor and bytes replicated (the price of the one-round
schedule). The ``--smoke`` gate asserts the deterministic facts:
parity for both plans vs the interpreter oracle; at least one
MultiJoinP lowers; the hypercube plan uses STRICTLY fewer collectives
than the cascade; receive-load imbalance stays <= 2.0 despite Zipf
2.0 keys; and a warm rebind with a NEW heavy-key set re-runs with
ZERO retraces.

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
import jax
import repro
from repro.core import codegen as CG
from repro.core import interpreter as I
from repro.core import materialization as M
from repro.core import nrc as N
from repro.core import skew as SKM
from repro.core.plans import MultiJoinP, collect_plan_params, _walk_plan
from repro.data.generators import TPCH_TYPES, gen_tpch
from repro.exec.dist import device_mesh_1d, receive_imbalance
from repro.storage import StorageCatalog, table_stats
from benchmarks.common import CATALOG

SMOKE = %(smoke)d
PN = 8
WARM_ITERS = 3 if SMOKE else 8
mesh = device_mesh_1d(PN)

db = gen_tpch(scale=48 if SMOKE else 192, skew=2.0, seed=0)
types = {k: TPCH_TYPES[k] for k in ("Lineitem", "Part", "Orders")}
inputs = {k: db[k] for k in types}

# the 3-relation chain: Lineitem joins Part on the Zipf-2.0 pid and
# Orders on oid, then aggregates revenue per order date
L = N.Var("Lineitem", types["Lineitem"])
P = N.Var("Part", types["Part"])
O = N.Var("Orders", types["Orders"])
inner = N.for_in("l", L, lambda l:
    N.for_in("p", P, lambda p:
        N.IfThen(l.pid.eq(p.pid),
            N.for_in("o", O, lambda o:
                N.IfThen(l.oid.eq(o.oid),
                    N.Singleton(N.record(odate=o.odate,
                                         total=l.qty * p.price)))))))
q = N.SumBy(inner, keys=("odate",), values=("total",))
prog = N.Program([N.Assignment("Q", q)])
sp = M.shred_program(prog, types, domain_elimination=True)
man = sp.manifests["Q"]
direct = I.eval_expr(q, inputs)

# persist through the streaming writer so the heavy-key sketch feeds
# the share planner exactly as in production
td = tempfile.mkdtemp()
cat = StorageCatalog(td)
cat.writer("hcbench", types, chunk_rows=512).append(inputs)
ds = cat.open("hcbench")
stats = table_stats(ds)
env = ds.load_env()
env = {k: b.resize(((b.capacity + PN - 1) // PN) * PN)
       for k, b in env.items()}


def rows_of(res):
    parts = {(): res[man.top],
             **{p_: res[n] for p_, n in man.dicts.items()}}
    return CG.parts_to_rows(parts, q.ty)


out = []
runners = {}
for mode in ("hypercube", "cascade"):
    cp = CG.compile_program(
        sp, CATALOG, skew_stats=stats, skew_partitions=PN,
        hypercube_mode="auto" if mode == "hypercube" else "off")
    mj = sum(1 for _, p in cp.plans for s in _walk_plan(p)
             if isinstance(s, MultiJoinP))
    CG.reset_trace_stats()
    t0 = time.perf_counter()
    runner, res, metrics = CG.compile_program_distributed(
        cp, env, mesh, cap_factor=2.0, adaptive=True)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(WARM_ITERS):
        res, m = runner(env)
        jax.block_until_ready(res)
    warm = (time.perf_counter() - t0) / WARM_ITERS
    runners[mode] = (cp, runner)
    out.append(dict(
        kind="mode", mode=mode, seconds=warm, cold_seconds=cold,
        ok=I.bags_equal(direct, rows_of(res)), multijoin=mj,
        imbalance=receive_imbalance(m, PN, floor=64),
        collectives=int(m["shuffle_collectives"]),
        hc_exchanges=int(m.get("hypercube_exchanges", 0)),
        shuffle_rows=int(m["shuffle_rows"]),
        replication_x100=int(m.get("replication_factor_x100", 0)),
        bytes_replicated=int(m.get("bytes_replicated", 0)),
        overflow=int(m["overflow_rows"])))

# warm heavy-key rebind: the SAME compiled hypercube plan serves a
# GROWN heavy-key set with zero retraces (DistRunner param rebind)
cp, runner = runners["hypercube"]
hk = sorted(n for n in collect_plan_params(cp.graph)
            if n.startswith("__hk"))
setA = SKM.decide_heavy_keys(stats["Lineitem__F"], "pid", PN)
setB = sorted(setA) + [max(setA) + 1, max(setA) + 2]
t0 = CG.TRACE_STATS.get("traces", 0)
res, _m = runner(env, params={hk[0]: SKM.pad_heavy(setB)})
out.append(dict(kind="rebind", ok=I.bags_equal(direct, rows_of(res)),
                retraces=CG.TRACE_STATS.get("traces", 0) - t0,
                n_params=len(hk), set_a=list(map(int, setA)),
                set_b=list(map(int, setB))))

# ...and through the QueryService plan cache: the hint SHAPE joins the
# cache key, heavy VALUES stay runtime parameters — a warm call with a
# new set must hit the cached hypercube plan without tracing
from repro.serve import QueryService
from repro.core.plans import MultiJoinP as MJ, _walk_plan as _wp
svc = QueryService(types, catalog=CATALOG, mesh=mesh,
                   dist_kwargs=dict(cap_factor=2.0, adaptive=True))
svc.execute(prog, env, skew_hints={"Lineitem__F": {"pid": setA}})
t0 = CG.TRACE_STATS.get("traces", 0)
res2 = svc.execute(prog, env,
                   skew_hints={"Lineitem__F": {"pid": setB}})
mj_svc = sum(1 for e in svc._cache.values() for _, p in e.cp.plans
             for s in _wp(p) if isinstance(s, MJ))
out.append(dict(kind="service", ok=I.bags_equal(direct, rows_of(res2)),
                retraces=CG.TRACE_STATS.get("traces", 0) - t0,
                hits=svc.stats["hits"], misses=svc.stats["misses"],
                multijoin=mj_svc))
print("JSON" + json.dumps(out))
"""


def run(smoke: bool = False):
    """The hypercube-vs-cascade scenario (and `make hypercube-smoke`)."""
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
        raise RuntimeError("hypercube benchmark child failed")
    payload = [l for l in res.stdout.splitlines()
               if l.startswith("JSON")][0]
    rows = json.loads(payload[4:])
    by_mode = {r["mode"]: r for r in rows if r["kind"] == "mode"}
    for mode, r in by_mode.items():
        assert r["ok"], f"{mode} produced wrong results"
        kw = {}
        if mode == "hypercube":
            kw = dict(replication_factor=r["replication_x100"] / 100.0,
                      bytes_replicated=r["bytes_replicated"])
        emit(f"hypercube3_zipf2.0_{mode}", r["seconds"] * 1e6,
             f"collectives={r['collectives']};"
             f"imb={r['imbalance']:.2f};"
             f"shuffle_rows={r['shuffle_rows']};"
             f"multijoin={r['multijoin']};overflow={r['overflow']};"
             f"coldS={r['cold_seconds']:.2f}", **kw)
    hc, cas = by_mode["hypercube"], by_mode["cascade"]
    # gate 1: the rewrite actually fired, and only under "auto"
    assert hc["multijoin"] >= 1 and hc["hc_exchanges"] >= 1, hc
    assert cas["multijoin"] == 0, cas
    # gate 2: one-round schedule -> strictly fewer collectives
    assert hc["collectives"] < cas["collectives"], (hc, cas)
    # gate 3: heavy-key spreading bounds the receive-load imbalance
    # even at Zipf 2.0
    assert hc["imbalance"] <= 2.0, hc
    speed = cas["seconds"] / max(hc["seconds"], 1e-9)
    emit("hypercube3_vs_cascade", 0.0,
         f"x{speed:.2f};collectives {cas['collectives']}->"
         f"{hc['collectives']};imb {cas['imbalance']:.2f}->"
         f"{hc['imbalance']:.2f}")
    for r in rows:
        if r["kind"] == "rebind":
            assert r["ok"] and r["retraces"] == 0, r
            emit("hypercube3_warm_rebind", 0.0,
                 f"retraces={r['retraces']};params={r['n_params']};"
                 f"heavy {len(r['set_a'])}->{len(r['set_b'])}")
        elif r["kind"] == "service":
            assert r["ok"] and r["retraces"] == 0, r
            assert r["hits"] >= 1 and r["multijoin"] >= 1, r
            emit("hypercube3_service_new_heavy_set", 0.0,
                 f"retraces={r['retraces']};hits={r['hits']};"
                 f"misses={r['misses']};multijoin={r['multijoin']}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI gate: parity + strictly fewer "
                         "collectives than the cascade + imbalance "
                         "<= 2.0 + zero warm retraces on a new "
                         "heavy-key set")
    args = ap.parse_args()
    run(smoke=args.smoke)
    if args.smoke:
        print("HYPERCUBE-SMOKE OK")

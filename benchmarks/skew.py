"""Skew-handling benchmark (paper Fig. 8 + App. E.5): nested-to-nested
narrow query at level 2 over increasingly skewed data, SHRED vs
SHRED_SKEW on 8 virtual devices — reporting runtime, shuffled rows,
collective counts and overflow (the TPU analogue of Spark's crashed
runs).

``run_auto`` / ``--smoke`` exercises the automatic pipeline end to
end: a skewed nested dataset is persisted through ``DatasetWriter``
(streaming heavy-key sketch + zone maps), ``table_stats`` feeds the
skew pass, and the same join->sum_by->nest query runs under two plans
per Zipf point —

  * **auto** — ``compile_program(skew_stats=...)``: SkewJoinP where
    the statistics predict imbalance, plain join otherwise;
  * **off**  — compiled without statistics: the plain join.

Reported per point: warm runtime, measured partition imbalance
(max/mean receive load over the exchange sites), shuffled rows, and
parity vs the interpreter oracle. The ``--smoke`` gate asserts the
deterministic facts: parity everywhere; zero heavy keys at uniform
(auto == off, same SHUFFLE metrics); at high Zipf auto bounds the
imbalance below threshold while cutting shuffled rows >= 1.3x vs off;
and ZERO retraces when a warm plan — DistRunner rebind and
QueryService ``skew_hints`` alike — serves a NEW heavy-key set.

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

_AUTO_CHILD = r"""
import os
# a CPU rehearsal on 8 virtual devices: never the accelerator the
# parent process may hold
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json, tempfile, time
sys.path.insert(0, r"%(src)s")
sys.path.insert(0, r"%(bench)s")
import jax
import numpy as np
import repro
from repro.core import codegen as CG
from repro.core import interpreter as I
from repro.core import materialization as M
from repro.core import nrc as N
from repro.core import skew as SKM
from repro.core.plans import SkewJoinP, _walk_plan, collect_plan_params
from repro.data.generators import TPCH_TYPES, gen_tpch
from repro.exec.dist import device_mesh_1d, receive_imbalance
from repro.serve import QueryService
from repro.storage import StorageCatalog, table_stats
from benchmarks.common import CATALOG, materialize_nested_input, \
    nested_to_nested_query

SMOKE = %(smoke)d
PN = 8
WARM_ITERS = 3 if SMOKE else 5
mesh = device_mesh_1d(PN)


def n_skew_nodes(cp):
    return sum(1 for _, p in cp.plans for s in _walk_plan(p)
               if isinstance(s, SkewJoinP))


out = []
sweep = (0.0, 2.0) if SMOKE else (0.0, 0.8, 1.2, 2.0)
for zipf in sweep:
    db = gen_tpch(scale=48, skew=zipf, seed=0)
    nested, nty = materialize_nested_input(db, 2)
    types = {"NCOP": nty, "Part": TPCH_TYPES["Part"]}
    inputs = {"NCOP": nested, "Part": db["Part"]}
    # persist through the streaming writer: heavy-key sketch + zone
    # maps land in the footer, table_stats feeds the compiler
    td = tempfile.mkdtemp()
    cat = StorageCatalog(td)
    cat.writer("skewbench", types, chunk_rows=512).append(inputs)
    ds = cat.open("skewbench")
    stats = table_stats(ds)
    q = nested_to_nested_query(2, "NCOP", nty)
    prog = N.Program([N.Assignment("Q", q)])
    sp = M.shred_program(prog, types, domain_elimination=True)
    man = sp.manifests["Q"]
    direct = I.eval_expr(q, inputs)
    env = ds.load_env()
    env = {k: b.resize(((b.capacity + PN - 1) // PN) * PN)
           for k, b in env.items()}

    def rows_of(res):
        parts = {(): res[man.top],
                 **{p: res[n] for p, n in man.dicts.items()}}
        return CG.parts_to_rows(parts, q.ty)

    runners = {}
    for mode in ("auto", "off"):
        cp = CG.compile_program(
            sp, CATALOG, skew_stats=stats if mode == "auto" else None,
            skew_partitions=PN)
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
            kind="mode", zipf=zipf, mode=mode, seconds=warm,
            cold_seconds=cold, ok=I.bags_equal(direct, rows_of(res)),
            skew_nodes=n_skew_nodes(cp),
            imbalance=receive_imbalance(m, PN, floor=64),
            shuffle_rows=int(m["shuffle_rows"]),
            collectives=int(m["shuffle_collectives"]),
            overflow=int(m["overflow_rows"]),
            planned=int(runner.stats.get("skew_join_planned", 0))))

    if zipf == max(sweep):
        # warm heavy-key rebinds: the SAME compiled skew plan serves a
        # DIFFERENT heavy-key set with zero retraces (DistRunner...).
        # The new set GROWS the old one: adaptive bucket capacities
        # were resolved under the warm set, so a shrinking rebind may
        # push a hot key back through the light exchange and trip the
        # metered-overflow safety valve — growing sets only move rows
        # to the broadcast path and stay exact (DESIGN.md).
        cp, runner = runners["auto"]
        names = sorted(collect_plan_params(cp.graph))
        ts = stats["NCOP__D_corders_oparts"]
        setA = SKM.decide_heavy_keys(ts, "pid", PN)
        setB = setA + [max(setA) + 1, max(setA) + 2]
        t0 = CG.TRACE_STATS.get("traces", 0)
        res, _m = runner(env, params={names[0]: SKM.pad_heavy(setB)})
        out.append(dict(kind="rebind",
                        ok=I.bags_equal(direct, rows_of(res)),
                        retraces=CG.TRACE_STATS.get("traces", 0) - t0,
                        set_a=setA, set_b=setB))
        # ...and through the QueryService plan cache via skew_hints
        svc = QueryService(types, catalog=CATALOG, mesh=mesh,
                           dist_kwargs=dict(cap_factor=2.0,
                                            adaptive=True))
        svc.execute(prog, env,
                    skew_hints={"NCOP__D_corders_oparts":
                                {"pid": setA}})
        t0 = CG.TRACE_STATS.get("traces", 0)
        res2 = svc.execute(prog, env,
                           skew_hints={"NCOP__D_corders_oparts":
                                       {"pid": setB}})
        out.append(dict(kind="service",
                        ok=I.bags_equal(direct, rows_of(res2)),
                        retraces=CG.TRACE_STATS.get("traces", 0) - t0,
                        hits=svc.stats["hits"],
                        misses=svc.stats["misses"]))
print("JSON" + json.dumps(out))
"""


def run_auto(smoke: bool = False):
    """The automatic-skew scenario (and the `make skew-smoke` gate)."""
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                       "src")
    bench = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    script = _AUTO_CHILD % {"src": os.path.abspath(src),
                            "bench": os.path.abspath(bench),
                            "smoke": int(smoke)}
    res = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=3000)
    if res.returncode != 0:
        print(res.stdout[-2000:])
        print(res.stderr[-2000:])
        raise RuntimeError("auto-skew benchmark child failed")
    payload = [l for l in res.stdout.splitlines()
               if l.startswith("JSON")][0]
    rows = json.loads(payload[4:])
    by_mode = {}
    for r in rows:
        if r["kind"] != "mode":
            continue
        assert r["ok"], f"zipf={r['zipf']} mode={r['mode']} wrong results"
        by_mode[(r["zipf"], r["mode"])] = r
        emit(f"autoskew{r['zipf']}_{r['mode']}", r["seconds"] * 1e6,
             f"skew_nodes={r['skew_nodes']};imb={r['imbalance']:.2f};"
             f"shuffle_rows={r['shuffle_rows']};"
             f"collectives={r['collectives']};overflow={r['overflow']};"
             f"coldS={r['cold_seconds']:.2f}")
    zipfs = sorted({z for z, _ in by_mode})
    lo, hi = zipfs[0], zipfs[-1]
    # uniform: zero predicted heavy keys -> auto IS the plain plan
    assert by_mode[(lo, "auto")]["skew_nodes"] == 0
    for k in ("shuffle_rows", "collectives"):
        assert by_mode[(lo, "auto")][k] == by_mode[(lo, "off")][k]
    # high Zipf: the skew plan exists, bounds the measured imbalance,
    # and cuts shuffled rows
    a, o = by_mode[(hi, "auto")], by_mode[(hi, "off")]
    assert a["skew_nodes"] >= 1 and a["planned"] >= 1
    assert a["imbalance"] <= 2.5 < o["imbalance"], (a, o)
    red = o["shuffle_rows"] / max(a["shuffle_rows"], 1)
    assert red >= 1.3, f"shuffle reduction x{red:.2f} < 1.3"
    speed = o["seconds"] / max(a["seconds"], 1e-9)
    emit(f"autoskew{hi}_auto_vs_off", 0.0,
         f"x{speed:.2f};shuffle_cut=x{red:.2f};"
         f"imb {o['imbalance']:.2f}->{a['imbalance']:.2f}")
    for r in rows:
        if r["kind"] == "rebind":
            assert r["ok"] and r["retraces"] == 0, r
            emit("autoskew_warm_rebind", 0.0,
                 f"retraces={r['retraces']};ok={r['ok']}")
        elif r["kind"] == "service":
            assert r["ok"] and r["retraces"] == 0 and r["hits"] >= 1, r
            emit("autoskew_service_new_heavy_set", 0.0,
                 f"retraces={r['retraces']};hits={r['hits']};"
                 f"misses={r['misses']}")


def run():
    run_auto(smoke=False)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI gate: parity + bounded imbalance + "
                         "zero warm retraces across two heavy-key sets")
    args = ap.parse_args()
    if args.smoke:
        run_auto(smoke=True)
        print("SKEW-SMOKE OK")
    else:
        run()

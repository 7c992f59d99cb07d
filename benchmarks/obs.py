"""Telemetry benchmark + the `make obs-smoke` gate.

Part A (in-process, single device) serves a parameterized query family
from an encoded stored dataset with the span tracer ON and asserts the
observability contract end to end:

  * the trace tree contains ``query.execute`` / ``query.compile`` /
    ``compile`` / ``decode`` / ``storage.load_part`` spans;
  * telemetry-enabled WARM serving performs ZERO retraces (spans inside
    jitted code are host-side and fire at trace time only);
  * the latency histogram yields finite, ordered p50 <= p95 <= p99;
  * a disabled ``span()`` costs < ~2us/call, and enabling the tracer
    does not blow up warm latency;
  * observed row counts flow through ``StatsFeedback`` into the dataset
    footer and round-trip back as ``TableStats.effective_rows``;
  * ``explain_analyze`` renders per-operator rows/timing locally.

Part B re-runs the skewed distributed scenario on 8 virtual devices in
a subprocess (the XLA flag must not leak into the parent): EXPLAIN
ANALYZE over a SkewJoin plan must render shipped rows + receive-load
imbalance per operator, and the trace tree must contain ``exchange``
spans from inside the shard_map region.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

import jax
import numpy as np

from repro.core import codegen as CG
from repro.obs import (TRACER, StatsFeedback, explain_analyze,
                       record_observed_stats, span, tracing)
from repro.obs.metrics import MetricsRegistry
from repro.serve import QueryService
from repro.storage import StorageCatalog

from .common import emit
from .serving import CATALOG, INPUT_TYPES, family, gen_data

_NOOP_SPAN_BUDGET_US = 2.0      # disabled-mode per-call ceiling


def _span_overhead_us(iters: int = 50_000) -> float:
    assert not TRACER.enabled
    t0 = time.perf_counter()
    for _ in range(iters):
        with span("noop", a=1):
            pass
    return (time.perf_counter() - t0) / iters * 1e6


def _warm_p50(svc, ds, thresholds) -> float:
    lat = MetricsRegistry()
    for th in thresholds:
        t0 = time.perf_counter()
        out = svc.execute_stored(family(th), ds)
        jax.block_until_ready({k: v.valid for k, v in out.items()})
        lat.observe("ms", (time.perf_counter() - t0) * 1e3)
    return lat.percentile("ms", 50)


def run_local(n_orders: int = 400, smoke: bool = True) -> dict:
    with tempfile.TemporaryDirectory() as tmp:
        data = gen_data(n_orders)
        cat = StorageCatalog(tmp)
        ds = cat.write("shop", data, INPUT_TYPES, chunk_rows=64,
                       encoding="auto")
        fb = StatsFeedback()
        svc = QueryService(INPUT_TYPES, catalog=CATALOG, feedback=fb)

        # -- cold + warm serving, telemetry ON ----------------------------
        lat = MetricsRegistry()
        with tracing(reset=True):
            t0 = time.perf_counter()
            svc.execute_stored(family(5.0), ds)
            cold_s = time.perf_counter() - t0
            traces_cold = CG.TRACE_STATS.get("traces", 0)
            for th in np.linspace(2.0, 18.0, 12):
                t0 = time.perf_counter()
                out = svc.execute_stored(family(float(th)), ds)
                jax.block_until_ready({k: v.valid
                                       for k, v in out.items()})
                lat.observe("warm_ms",
                            (time.perf_counter() - t0) * 1e3)
            retraces = CG.TRACE_STATS.get("traces", 0) - traces_cold
            names = set(TRACER.span_names())
            n_spans = len(TRACER.spans())
        pcts = lat.percentiles("warm_ms")

        # -- observed-stats feedback -> footer round trip -----------------
        env_mem = svc.shred_inputs(data)
        fb.record_env(env_mem)
        n_parts = record_observed_stats(ds.dir, fb.part_meters())
        ds2 = cat.open("shop", refresh=True)
        measured = {p: ds2.parts[p].stats().effective_rows
                    for p in ds2.parts}

        # -- explain_analyze, local render --------------------------------
        res = explain_analyze(family(4.0), env_mem, INPUT_TYPES,
                              catalog=CATALOG)
        text = res.pretty()

        # -- disabled-mode overhead ---------------------------------------
        noop_us = _span_overhead_us()
        p50_off = _warm_p50(svc, ds, [3.0, 7.0, 11.0, 15.0])
        with tracing():
            p50_on = _warm_p50(svc, ds, [3.0, 7.0, 11.0, 15.0])

        emit("obs_warm_traced", pcts["p50"] * 1e3,
             f"n={n_orders};retraces={retraces};span_names="
             f"{len(names)}",
             compile_ms=cold_s * 1e3, p50_ms=pcts["p50"],
             p95_ms=pcts["p95"], p99_ms=pcts["p99"], spans=n_spans)
        emit("obs_span_overhead", noop_us,
             f"disabled_us={noop_us:.3f};budget={_NOOP_SPAN_BUDGET_US}")
        emit("obs_explain_local", res.total_ms * 1e3,
             f"nodes={len(res.nodes())};assignments="
             f"{len(res.assignments)}", trace_ms=res.total_ms)
        emit("obs_feedback_footer", 0.0,
             f"parts_updated={n_parts};measured_tops="
             f"{measured.get('Ord__F')}")

        if smoke:
            for want in ("query.execute", "query.compile", "compile",
                         "decode", "storage.load_part"):
                assert want in names, (want, sorted(names))
            assert retraces == 0, (
                f"telemetry-enabled warm serving retraced {retraces}x")
            assert pcts["p50"] <= pcts["p95"] <= pcts["p99"], pcts
            assert all(np.isfinite(v) for v in pcts.values()), pcts
            assert noop_us < _NOOP_SPAN_BUDGET_US, (
                f"disabled span costs {noop_us:.2f}us/call")
            # enabling spans must not blow up warm latency (generous
            # bound: timing on shared CI machines is noisy)
            assert p50_on <= max(3.0 * p50_off, p50_off + 5.0), (
                p50_on, p50_off)
            assert n_parts >= 1 and fb.rows, "feedback did not record"
            assert measured["Ord__F"] == fb.rows["Ord__F"], (
                measured, fb.rows)
            assert "rows=" in text and "ms=" in text
            assert any("Scan" in n.op for n in res.nodes())
            print("# obs local smoke OK: spans present, 0 retraces, "
                  "percentiles ordered, overhead bounded, footer "
                  "round-trip")
    return {"retraces": retraces, "noop_us": noop_us, "pcts": pcts}


_DIST_CHILD = r"""
import os, sys, json
# a CPU rehearsal on 8 virtual devices: never the accelerator the
# parent process may hold
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, %(src)r)
import jax, numpy as np
import jax.numpy as jnp
from repro.core import codegen as CG
from repro.core import nrc as N
from repro.core.skew import TableStats
from repro.obs import TRACER, StatsFeedback, explain_analyze, tracing

PART_T = N.bag(N.tuple_t(pid=N.INT, pname=N.INT, price=N.REAL))
COP_T = N.bag(N.tuple_t(
    cname=N.INT,
    corders=N.bag(N.tuple_t(
        odate=N.INT,
        oparts=N.bag(N.tuple_t(pid=N.INT, qty=N.REAL))))))
TYPES = {"COP": COP_T, "Part": PART_T}

def query():
    COP, Part = N.Var("COP", COP_T), N.Var("Part", PART_T)
    def oparts_q(co):
        inner = N.for_in("op", co.oparts, lambda op:
            N.for_in("p", Part, lambda p:
                N.IfThen(op.pid.eq(p.pid),
                         N.Singleton(N.record(pname=p.pname,
                                              total=op.qty * p.price)))))
        return N.SumBy(inner, keys=("pname",), values=("total",))
    return N.for_in("cop", COP, lambda cop: N.Singleton(N.record(
        cname=cop.cname,
        corders=N.for_in("co", cop.corders, lambda co:
            N.Singleton(N.record(odate=co.odate, oparts=oparts_q(co)))))))

rng = np.random.RandomState(0)
parts = [{"pid": i, "pname": 100 + i, "price": float(rng.randint(1, 20))}
         for i in range(1, 21)]
cop = []
for c in range(8):
    orders = []
    for o in range(rng.randint(1, 4)):
        items = [{"pid": 7 if rng.rand() < 0.7
                  else int(rng.randint(1, 21)),
                  "qty": float(rng.randint(1, 5))}
                 for _ in range(rng.randint(1, 6))]
        orders.append({"odate": 20200000 + o, "oparts": items})
    cop.append({"cname": 1000 + c, "corders": orders})

env = CG.columnar_shred_inputs({"COP": cop, "Part": parts}, TYPES)
def pad(b, m=8):
    cap = ((b.capacity + m - 1) // m) * m
    return b if cap == b.capacity else b.resize(cap)
env = {k: pad(v) for k, v in env.items()}

mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
stats = {"COP__D_corders_oparts":
         TableStats(rows=200, heavy={"pid": [(7, 120)]})}
with tracing(reset=True):
    res = explain_analyze(
        N.Program([N.Assignment("Q", query())]), env, TYPES,
        mesh=mesh, skew_stats=stats, skew_partitions=8)
names = TRACER.span_names()
fb = StatsFeedback()
ratio = fb.record_metrics("fam", res.metrics, 8)
text = res.pretty()
sk = res.find("SkewJoinP") + res.find("MultiJoinP")
print("JSON" + json.dumps({
    "names": sorted(set(names)), "text": text,
    "skew_nodes": len(sk),
    "skew_rows": sk[0].rows_out if sk else None,
    "imbalance": ratio,
    "total_ms": res.total_ms}))
"""


def run_dist(smoke: bool = True) -> dict:
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "..", "src")
    script = _DIST_CHILD % {"src": os.path.abspath(src)}
    res = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=3000)
    if res.returncode != 0:
        print(res.stdout[-2000:])
        print(res.stderr[-2000:])
        raise RuntimeError("obs dist child failed")
    payload = [l for l in res.stdout.splitlines()
               if l.startswith("JSON")][0]
    out = json.loads(payload[4:])
    emit("obs_explain_dist", out["total_ms"] * 1e3,
         f"skew_nodes={out['skew_nodes']};"
         f"imbalance={out['imbalance']:.2f};"
         f"span_names={len(out['names'])}",
         trace_ms=out["total_ms"])
    if smoke:
        for want in ("exchange", "compile"):
            assert want in out["names"], (want, out["names"])
        assert out["skew_nodes"] >= 1, "no SkewJoinP in the dist plan"
        assert out["skew_rows"] and out["skew_rows"] > 0
        assert "SkewJoin" in out["text"] and "imbalance=" in out["text"]
        assert "shipped=" in out["text"]
        print("# obs dist smoke OK: exchange spans traced, SkewJoin "
              "explain rendered with shipped rows + imbalance")
    return out


def run(smoke: bool = False, n_orders: int = 400):
    run_local(n_orders=n_orders, smoke=smoke)
    run_dist(smoke=smoke)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--n-orders", type=int, default=400)
    args = ap.parse_args()
    run(smoke=args.smoke, n_orders=args.n_orders)
    if args.smoke:
        print("# obs smoke OK")


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""One run of one benchmark cell on the accelerator it is started on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up generates the cell's data from ``--seed`` with the generator its
configuration names (NumPy, in bulk), persists it once per
(configuration, seed) under ``bench/.store/``,
builds the served path of the configuration and warms its query family
with one request. The window then sends requests back to back through
``ServingRuntime.submit`` (one client, closed loop) for ``--seconds``.
Each request draws its parameters from the traffic file; its latency
runs from ``submit`` until the answer's parts are on the host.

After the window every answer is compared with the query family's plain
NumPy reference; ``correct`` holds only when every request was served
ok, not degraded, and equal to the reference bit for bit.

``--trace 0`` reports the cell's end-to-end metrics (``qps``,
``latency_p95_ms``, ``setup_s``); ``--trace 1`` turns on the engine's
span tracer for the window and a ``jax.profiler`` window over its first
requests, and reports the per-layer metrics whose readers
(``bench/metrics/<name>.py``) find something to read.

The last line of standard output is one JSON object; the numbers that
decided ``correct`` are the last lines of standard error and the last
key of that object. Without a TPU, or with fewer chips than the cell
asks for, the run exits 2 and prints no result.

``--control f32`` puts the reference, computed in float32, in the
engine's place (the precision control); the benchmark's own runs never
pass it.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
PROFILED_REQUESTS = 3


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def fail(msg: str, code: int = 2) -> None:
    log(f"bench: {msg}")
    sys.exit(code)


def percentile(values, q: float) -> float:
    """The q-th percentile, linear between closest ranks (NumPy's
    default)."""
    import numpy as np
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def peaks_for(kind: str) -> dict:
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(have {sorted(table)})")
    return table[kind]


def run(cell, seed: int, seconds: float, trace: bool,
        control: str = "", store_root: str = None,
        cache_dir: str = None, trace_dir: str = None,
        require_tpu: bool = True) -> dict:
    """One run of ``cell``; returns the result object. A test steers it
    off the chip with ``require_tpu=False`` (no peaks, no cache)."""
    import jax
    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            fail(f"JAX found no TPU: {len(devices)} {devices[0].platform} "
                 f"device(s)")
        if len(devices) < cell.chips:
            fail(f"{cell.name} needs {cell.chips} chips; JAX found "
                 f"{len(devices)}")
        peaks_for(devices[0].device_kind)
    import repro  # noqa: F401  (64-bit mode)
    from repro.core import codegen as CG
    from repro.obs.metrics import REGISTRY
    from repro.obs.trace import TRACER
    from harness import profile, serving, store, traffic
    if cache_dir is not None:
        from repro import compile_cache
        compile_cache.enable(cache_dir)
    t_init = time.perf_counter()
    log(f"cell {cell.name} seed {seed}: {len(devices)} "
        f"{devices[0].device_kind} device(s); init "
        f"{t_init - T_START:.3f} s")

    cfg = cell.config
    query = cell.query()
    dataset = cell.dataset()
    types = dataset.types(cfg["tables"])
    cols = dataset.generate(cfg, seed)
    t_data = time.perf_counter()
    root = store_root or os.path.join(BENCH, ".store", cfg["name"])
    ds, written = store.open_or_write(root, f"seed{seed}", cols, types,
                                      int(cfg["chunk_rows"]))
    t_store = time.perf_counter()
    log(f"data: generate {t_data - t_init:.3f} s, "
        f"{'write' if written else 'reopen'} {t_store - t_data:.3f} s; "
        f"rows {json.dumps(store.table_rows(cols))}")

    warm_params = traffic.first(cell.traffic, cols, seed)
    server = serving.Server(cfg, ds, types, dataset.UNIQUE_KEYS,
                            lambda params: query.program(params, types),
                            warm_params)
    t_load = time.perf_counter()
    draw = traffic.sampler(cell.traffic, cols, seed)
    prep = query.prepare(cols) if control else None

    hits0 = REGISTRY.get("compile_cache.hits")
    miss0 = REGISTRY.get("compile_cache.misses")
    TRACER.reset()
    TRACER.enable(True)
    warm = server.submit(server.request(warm_params))
    TRACER.enable(False)
    plan_s = sum(s.dur or 0.0 for s in TRACER.spans()
                 if s.name == "query.compile")
    TRACER.reset()
    if not warm.ok:
        r = warm.response
        fail(f"warm-up request failed: ok={r.ok} degraded={r.degraded} "
             f"error={r.error!r}", code=1)
    t_warm = time.perf_counter()
    setup_s = t_warm - T_START
    log(f"set-up: load {server.load_s:.3f} s, serve build "
        f"{t_load - t_store - server.load_s:.3f} s, warm-up "
        f"{t_warm - t_load:.3f} s (plan {plan_s:.3f} s); compile cache "
        f"{int(REGISTRY.get('compile_cache.hits') - hits0)} hits, "
        f"{int(REGISTRY.get('compile_cache.misses') - miss0)} misses; "
        f"lowered {json.dumps(server.lowered())}; setup_s {setup_s:.3f}")

    # -- the window --------------------------------------------------------
    trees, dist_metrics, records = [], [], []
    tdir = trace_dir or os.path.join(BENCH, ".trace", cell.name)
    profiling = trace
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(tdir)
        window_mark = jax.profiler.TraceAnnotation("bench.window")
        window_mark.__enter__()
        TRACER.reset()
        TRACER.enable(True)

    def stop_profile():
        window_mark.__exit__(None, None, None)
        jax.profiler.stop_trace()
    annotate = jax.profiler.TraceAnnotation
    traces0 = CG.TRACE_STATS.get("traces", 0)
    t_open = time.perf_counter()
    t_last = t_open
    while t_last - t_open < seconds:
        with annotate("bench.draw"):
            params = draw()
            req = server.request(params)
        t0 = time.perf_counter()
        if control:
            with annotate("bench.submit"):
                rows = query.control(prep, params)
            ok, metrics, t1 = True, None, time.perf_counter()
        else:
            reply = server.submit(req, annotate)
            ok, metrics, t1 = reply.ok, reply.metrics, reply.done
            rows = query.answer_rows(reply.outputs, server.top) \
                if ok else None
        t_last = time.perf_counter()
        records.append((params, ok, rows, t1 - t0))
        if metrics is not None:
            dist_metrics.append(metrics)
        if trace:
            trees.extend(s.tree() for s in TRACER.roots)
            TRACER.reset()
        if profiling and len(records) == PROFILED_REQUESTS:
            stop_profile()
            profiling = False
    if profiling:
        stop_profile()
    TRACER.enable(False)
    window_s = t_last - t_open
    device = device_info(devices)
    del server, warm

    # -- correctness, against the plain reference --------------------------
    prep = prep if prep is not None else query.prepare(cols)
    failed_requests = wrong = 0
    worst_gap = 0.0
    for params, ok, rows, _ in records:
        if not ok:
            failed_requests += 1
            continue
        equal, gap = query.compare(rows, query.reference(prep, params))
        wrong += 0 if equal else 1
        worst_gap = max(worst_gap, gap)
    attempted = len(records)
    check = {"failed_requests": {"value": failed_requests, "limit": 0},
             "wrong_answers": {"value": wrong, "limit": 0},
             query.GAP: {"value": worst_gap, "limit": 0}}
    correct = attempted > 0 and all(v["value"] <= v["limit"]
                                    for v in check.values())
    n_ok = attempted - failed_requests - wrong
    lat_ms = [r[3] * 1e3 for r in records]
    log(f"window: {attempted} requests in {window_s:.3f} s; latency ms "
        f"median {percentile(lat_ms, 50):.3f} p95 "
        f"{percentile(lat_ms, 95):.3f} max {max(lat_ms):.3f}; retraces "
        f"{CG.TRACE_STATS.get('traces', 0) - traces0}")

    result = {"correct": bool(correct), "attempted": attempted,
              "failed": attempted - n_ok, "device": device}
    if not trace:
        values = {"qps": n_ok / window_s,
                  "latency_p95_ms": percentile(lat_ms, 95),
                  "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    else:
        prof = profile.reduce(profile.load(tdir))
        obs = SimpleNamespace(spans=trees, profile=prof,
                              profiled_requests=min(len(records),
                                                    PROFILED_REQUESTS),
                              dist_metrics=dist_metrics, chips=cell.chips)
        readers = cell.metric_readers()
        metrics = {}
        for m in cell.per_layer:
            v = readers[m["name"]].read(obs)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        result["metrics"] = metrics
        if prof is not None:
            result["device"].update(busy_s=prof["busy_s"],
                                    window_s=prof["window_s"])
            result["breakdown"] = {"device_ops": prof["device_ops"],
                                   "idle_gaps": prof["idle_gaps"]}
    result["check"] = check
    for name, v in check.items():
        log(f"check {name}: {v['value']} (limit {v['limit']})")
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("", "f32"), default="")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"the engine is not beside the benchmark ({SRC}/repro is "
             f"missing)")
    sys.path[:0] = [BENCH, SRC]
    from harness.cell import resolve
    try:
        cell = resolve(args.workload)
    except (KeyError, FileNotFoundError) as e:
        fail(str(e))
    # the compile cache stays inside the checkout, whatever the
    # environment names
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    result = run(cell, args.seed, args.seconds, bool(args.trace),
                 control=args.control, cache_dir=BENCH)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()

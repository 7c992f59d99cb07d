"""Unified telemetry: metrics registry, span tracer, EXPLAIN ANALYZE,
and the observed-stats feedback loop.

The differential acceptance mirrors the repo's seed-style invariant:
turning the tracer ON must not change a single output bit and must not
cost a single extra retrace (spans inside jitted code are host-side and
fire at trace time only)."""

import contextlib
import glob
import json
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import codegen as CG
from repro.core import nrc as N
from repro.core import plans as P
from repro.core.skew import TableStats, decide_heavy_keys
from repro.obs import (REGISTRY, TRACER, MetricsRegistry, StatsFeedback,
                       explain_analyze, metrics_scope,
                       record_observed_stats, span, tracing)
from repro.serve.query_service import QueryService
from repro.serve.runtime import QueryRequest, ServingRuntime
from repro.storage import StorageCatalog

from helpers import (INPUT_TYPES, gen_cop, gen_parts,
                     running_example_query)


def _program():
    return N.Program([N.Assignment("Q", running_example_query())])


def _data():
    return {"Part": gen_parts(n=20, seed=0),
            "COP": gen_cop(6, 3, 4, 20, seed=1)}


def _env():
    return CG.columnar_shred_inputs(_data(), INPUT_TYPES)


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------

def test_registry_counters_gauges_and_views():
    r = MetricsRegistry()
    r.inc("sort.lexsort")
    r.inc("sort.lexsort", 2)
    r.set_gauge("shuffle.size_used_j0", 96)
    assert r.get("sort.lexsort") == 3
    assert r.get("shuffle.size_used_j0") == 96
    assert r.get("missing", -1) == -1

    # domain views behave like the dicts they replaced
    sort = r.view("sort")
    assert sort["lexsort"] == 3
    assert dict(sort) == {"lexsort": 3}
    sort["lexsort"] = 0
    sort["build_reuse"] = sort.get("build_reuse", 0) + 1
    assert r.get("sort.lexsort") == 0
    assert "build_reuse" in sort and len(sort) == 2
    del sort["build_reuse"]
    assert "build_reuse" not in sort
    sort.clear()
    assert dict(sort) == {} and r.get("shuffle.size_used_j0") == 96

    r.reset()
    assert r.snapshot() == {}


def test_engine_stats_names_are_registry_views():
    from repro.exec import ops as X
    from repro.exec import dist as D
    from repro.storage import reader as R
    X.SORT_STATS["lexsort"] = 7
    assert REGISTRY.get("sort.lexsort") == 7
    D.SHUFFLE_STATS["exchanges"] = 2
    assert REGISTRY.get("shuffle.exchanges") == 2
    R.STORAGE_STATS["parts_loaded"] = 1
    assert REGISTRY.get("storage.parts_loaded") == 1
    # the autouse fixture wipes these between tests — the historical
    # per-site SHUFFLE_STATS key leakage cannot recur
    assert CG.TRACE_STATS.get("traces", 0) == 0


def test_metrics_scope_nested_deltas():
    REGISTRY.inc("eval.join", 5)
    with metrics_scope() as outer:
        REGISTRY.inc("eval.join", 2)
        with metrics_scope() as inner:
            REGISTRY.inc("eval.join")
            REGISTRY.inc("eval.scan", 4)
        assert inner.get("eval.join") == 1
        assert inner.get("eval.scan") == 4
        REGISTRY.inc("eval.join")
    assert outer.get("eval.join") == 4      # 2 + 1 + 1, not the base 5
    assert outer.get("eval.scan") == 4
    assert outer.get("eval.never", 0) == 0
    assert REGISTRY.get("eval.join") == 9


def test_histogram_percentiles_match_numpy():
    rng = np.random.RandomState(0)
    samples = np.exp(rng.normal(3.0, 1.2, size=5000))   # lognormal ms
    r = MetricsRegistry()
    for v in samples:
        r.observe("lat", float(v))
    for q in (50, 90, 95, 99):
        got = r.percentile("lat", q)
        want = float(np.percentile(samples, q))
        assert abs(got - want) / want < 0.10, (q, got, want)
    ps = r.percentiles("lat")
    assert ps["p50"] <= ps["p95"] <= ps["p99"]
    assert np.isfinite(list(ps.values())).all()


def test_histogram_edge_cases():
    r = MetricsRegistry()
    assert np.isnan(r.percentile("empty", 50))
    r.observe("one", 42.0)
    assert r.percentile("one", 50) == pytest.approx(42.0, rel=0.1)
    r.observe("z", 0.0)
    r.observe("z", -1.0)
    assert r.percentile("z", 50) == 0.0


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_span_tree_and_chrome_export(tmp_path):
    with tracing(reset=True):
        with span("outer", kind="t"):
            with span("inner", i=0):
                pass
            with span("inner", i=1):
                pass
    roots = TRACER.tree()
    assert len(roots) == 1 and roots[0]["name"] == "outer"
    assert [c["name"] for c in roots[0]["children"]] == ["inner", "inner"]
    assert roots[0]["ms"] >= 0
    events = TRACER.chrome_trace()
    assert len(events) == 3
    for ev in events:
        assert ev["ph"] == "X" and "ts" in ev and "dur" in ev
    path = TRACER.save(str(tmp_path / "trace.json"))
    blob = json.loads(open(path).read())
    assert len(blob["traceEvents"]) == 3 and blob["tree"]


def test_spans_disabled_record_nothing():
    assert not TRACER.enabled
    with span("ghost", x=1) as sp:
        sp.attrs["y"] = 2       # writable sink, discarded
    assert TRACER.spans() == []


def test_unbalanced_exception_unwinds_spans():
    with tracing(reset=True):
        with pytest.raises(RuntimeError):
            with span("outer"):
                with span("inner"):
                    raise RuntimeError("boom")
    # both spans closed despite the unwind; durations recorded
    assert TRACER.span_names().count("outer") == 1
    for sp in TRACER.spans():
        assert sp.dur is not None


# ---------------------------------------------------------------------------
# differential: telemetry must not change results or cost retraces
# ---------------------------------------------------------------------------

def _compiled_text(svc, env) -> str:
    entry, params, env_c = svc._lookup(_program(), env, None)
    return entry.exe._fn.lower(env_c, entry.exe.bind(params)) \
        .compile().as_text()


def _instructions(hlo: str) -> list:
    """The compiled module's computations, instruction by instruction,
    without their metadata (op names, source frames)."""
    return [re.sub(r", metadata=\{[^}]*\}", "", line)
            for line in hlo.splitlines()
            if line.lstrip().startswith(("%", "ROOT ", "ENTRY "))]


def _assert_same_bits(base, out):
    for k in base:
        assert np.array_equal(np.asarray(base[k].valid),
                              np.asarray(out[k].valid))
        for c in base[k].columns:
            assert np.array_equal(np.asarray(base[k].col(c)),
                                  np.asarray(out[k].col(c)))


def test_tracing_is_bit_identical_and_zero_retrace(monkeypatch):
    svc = QueryService(INPUT_TYPES)
    env = _env()
    t0 = CG.TRACE_STATS.get("traces", 0)
    base = svc.execute(_program(), env)
    t_cold = CG.TRACE_STATS.get("traces", 0)
    warm_off = svc.execute(_program(), env)
    assert CG.TRACE_STATS.get("traces", 0) == t_cold

    with tracing(reset=True):
        warm_on = svc.execute(_program(), env)
        names = TRACER.span_names()
        (ex,) = TRACER.find("query.execute")
    # enabling the tracer on a WARM family: no retrace, same bits
    assert CG.TRACE_STATS.get("traces", 0) == t_cold
    assert "query.execute" in names and "query.dispatch" in names
    assert "compile" not in names           # warm: nothing compiled
    assert ex.attrs["answer_bytes"] == sum(
        a.nbytes for a in jax.tree_util.tree_leaves(warm_on))
    for out in (warm_off, warm_on):
        _assert_same_bits(base, out)

    # the plan-operator scopes are metadata only: the family traced
    # with every named scope a no-op compiles to the same instructions,
    # gives the same bits and costs the same number of traces
    scoped = _compiled_text(svc, env)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare_svc = QueryService(INPUT_TYPES)
    t1 = CG.TRACE_STATS.get("traces", 0)
    bare = bare_svc.execute(_program(), env)
    assert CG.TRACE_STATS.get("traces", 0) - t1 == t_cold - t0
    _assert_same_bits(base, bare)
    plain = _compiled_text(bare_svc, env)
    assert "SumAggP" in scoped and "SumAggP" not in plain
    assert _instructions(scoped) == _instructions(plain)


_MIRRORED = ("serve.submit", "query.execute", "storage.load_part",
             "storage.to_device", "query.dispatch")


def _profiled_submit(rt, req, trace_dir, enabled: bool) -> list:
    """(name, start, end) of the engine's host events that a profiler
    session around one served request records."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(trace_dir))
    try:
        with tracing(enabled, reset=True):
            assert rt.submit(req).ok
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                        recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if not plane.name.startswith("/device")
            for line in plane.lines for e in line.events
            if e.name in _MIRRORED]


def test_spans_mirror_on_the_profiler_clock(tmp_path):
    ds = StorageCatalog(str(tmp_path / "store")).write(
        "d", _data(), INPUT_TYPES, chunk_rows=8)
    rt = ServingRuntime(QueryService(INPUT_TYPES))
    req = QueryRequest(_program(), ds)
    assert rt.submit(req).ok                # compiled outside the trace
    events = _profiled_submit(rt, req, tmp_path / "on", True)
    first = {}
    for name, a, b in events:
        first.setdefault(name, (a, b))
    assert set(first) == set(_MIRRORED)

    def inside(inner, outer):
        return first[outer][0] <= first[inner][0] \
            and first[inner][1] <= first[outer][1]
    assert inside("query.execute", "serve.submit")
    assert inside("storage.load_part", "query.execute")
    assert inside("storage.to_device", "storage.load_part")
    assert inside("query.dispatch", "query.execute")
    # the tracer's own tree agrees, and the copy's size is recorded
    (sp,) = [s for s in TRACER.find("storage.to_device")
             if s.attrs["col"] == "valid"][:1]
    assert sp.attrs["bytes"] == sp.attrs["rows"] > 0
    assert _profiled_submit(rt, req, tmp_path / "off", False) == []


def test_cold_compile_emits_compile_spans():
    svc = QueryService(INPUT_TYPES)
    env = _env()
    with tracing(reset=True):
        svc.execute(_program(), env)
        names = TRACER.span_names()
    assert "query.execute" in names and "query.compile" in names
    assert "compile" in names               # plan + xla_trace spans


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE (local path; the dist path gates in `make obs-smoke`)
# ---------------------------------------------------------------------------

def test_explain_analyze_local_annotations():
    res = explain_analyze(_program(), _env(), INPUT_TYPES)
    assert not res.distributed and res.total_ms > 0
    scans = [n for n in res.nodes() if "Scan" in n.op]
    gammas = res.find("SumAggP") + res.find("GroupAggP")
    assert scans and gammas
    for node in res.nodes():
        assert node.rows_out is not None
        if node.children:
            assert node.rows_in == sum(c.rows_out
                                       for c in node.children)
    text = res.pretty()
    assert "EXPLAIN ANALYZE" in text and "rows=" in text
    assert "Gamma" in text or "Join" in text
    blob = res.to_json()
    assert blob["assignments"]
    assert blob["assignments"][0]["plan"]["op"]


def test_explain_analyze_accepts_bare_expr_and_infers_types():
    res = explain_analyze(running_example_query(), _env())
    assert any("Scan" in n.op for n in res.nodes()) and res.outputs


# ---------------------------------------------------------------------------
# feedback: measured rows into planner stats + footer round-trip
# ---------------------------------------------------------------------------

def test_feedback_rows_flow_into_table_stats():
    fb = StatsFeedback()
    env = _env()
    fb.record_env(env)
    assert fb.observed_rows("COP__F") == 6
    stats = {"COP__F": TableStats(rows=4096)}   # capacity-class guess
    fb.apply(stats)
    ts = stats["COP__F"]
    assert ts.effective_rows == 6 and ts.rows == 4096
    # heavy-key decisions read the measured rows, not the estimate:
    # 30 hits in 1000 estimated rows is light (fair share 125), but 30
    # in 100 MEASURED rows crosses the fair share (12.5) -> heavy
    ts2 = TableStats(rows=1000, heavy={"k": [(7, 30)]},
                     meters={"rows": 100})
    with_meters = decide_heavy_keys(ts2, "k", n_partitions=8)
    without = decide_heavy_keys(
        TableStats(rows=1000, heavy={"k": [(7, 30)]}), "k",
        n_partitions=8)
    assert with_meters == [7] and without == []


def test_feedback_imbalance_monotone_and_serializable(tmp_path):
    fb = StatsFeedback()
    ratio = fb.record_metrics("fam", {"part_max_j0": 30,
                                      "part_rows_j0": 60}, 4)
    assert ratio == pytest.approx(2.0)
    fb.record_metrics("fam", {"part_max_j0": 15, "part_rows_j0": 60}, 4)
    assert fb.imbalance_x100["fam"] == 200      # max, not latest
    p = str(tmp_path / "fb.json")
    fb.rows["X"] = 11
    fb.save(p)
    back = StatsFeedback.load(p)
    assert back.rows == fb.rows
    assert back.imbalance_x100 == fb.imbalance_x100


def test_observed_stats_footer_round_trip(tmp_path):
    from repro.storage import StorageCatalog
    data = {"Part": gen_parts(n=20, seed=0),
            "COP": gen_cop(6, 3, 4, 20, seed=1)}
    cat = StorageCatalog(str(tmp_path))
    ds = cat.write("shop", data, INPUT_TYPES)
    part = next(iter(ds.parts))
    est = ds.parts[part].stats().rows
    n = record_observed_stats(ds.dir, {part: {"rows": est + 5},
                                       "no_such_part": {"rows": 1}})
    assert n == 1
    ds2 = cat.open("shop", refresh=True)
    ts = ds2.parts[part].stats()
    assert ts.meters["rows"] == est + 5
    assert ts.effective_rows == est + 5 and ts.rows == est


def test_query_service_feedback_measures_on_cold_compile():
    fb = StatsFeedback()
    svc = QueryService(INPUT_TYPES, feedback=fb)
    env = _env()
    out = svc.execute(_program(), env)
    assert out and fb.rows                  # measured on the miss
    assert fb.observed_rows("COP__F") == 6
    rows_before = dict(fb.rows)
    svc.execute(_program(), env)            # warm: no re-measurement
    assert fb.rows == rows_before

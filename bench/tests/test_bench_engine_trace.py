"""What the engine's own instrumentation gives the benchmark: the readers
of the per-layer metrics fed by its spans and counters, the
plan-operator scopes in the served programs' compiled HLO, and traced
runs that report the new metrics."""

import re
from types import SimpleNamespace

from benchtest import SF1, X4, read_metric, run_four_devices, run_small, \
    server_for, small_cell, store_small

NEW = ("storage.to_device_ms", "dist.meters_ms", "query.answer_mb")


def span(name, ms, children=(), **attrs):
    return {"name": name, "ms": ms, "attrs": attrs,
            "children": list(children)}


def observed(trees):
    return SimpleNamespace(spans=trees, profile=None, profiled_requests=0,
                           dist_metrics=[], chips=1)


def test_engine_span_readers():
    stored = span("serve.submit", 100.0, [
        span("query.execute", 90.0, [
            span("storage.load_part", 30.0, [
                span("storage.chunk", 20.0),
                span("storage.to_device", 2.0, col="a"),
                span("storage.to_device", 1.5, col="valid")]),
            span("storage.load_part", 10.0, [
                span("storage.to_device", 0.5, col="valid")]),
            span("query.dispatch", 4.0)],
            path="stored", answer_bytes=3_000_000)])
    obs = observed([stored, stored])
    assert read_metric("storage.to_device_ms", obs) == 4.0
    assert read_metric("query.answer_mb", obs) == 3.0
    assert read_metric("dist.meters_ms", obs) is None
    # the new spans sit under query.execute: the older readers keep
    # their meaning (dispatch counts in the plan cache's self time)
    assert read_metric("storage.scan_ms", obs) == 40.0
    assert read_metric("plan_cache.self_ms", obs) == 50.0
    assert read_metric("runtime.self_ms", obs) == 10.0

    mesh = span("serve.submit", 100.0, [
        span("query.execute", 95.0, [
            span("query.dispatch", 3.0),
            span("dist.device_wait", 80.0),
            span("dist.meters", 2.5)],
            path="dist", answer_bytes=5_000_000)])
    obs = observed([mesh, mesh, mesh])
    assert read_metric("dist.meters_ms", obs) == 2.5
    assert read_metric("query.answer_mb", obs) == 5.0
    assert read_metric("storage.to_device_ms", obs) is None
    assert read_metric("runtime.self_ms", obs) == 5.0


def test_engine_span_readers_read_nothing_without_the_spans():
    # a program without the spans and the attribute (the parent of the
    # change that added them), and a window with no trees at all
    older = span("serve.submit", 100.0, [
        span("query.execute", 90.0, [span("storage.load_part", 30.0)],
             path="stored")])
    for obs in (observed([older]), observed([])):
        for name in NEW:
            assert read_metric(name, obs) is None


def plan_ops() -> set:
    """The plan node classes: the names of the plan-operator scopes."""
    from repro.core import plans as P
    return {n for n, c in vars(P).items()
            if isinstance(c, type) and issubclass(c, P.Plan)}


def op_names(hlo: str, opcode: str) -> list:
    """The ``op_name`` metadata of every ``opcode`` instruction of a
    compiled module's text ('' where an instruction has none)."""
    out = []
    for line in hlo.splitlines():
        body, _, meta = line.partition(", metadata={")
        if re.match(r"\s*(ROOT )?%[\w.\-]+ = .*\b" + re.escape(opcode)
                    + r"\(", body):
            m = re.search(r'op_name="([^"]*)"', meta)
            out.append(m.group(1) if m else "")
    return out


def plan_scope(op_name: str):
    """(innermost plan-operator scope, the sub-scopes below it)."""
    parts = op_name.split("/")
    at = [i for i, p in enumerate(parts) if p in plan_ops()]
    if not at:
        return None, []
    return parts[at[-1]], parts[at[-1] + 1:]


def test_one_chip_program_ops_carry_their_plan_operator(tmp_path):
    cell = small_cell(SF1, 0.001)
    cols, ds, types = store_small(cell, 2**31 + 3, str(tmp_path))
    server = server_for(cell, ds, types, {"threshold": 150000.0})
    assert server.submit(server.request({"threshold": 150000.0})).ok
    (entry,) = server.service._cache.values()
    env = ds.load_env(columns={p: r.columns
                               for p, r in entry.storage_req.items()},
                      capacities=entry.class_caps)
    hlo = entry.exe._fn.lower(env, entry.exe.bind()).compile().as_text()
    whiles = op_names(hlo, "while")
    # the fk joins' binary searches: one loop each, Part and Orders
    assert len(whiles) >= 2
    for name in whiles:
        op, below = plan_scope(name)
        assert op == "JoinP" and below[0] == "search", name
    sorts = op_names(hlo, "sort")
    assert sorts
    for name in sorts:
        op, below = plan_scope(name)
        assert op is not None and below[0] == "sort", name


def test_one_chip_traced_run_reports_the_new_metrics(tmp_path):
    res = run_small(small_cell(SF1, 0.001), 2**31 + 11, tmp_path,
                    trace=True)
    assert res["correct"]
    got = res["metrics"]
    assert {"runtime.self_ms", "plan_cache.self_ms", "storage.scan_ms",
            "storage.to_device_ms", "query.answer_mb"} <= set(got)
    assert "dist.meters_ms" not in got
    assert 0 < got["storage.to_device_ms"]["value"] \
        < got["storage.scan_ms"]["value"]
    # the answer: revenue and date per row of the Lineitem class, and
    # the valid mask (17 bytes a row)
    assert got["query.answer_mb"]["value"] > 0
    assert got["query.answer_mb"]["unit"] == "MB"


def test_four_device_collectives_carry_their_scope_and_metrics_read():
    out = run_four_devices("""
        import tempfile
        from test_bench_engine_trace import op_names, plan_scope
        cell = small_cell(X4, 2000 / 1_500_000)
        tmp = tempfile.mkdtemp()
        cols, ds, types = store_small(cell, 7, tmp + "/s")
        s = server_for(cell, ds, types, {"threshold": 150000.0})
        assert s.submit(s.request({"threshold": 150000.0})).ok
        (entry,) = s.service._cache.values()
        r = entry.runner
        hlo = r._sm.lower(s.env, r.params).compile().as_text()
        a2a = op_names(hlo, "all-to-all")
        assert a2a
        for name in a2a:
            op, below = plan_scope(name)
            assert op is not None, name
            assert {"exchange", "hypercube"} & set(below), name
        assert any(plan_scope(n)[0] == "MultiJoinP" for n in a2a)
        res = run_small(cell, 2**31 + 23, tmp, trace=True)
        assert res["correct"], res
        got = res["metrics"]
        assert {"runtime.self_ms", "exchange.receive_imbalance",
                "dist.meters_ms", "query.answer_mb"} <= set(got), got
        assert "storage.to_device_ms" not in got
        print("OK", len(a2a))
    """)
    assert "OK" in out

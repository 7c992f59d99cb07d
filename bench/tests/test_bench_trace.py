"""The reduction from profiler events and span trees to per-layer
metrics: busy union, idle share, op classes, idle gaps, span self time."""

import gzip
import json
import os
from types import SimpleNamespace

import pytest

from benchtest import read_metric
from harness import profile, spans

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def ev(plane, name, t0, dur, line=profile.OPS_LINE):
    return {"plane": plane, "line": line, "name": name, "t0": t0, "dur": dur}


def test_merge_is_the_union():
    assert profile.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_op_names_from_hlo_text():
    name = "%fusion.76 = s32[1048576]{0} fusion(s32[1048576]{0} %a), kind=kLoop"
    assert profile.op_name(name) == "fusion.76"
    assert profile.op_base(name) == "fusion"
    assert profile.op_class("%all-to-all.3 = (s32[4]) all-to-all(%x)") \
        == "collective"
    # the TPU trace writes ``all_to_all.13`` beside ``all-reduce.52``
    assert profile.op_class("%all_to_all.13 = u32[4,8,4] all-to-all(%b)") \
        == "collective"
    assert profile.op_class("sort.2") == "sort"
    assert profile.op_class("while.8") is None


def test_busy_idle_classes_and_gaps_on_two_devices():
    d0, d1 = "/device:TPU:0", "/device:TPU:1"
    events = [
        ev("/host:CPU", "bench.window", 0, 100e6, "python"),
        ev("/host:CPU", "bench.submit", 0, 60e6, "python"),
        ev("/host:CPU", "bench.handoff", 60e6, 40e6, "python"),
        ev(d0, "%sort.12 = s32[8]{0} sort(s32[8]{0} %p)", 10e6, 20e6),
        ev(d0, "fusion.3", 20e6, 20e6),        # overlaps the sort
        ev(d0, "all-to-all.1", 70e6, 10e6),
        ev(d1, "sort.4", 10e6, 40e6),
        ev(d1, "fusion.3", 150e6, 10e6),       # outside the window
    ]
    r = profile.reduce(events)
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(0.1)
    # device 0 busy [10, 40) + [70, 80) = 40 ms; device 1 [10, 50) = 40 ms
    assert r["busy_s"] == pytest.approx(0.040)
    assert r["class_s"]["sort"] == pytest.approx((0.020 + 0.040) / 2)
    assert r["class_s"]["collective"] == pytest.approx(0.010 / 2)
    assert r["device_ops"][0] == ["sort.4", pytest.approx(0.020)]
    # device 0 idle: [0,10) submit, [40,70) mostly submit, [80,100) handoff
    assert r["idle_gaps"][0] == ["bench.submit", pytest.approx(0.030)]
    assert ["bench.handoff", pytest.approx(0.020)] in r["idle_gaps"]


def test_no_window_or_no_device_reads_nothing():
    assert profile.reduce([ev("/device:TPU:0", "sort", 0, 1)]) is None
    assert profile.reduce([ev("/host:CPU", "bench.window", 0, 1, "py")]) \
        is None


def test_span_self_time_and_readers():
    tree = {"name": "serve.submit", "ms": 100.0, "children": [
        {"name": "query.execute", "ms": 90.0, "children": [
            {"name": "storage.load_part", "ms": 30.0, "children": [
                {"name": "storage.chunk", "ms": 20.0, "children": []}]},
            {"name": "storage.load_part", "ms": 25.0, "children": []},
            {"name": "query.compile", "ms": 5.0, "children": []}]}]}
    assert spans.self_ms(tree) == 10.0
    ex = spans.find(tree, "query.execute")[0]
    assert spans.self_ms(ex, only=("storage.load_part",)) == 35.0
    obs = SimpleNamespace(spans=[tree, tree], profile=None,
                          profiled_requests=0, dist_metrics=[], chips=1)
    assert read_metric("runtime.self_ms", obs) == 10.0
    assert read_metric("plan_cache.self_ms", obs) == 35.0
    assert read_metric("storage.scan_ms", obs) == 55.0
    for name in ("device.busy_ms", "device.idle_share", "operators.sort_ms",
                 "exchange.collective_ms", "exchange.receive_imbalance"):
        assert read_metric(name, obs) is None


def test_receive_imbalance_reader():
    m = {"part_max_0": 300, "part_rows_0": 800, "part_max_1": 50,
         "part_rows_1": 60}
    obs = SimpleNamespace(dist_metrics=[m, {}], chips=4)
    assert read_metric("exchange.receive_imbalance", obs) == \
        pytest.approx(1.5)    # site 1 is under 64 rows


def test_recorded_chip_trace():
    """Three requests of ``tpch_sf0.1-revenue`` traced on a TPU v5 lite
    (device ops and the benchmark's host annotations, as ``load`` keeps
    them)."""
    with gzip.open(os.path.join(DATA, "tpch_sf0.1-revenue.events.json.gz"),
                   "rt") as f:
        events = json.load(f)
    r = profile.reduce(events)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(4.302855324)
    assert r["busy_s"] == pytest.approx(3.952344134)
    # the trace names its sorts: 3 a request, 9.9 ms in all
    assert r["class_s"] == {"sort": pytest.approx(0.009913068)}
    # the joins' binary searches (``while`` loops of jnp.searchsorted)
    # hold most of the device time
    assert [op for op, _ in r["device_ops"][:2]] == ["while.8", "while.9"]
    assert sum(s for _, s in r["device_ops"]) <= r["busy_s"]
    assert r["idle_gaps"][0][0] == "bench.submit"
    assert sum(g for _, g in r["idle_gaps"]) <= r["window_s"] - r["busy_s"] + 1e-9
    obs = SimpleNamespace(spans=[], profile=r, profiled_requests=3,
                          dist_metrics=[], chips=1)
    assert read_metric("device.busy_ms", obs) == pytest.approx(1317.448044666)
    assert read_metric("device.idle_share", obs) == pytest.approx(8.146013835)
    assert read_metric("operators.sort_ms", obs) == pytest.approx(3.304356)
    assert read_metric("exchange.collective_ms", obs) is None

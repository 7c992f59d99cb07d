"""The query family's plain reference equals the interpreter oracle and
the engine's served answers, on one device and on four virtual ones."""

import numpy as np
import pytest

from benchtest import (SF1, X4, heaviest_price, run_four_devices, run_small,
                       server_for, small_cell, store_small)
from harness import traffic

ORDERS_200 = 200 / 1_500_000


def oracle_rows(query, cols, types, params):
    from repro.core import interpreter as I
    inputs = {t: [] for t in types}
    names = {t: [k.split(".", 1)[1] for k in cols if k.startswith(t + ".")]
             for t in types}
    for t in types:
        arrays = [cols[f"{t}.{c}"] for c in names[t]]
        inputs[t] = [{c: (float(v) if a.dtype.kind == "f" else int(v))
                      for c, a, v in zip(names[t], arrays, row)}
                     for row in zip(*arrays)]
    prog = query.program(params, types)
    out = I.eval_expr(prog.assignments[0].expr, inputs)
    out = sorted((r["odate"], r["total"]) for r in out)
    return (np.array([d for d, _ in out], dtype=np.int64),
            np.array([t for _, t in out], dtype=np.float64))


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_reference_equals_oracle_and_engine(seed, tmp_path):
    cell = small_cell(SF1, ORDERS_200)
    query = cell.query()
    cols, ds, types = store_small(cell, seed, str(tmp_path))
    prep = query.prepare(cols)
    draw = traffic.sampler(cell.traffic, cols, seed)
    server = server_for(cell, ds, types, draw())
    for _ in range(3):
        params = draw()
        want = query.reference(prep, params)
        got = oracle_rows(query, cols, types, params)
        assert np.array_equal(got[0], want[0])
        assert np.array_equal(got[1], want[1])
        reply = server.submit(server.request(params))
        assert reply.ok, reply.response.error
        served = query.answer_rows(reply.outputs, server.top)
        assert np.array_equal(served[0], want[0])
        assert np.array_equal(served[1], want[1])


def test_one_device_run_is_correct(tmp_path):
    res = run_small(small_cell(SF1, 0.001), 2**31 + 9, tmp_path)
    assert res["correct"] and res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"qps", "latency_p95_ms", "setup_s"}
    assert list(res)[-1] == "check"


def test_four_devices_correct_and_seed_independent():
    out = run_four_devices("""
        import tempfile
        import numpy as np
        from repro.core import codegen as CG
        from repro.core.plans import plan_pretty
        from harness.serving import place_on_mesh
        cell = small_cell(X4, 2000 / 1_500_000)
        tmp = tempfile.mkdtemp()
        res = run_small(cell, 2**31 + 21, tmp)
        assert res["correct"], res
        query = cell.query()
        servers, plans = [], []
        for seed in (3, 4):
            cols, ds, types = store_small(cell, seed, f"{tmp}/s{seed}")
            s = server_for(cell, ds, types, {"threshold": 150000.0})
            ok, *_ = s.submit(s.request({"threshold": 150000.0}))
            assert ok
            servers.append((s, ds, cols))
            plans.append([plan_pretty(p) for e in s.service._cache.values()
                          for _, p in e.cp.plans])
        assert plans[0] == plans[1]
        assert "MultiJoin" in "".join(plans[0])
        # seed 4's data through seed 3's warm program: no retrace
        first = servers[0][0]
        _, ds4, cols4 = servers[1]
        env4, _, hints4 = place_on_mesh(cell.config, ds4)
        first.env, first.hints = env4, hints4
        prep = query.prepare(cols4)
        traces = CG.TRACE_STATS.get("traces", 0)
        # the heaviest part's rows kept, then left out
        heavy = heaviest_price(cols4)
        for t in (heavy, heavy + 1.0):
            ok, out, *_ = first.submit(first.request({"threshold": t}))
            got = query.answer_rows(out, first.top)
            want = query.reference(prep, {"threshold": t})
            assert ok and all(np.array_equal(g, w) for g, w in zip(got, want))
        assert CG.TRACE_STATS.get("traces", 0) == traces
        print("OK")
    """)
    assert out.strip().endswith("OK")

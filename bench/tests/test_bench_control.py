"""What decides ``correct`` fails what it must: the float32 control put
in the engine's place, an answer altered where it is produced, and (on
four virtual devices) the exchange between chips left out and the heavy
part's rows misplaced."""

import pytest

from benchtest import SF1, X4, run_four_devices, run_small, small_cell


def test_float32_control_reads_not_correct(tmp_path):
    # 30,000 orders: a day's revenue passes 2^24, which float32 rounds
    res = run_small(small_cell(SF1, 0.02), 2**31 + 3, tmp_path,
                    control="f32")
    assert not res["correct"]
    assert res["check"]["wrong_answers"]["value"] >= 1
    assert res["check"]["worst_total_gap_cents"]["value"] > 0


def _alter_answers(monkeypatch):
    from repro.serve.query_service import QueryService
    stored, execute = QueryService.execute_stored, QueryService.execute

    def bump(out):
        top = [k for k in out if "total" in out[k].data][0]
        bag = out[top]
        bag.data["total"] = bag.data["total"] + bag.valid.astype("float64")
        return out

    monkeypatch.setattr(QueryService, "execute_stored",
                        lambda *a, **k: bump(stored(*a, **k)))
    monkeypatch.setattr(QueryService, "execute",
                        lambda *a, **k: bump(execute(*a, **k)))


def test_answer_altered_where_produced_reads_not_correct(tmp_path,
                                                         monkeypatch):
    _alter_answers(monkeypatch)
    res = run_small(small_cell(SF1, 0.001), 5, tmp_path)
    assert not res["correct"]
    assert res["check"]["wrong_answers"]["value"] == res["attempted"]


@pytest.mark.parametrize("fault", ["exchange_left_out", "answer_altered",
                                   "heavy_rows_misplaced"])
def test_four_device_faults_read_not_correct(fault):
    out = run_four_devices("""
        import tempfile
        import jax
        fault = %r
        cell = small_cell(X4, 2000 / 1_500_000)
        if fault == "exchange_left_out":
            # every chip keeps the rows it would have sent
            jax.lax.all_to_all = lambda x, *a, **k: x
        elif fault == "heavy_rows_misplaced":
            # the heaviest part's row reaches one chip only, while its
            # line items (61%% of all) are spread over the four by their
            # order key; every request keeps every part
            import numpy as np
            from harness import traffic
            from repro.columnar.table import FlatBag
            from repro.exec import dist
            cols, _, _ = store_small(cell, 7, tempfile.mkdtemp())
            heavy = int(np.argmax(np.bincount(cols["Lineitem.pid"])))
            local_join = dist.DistContext._local_join
            def heavy_part_on_chip_0_only(self, left, right, left_on,
                                          right_on, *a):
                for c in right_on:
                    if c.endswith(".pid"):
                        away = ((right.data[c] == heavy)
                                & (jax.lax.axis_index(self.axis) != 0))
                        right = FlatBag(right.data, right.valid & ~away)
                return local_join(self, left, right, left_on, right_on, *a)
            dist.DistContext._local_join = heavy_part_on_chip_0_only
            traffic.sampler = lambda *a, **k: lambda: {"threshold": 0.0}
        else:
            from repro.serve.query_service import QueryService
            execute = QueryService.execute
            def bumped(*a, **k):
                out = execute(*a, **k)
                for bag in out.values():
                    if "total" in bag.data:
                        bag.data["total"] = bag.data["total"] + 1.0
                return out
            QueryService.execute = bumped
        res = run_small(cell, 7, tempfile.mkdtemp())
        print("CORRECT", res["correct"], res["check"]["wrong_answers"])
    """ % fault)
    assert "CORRECT False" in out

"""The generator keeps TPC-H's ratios, columns and domains and is a
function of its seed; the store is written once per (configuration,
seed) and rewritten when torn."""

import os

import numpy as np
import pytest

from benchtest import SF1, X4, small_cell, store_small
from harness import store


def _gen(workload, sf, seed, **cfg):
    cell = small_cell(workload, sf)
    cell.config.update(cfg)
    return cell.dataset(), cell.dataset().generate(cell.config, seed)


def test_same_seed_same_data_other_seed_other_data():
    _, a = _gen(SF1, 0.002, 2**31 + 7)
    _, b = _gen(SF1, 0.002, 2**31 + 7)
    _, c = _gen(SF1, 0.002, 2**31 + 8)
    assert a.keys() == b.keys()
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["Lineitem.pid"][:500], c["Lineitem.pid"][:500])


@pytest.mark.parametrize("workload,sf", [(SF1, 0.01), (X4, 0.01)])
def test_tpch_ratios_and_domains(workload, sf):
    tpch, cols = _gen(workload, sf, 3)
    rows = store.table_rows(cols)
    assert rows["Orders"] == 15_000 and rows["Part"] == 2_000
    # every column of TPC-H's LINEITEM, PART and ORDERS (Clause 1.4)
    assert [len([k for k in cols if k.startswith(t + ".")])
            for t in ("Lineitem", "Part", "Orders")] == [16, 9, 9]
    _, per_order = np.unique(cols["Lineitem.oid"], return_counts=True)
    assert per_order.min() == 1 and per_order.max() == 7
    assert abs(per_order.mean() - 4.0) < 0.05
    d = cols["Orders.odate"] - tpch.START_DATE
    assert d.min() >= 0 and d.max() <= 2405 and d.max() > 2390
    q = cols["Lineitem.qty"]
    assert q.min() == 1 and q.max() == 50 and np.all(q == np.round(q))
    pk = cols["Part.pid"]
    assert np.array_equal(cols["Part.price"],
                          (90000 + (pk // 10) % 20001 + 100 * (pk % 1000))
                          .astype(np.float64))
    assert np.all(cols["Orders.cid"] % 3 != 0)
    assert cols["Orders.cid"].max() <= 150_000 * sf
    top = np.bincount(cols["Lineitem.pid"]).max() / q.size
    if workload == X4:
        # Zipf 2.0 over 2,000 keys: rank 1 holds 6 / pi^2 of the rows
        assert abs(top - 0.608) < 0.02
    else:
        assert top < 0.01


def test_derived_columns_follow_clause_4_2_3():
    tpch, cols = _gen(X4, 0.005, 2**31 + 11)
    okey = cols["Orders.oid"]
    assert np.array_equal(okey[:10], [1, 2, 3, 4, 5, 6, 7, 8, 33, 34])
    order = np.searchsorted(okey, cols["Lineitem.oid"])
    assert np.array_equal(okey[order], cols["Lineitem.oid"])
    price = cols["Part.price"][cols["Lineitem.pid"] - 1]
    assert np.array_equal(cols["Lineitem.eprice"],
                          cols["Lineitem.qty"] * price)
    odate = cols["Orders.odate"][order]
    ship, rcpt = cols["Lineitem.sdate"], cols["Lineitem.rdate"]
    assert np.all((ship - odate >= 1) & (ship - odate <= 121))
    assert np.all((rcpt - ship >= 1) & (rcpt - ship <= 30))
    late = rcpt > tpch.CURRENT_DATE
    assert np.all((cols["Lineitem.rflag"] == tpch.FLAG_N) == late)
    assert np.array_equal(cols["Lineitem.lstatus"] == tpch.STATUS_O,
                          ship > tpch.CURRENT_DATE)
    first = np.r_[True, order[1:] != order[:-1]]
    assert np.all(cols["Lineitem.lnum"][first] == 1)
    n_open = np.bincount(order, weights=cols["Lineitem.lstatus"])
    n_lines = np.bincount(order)
    want = np.where(n_open == 0, tpch.STATUS_F,
                    np.where(n_open == n_lines, tpch.STATUS_O, tpch.STATUS_P))
    assert np.array_equal(cols["Orders.ostatus"], want)
    s = tpch.sizes(small_cell(X4, 0.005).config)["suppliers"]
    assert cols["Lineitem.sid"].min() >= 1 and cols["Lineitem.sid"].max() <= s
    # the heaviest part is the seed's, not the price formula's cheapest
    heavy = np.argmax(np.bincount(cols["Lineitem.pid"]))
    assert heavy != 1


def test_listed_tables_only():
    tpch, cols = _gen(SF1, 0.001, 5,
                      tables=["Customer", "Nation", "Region"])
    assert store.table_rows(cols) == {"Customer": 150, "Nation": 25,
                                      "Region": 5}
    assert np.all(np.isin(cols["Customer.nid"], cols["Nation.nid"]))
    assert set(tpch.types(["Customer"])) == {"Customer"}


def test_store_written_once_reopened_and_rewritten_when_torn(tmp_path):
    cell = small_cell(SF1, 0.001)
    root = str(tmp_path)
    cols, ds, types = store_small(cell, 11, root, 1024)
    assert ds.parts["Lineitem__F"].rows == cols["Lineitem.oid"].size
    _, written = store.open_or_write(root, "seed11", cols, types, 1024)
    assert not written
    footer = os.path.join(root, "seed11", "footer.json")
    with open(footer, "r+") as f:
        f.truncate(os.path.getsize(footer) // 2)
    ds, written = store.open_or_write(root, "seed11", cols, types, 1024)
    assert written and ds.parts["Orders__F"].rows == 1500
    # another seed's data replaces it
    store_small(cell, 12, root, 1024)
    assert os.listdir(root) == ["seed12"]


def test_traffic_draws_follow_the_seed_and_the_specs():
    from harness import traffic
    cols = {"Part.price": np.array([90000.0, 90100.0, 91000.0])}
    mix = {"params": {"t": {"uniform_int_over": "Part.price"}}}
    draw, again = (traffic.sampler(mix, cols, 2**31 + 1),
                   traffic.sampler(mix, cols, 2**31 + 1))
    seq = [draw() for _ in range(50)]
    assert seq == [again() for _ in range(50)]
    assert all(90000 <= p["t"] <= 91000 and p["t"] == int(p["t"])
               for p in seq)
    assert len({p["t"] for p in seq}) > 10
    assert traffic.first(mix, cols, 2**31 + 1) != seq[0]
    with pytest.raises(ValueError):
        traffic.sampler({"params": {"t": {"const": 7}}}, cols, 1)

"""Query family ``revenue``: Lineitem x Part x Orders, revenue per order
date over the parts priced at least ``threshold``.

    for l in Lineitem, p in Part, o in Orders
      if l.pid == p.pid && p.price >= threshold && l.oid == o.oid
      sumBy^{total}_{odate} <odate := o.odate, total := l.qty * p.price>

``threshold`` is a constant that the engine's plan cache lifts to a
runtime parameter, so every threshold is one compiled family.

The plain reference below imports nothing of the engine: NumPy joins by
sorted lookup and sums per date with ``np.bincount`` in float64.
``compare`` decides whether a served answer equals it.
"""

from __future__ import annotations

import numpy as np

def program(params: dict, types: dict):
    """The NRC program of one request over tables of the engine types
    ``types``."""
    from repro.core import nrc as N
    threshold = float(params["threshold"])
    L = N.Var("Lineitem", types["Lineitem"])
    P = N.Var("Part", types["Part"])
    O = N.Var("Orders", types["Orders"])

    def per_item(l):
        return N.for_in("p", P, lambda p: N.IfThen(
            N.BoolOp("&&", l.pid.eq(p.pid),
                     p.price.ge(N.Const(threshold, N.REAL))),
            N.for_in("o", O, lambda o: N.IfThen(
                l.oid.eq(o.oid),
                N.Singleton(N.record(odate=o.odate,
                                     total=l.qty * p.price))))))

    q = N.SumBy(N.for_in("l", L, per_item), keys=("odate",),
                values=("total",))
    return N.Program([N.Assignment("Q", q)])


def answer_rows(outputs: dict, top: str) -> tuple:
    """(odate, total) rows of the served answer (host arrays), sorted
    by date."""
    bag = outputs[top]
    valid = np.asarray(bag.valid)
    odate = np.asarray(bag.data["odate"])[valid]
    total = np.asarray(bag.data["total"])[valid]
    order = np.argsort(odate, kind="stable")
    return odate[order], total[order]


GAP = "worst_total_gap_cents"


def compare(got: tuple, want: tuple) -> tuple:
    """(rows equal bit for bit, worst |served - reference| total of a
    date, a date missing on one side counting its whole total)."""
    dates = np.union1d(got[0], want[0])

    def on(side):
        out = np.zeros(dates.size)
        out[np.searchsorted(dates, side[0])] = side[1]
        return out

    equal = (got[0].shape == want[0].shape
             and np.array_equal(got[0], want[0])
             and np.array_equal(got[1], want[1]))
    gap = float(np.max(np.abs(on(got) - on(want)))) if dates.size else 0.0
    return equal, gap


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _lookup(keys: np.ndarray, values: list, probe: np.ndarray) -> list:
    """values[i] where keys[i] == probe, for unique ``keys``; every
    probe must match."""
    order = np.argsort(keys, kind="stable")
    sk = keys[order]
    if sk.size > 1 and not np.all(sk[1:] != sk[:-1]):
        raise AssertionError("lookup keys are not unique")
    pos = np.clip(np.searchsorted(sk, probe), 0, max(sk.size - 1, 0))
    if probe.size and not np.array_equal(sk[pos], probe):
        raise AssertionError("a probe key has no match")
    return [v[order][pos] for v in values]


def prepare(cols: dict) -> dict:
    """The joins, once per dataset: each line item's price, date and
    revenue."""
    (price,) = _lookup(cols["Part.pid"], [cols["Part.price"]],
                       cols["Lineitem.pid"])
    (odate,) = _lookup(cols["Orders.oid"], [cols["Orders.odate"]],
                       cols["Lineitem.oid"])
    return {"price": price, "odate": odate,
            "revenue": cols["Lineitem.qty"] * price}


def reference(prep: dict, params: dict) -> tuple:
    """(odate, total) of one request, sorted by date, in float64."""
    keep = prep["price"] >= float(params["threshold"])
    dates, inv = np.unique(prep["odate"][keep], return_inverse=True)
    totals = np.bincount(inv, weights=prep["revenue"][keep],
                         minlength=dates.size)
    return dates, totals


def control(prep: dict, params: dict) -> tuple:
    """The reference one precision lower: float32 revenue summed in
    float32 on the default device (the step that would tempt a later
    change). A day's revenue passes 2^24 at the benchmark's scales, so
    this must read as not correct."""
    import jax
    import jax.numpy as jnp
    keep = prep["price"] >= float(params["threshold"])
    dates, inv = np.unique(prep["odate"][keep], return_inverse=True)
    if dates.size == 0:
        return dates, np.zeros(0)
    rev = jnp.asarray(prep["revenue"][keep], dtype=jnp.float32)
    totals = jax.ops.segment_sum(rev, jnp.asarray(inv, dtype=jnp.int32),
                                 num_segments=int(dates.size))
    return dates, np.asarray(jax.device_get(totals)).astype(np.float64)

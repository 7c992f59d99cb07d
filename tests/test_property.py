"""Hypothesis property tests on the system's invariants.

Central property: for random nested databases and the benchmark query
family, the shredded route (shred -> materialize -> execute -> unshred)
equals direct NRC evaluation; value shredding round-trips; columnar ops
match their Python semantics."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import codegen as CG
from repro.core import interpreter as I
from repro.core import materialization as M
from repro.core import nrc as N
from repro.columnar.table import FlatBag
from repro.exec import ops as X

from helpers import COP_T, INPUT_TYPES, PART_T, running_example_query


# -- strategies -------------------------------------------------------------

@st.composite
def cop_db(draw):
    n_parts = draw(st.integers(1, 8))
    parts = [{"pid": i, "pname": 100 + i,
              "price": float(draw(st.integers(1, 9)))}
             for i in range(1, n_parts + 1)]
    n_cust = draw(st.integers(0, 5))
    cops = []
    for c in range(n_cust):
        n_ord = draw(st.integers(0, 3))
        orders = []
        for o in range(n_ord):
            n_it = draw(st.integers(0, 4))
            items = [{"pid": draw(st.integers(1, n_parts + 2)),  # some misses
                      "qty": float(draw(st.integers(1, 5)))}
                     for _ in range(n_it)]
            orders.append({"odate": 20200000 + o, "oparts": items})
        cops.append({"cname": 1000 + c, "corders": orders})
    return {"COP": cops, "Part": parts}


@settings(max_examples=25, deadline=None)
@given(cop_db(), st.booleans())
def test_shred_equals_direct(db, domain_elim):
    q = running_example_query()
    direct = I.eval_expr(q, db)
    prog = N.Program([N.Assignment("Q", q)])
    sp = M.shred_program(prog, INPUT_TYPES, domain_elimination=domain_elim)
    env = M.shredded_input_env(db, INPUT_TYPES)
    env = I.eval_program(sp.program, env)
    got = M.unshred_from_env(env, sp.manifests["Q"])
    assert I.bags_equal(direct, got)


@settings(max_examples=25, deadline=None)
@given(cop_db())
def test_value_shred_roundtrip(db):
    shredded = I.shred_value(db["COP"], COP_T, root="COP")
    back = I.unshred_value(shredded, COP_T)
    assert I.bags_equal(db["COP"], back)


# -- columnar op semantics ----------------------------------------------------

@st.composite
def keyed_rows(draw):
    n = draw(st.integers(1, 24))
    rows = [{"k": draw(st.integers(0, 6)), "v": float(draw(st.integers(0, 9)))}
            for _ in range(n)]
    return rows


@settings(max_examples=30, deadline=None)
@given(keyed_rows(), st.integers(0, 8))
def test_sum_by_matches_python(rows, extra_cap):
    bag = FlatBag.from_rows(rows, {"k": "int", "v": "real"},
                            capacity=len(rows) + extra_cap)
    out = X.sum_by(bag, ("k",), ("v",)).to_rows()
    want = {}
    for r in rows:
        want[r["k"]] = want.get(r["k"], 0.0) + r["v"]
    got = {r["k"]: r["v"] for r in out}
    assert got == want


@settings(max_examples=30, deadline=None)
@given(keyed_rows())
def test_dedup_matches_python(rows):
    bag = FlatBag.from_rows(rows, {"k": "int", "v": "real"})
    out = X.dedup(bag, ("k", "v")).to_rows()
    want = {(r["k"], r["v"]) for r in rows}
    got = {(r["k"], r["v"]) for r in out}
    assert got == want and len(out) == len(want)


@settings(max_examples=30, deadline=None)
@given(keyed_rows(), st.integers(1, 6))
def test_fk_join_matches_python(rows, n_right):
    right_rows = [{"k": i, "w": float(i * 10)} for i in range(n_right)]
    left = FlatBag.from_rows(rows, {"k": "int", "v": "real"})
    right = FlatBag.from_rows(right_rows, {"k": "int", "w": "real"})
    out = X.fk_join(left, right, ("k",), ("k",), how="inner").to_rows()
    want = sorted((r["k"], r["v"], float(r["k"] * 10))
                  for r in rows if r["k"] < n_right)
    got = sorted((r["k"], r["v"], r["w"]) for r in out)
    assert got == want


I64 = np.iinfo(np.int64)


def _probe_case(kind: str, n: int, r: int, seed: int):
    """(sorted build keys, probe keys) for one case of the probe test."""
    rng = np.random.default_rng(seed)
    if kind == "dup":
        build = rng.integers(-4, 4, r)
        probe = rng.integers(-5, 5, n)
    else:
        build = rng.integers(I64.min, I64.max, r, dtype=np.int64)
        probe = rng.integers(I64.min, I64.max, n, dtype=np.int64)
    if kind == "padded":
        # _build_side's invalid rows: I64_MAX after the valid keys
        build[r // 2:] = I64.max
    if kind == "extremes":
        build[:2] = I64.min
        build[-2:] = I64.max
        probe[:4] = [I64.min, I64.max, I64.min, I64.max]
    if r and kind != "extremes":
        # probes that hit build keys exactly
        probe[: n // 2] = rng.choice(build, n // 2)
    return (jnp.asarray(np.sort(build).astype(np.int64)),
            jnp.asarray(probe.astype(np.int64)))


@pytest.mark.parametrize("kind,n,r,merged", [
    ("empty_build", 9, 0, False),
    ("empty_probe", 0, 10, False),
    ("one_row_build", 7, 1, True),
    ("dup", 50, 40, True),
    ("padded", 100, 64, True),
    ("extremes", 33, 12, True),
    ("non_pow2", 1000, 333, True),
    ("probe_smaller", 5, 1000, False),
    ("probe_larger", 4096, 17, True),
])
def test_probe_rank_equals_searchsorted(kind, n, r, merged):
    """The fk probe equals ``searchsorted(side="left")`` bit for bit on
    both sides of its size rule, and so does the co-sort alone."""
    build, probe = _probe_case(kind, n, r, seed=n * 1009 + r)
    want = np.asarray(jnp.searchsorted(build, probe, side="left"))
    got = X._probe_left(build, probe)
    assert X.SORT_STATS.get("merge_probe", 0) == int(merged)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), want)
    rank = X._merge_rank_left(build, probe)
    assert rank.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(rank), want)


@settings(max_examples=20, deadline=None)
@given(keyed_rows(), st.integers(1, 5))
def test_general_join_matches_python(rows, n_right):
    # right side with duplicate keys (M:N)
    right_rows = [{"k": i % 3, "w": float(i)} for i in range(n_right)]
    left = FlatBag.from_rows(rows, {"k": "int", "v": "real"})
    right = FlatBag.from_rows(right_rows, {"k": "int", "w": "real"})
    want = sorted((l["k"], l["v"], r["w"])
                  for l in rows for r in right_rows if l["k"] == r["k"])
    cap = max(len(want), 1)
    out, overflow = X.general_join(left, right, ("k",), ("k",), cap)
    got = sorted((r["k"], r["v"], r["w"]) for r in out.to_rows())
    assert int(overflow) == 0
    assert got == want


@settings(max_examples=20, deadline=None)
@given(keyed_rows())
def test_nest_level_partitions_rows(rows):
    bag = FlatBag.from_rows(rows, {"k": "int", "v": "real"})
    parents, children = X.nest_level(bag, ("k",), ("v",), "lbl")
    prows = parents.to_rows()
    crows = children.to_rows()
    assert {p["k"] for p in prows} == {r["k"] for r in rows}
    # every child's label maps to exactly one parent's key group
    lbl_to_k = {p["lbl"]: p["k"] for p in prows}
    got = sorted((lbl_to_k[c["lbl"]], c["v"]) for c in crows)
    want = sorted((r["k"], r["v"]) for r in rows)
    assert got == want


# -- grouping by sorts and scans, against the gathers and scatters ----------

def _tail_case(case: str):
    """(bag, invalid_last) for one case of the segment-tail test: rows
    clustered by (k, kf) among the valid ones, int64 and integer-valued
    float64 values."""
    rng = np.random.default_rng(len(case))
    n = {"one_row": 1, "non_pow2": 1000}.get(case, 64)
    k = np.sort(rng.integers(0, 5, n))
    if case == "one_segment":
        k[:] = 3
    if case == "own_segments":
        k = np.arange(n)
    kf = np.where(k % 2 == 0, 2.0, -1.0) * (k // 3)   # clustered with k
    valid = np.ones(n, bool)
    if case == "all_invalid":
        valid[:] = False
    if case == "leading_invalid":
        valid[:5] = False
    if case == "interleaved_invalid":
        valid = rng.random(n) < 0.6
    if case in ("trailing_invalid", "non_pow2", "own_segments"):
        valid[n * 2 // 3:] = False
    bag = FlatBag({"k": jnp.asarray(k), "kf": jnp.asarray(kf),
                   "i": jnp.asarray(rng.integers(-99, 99, n)),
                   "f": jnp.asarray(rng.integers(-99, 99, n)
                                    .astype(np.float64))},
                  jnp.asarray(valid))
    invalid_last = not (~valid[:-1] & valid[1:]).any()
    return bag, invalid_last


def _bits(a):
    a = np.asarray(a)
    return a.view(np.int64) if a.dtype == np.float64 else a


@pytest.mark.parametrize("case", [
    "all_invalid", "one_row", "leading_invalid", "interleaved_invalid",
    "trailing_invalid", "own_segments", "one_segment", "non_pow2"])
def test_segment_tail_equals_scatter_formula(case):
    """The scan-and-compaction tail equals the segment_min/segment_sum
    formula it replaced bit for bit, on both segment-id paths; rows past
    the segment count are zero."""
    from benchmarks.segment_tail import scatter_tail
    bag, invalid_last = _tail_case(case)
    cols, vals = ("k", "kf"), ("i", "f")
    seg = X._presorted_seg_ids(bag, cols, invalid_last)
    np.testing.assert_array_equal(
        np.asarray(seg), np.asarray(X._presorted_seg_ids(bag, cols, False)))
    assert X.SORT_STATS.get("seg_shift", 0) == int(invalid_last)
    exists, first_valid, firsts, summed = X._segment_firsts(
        bag, seg, cols, False, vals)
    assert X.SORT_STATS.get("segment_scan", 0) == 1
    w_exists, w_first_valid, w_firsts, w_summed = scatter_tail(
        bag, seg, cols, vals)
    ex = np.asarray(w_exists)
    np.testing.assert_array_equal(np.asarray(exists), ex)
    np.testing.assert_array_equal(np.asarray(first_valid),
                                  np.asarray(w_first_valid))
    for got, want in ((firsts, w_firsts), (summed, w_summed)):
        for c in want:
            assert got[c].dtype == want[c].dtype
            np.testing.assert_array_equal(_bits(got[c])[ex],
                                          _bits(want[c])[ex])
            np.testing.assert_array_equal(_bits(got[c])[~ex], 0)


def _sort_case(case: str):
    rng = np.random.default_rng(len(case) + 100)
    n = 77
    valid = rng.random(n) < 0.7
    if case == "all_invalid":
        valid[:] = False
    key = rng.integers(0, 4, n)
    kf = rng.integers(-2, 3, n).astype(np.float64)
    if case == "float_bits":
        special = np.array([0x0000000000000000, 0x8000000000000000,
                            0x7FF8000000000000, 0x7FF8000000000001,
                            0xFFF8000000000000, 0x7FF0000000000000],
                           dtype=np.uint64).view(np.float64)
        kf = rng.choice(special, n)
    return FlatBag({"key": jnp.asarray(key), "kf": jnp.asarray(kf),
                    "v": jnp.asarray(rng.integers(-9, 9, n)
                                     .astype(np.float64)),
                    "w": jnp.asarray(rng.integers(0, 9, n)
                                     .astype(np.int32)),
                    "b": jnp.asarray(rng.random(n) < 0.5)},
                   jnp.asarray(valid))


@pytest.mark.parametrize("case,cols", [
    ("duplicates", ("key",)), ("duplicates", ("key", "kf")),
    ("float_bits", ("kf",)), ("float_bits", ("kf", "key")),
    ("all_invalid", ("key", "kf"))])
def test_lexsort_carries_columns_like_argsort_and_gathers(case, cols):
    """One sort that carries the columns equals a stable argsort by
    (invalid-last, key bit-views) and a gather of every column, bit for
    bit on every row, NaN payloads and signed zeros included."""
    from benchmarks.segment_tail import gather_lexsort
    bag = _sort_case(case)
    got = X._lexsort(bag, cols)
    want = gather_lexsort(bag, cols)
    np.testing.assert_array_equal(np.asarray(got.valid),
                                  np.asarray(want.valid))
    assert list(got.data) == list(bag.data)
    for c, a in want.data.items():
        assert got.data[c].dtype == a.dtype
        np.testing.assert_array_equal(_bits(got.data[c]), _bits(a))
    assert got.props.invalid_last and got.props.sorted_by == cols

"""Sort-order-aware fused executor: physical properties, sort sharing,
plan ordering pass, and general_join overflow accounting.

The headline acceptance: a ``join -> sum_by -> nest_level`` pipeline on
shared keys sorts the probe side EXACTLY once (asserted through the
SORT_STATS hook), and produces the same answer as a plain-Python
join -> group-sum -> nest over the input rows."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.columnar.table import FlatBag
from repro.core import nrc as N
from repro.core import plans as P
from repro.exec import ops as X


def _mk_left(n=24, seed=0):
    rng = np.random.RandomState(seed)
    rows = [{"k": int(rng.randint(0, 6)), "g": int(rng.randint(0, 4)),
             "v": float(rng.randint(0, 9))} for _ in range(n)]
    return FlatBag.from_rows(rows, {"k": "int", "g": "int", "v": "real"},
                             capacity=n + 4), rows


def _mk_right(n=6):
    return FlatBag.from_rows([{"k": i, "w": float(i * 10)}
                              for i in range(n)],
                             {"k": "int", "w": "real"})


def _pipeline(left, right, use_kernel=False):
    j = X.fk_join(left, right, ("k",), ("k",), use_kernel=use_kernel)
    agg = X.sum_by(j, ("g", "k"), ("v", "w"), use_kernel=use_kernel)
    parents, children = X.nest_level(agg, ("g",), ("k", "v", "w"), "lbl",
                                     use_kernel=use_kernel)
    lbl = {r["lbl"]: r["g"] for r in parents.to_rows()}
    return sorted((lbl[r["lbl"]], r["k"], r["v"], r["w"])
                  for r in children.to_rows())


def _reference(rows, n_right):
    """``_pipeline`` in plain Python over the probe side's valid rows:
    join on k with ``_mk_right(n_right)`` (keys 0..n_right-1, w = 10k),
    sum v and w per (g, k), and nest the groups under g."""
    sums = {}
    for r in rows:
        if r["k"] < n_right:
            acc = sums.setdefault((r["g"], r["k"]), [0.0, 0.0])
            acc[0] += r["v"]
            acc[1] += float(r["k"] * 10)
    return sorted((g, k, v, w) for (g, k), (v, w) in sums.items())


# -- acceptance: one probe-side sort for join -> sum_by -> nest_level --------

@pytest.mark.parametrize("use_kernel", [False, True])
def test_pipeline_sorts_probe_side_exactly_once(use_kernel):
    left, rows = _mk_left()
    fused = _pipeline(left, _mk_right(6), use_kernel=use_kernel)
    assert X.SORT_STATS.get("lexsort", 0) == 1, X.SORT_STATS
    assert X.SORT_STATS.get("sort_skipped", 0) >= 1, X.SORT_STATS
    # the one argsort is the (small) build side, never the probe side
    assert X.SORT_STATS.get("build_argsort", 0) <= 1, X.SORT_STATS
    assert fused == _reference(rows, 6)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 40), st.integers(1, 8), st.integers(0, 5))
def test_fused_pipeline_matches_reference(n, n_right, seed):
    left, rows = _mk_left(n, seed)
    fused = _pipeline(left, _mk_right(n_right))
    assert fused == _reference(rows, n_right)


# -- physical props propagation ----------------------------------------------

def test_sum_by_delivers_sorted_by_keys():
    bag, _ = _mk_left()
    out = X.sum_by(bag, ("g", "k"), ("v",))
    assert out.props.sorted_by == ("g", "k")
    assert out.props.invalid_last
    # grouping by the PREFIX reuses the sort
    X.reset_sort_stats()
    X.sum_by(out, ("g",), ("v",))
    assert "lexsort" not in X.SORT_STATS


def test_mask_preserves_order_drops_invalid_last():
    bag, _ = _mk_left()
    out = X.sum_by(bag, ("k",), ("v",))
    masked = out.mask(out.col("v") > 3)
    assert masked.props.sorted_by == ("k",)
    assert not masked.props.invalid_last
    X.reset_sort_stats()
    X.dedup(masked, ("k",))           # still no sort needed
    assert "lexsort" not in X.SORT_STATS


def test_with_columns_overwrite_invalidates():
    bag, _ = _mk_left()
    out = X.sum_by(bag, ("k",), ("v",))
    kept = out.with_columns(extra=out.col("v") * 2)
    assert kept.props.sorted_by == ("k",)
    clobbered = out.with_columns(k=out.col("v").astype(jnp.int64))
    assert clobbered.props.sorted_by is None


def test_build_argsort_cached_across_joins():
    left, _ = _mk_left()
    right = _mk_right()
    X.reset_sort_stats()
    X.fk_join(left, right, ("k",), ("k",))
    X.fk_join(left, right, ("k",), ("k",))
    assert X.SORT_STATS.get("build_argsort", 0) == 1
    assert X.SORT_STATS.get("build_reuse", 0) == 1
    assert X.SORT_STATS.get("key_reuse", 0) >= 1   # probe key packed once


def test_sorted_build_side_skips_argsort():
    left, _ = _mk_left()
    # sum_by output is unique + sorted on its key: a free build side
    raw = FlatBag.from_rows([{"k": i % 5, "w": float(i)} for i in range(12)],
                            {"k": "int", "w": "real"})
    right = X.sum_by(raw, ("k",), ("w",))
    X.reset_sort_stats()
    X.fk_join(left, right, ("k",), ("k",))
    assert X.SORT_STATS.get("build_argsort", 0) == 0
    assert X.SORT_STATS.get("build_sort_skipped", 0) == 1


def test_fk_join_probes_by_co_sort_without_a_loop():
    """At 2^12 probe rows against 2^10 build rows the default path ranks
    by the co-sort: no ``while`` (binary search) in the compiled
    program, one ``merge_probe`` per join, and the joins' answers."""
    import jax
    rng = np.random.default_rng(7)
    n, r = 2**12, 2**10
    lk = jnp.asarray(rng.integers(0, 2 * r, n))
    lv = jnp.asarray(rng.integers(0, 100, n))
    rk = jnp.asarray(rng.permutation(2 * r)[:r])   # unique, unsorted
    rv = jnp.asarray(rng.integers(0, 100, r))
    valid_r = jnp.asarray(rng.random(r) < 0.9)

    def two_joins(lk, lv, rk, rv, valid_r):
        left = FlatBag({"k": lk, "v": lv}, jnp.ones(n, bool))
        a = FlatBag({"k": rk, "a": rv}, valid_r)
        b = FlatBag({"k": rk, "b": rv * 2}, jnp.ones(r, bool))
        j = X.fk_join(X.fk_join(left, a, ("k",), ("k",)), b, ("k",), ("k",))
        return j.col("a"), j.col("b"), j.valid

    fn = jax.jit(two_joins)
    X.reset_sort_stats()
    a, b, valid = fn(lk, lv, rk, rv, valid_r)
    assert X.SORT_STATS.get("merge_probe", 0) == 2
    hlo = fn.lower(lk, lv, rk, rv, valid_r).compile().as_text()
    assert " while(" not in hlo
    row = {int(k): i for i, k in enumerate(np.asarray(rk))}
    want = np.array([k in row and bool(valid_r[row[k]])
                     for k in np.asarray(lk).tolist()])
    np.testing.assert_array_equal(np.asarray(valid), want)
    idx = [row[k] for k in np.asarray(lk)[want].tolist()]
    np.testing.assert_array_equal(np.asarray(a)[want], np.asarray(rv)[idx])
    np.testing.assert_array_equal(np.asarray(b)[want],
                                  2 * np.asarray(rv)[idx])


def test_general_join_preserves_probe_order():
    left = X.sum_by(_mk_left()[0], ("g", "k"), ("v",))
    right = _mk_right()
    out, _ = X.general_join(left, right, ("k",), ("k",), 64)
    assert out.props.sorted_by == ("g", "k")
    assert out.props.invalid_last


# -- plan-level ordering pass -------------------------------------------------

def _scan_plan(bag, alias):
    return P.ScanP(bag, alias)


def test_push_order_reorders_keys_for_prefix_sharing():
    # dedup(g) above sum_by(keys incl g): keys get reordered g-first
    agg = P.SumAggP(_scan_plan("L", "l"), keys=("l.k", "l.g"),
                    vals=("l.v",))
    plan = P.push_order(P.DeDupP(agg, cols=("l.g",)))
    assert isinstance(plan, P.DeDupP)
    assert plan.child.keys[0] == "l.g"
    assert set(plan.child.keys) == {"l.g", "l.k"}
    P.annotate_orders(plan)
    assert plan.child.delivered_ord == plan.child.keys
    assert plan.required_ord == ("l.g",)


def test_push_order_fuses_join_agg():
    join = P.JoinP(_scan_plan("L", "l"), _scan_plan("R", "r"),
                   ("l.k",), ("r.k",))
    plan = P.push_order(P.SumAggP(join, keys=("l.g", "l.k"),
                                  vals=("l.v",)))
    assert isinstance(plan, P.FusedJoinAggP)
    assert P.delivered_order(plan) == ("l.g", "l.k")


def test_fused_join_agg_plan_executes_with_one_sort():
    left, rows = _mk_left()
    right = _mk_right()
    env = {"L": left, "R": right}
    join = P.JoinP(_scan_plan("L", "l"), _scan_plan("R", "r"),
                   ("l.k",), ("r.k",))
    plan = P.push_order(P.SumAggP(join, keys=("l.g", "l.k"),
                                  vals=("l.v", "r.w")))
    assert isinstance(plan, P.FusedJoinAggP)
    out = P.eval_plan(plan, env)
    assert X.SORT_STATS.get("lexsort", 0) == 1
    want = {}
    wmap = {i: float(i * 10) for i in range(right.capacity)}
    for r in rows:
        if r["k"] in wmap:
            key = (r["g"], r["k"])
            v, w = want.get(key, (0.0, 0.0))
            want[key] = (v + r["v"], w + wmap[r["k"]])
    got = {(r["l.g"], r["l.k"]): (r["l.v"], r["r.w"])
           for r in out.to_rows()}
    assert got == want


def test_scan_memo_shares_build_cache_across_assignments():
    left, _ = _mk_left()
    right = _mk_right()
    env = {"L": left, "R": right}
    join = P.JoinP(_scan_plan("L", "l"), _scan_plan("R", "r"),
                   ("l.k",), ("r.k",))
    P.eval_plan(join, env)
    P.eval_plan(join, env)   # second assignment scanning the same dict
    assert X.SORT_STATS.get("build_argsort", 0) == 1
    assert X.SORT_STATS.get("build_reuse", 0) == 1


# -- general_join overflow accounting ----------------------------------------

def _overflow_case(n_left, dup, cap):
    left = FlatBag.from_rows([{"k": i % 3, "v": float(i)}
                              for i in range(n_left)],
                             {"k": "int", "v": "real"})
    right = FlatBag.from_rows([{"k": i % 3, "w": float(i)}
                               for i in range(dup * 3)],
                              {"k": "int", "w": "real"})
    return X.general_join(left, right, ("k",), ("k",), cap)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_general_join_overflow_exact_count(use_kernel):
    n_left, dup = 9, 4     # every left row matches `dup` right rows
    total = n_left * dup
    for cap in (total, total - 1, total - 7, 1):
        left = FlatBag.from_rows([{"k": i % 3, "v": float(i)}
                                  for i in range(n_left)],
                                 {"k": "int", "v": "real"})
        right = FlatBag.from_rows([{"k": i % 3, "w": float(i)}
                                   for i in range(dup * 3)],
                                  {"k": "int", "w": "real"})
        out, overflow = X.general_join(left, right, ("k",), ("k",), cap,
                                       use_kernel=use_kernel)
        assert int(overflow) == max(total - cap, 0)
        assert int(out.count()) == min(total, cap)


def test_general_join_left_outer_counts_unmatched_rows():
    left = FlatBag.from_rows([{"k": i, "v": float(i)} for i in range(6)],
                             {"k": "int", "v": "real"})
    right = FlatBag.from_rows([{"k": 0, "w": 1.0}, {"k": 0, "w": 2.0}],
                              {"k": "int", "w": "real"})
    # k=0 matches twice, k=1..5 unmatched -> 1 row each: total 7
    out, overflow = X.general_join(left, right, ("k",), ("k",), 5,
                                   how="left_outer")
    assert int(overflow) == 2
    assert int(out.count()) == 5
    out2, ov2 = X.general_join(left, right, ("k",), ("k",), 16,
                               how="left_outer")
    assert int(ov2) == 0
    rows = out2.to_rows()
    assert sum(1 for r in rows if not r["__matched"]) == 5
    assert sum(1 for r in rows if r["__matched"]) == 2


def test_general_join_all_invalid_left():
    left = FlatBag.from_rows([], {"k": "int", "v": "real"}, capacity=4)
    right = _mk_right()
    out, overflow = X.general_join(left, right, ("k",), ("k",), 8)
    assert int(overflow) == 0
    assert int(out.count()) == 0


# -- distributed: key caches survive the exchange -----------------------------

def test_dist_join_reuses_shipped_keys():
    from repro.exec.dist import device_mesh_1d, run_distributed
    bag, rows = _mk_left(16)
    right = _mk_right(8)
    mesh = device_mesh_1d(1)

    def fn(env, ctx):
        X.reset_sort_stats()
        out = ctx.join(env["L"], env["R"], ("k",), ("k",))
        # both exchanges pack once and ship the packed key with the
        # rows, so the local join's probe pack AND build pack are cache
        # hits on the receiving side
        assert X.SORT_STATS.get("key_reuse", 0) >= 2, X.SORT_STATS
        return {"out": out}

    out, _ = run_distributed(fn, {"L": bag, "R": right}, mesh, jit=False)
    got = sorted((r["k"], r["v"], r["w"]) for r in out["out"].to_rows())
    want = sorted((r["k"], r["v"], float(r["k"] * 10)) for r in rows)
    assert got == want


def test_served_revenue_request_groups_by_shift_scan_and_sort(tmp_path):
    """One request of the served revenue family (query (b) over stored
    TPC-H) groups by the shifted compare, the segmented scan and the
    compaction sort, and its compiled program holds no scatter."""
    import chip_smoke as S
    from benchmarks.common import CATALOG
    from repro.data.generators import TPCH_TYPES, gen_tpch
    from repro.serve import QueryService
    types = {k: TPCH_TYPES[k] for k in ("Lineitem", "Orders", "Part")}
    ds = S.write_dataset(str(tmp_path), "t", gen_tpch(40, 0.0, 0), types,
                         64)
    svc = QueryService(types, catalog=CATALOG)
    X.reset_sort_stats()
    svc.execute_stored(S.query_b(50.0), ds)
    assert X.SORT_STATS.get("segment_scan", 0) >= 1, X.SORT_STATS
    assert X.SORT_STATS.get("seg_shift", 0) >= 1, X.SORT_STATS
    (entry,) = svc._cache.values()
    env = ds.load_env(columns={p: r.columns
                               for p, r in entry.storage_req.items()})
    hlo = entry.exe._fn.lower(env, entry.exe.bind()).compile().as_text()
    assert " scatter(" not in hlo

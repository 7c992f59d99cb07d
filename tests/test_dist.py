"""Distributed engine tests — run in a subprocess with 8 virtual devices
(XLA device count must be set before jax init; tests elsewhere keep the
default single device per the dry-run isolation rule)."""

import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(body: str) -> str:
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, %r)
        sys.path.insert(0, %r)
        import numpy as np
        import jax
        import repro
        from repro.core import nrc as N
        from repro.core import interpreter as I
        from repro.core import materialization as M
        from repro.core import codegen as CG
        from repro.core.plans import ExecSettings
        from repro.core.unnesting import Catalog
        from repro.exec.dist import device_mesh_1d, run_distributed
        from helpers import INPUT_TYPES, gen_cop, gen_parts, \
            running_example_query
    """) % (SRC, os.path.dirname(__file__)) + textwrap.dedent(body)
    res = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, f"STDOUT:{res.stdout}\nSTDERR:{res.stderr}"
    return res.stdout


@pytest.mark.slow
def test_distributed_shredded_route_matches_oracle():
    out = run_sub("""
        data = {"COP": gen_cop(n_cust=16, seed=2, zipf=0.6),
                "Part": gen_parts(29)}
        direct = I.eval_expr(running_example_query(), data)
        prog = N.Program([N.Assignment("Q", running_example_query())])
        sp = M.shred_program(prog, INPUT_TYPES, domain_elimination=True)
        catalog = Catalog(unique_keys={"Part__F": ("pid",)})
        env = CG.columnar_shred_inputs(data, INPUT_TYPES)
        PN = 8
        env = {k: b.resize(((b.capacity + PN - 1)//PN)*PN)
               for k, b in env.items()}
        mesh = device_mesh_1d(PN)
        # the planned skew path: the probe side's heavy keys come from a
        # streaming sketch of its join column, as a stored dataset's
        # footer would carry it
        from repro.core import skew as SK
        from repro.core.plans import SkewJoinP, _walk_plan
        probe = env["COP__D_corders_oparts"]
        pids = np.asarray(probe.col("pid"))[np.asarray(probe.valid)]
        sketch = SK.HeavyKeySketch(k=64)
        sketch.update(pids)
        stats = {"COP__D_corders_oparts": SK.TableStats(
            rows=len(pids), distinct={"pid": len(set(pids.tolist()))},
            heavy={"pid": sketch.heavy(threshold=0.025)})}
        plans = {False: CG.compile_program(sp, catalog),
                 True: CG.compile_program(sp, catalog, skew_stats=stats,
                                          skew_partitions=PN)}
        (sj,) = [n for _, p in plans[True].plans for n in _walk_plan(p)
                 if isinstance(n, SkewJoinP)]
        assert [k for k in sj.heavy_default
                if k != np.iinfo(np.int64).max] == [7], sj.heavy_default
        shuffles = {}
        for skew in (False, True):
            cp = plans[skew]
            def fn(env_local, ctx):
                out_env = CG.run_flat_program(cp, env_local,
                                              ExecSettings(dist=ctx))
                man = sp.manifests["Q"]
                names = [man.top] + list(man.dicts.values())
                return {k: out_env[k] for k in names}
            out, metrics = run_distributed(fn, env, mesh,
                                           cap_factor=16.0)
            man = sp.manifests["Q"]
            parts = {(): out[man.top]}
            for path, name in man.dicts.items():
                parts[path] = out[name]
            result = CG.parts_to_rows(parts, running_example_query().ty)
            assert I.bags_equal(direct, result), f"skew={skew} mismatch"
            shuffles[skew] = metrics["shuffle_rows"]
        # the planned skew join must shuffle strictly less on zipf data
        assert shuffles[True] < shuffles[False], shuffles
        print("OK", shuffles)
    """)
    assert "OK" in out


@pytest.mark.slow
def test_exchange_preserves_rows_and_detects_overflow():
    out = run_sub("""
        from repro.columnar.table import FlatBag
        import jax.numpy as jnp
        rows = [{"k": i % 13, "v": float(i)} for i in range(64)]
        bag = FlatBag.from_rows(rows, {"k": "int", "v": "real"},
                                capacity=64)
        mesh = device_mesh_1d(8)
        def fn(env, ctx):
            return {"out": ctx.exchange(env["bag"], ("k",))}
        out, metrics = run_distributed(fn, {"bag": bag}, mesh,
                                       cap_factor=16.0)
        got = sorted((r["k"], r["v"]) for r in out["out"].to_rows())
        want = sorted((r["k"], r["v"]) for r in rows)
        assert got == want, (got, want)
        assert metrics["overflow_rows"] == 0
        # tight capacity must overflow (and count it) on skewed keys
        rows2 = [{"k": 0, "v": float(i)} for i in range(64)]
        bag2 = FlatBag.from_rows(rows2, {"k": "int", "v": "real"},
                                 capacity=64)
        out2, m2 = run_distributed(fn, {"bag": bag2}, mesh,
                                   cap_factor=1.0)
        assert m2["overflow_rows"] > 0
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_distributed_sum_by_and_dedup():
    out = run_sub("""
        from repro.columnar.table import FlatBag
        rows = [{"k": i % 5, "v": 1.0} for i in range(40)]
        bag = FlatBag.from_rows(rows, {"k": "int", "v": "real"},
                                capacity=40)
        mesh = device_mesh_1d(8)
        def fn(env, ctx):
            return {"s": ctx.sum_by(env["bag"], ("k",), ("v",)),
                    "d": ctx.dedup(env["bag"], ("k",))}
        out, metrics = run_distributed(fn, {"bag": bag}, mesh,
                                       cap_factor=16.0)
        s = {r["k"]: r["v"] for r in out["s"].to_rows()}
        assert s == {k: 8.0 for k in range(5)}, s
        d = sorted(r["k"] for r in out["d"].to_rows())
        assert d == list(range(5)), d
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_exchange_roundtrip_property():
    """Hypothesis property (via the tier-1 shim): the packed exchange
    preserves the multiset of valid rows for random dtypes / validity
    patterns, and overflows nothing at generous capacity."""
    out = run_sub("""
        import collections
        import jax.numpy as jnp
        import _hypothesis_shim as hyp
        st = hyp.strategies
        from repro.columnar.table import FlatBag
        mesh = device_mesh_1d(8)

        def fn(env, ctx):
            return {"out": ctx.exchange(env["bag"], ("k",))}

        @hyp.settings(max_examples=6, deadline=None)
        @hyp.given(st.integers(1, 12),
                   st.sampled_from(["int", "real", "string", "bool"]),
                   st.integers(0, 3), st.floats(0.2, 1.0))
        def check(n_keys, kind, seed, valid_frac):
            cap = 48
            rng = np.random.RandomState(seed)
            keys = jnp.asarray(rng.randint(0, n_keys, cap), jnp.int64)
            if kind == "int":
                v = jnp.asarray(rng.randint(-50, 50, cap), jnp.int64)
            elif kind == "real":
                v = jnp.asarray(rng.randn(cap), jnp.float64)
            elif kind == "string":
                v = jnp.asarray(rng.randint(0, 5, cap), jnp.int32)
            else:
                v = jnp.asarray(rng.randint(0, 2, cap), bool)
            valid = jnp.asarray(rng.rand(cap) < valid_frac)
            bag = FlatBag({"k": keys, "v": v}, valid)
            before = collections.Counter(
                (int(k), float(x)) for k, x, ok in
                zip(keys, v.astype(jnp.float64), valid) if ok)
            out, m = run_distributed(fn, {"bag": bag}, mesh,
                                     cap_factor=16.0)
            ob = out["out"]
            after = collections.Counter(
                (int(k), float(x)) for k, x, ok in
                zip(ob.col("k"), ob.col("v").astype(jnp.float64),
                    ob.valid) if ok)
            assert before == after, (kind, seed, before, after)
            assert m["overflow_rows"] == 0, m
            assert m["shuffle_collectives"] == 1, m

        check()
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_exchange_overflow_edge_and_adaptive():
    out = run_sub("""
        from repro.columnar.table import FlatBag
        rows = [{"k": 0, "v": float(i)} for i in range(64)]
        bag = FlatBag.from_rows(rows, {"k": "int", "v": "real"},
                                capacity=64)
        mesh = device_mesh_1d(8)
        def fn(env, ctx):
            return {"out": ctx.exchange(env["bag"], ("k",))}
        # bucket exactly equal to the per-sender count: everything fits
        out, m = run_distributed(fn, {"bag": bag}, mesh, cap_factor=8.0)
        assert m["overflow_rows"] == 0 and m["shuffle_rows"] == 64, m
        # one short: each of the 8 senders drops exactly one row
        out, m = run_distributed(fn, {"bag": bag}, mesh, cap_factor=7.0)
        assert m["overflow_rows"] == 8 and m["shuffle_rows"] == 56, m
        # adaptive capacity: starts undersized, regrows to the true max
        out, m = run_distributed(fn, {"bag": bag}, mesh, cap_factor=1.0,
                                 adaptive=True)
        got = sorted(r["v"] for r in out["out"].to_rows())
        assert got == [float(i) for i in range(64)], got
        assert m["overflow_rows"] == 0 and m["size_need_0"] == 8, m
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_exchange_elision_and_shuffle_stats():
    """Partitioning-aware elision: join -> sum_by on the same key moves
    probe rows across the wire exactly once; co-partitioned joins
    exchange neither side."""
    out = run_sub("""
        from repro.columnar.table import FlatBag
        L = FlatBag.from_rows([{"k": i % 7, "v": float(i)}
                               for i in range(64)],
                              {"k": "int", "v": "real"}, capacity=64)
        R = FlatBag.from_rows([{"k": i, "w": float(10 * i)}
                               for i in range(8)],
                              {"k": "int", "w": "real"}, capacity=8)
        mesh = device_mesh_1d(8)
        want = {}
        for i in range(64):
            want[i % 7] = want.get(i % 7, 0.0) + float(i)

        def fn(env, ctx):
            j = ctx.join(env["L"], env["R"], ("k",), ("k",))
            return {"out": ctx.sum_by(j, ("k",), ("v",),
                                      local_preagg=False)}

        out, m = run_distributed(fn, {"L": L, "R": R}, mesh,
                                 cap_factor=16.0)
        got = sorted((r["k"], r["v"]) for r in out["out"].to_rows())
        assert got == sorted(want.items()), got
        assert m["exchanges"] == 2, m
        assert m["exchanges_elided"] == 1, m
        # a pre-partitioned probe flows through sum_by AND dedup with no
        # further exchange: one wire crossing for the whole pipeline
        def fn2(env, ctx):
            a = ctx.exchange(env["L"], ("k",))
            s = ctx.sum_by(a, ("k",), ("v",), local_preagg=False)
            return {"out": ctx.dedup(s, ("k",))}
        out, m = run_distributed(fn2, {"L": L, "R": R}, mesh,
                                 cap_factor=16.0)
        got = sorted(r["k"] for r in out["out"].to_rows())
        assert got == list(range(7)), got
        assert m["exchanges"] == 1 and m["exchanges_elided"] == 2, m
        # co-partitioned join: neither side moves again
        def fn3(env, ctx):
            a = ctx.exchange(env["L"], ("k",))
            b = ctx.exchange(env["R"], ("k",))
            j = ctx.join(a, b, ("k",), ("k",))
            return {"out": ctx.sum_by(j, ("k",), ("v",),
                                      local_preagg=False)}
        out, m = run_distributed(fn3, {"L": L, "R": R}, mesh,
                                 cap_factor=16.0)
        got = sorted((r["k"], r["v"]) for r in out["out"].to_rows())
        assert got == sorted(want.items()), got
        assert m["exchanges"] == 2 and m["exchanges_elided"] == 3, m
        # routing reuse: exchanging the SAME bag on the same key twice
        # argsorts the destinations once (props.route_cache)
        from repro.exec import dist as D
        def fn4(env, ctx):
            a = ctx.exchange(env["L"], ("k",))
            b = ctx.exchange(env["L"], ("k",))
            return {"a": a, "b": b}
        out, m = run_distributed(fn4, {"L": L, "R": R}, mesh,
                                 cap_factor=16.0)
        assert m["exchanges"] == 2, m
        assert D.SHUFFLE_STATS.get("route_argsort", 0) == 1, \
            dict(D.SHUFFLE_STATS)
        assert D.SHUFFLE_STATS.get("route_reuse", 0) == 1, \
            dict(D.SHUFFLE_STATS)
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_exchange_packed_kernel_path():
    out = run_sub("""
        from repro.columnar.table import FlatBag
        rows = [{"k": i % 13, "v": float(i)} for i in range(64)]
        bag = FlatBag.from_rows(rows, {"k": "int", "v": "real"},
                                capacity=64)
        mesh = device_mesh_1d(8)
        def fn(env, ctx):
            return {"out": ctx.exchange(env["bag"], ("k",))}
        out, m = run_distributed(fn, {"bag": bag}, mesh, cap_factor=4.0,
                                 use_kernel=True)
        got = sorted((r["k"], r["v"]) for r in out["out"].to_rows())
        want = sorted((r["k"], r["v"]) for r in rows)
        assert got == want, (got, want)
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_heavy_key_detection():
    """``is_member`` against a planned heavy set padded by ``pad_heavy``:
    present keys are members, absent keys and the padding value are
    not, on the jnp path and the kernel path alike."""
    out = run_sub("""
        import jax.numpy as jnp
        from repro.core import skew as SK
        heavy = jnp.asarray(SK.pad_heavy([42, 7, -3]))
        pad = int(np.iinfo(np.int64).max)
        key = jnp.asarray([7, 3, 42, -3, 0, pad, 8, 41], jnp.int64)
        want = [True, False, True, True, False, False, False, False]
        for use_kernel in (False, True):
            got = SK.is_member(key, heavy, use_kernel=use_kernel)
            assert [bool(x) for x in got] == want, (use_kernel, got)
        print("OK")
    """)
    assert "OK" in out

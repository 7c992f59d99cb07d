"""Bring-up on the TPU, rehearsed on the CPU: the smoke script refuses
anything but a TPU, its phases pass on CPU devices at a tiny scale, no
mesh shrinks, kernels interpret only off the TPU, device-decode faults
keep their type, float64 lanes round-trip, the compile cache stays off
unless an entry point turns it on, and the Zipf generator draws what it
always drew."""

import argparse
import os
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import chip_smoke as S
from repro.data import generators as G
from repro.errors import ChunkCorruptionError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env_extra=None, cwd=ROOT, code=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    cmd = [sys.executable] + (["-c", code] if code else args)
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=600)


# -- the smoke script -------------------------------------------------------

def test_chip_smoke_refuses_cpu():
    res = _run(["chip_smoke.py", "--scale", "50"])
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert "no TPU" in res.stderr


def test_chip_smoke_refuses_a_lone_directory(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    res = _run(["chip_smoke.py"], cwd=str(tmp_path))
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout


def test_chip_smoke_one_chip_phases_on_cpu(tmp_path):
    args = argparse.Namespace(chips=1, scale=300, seed=3)
    S.run_one_chip(args, str(tmp_path), chunk_rows=128)


def test_chip_smoke_four_chip_phases_on_virtual_devices(tmp_path):
    code = textwrap.dedent(f"""
        import argparse, sys
        sys.path[:0] = [{os.path.join(ROOT, 'src')!r}, {ROOT!r}]
        import jax, repro, chip_smoke as S
        args = argparse.Namespace(chips=4, scale=600, seed=1)
        S.run_four_chips(args, {str(tmp_path)!r}, jax.devices(),
                         chunk_rows=128)
        print("FOUR-CHIP REHEARSAL OK")
    """)
    res = _run(None, {"XLA_FLAGS":
                      "--xla_force_host_platform_device_count=4"},
               code=code)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "MultiJoinP x" in res.stdout and "SkewJoinP x" in res.stdout
    assert "FOUR-CHIP REHEARSAL OK" in res.stdout


def test_numpy_reference_agrees_with_the_oracle():
    """The smoke's NumPy reference and the interpreter oracle agree on
    the join-chain query, so a full-scale check against the reference
    checks the same semantics."""
    from repro.core import interpreter as I
    db = G.gen_tpch(120, 0.0, 5)
    oracle = I.eval_expr(S.query_b(40.0).assignments[0].expr, db)
    dates, totals = S.ref_b(S.columns(db), 40.0)
    got = sorted((r["odate"], r["total"]) for r in oracle)
    assert got == list(zip(dates.tolist(), totals.tolist()))


# -- meshes, kernels, decode ------------------------------------------------

@pytest.mark.parametrize("make", ["device_mesh_1d", "make_query_mesh"])
def test_mesh_refuses_to_shrink(make):
    from repro.exec.dist import device_mesh_1d
    from repro.launch.mesh import make_query_mesh
    fn = {"device_mesh_1d": device_mesh_1d,
          "make_query_mesh": make_query_mesh}[make]
    n = len(jax.devices())
    assert fn(n).size == n
    with pytest.raises(ValueError, match="needs"):
        fn(n + 1)


_SITES = {"part_max_j0": 30, "part_rows_j0": 60,     # 2.0 at P=4
          "part_max_j1": 10, "part_rows_j1": 10,     # 4.0, a tiny site
          "part_max_j2": 5}                          # no row count


@pytest.mark.parametrize("metrics,n,floor,want", [
    (_SITES, 4, 1, 4.0),
    (_SITES, 4, 64, 1.0),
    (_SITES, 4, 20, 2.0),
    (_SITES, 1, 1, 1.0),
    ({}, 4, 1, 1.0),
    (None, 4, 1, 1.0),
])
def test_receive_imbalance(metrics, n, floor, want):
    from repro.exec.dist import receive_imbalance
    assert receive_imbalance(metrics, n, floor=floor) == want


def test_kernels_interpret_only_off_the_tpu(monkeypatch):
    from repro.kernels import ops as K
    assert K._interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert K._interpret() is False


def _encoded_dataset(tmp_path):
    from repro.core import nrc as N
    from repro.storage import StorageCatalog
    ty = {"T": N.bag(N.tuple_t(k=N.INT))}
    rows = [{"k": i // 16} for i in range(512)]
    cat = StorageCatalog(str(tmp_path))
    cat.writer("d", ty, chunk_rows=512).append({"T": rows})
    ds = cat.open("d")
    assert ds.parts["T__F"].meta.chunks[0].encodings
    return ds


def test_device_decode_failure_keeps_its_type(tmp_path, monkeypatch):
    from repro.storage import reader
    ds = _encoded_dataset(tmp_path)

    def refuse(enc, members):
        raise RuntimeError("kernel refused by the compiler")

    monkeypatch.setattr(reader, "DEVICE_DECODE", True)
    monkeypatch.setattr(reader, "_decode_device", refuse)
    with pytest.raises(RuntimeError, match="refused"):
        ds.load_env()


def test_device_decode_torn_blob_is_corruption(tmp_path, monkeypatch):
    from repro.faults import FAULTS
    from repro.storage import reader
    ds = _encoded_dataset(tmp_path)
    monkeypatch.setattr(reader, "DEVICE_DECODE", True)
    FAULTS.arm("storage.chunk", "torn", first=0, count=1, arg=0.5)
    try:
        with pytest.raises(ChunkCorruptionError):
            ds.load_env()
    finally:
        FAULTS.reset()


# -- float64 lanes ------------------------------------------------------------

def test_float64_lanes_round_trip_bit_for_bit():
    from repro.exec import ops as X
    rs = np.random.RandomState(0)
    x = np.concatenate([rs.rand(500), rs.randn(500) * 1e300,
                        [0.0, -0.0, np.inf, -np.inf, 5e-324, np.nan]])
    lanes = jax.jit(X._to_i64_bits)(x)
    assert np.array_equal(np.asarray(lanes), x.view(np.int64))
    back = np.asarray(jax.jit(lambda a: X._from_i64_bits(
        a, jnp.float64))(lanes))
    assert np.array_equal(back.view(np.int64), x.view(np.int64))


def test_float64_pair_words_are_exact_for_f32_pairs():
    """The TPU form: a value the chip can hold (an f32 pair hi + lo)
    crosses a lane exactly, and distinct values stay distinct."""
    from repro.exec import ops as X
    rs = np.random.RandomState(1)
    hi = rs.randn(1000).astype(np.float32).astype(np.float64)
    lo = (hi * rs.uniform(-2**-25, 2**-25, 1000)).astype(np.float32)
    x = np.concatenate([hi + lo.astype(np.float64),
                        np.arange(1, 200, dtype=np.float64),
                        [np.inf, -np.inf, np.nan]])
    lanes = np.asarray(jax.jit(X._f64_pair_bits)(x))
    back = np.asarray(jax.jit(X._f64_from_pair_bits)(lanes))
    assert np.array_equal(back, x, equal_nan=True)
    assert len(np.unique(lanes)) == len(np.unique(x))


# -- compile cache ------------------------------------------------------------

def test_importing_repro_leaves_the_compile_cache_off():
    res = _run(None, code=(
        "import sys; sys.path.insert(0, 'src'); import jax, repro; "
        "print('DIR', jax.config.jax_compilation_cache_dir)"))
    assert res.returncode == 0, res.stderr
    assert "DIR None" in res.stdout


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_directory(tmp_path, from_env):
    env_dir = str(tmp_path / "from_env")
    code = textwrap.dedent(f"""
        import sys; sys.path.insert(0, 'src')
        import jax
        from repro import compile_cache
        print('PATH', compile_cache.enable({str(tmp_path)!r}))
        print('DIR', jax.config.jax_compilation_cache_dir)
    """)
    res = _run(None, {"JAX_COMPILATION_CACHE_DIR": env_dir}
               if from_env else None, code=code)
    assert res.returncode == 0, res.stderr
    want = env_dir if from_env else str(tmp_path / ".jax_cache")
    assert f"PATH {want}" in res.stdout
    assert f"DIR {want}" in res.stdout


def test_compile_cache_counts_in_the_metrics_registry(tmp_path):
    """A compile writes the cache (a miss); the same program compiled
    again after the in-memory caches are cleared reads it (a hit)."""
    code = textwrap.dedent(f"""
        import sys; sys.path.insert(0, 'src')
        import jax, jax.numpy as jnp
        from repro import compile_cache
        from repro.obs.metrics import REGISTRY
        compile_cache.enable({str(tmp_path)!r})
        f = lambda x: jnp.sort(x * 3 + 1)
        jax.jit(f)(jnp.arange(64)).block_until_ready()
        print('AFTER1', REGISTRY.get('compile_cache.hits'),
              REGISTRY.get('compile_cache.misses'))
        jax.clear_caches()
        jax.jit(f)(jnp.arange(64)).block_until_ready()
        print('AFTER2', REGISTRY.get('compile_cache.hits'),
              REGISTRY.get('compile_cache.misses'))
    """)
    res = _run(None, code=code)
    assert res.returncode == 0, res.stderr
    after = {ln.split()[0]: tuple(map(int, ln.split()[1:]))
             for ln in res.stdout.splitlines() if ln.startswith("AFTER")}
    assert after["AFTER1"][0] == 0 and after["AFTER1"][1] >= 1
    assert after["AFTER2"][0] >= 1 and after["AFTER2"][1] == after["AFTER1"][1]


# -- the Zipf generator ------------------------------------------------------

def _choice_per_row(rng, n, skew, size):
    """The generator's original draw: one ``choice`` over all n keys."""
    ranks = np.arange(1, n + 1, dtype=np.float64)
    probs = ranks ** (-skew)
    probs /= probs.sum()
    return rng.choice(np.arange(1, n + 1), size=size, p=probs)


@pytest.mark.parametrize("seed", [0, 3])
@pytest.mark.parametrize("skew", [0.5, 1.0, 2.0])
def test_zipf_choice_draws_what_choice_drew(seed, skew):
    a, b = np.random.RandomState(seed), np.random.RandomState(seed)
    for n in (8, 1000, 40000):
        for _ in range(50):
            assert G.zipf_choice(a, n, skew, 1)[0] == \
                _choice_per_row(b, n, skew, 1)[0]
            assert a.randint(1, 50) == b.randint(1, 50)
        assert np.array_equal(G.zipf_choice(a, n, skew, 300),
                              _choice_per_row(b, n, skew, 300))


def test_gen_tpch_skewed_data_is_unchanged(monkeypatch):
    new = G.gen_tpch(scale=150, skew=1.5, seed=4)
    monkeypatch.setattr(G, "zipf_choice", _choice_per_row)
    assert G.gen_tpch(scale=150, skew=1.5, seed=4) == new

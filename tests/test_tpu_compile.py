"""Compile-only checks for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode and XLA:CPU accept: 64-bit
bitcasts, 64-bit max all-reduces, 64-bit operands of a kernel, kernel
blocks that do not match the operand's tiling. These tests compile the
served programs and the kernels for a described ``v5e:2x2`` so such a
program fails here instead of on the chip. Nothing runs.

The topology is described inside a module fixture (never at import):
only one process at a time may load the TPU compiler, and every pytest
worker imports this file.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

import chip_smoke as S
from benchmarks.common import CATALOG
from repro.core import codegen as CG
from repro.core import materialization as M
from repro.core.plans import MultiJoinP, _walk_plan
from repro.data.generators import TPCH_TYPES, gen_tpch
from repro.exec import dist as D
from repro.kernels import decode as DC
from repro.kernels import gather_join as GJ
from repro.kernels import segment_fused as SF
from repro.kernels import segment_reduce as SR
from repro.kernels import shuffle_pack as SP
from repro.serve import QueryService
from repro.storage import table_stats

# capacity classes of TPC-H SF1 (1.5M orders, ~6M line items)
SF1_CAPS = {"Lineitem__F": 1 << 23, "Orders__F": 1 << 21,
            "Part__F": 1 << 20, "Customer__F": 1 << 19}
WIDTH = 1 << 16          # kernel operand rows


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent
    # cache but cannot be read back without one: keep it out
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding, caps=None):
    def leaf(a, cap=None):
        shape = (cap,) if cap is not None else a.shape
        return jax.ShapeDtypeStruct(shape, a.dtype, sharding=sharding)
    if caps is None:
        return jax.tree.map(leaf, tree)
    return {k: jax.tree.map(lambda a, c=caps[k]: leaf(a, c), b)
            for k, b in tree.items()}


def test_local_query_b_compiles_at_sf1(one_chip, tmp_path):
    """The served local program of the join-chain query, at the
    capacity classes of 1.5M orders, fits one v5e."""
    types = {k: TPCH_TYPES[k]
             for k in ("Lineitem", "Orders", "Customer", "Part")}
    ds = S.write_dataset(str(tmp_path), "t", gen_tpch(200, 0.0, 0),
                         types, 64)
    svc = QueryService(types, catalog=CATALOG)
    svc.execute_stored(S.query_b(50.0), ds)
    (entry,) = svc._cache.values()
    env = ds.load_env(columns={p: r.columns
                               for p, r in entry.storage_req.items()})
    compiled = entry.exe._fn.lower(
        _shapes(env, one_chip, SF1_CAPS),
        _shapes(entry.exe.bind(), one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < 16 * 2**30


def test_distributed_real_columns_compile_on_four_chips(topo, tmp_path):
    """A MultiJoinP and an exchange that carry REAL (float64) columns
    compile for a 4-chip mesh: float64 lanes cross as f32-pair words
    on the TPU, and max metrics all-reduce in 32 bits."""
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    types = {k: TPCH_TYPES[k] for k in ("Lineitem", "Part", "Orders")}
    ds = S.write_dataset(str(tmp_path), "t",
                         gen_tpch(64, 2.0, 0), types, 512)
    sp = M.shred_program(S.query_b(30.0), types, domain_elimination=True)
    cp = CG.compile_program(sp, CATALOG, skew_stats=table_stats(ds),
                            skew_partitions=4, hypercube_mode="auto")
    assert any(isinstance(s, MultiJoinP)
               for _, p in cp.plans for s in _walk_plan(p))
    fn, params = CG.dist_program_fn(cp)
    env = {k: b.resize(4096) for k, b in ds.load_env().items()}
    assert env["Lineitem__F"].data["qty"].dtype == jnp.float64
    D.reset_shuffle_stats()
    sm = D.shard_program(fn, mesh, has_params=True)
    compiled = sm.lower(
        _shapes(env, NamedSharding(mesh, P("data"))),
        {k: jax.ShapeDtypeStruct(np.shape(v), jnp.asarray(v).dtype,
                                 sharding=NamedSharding(mesh, P()))
         for k, v in params.items()}).compile()
    assert D.SHUFFLE_STATS.get("hypercube_exchanges", 0) >= 1
    assert D.SHUFFLE_STATS.get("exchanges", 0) >= 1
    assert "all-to-all" in compiled.as_text()


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


KERNELS = {
    "segment_reduce": (
        lambda v, s: SR.segment_reduce_pallas(v, s, WIDTH, interpret=False),
        [((WIDTH, 4), jnp.float32), ((WIDTH,), jnp.int32)]),
    "segment_sum_first": (
        lambda v, k, s: SF.segment_sum_first_pallas(v, k, s, WIDTH,
                                                    interpret=False),
        [((WIDTH, 2), jnp.float32), ((WIDTH, 3), jnp.int64),
         ((WIDTH,), jnp.int32)]),
    "merge_positions": (
        lambda a, b: GJ.merge_positions_pallas(a, b, interpret=False),
        [((WIDTH,), jnp.int64), ((WIDTH,), jnp.int64)]),
    "member_mask": (
        lambda k, h: SP.member_mask_pallas(k, h, interpret=False),
        [((WIDTH,), jnp.int64), ((64,), jnp.int64)]),
    "rle_expand": (
        lambda v, s, e: DC.rle_expand_pallas(v, s, e, WIDTH,
                                             interpret=False),
        [((4096,), jnp.int64)] * 3),
    "bitunpack": (
        lambda w: DC.bitunpack_pallas(w, 8, 4, WIDTH, 5, interpret=False),
        [((WIDTH // 4,), jnp.uint32)]),
    "dict_gather": (
        lambda v, c: DC.dict_gather_pallas(v, c, interpret=False),
        [((256,), jnp.int64), ((WIDTH,), jnp.int32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = KERNELS[name]
    compiled = jax.jit(fn).lower(
        *[_spec(one_chip, s, d) for s, d in args]).compile()
    assert "tpu_custom_call" in compiled.as_text()

"""Skew-resilient distributed processing demo (paper §5) on 8 virtual
devices: persists Zipf-skewed data, lets the stored heavy-key sketch
and zone maps drive the compiler's ``SkewJoinP`` decision (DESIGN.md
"Automated skew handling"), runs the same shredded query with the plain
and the planned skew join, prints the shuffle/broadcast/overflow
metrics, and rebinds a NEW heavy-key set on the warm runner with zero
retraces.

    PYTHONPATH=src python examples/skew_demo.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tests"))

import tempfile

import jax

from repro.core import codegen as CG
from repro.core import interpreter as I
from repro.core import materialization as M
from repro.core import nrc as N
from repro.core import skew as SKM
from repro.core.plans import SkewJoinP, _walk_plan, collect_plan_params
from repro.core.unnesting import Catalog
from repro.exec.dist import device_mesh_1d
from repro.storage import StorageCatalog, table_stats
from helpers import INPUT_TYPES, gen_cop, gen_parts, running_example_query

print(f"devices: {len(jax.devices())}")
data = {"COP": gen_cop(n_cust=24, max_orders=4, max_items=24, seed=7,
                       zipf=0.75),
        "Part": gen_parts(29)}
direct = I.eval_expr(running_example_query(), data)

prog = N.Program([N.Assignment("Q", running_example_query())])
sp = M.shred_program(prog, INPUT_TYPES, domain_elimination=True)
catalog = Catalog(unique_keys={"Part__F": ("pid",)})
env = CG.columnar_shred_inputs(data, INPUT_TYPES)
PN = 8
env = {k: b.resize(((b.capacity + PN - 1) // PN) * PN)
       for k, b in env.items()}
mesh = device_mesh_1d(PN)
man = sp.manifests["Q"]

cat = StorageCatalog(tempfile.mkdtemp())
cat.writer("cop", INPUT_TYPES, chunk_rows=256).append(data)
stats = table_stats(cat.open("cop"))


def correct(out):
    parts = {(): out[man.top], **{p: out[n] for p, n in man.dicts.items()}}
    return I.bags_equal(direct, CG.parts_to_rows(
        parts, running_example_query().ty))


for label, skew_stats in (("plain join  ", None), ("planned skew", stats)):
    cp = CG.compile_program(sp, catalog, skew_stats=skew_stats,
                            skew_partitions=PN)
    n_sj = sum(1 for _, p in cp.plans for s in _walk_plan(p)
               if isinstance(s, SkewJoinP))
    CG.reset_trace_stats()
    runner, out, metrics = CG.compile_program_distributed(
        cp, env, mesh, cap_factor=16.0)
    print(f"{label}: {n_sj} SkewJoinP node(s), params={cp.skew_params}, "
          f"correct={correct(out)}  metrics={metrics}")
print("note: the planned skew join leaves heavy keys in place and "
      "broadcasts the matching build rows (paper Fig. 6)")

if cp.skew_params:
    traces = CG.TRACE_STATS.get("traces", 0)
    (name,) = collect_plan_params(cp.graph)
    out, metrics = runner(env, params={name: SKM.pad_heavy([7, 11, 13])})
    print(f"rebound heavy set: correct={correct(out)}  retraces="
          f"{CG.TRACE_STATS.get('traces', 0) - traces}")

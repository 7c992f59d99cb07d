"""Shared helpers of the benchmark's tests: paths, a cell cut to a test
size, and a child process on four virtual CPU devices."""

import copy
import os
import subprocess
import sys
import textwrap

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
for p in (SRC, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness.cell import resolve  # noqa: E402

SF1 = "tpch_sf0.1-revenue"
X4 = "tpch_zipf2_x4-revenue"


def small_cell(workload: str, scale_factor: float):
    """The cell with its configuration cut to ``scale_factor`` (and, on
    the mesh, capacities fixed for that scale)."""
    cell = resolve(workload)
    cell.config = copy.deepcopy(cell.config)
    cell.config["scale_factor"] = scale_factor
    if "mesh_capacity" in cell.config:
        n = cell.dataset().sizes(cell.config)
        chips = int(cell.config["chips"])
        up = lambda r: -(-r // chips) * chips  # noqa: E731
        cell.config["mesh_capacity"] = {
            "Lineitem__F": up(n["orders"] * 5), "Part__F": up(n["parts"]),
            "Orders__F": up(n["orders"])}
    return cell


def store_small(cell, seed: int, root: str, chunk_rows: int = 256):
    """(columns, stored dataset, engine types) of the cell's data at
    ``seed``, stored under ``root``."""
    from harness import store
    dataset = cell.dataset()
    types = dataset.types(cell.config["tables"])
    cols = dataset.generate(cell.config, seed)
    ds, _ = store.open_or_write(root, f"seed{seed}", cols, types, chunk_rows)
    return cols, ds, types


def server_for(cell, ds, types, params: dict):
    """The cell's system under test over the stored dataset ``ds``."""
    from harness.serving import Server
    query = cell.query()
    return Server(cell.config, ds, types, cell.dataset().UNIQUE_KEYS,
                  lambda p: query.program(p, types), params)


def heaviest_price(cols) -> float:
    """The price of the part that most line items name."""
    import numpy as np
    pid = int(np.argmax(np.bincount(cols["Lineitem.pid"])))
    return float(cols["Part.price"][cols["Part.pid"] == pid][0])


def read_metric(name: str, obs):
    """What the per-layer metric ``name``'s reader finds in ``obs``."""
    from harness.cell import load_module, metric_path
    return load_module(metric_path(name),
                       "metric_" + name.replace(".", "_")).read(obs)


def run_small(cell, seed: int, tmp, seconds: float = 1.0, **kw) -> dict:
    import run as R
    return R.run(cell, seed, seconds, kw.pop("trace", False),
                 store_root=os.path.join(str(tmp), "store"),
                 trace_dir=os.path.join(str(tmp), "trace"),
                 require_tpu=False, **kw)


def run_four_devices(body: str, timeout: int = 600) -> str:
    """Run ``body`` in a child process with four virtual CPU devices and
    this module imported; returns its standard output."""
    script = textwrap.dedent("""
        import sys
        sys.path.insert(0, %r)
        from benchtest import *
    """) % os.path.dirname(os.path.abspath(__file__)) + textwrap.dedent(body)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert res.returncode == 0, f"STDOUT:{res.stdout}\nSTDERR:{res.stderr}"
    return res.stdout

"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax initialization)."""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """The assignment's production mesh: 16x16 = 256 chips per pod;
    multi-pod = 2 pods = 512 chips with a leading "pod" axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_query_mesh(n_partitions: int, axis: str = "data"):
    """1-D mesh for the distributed query engine (bags are row-sharded
    over pod x data; the model axis replicates — DESIGN.md §5)."""
    from repro.exec.dist import device_mesh_1d
    return device_mesh_1d(n_partitions, axis)

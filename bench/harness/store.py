"""A generated dataset, persisted once per (configuration, seed) through
the engine's storage writer and reopened by later runs.

Columns are keyed ``<table>.<column>``; each table is stored as the flat
input part ``<table>__F``.
"""

from __future__ import annotations

import os
import shutil

import numpy as np


def table_rows(cols: dict) -> dict:
    """Rows per table of generated columns."""
    rows = {}
    for key, a in cols.items():
        rows.setdefault(key.split(".", 1)[0], int(a.shape[0]))
    return rows


def flat_parts(cols: dict) -> dict:
    """The generated tables as the engine's flat input parts."""
    from repro.columnar.table import FlatBag
    env = {}
    for t, rows in table_rows(cols).items():
        data = {k.split(".", 1)[1]: v for k, v in cols.items()
                if k.split(".", 1)[0] == t}
        env[f"{t}__F"] = FlatBag(data, np.ones(rows, dtype=bool))
    return env


def open_or_write(root: str, name: str, cols: dict, types: dict,
                  chunk_rows: int):
    """The stored dataset ``root/name`` holding ``cols`` (of the engine
    types ``types``): reopened when a complete footer with the same row
    counts is there, else (absent, torn footer, other rows) written
    afresh through the storage writer's columnar entry, after the other
    datasets under ``root`` are removed (one run's data at a time in a
    checkout). Returns ``(dataset, written)``."""
    from repro.errors import ReproError
    from repro.storage import StorageCatalog
    want = {f"{t}__F": r for t, r in table_rows(cols).items()}
    path = os.path.join(root, name)
    cat = StorageCatalog(root)
    if os.path.isdir(path):
        try:
            ds = cat.open(name)
            if {p: ds.parts[p].rows for p in want} == want:
                return ds, False
        except (ReproError, KeyError, OSError, ValueError):
            pass
    if os.path.isdir(root):
        for other in os.listdir(root):
            shutil.rmtree(os.path.join(root, other), ignore_errors=True)
    cat.writer(name, types, chunk_rows=chunk_rows).write_parts(
        flat_parts(cols))
    return cat.open(name, refresh=True), True

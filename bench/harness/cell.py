"""Resolve a benchmark cell from ``BENCHMARK.json`` to its files.

Everything that belongs to one configuration, one traffic mix, one
query family or one per-layer metric sits in a file of its own, found by
name:

    bench/configs/<config>.json      dataset, tables, sizes, skew, chips,
                                     guarantees
    bench/datasets/<dataset>.py      the generator a configuration names:
                                     ``generate(cfg, seed)``, ``types``,
                                     ``UNIQUE_KEYS``
    bench/traffic/<traffic>.json     the query family and parameter draws
    bench/queries/<query>.py         NRC builder, plain NumPy reference,
                                     ``compare``
    bench/metrics/<metric>.py        ``read(obs)`` -> number or None

A cell is added by adding files and ``BENCHMARK.json`` entries only.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Dict, List

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_module(path: str, name: str):
    """Import a Python file by path (metric files carry dots in their
    names)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    dataset_path: str
    query_path: str
    end_to_end: List[dict]
    per_layer: List[dict] = field(default_factory=list)

    def dataset(self):
        return load_module(self.dataset_path,
                           "bench_dataset_" + self.config["dataset"])

    def query(self):
        return load_module(self.query_path,
                           "bench_query_" + self.traffic["query"])

    def metric_readers(self) -> Dict[str, object]:
        """name -> module of every per-layer metric this cell reports."""
        return {m["name"]: load_module(metric_path(m["name"]),
                                       "bench_metric_" + m["name"]
                                       .replace(".", "_"))
                for m in self.per_layer}


def metric_path(name: str) -> str:
    return os.path.join(BENCH, "metrics", name + ".py")


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, spec: dict = None) -> Cell:
    """The cell named ``workload`` with its configuration, traffic,
    query and metric entries; raises ``KeyError`` for an unknown name
    and ``FileNotFoundError`` for a missing file."""
    spec = spec if spec is not None else \
        read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = read_json(os.path.join(ROOT, configs[w["config"]]["file"]))
    traffic = read_json(os.path.join(BENCH, "traffic",
                                     w["traffic"] + ".json"))
    dataset_path = os.path.join(BENCH, "datasets",
                                config["dataset"] + ".py")
    query_path = os.path.join(BENCH, "queries", traffic["query"] + ".py")
    for path in (dataset_path, query_path):
        if not os.path.isfile(path):
            raise FileNotFoundError(path)
    per_layer = [m for m in spec["per_layer"] if reports(m, workload)]
    for m in per_layer:
        if not os.path.isfile(metric_path(m["name"])):
            raise FileNotFoundError(metric_path(m["name"]))
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, dataset_path=dataset_path,
                query_path=query_path,
                end_to_end=[m for m in spec["end_to_end"]
                            if reports(m, workload)],
                per_layer=per_layer)

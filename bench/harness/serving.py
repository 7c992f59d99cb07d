"""The system under test, set up for one configuration.

``serving: "stored"`` (one chip): ``ServingRuntime`` over a
``QueryService`` that serves each request from the stored dataset
(``execute_stored``: zone-map chunk selection, column scan and decode,
host-to-device copy, the cached executable).

``serving: "mesh"`` (four chips): the stored parts are loaded once,
padded to the configuration's fixed capacities and placed row-sharded
on a 1-D mesh; ``ServingRuntime`` over ``QueryService(mesh=...)`` serves
each request through the distributed program (packed exchange,
one-round HyperCube join) with the heavy keys that the planner decides
from the dataset's persisted sketches, for the columns the
configuration names (``heavy_key_columns``).
"""

from __future__ import annotations

import contextlib
import time
from typing import NamedTuple, Optional

import numpy as np


class Reply(NamedTuple):
    ok: bool                 # served ok and not degraded
    outputs: Optional[dict]  # the answer's parts, on the host
    metrics: Optional[dict]  # the distributed execute's meters
    response: object         # the runtime's QueryResponse
    done: float              # perf_counter() once the answer is on the host


class Server:
    def __init__(self, cfg: dict, dataset, types: dict, unique_keys: dict,
                 program_fn, probe_params: dict):
        """``types``: the engine type of each stored table;
        ``unique_keys``: part -> columns declared unique to the planner;
        ``program_fn(params)``: the NRC program of one request."""
        import jax
        from repro.core import materialization as M
        from repro.core.unnesting import Catalog
        from repro.serve import QueryService
        from repro.serve.runtime import ServingRuntime
        self.jax = jax
        self.cfg = cfg
        self.program_fn = program_fn
        catalog = Catalog(unique_keys={
            p: tuple(c) for p, c in unique_keys.items()
            if p[:-len("__F")] in types})
        self.hints: Optional[dict] = None
        self.mesh = None
        self.load_s = 0.0
        if cfg["serving"] == "stored":
            self.env = dataset
            svc = QueryService(types, catalog=catalog)
        elif cfg["serving"] == "mesh":
            t0 = time.perf_counter()
            self.env, self.mesh, self.hints = place_on_mesh(cfg, dataset)
            self.load_s = time.perf_counter() - t0
            svc = QueryService(
                types, catalog=catalog, mesh=self.mesh,
                hypercube_mode="auto",
                dist_kwargs=dict(cap_factor=float(cfg["cap_factor"]),
                                 adaptive=False))
        else:
            raise ValueError(f"unknown serving path {cfg['serving']!r}")
        self.service = svc
        self.runtime = ServingRuntime(svc)
        probe = program_fn(probe_params)
        name = probe.assignments[0].name
        self.top = M.shred_program(probe, types, domain_elimination=True
                                   ).manifests[name].top

    def request(self, params: dict):
        from repro.serve.runtime import QueryRequest
        return QueryRequest(self.program_fn(params), self.env,
                            skew_hints=self.hints)

    def submit(self, req, mark=None) -> Reply:
        """Serve one request through the runtime and copy its answer to
        the host; ``mark(name)`` (a ``jax.profiler.TraceAnnotation``)
        labels the submit and the hand-off."""
        mark = mark or (lambda name: contextlib.nullcontext())
        with mark("bench.submit"):
            resp = self.runtime.submit(req)
        with mark("bench.handoff"):
            ok = bool(resp.ok) and resp.degraded == ()
            out = self.jax.device_get(resp.outputs) if ok else None
        done = time.perf_counter()
        metrics = self.service.last_metrics \
            if self.mesh is not None else None
        return Reply(ok, out, dict(metrics) if metrics else None, resp,
                     done)

    def lowered(self) -> dict:
        """Counts of the plan node kinds of every cached family."""
        from repro.core.plans import _walk_plan
        kinds: dict = {}
        for e in self.service._cache.values():
            for _, p in e.cp.plans:
                for s in _walk_plan(p):
                    k = type(s).__name__
                    kinds[k] = kinds.get(k, 0) + 1
        return kinds


def place_on_mesh(cfg: dict, dataset):
    """(env on the mesh, mesh, skew hints) for the configuration's
    chips, with every part padded to its fixed capacity."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.columnar.table import FlatBag
    from repro.core import skew as SK
    from repro.exec.dist import device_mesh_1d
    from repro.storage import table_stats
    n = int(cfg["chips"])
    mesh = device_mesh_1d(n)
    stats = table_stats(dataset)
    hints = {part: {col: SK.decide_heavy_keys(stats[part], col, n)}
             for part, col in cfg["heavy_key_columns"].items()}
    caps = cfg["mesh_capacity"]
    sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
    env = {}
    for name, bag in dataset.load_env().items():
        cap = int(caps[name])
        rows = dataset.parts[name].rows
        if rows > cap or cap % n:
            raise ValueError(f"{name}: {rows} rows do not fit the fixed "
                             f"capacity {cap} over {n} chips")
        host = jax.device_get(bag.resize(cap))
        env[name] = FlatBag(
            {c: jax.device_put(np.asarray(a), sharding)
             for c, a in host.data.items()},
            jax.device_put(np.asarray(host.valid), sharding))
    return env, mesh, hints

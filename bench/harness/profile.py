"""Reduce a JAX profiler trace to the device numbers of a traced run.

``load(dir)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
under ``dir`` into plain event dicts; ``reduce(events)`` turns them into
busy time, window, per-class device time and the breakdown. The two are
apart so that the reduction can be tested on a small recorded trace.

An event dict is ``{"plane", "line", "name", "t0", "dur"}`` with times
in nanoseconds on the profiler's one clock. Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per executed
HLO operation. The benchmark's own ``jax.profiler.TraceAnnotation``
spans (``bench.*``) are on host threads.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
HOST_MARKS = ("bench.draw", "bench.submit", "bench.handoff")
OPS_LINE = "XLA Ops"

# op classes, by the base of the HLO instruction's name (``sort.12`` ->
# ``sort``); a class's time is the union of its ops' intervals, so an op
# nested in another of its class counts once
CLASSES = {
    "sort": re.compile(r"^sort"),
    "collective": re.compile(
        r"^(all[-_]to[-_]all|all[-_]reduce|all[-_]gather|"
        r"collective[-_]permute|reduce[-_]scatter)"),
}


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def load(trace_dir: str) -> List[dict]:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``: every op
    on a device's ``XLA Ops`` line, and the benchmark's host
    annotations."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return []
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = []
    for plane in pd.planes:
        device = is_device_plane(plane.name)
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for e in line.events:
                if not device and not e.name.startswith("bench."):
                    continue
                out.append({"plane": plane.name, "line": line.name,
                            "name": e.name, "t0": float(e.start_ns),
                            "dur": float(e.duration_ns)})
    return out


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def op_name(name: str) -> str:
    """The HLO instruction's name of a device event: the trace gives
    ``%fusion.76 = s32[...] fusion(...)``; other names pass through."""
    if name.startswith("%") and " = " in name:
        return name[1:name.index(" = ")]
    return name


def op_base(name: str) -> str:
    """``fusion.123`` -> ``fusion``: one name per kind of op."""
    return re.sub(r"(\.\d+)+$", "", op_name(name))


def op_class(name: str) -> Optional[str]:
    base = op_base(name)
    for cls, pat in CLASSES.items():
        if pat.search(base):
            return cls
    return None


def top_level(ops: List[dict]) -> List[dict]:
    """The ops not nested in an earlier op (a ``while`` holds its body's
    ops on the same line)."""
    out, end = [], float("-inf")
    for e in sorted(ops, key=lambda e: (e["t0"], -e["dur"])):
        if e["t0"] + e["dur"] <= end:
            continue
        out.append(e)
        end = max(end, e["t0"] + e["dur"])
    return out


def reduce(events: List[dict], top: int = 10) -> Optional[dict]:
    """Busy and window seconds, device seconds per op class and per op,
    and the longest idle gaps, over the ``bench.window`` annotation.
    Per-device numbers are averaged over the device planes. None when
    the trace has no window or no device op in it."""
    windows = [e for e in events if e["name"] == WINDOW]
    if not windows:
        return None
    w = max(windows, key=lambda e: e["dur"])
    w0, w1 = w["t0"], w["t0"] + w["dur"]
    by_plane: Dict[str, List[dict]] = defaultdict(list)
    for e in events:
        if is_device_plane(e["plane"]):
            a, b = max(e["t0"], w0), min(e["t0"] + e["dur"], w1)
            if b > a:
                by_plane[e["plane"]].append(dict(e, t0=a, dur=b - a))
    if not by_plane:
        return None
    n = len(by_plane)
    busy = 0.0
    per_class: Dict[str, float] = defaultdict(float)
    per_op: Dict[str, float] = defaultdict(float)
    for ops in by_plane.values():
        busy += union_s(ops)
        for cls in CLASSES:
            mine = [e for e in ops if op_class(e["name"]) == cls]
            if mine:
                per_class[cls] += union_s(mine)
        for e in top_level(ops):
            per_op[op_name(e["name"])] += e["dur"]
    first = sorted(by_plane)[0]
    gaps = idle_gaps(by_plane[first], (w0, w1),
                     [e for e in events if e["name"] in HOST_MARKS])
    return {
        "devices": n,
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy / n * 1e-9,
        "class_s": {k: v / n * 1e-9 for k, v in per_class.items()},
        # top-level ops, by instruction name, device seconds
        "device_ops": [[k, v / n * 1e-9] for k, v in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": gaps[:top],
    }


def union_s(ops: List[dict]) -> float:
    """Nanoseconds covered by the union of the ops' intervals."""
    return sum(b - a for a, b in
               merge([(e["t0"], e["t0"] + e["dur"]) for e in ops]))


def idle_gaps(ops: List[dict], window: Tuple[float, float],
              marks: List[dict]) -> List[list]:
    """Idle stretches of one device inside the window, longest first,
    each labelled by the host annotation that overlaps it most
    (``host`` where none does)."""
    busy = merge([(e["t0"], e["t0"] + e["dur"]) for e in ops])
    edges = [window[0]] + [x for ab in busy for x in ab] + [window[1]]
    out = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        best, label = 0.0, "host"
        for m in marks:
            ov = min(b, m["t0"] + m["dur"]) - max(a, m["t0"])
            if ov > best:
                best, label = ov, m["name"]
        out.append([label, (b - a) * 1e-9])
    out.sort(key=lambda g: -g[1])
    return out

"""The one traffic generator: per-request parameters drawn from a traffic
file's ``params`` specs, seeded. The harness sends the requests from one
client in a closed loop.

A spec, per parameter:

    {"uniform_int_over": "<Table>.<column>"}   a whole number uniform over
                                               [min, max] of the generated
                                               column, as float64
"""

from __future__ import annotations

from typing import Callable

from .seeds import rng_for

WINDOW_STREAM = 1
WARMUP_STREAM = 2


def sampler(traffic: dict, cols: dict, seed: int,
            stream: int = WINDOW_STREAM) -> Callable[[], dict]:
    """``draw()`` -> one request's parameters; the same seed and stream
    give the same sequence."""
    rng = rng_for(seed, stream)
    bounds = {}
    for name, spec in sorted(traffic["params"].items()):
        if set(spec) != {"uniform_int_over"}:
            raise ValueError(f"unknown parameter spec {spec!r}")
        col = cols[spec["uniform_int_over"]]
        bounds[name] = (int(col.min()), int(col.max()) + 1)

    def draw() -> dict:
        return {name: float(rng.integers(lo, hi))
                for name, (lo, hi) in bounds.items()}

    return draw


def first(traffic: dict, cols: dict, seed: int) -> dict:
    """Parameters of the warm-up request (a stream of its own, so the
    window's draws do not depend on the warm-up)."""
    return sampler(traffic, cols, seed, WARMUP_STREAM)()

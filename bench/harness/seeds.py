"""Seeded NumPy streams: one independent stream per (seed, purpose)."""

from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """The stream ``stream`` of ``seed``; any whole seed, negative or
    above 2**63, maps to one stream."""
    return np.random.default_rng([int(seed) % (1 << 63), int(stream)])

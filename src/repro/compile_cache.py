"""JAX's persistent compilation cache for the entry points.

``chip_smoke.py`` and ``benchmarks/run.py`` call ``enable`` before they
compile anything. Importing ``repro`` never turns the cache on, so the
tests compile as they always did.

The directory is ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX
reads it itself), else the fixed ``<checkout>/.jax_cache``. The path is
part of a cache entry's key, so it never depends on a temporary name, a
process id or the time.

After ``enable``, the metrics registry counts ``compile_cache.hits``
(reads that found an executable) and ``compile_cache.misses``
(compiles written to the cache).
"""

from __future__ import annotations

import os

from repro.obs.metrics import REGISTRY

_EVENTS = {"/jax/compilation_cache/cache_hits": "compile_cache.hits",
           "/jax/compilation_cache/cache_misses": "compile_cache.misses"}
_registered = False


def _count(event: str, **_kw) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        REGISTRY.inc(name)


def enable(checkout: str) -> str:
    """Turn the persistent cache on; returns its directory."""
    import jax
    from jax import monitoring

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(checkout), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # every program of a query family is worth keeping: the default
    # 1 s floor would skip the small plans a warm server re-enters
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    global _registered
    if not _registered:
        monitoring.register_event_listener(_count)
        _registered = True
    return path

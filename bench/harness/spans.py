"""Per-request span trees of the engine's host tracer, reduced.

Each request of a traced window leaves one root span tree
(``repro.obs.trace.Span.tree()``): ``{"name", "ms", "children"}``.
"""

from __future__ import annotations

from typing import Iterator, List


def walk(tree: dict) -> Iterator[dict]:
    yield tree
    for c in tree.get("children", ()):
        yield from walk(c)


def find(tree: dict, name: str) -> List[dict]:
    return [s for s in walk(tree) if s["name"] == name]


def self_ms(span: dict, only: tuple = ()) -> float:
    """The span's duration less its children's (only the children named
    in ``only``, where given)."""
    kids = [c for c in span.get("children", ())
            if not only or c["name"] in only]
    return span["ms"] - sum(c["ms"] for c in kids)


def per_request(trees: List[dict], fn) -> float:
    """Mean over requests of ``fn(tree)``."""
    return sum(fn(t) for t in trees) / len(trees)

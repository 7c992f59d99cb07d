"""Serving runtime (``serve/runtime.py``): the ``serve.submit`` span
less its child spans, in ms per request of the traced window."""

from harness import spans


def read(obs):
    trees = [t for t in obs.spans if t["name"] == "serve.submit"]
    if not trees:
        return None
    return spans.per_request(trees, spans.self_ms)

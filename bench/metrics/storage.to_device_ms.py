"""Storage scan (``storage/reader.py``): ``storage.to_device`` spans
summed per request, in ms: the ``jax.device_put`` of each loaded column
and of the valid mask, as long as the host is held by it."""

from harness import spans


def read(obs):
    if not obs.spans:
        return None
    puts = [s for t in obs.spans for s in spans.find(t, "storage.to_device")]
    if not puts:
        return None
    return sum(s["ms"] for s in puts) / len(obs.spans)

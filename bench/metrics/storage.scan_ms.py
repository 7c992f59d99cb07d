"""Storage scan (``storage/reader.py``): ``storage.load_part`` spans
summed per request (chunk reads, decode, host-to-device copy), in ms."""

from harness import spans


def read(obs):
    if not obs.spans:
        return None
    loads = [s for t in obs.spans for s in spans.find(t, "storage.load_part")]
    if not loads:
        return None
    return sum(s["ms"] for s in loads) / len(obs.spans)

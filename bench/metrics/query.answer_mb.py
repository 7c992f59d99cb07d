"""Hand-off: the ``answer_bytes`` attribute of the ``query.execute``
spans (the answer's data and valid arrays, global over the chips)
summed per request, in MB (1e6 bytes): what the copy to the host
moves."""

from harness import spans


def read(obs):
    if not obs.spans:
        return None
    sizes = [s["attrs"]["answer_bytes"] for t in obs.spans
             for s in spans.find(t, "query.execute")
             if "answer_bytes" in s.get("attrs", {})]
    if not sizes:
        return None
    return sum(sizes) / 1e6 / len(obs.spans)

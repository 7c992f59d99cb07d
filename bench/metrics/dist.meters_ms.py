"""Exchange (``exec/dist.py``): ``dist.meters`` spans summed per
request, in ms: the host reads of the distributed execute's meters,
after ``dist.device_wait`` has waited for the program."""

from harness import spans


def read(obs):
    if not obs.spans:
        return None
    reads = [s for t in obs.spans for s in spans.find(t, "dist.meters")]
    if not reads:
        return None
    return sum(s["ms"] for s in reads) / len(obs.spans)

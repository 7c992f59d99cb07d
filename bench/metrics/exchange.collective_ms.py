"""Exchange (``exec/dist.py``): device time of the all-to-all,
all-reduce and all-gather ops in the profiler window, averaged over the
chips, in ms per request."""


def read(obs):
    p = obs.profile
    if not p or not obs.profiled_requests or "collective" not in p["class_s"]:
        return None
    return p["class_s"]["collective"] * 1e3 / obs.profiled_requests

"""Device: the union of the op intervals on each chip in the profiler
window, averaged over the chips, in ms per request."""


def read(obs):
    p = obs.profile
    if not p or not obs.profiled_requests or p["busy_s"] <= 0:
        return None
    return p["busy_s"] * 1e3 / obs.profiled_requests

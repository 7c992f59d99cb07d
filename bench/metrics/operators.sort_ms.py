"""Local operators (``exec/ops.py``): device time of the sort ops in the
profiler window, averaged over the chips, in ms per request."""


def read(obs):
    p = obs.profile
    if not p or not obs.profiled_requests or "sort" not in p["class_s"]:
        return None
    return p["class_s"]["sort"] * 1e3 / obs.profiled_requests

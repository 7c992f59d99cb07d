"""Device: the share of the profiler window in which no op ran, averaged
over the chips, in %."""


def read(obs):
    p = obs.profile
    if not p or p["window_s"] <= 0 or p["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - p["busy_s"] / p["window_s"])

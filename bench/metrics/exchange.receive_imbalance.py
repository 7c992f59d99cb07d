"""Exchange (``exec/dist.py``): the worst receive-load imbalance of the
window's requests. Per request, over the exchange sites that moved at
least 64 rows: the most rows one chip received (``part_max_<site>``)
over the mean (``part_rows_<site>`` / chips). 1.0 is even."""

FLOOR = 64


def imbalance(metrics: dict, chips: int) -> float:
    worst = 1.0
    for k, v in metrics.items():
        if not k.startswith("part_max_"):
            continue
        total = metrics.get("part_rows_" + k[len("part_max_"):], 0)
        if total >= FLOOR:
            worst = max(worst, float(v) * chips / float(total))
    return worst


def read(obs):
    if not obs.dist_metrics or obs.chips <= 1:
        return None
    return max(imbalance(m, obs.chips) for m in obs.dist_metrics)

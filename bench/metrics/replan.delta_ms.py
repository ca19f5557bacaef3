"""Median answer time of the replan window's delta answers (the cached
scan extended by one window), on the host clock."""
from bench.common import quantile

LAYER = "fl.replan"
UNIT = "ms"
MOVES = "replan_p50_ms"


def read(run):
    ms = [m for _, mode, m in run.record["answers"] if mode == "delta"]
    return quantile(ms, 0.5) if ms else None

"""Median answer time of the replan window's full rescans: the eq.-13
candidate scan and the forest inference over the whole pool."""
from bench.common import quantile

LAYER = "core.search"
UNIT = "ms"
MOVES = "replan_p95_ms"


def read(run):
    ms = [m for _, mode, m in run.record["answers"] if mode == "full"]
    return quantile(ms, 0.5) if ms else None

"""Median time of a delta answer's scoring of the revealed window (the
program's `replan.delta.score` span: one-window `step_candidates`,
histogram, features, forest, and the read of the utilities), from the
trace."""
from bench import program_spans as P

LAYER = "fl.replan"
UNIT = "ms"
MOVES = "replan_p50_ms"


def read(run):
    ms = [1e3 * s.seconds for s in P.find(run, "replan.delta.score")]
    return P.median(ms)

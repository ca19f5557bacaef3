"""Median time of a delta answer's re-reduce of the survivors' scores
(the program's `replan.delta.reduce` span: event positions, gather,
bucket padding, the device sum and its read), from the trace."""
from bench import program_spans as P

LAYER = "fl.replan"
UNIT = "ms"
MOVES = "replan_p50_ms"


def read(run):
    ms = [1e3 * s.seconds for s in P.find(run, "replan.delta.reduce")]
    return P.median(ms)

"""Median power-of-two row bucket of the survivor pool that a delta
answer re-reduces (the `bucket` count of the program's `replan.delta`
span), from the trace."""
from bench import program_spans as P

LAYER = "fl.replan"
UNIT = "rows"
MOVES = "replan_p50_ms"


def read(run):
    return P.median([s.counts["bucket"]
                     for s in P.find(run, "replan.delta")])

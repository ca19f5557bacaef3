"""Median time of the replan window's invalidation checks (the program's
`replan.check` span: `_delta_blocker` with its drift check's eager
protocol steps), over every answer, from the trace."""
from bench import program_spans as P

LAYER = "fl.replan"
UNIT = "ms"
MOVES = "replan_p50_ms"


def read(run):
    ms = [1e3 * s.seconds for s in P.find(run, "replan.check")]
    return P.median(ms)

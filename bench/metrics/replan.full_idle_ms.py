"""Median, over the replan window's full rescans, of the time inside the
program's `replan.full` span in which no operation ran on the chip: the
host part of a full rescan (draw, scan set-up, reads, selection), from
the trace."""
from bench import program_spans as P

LAYER = "core.search"
UNIT = "ms"
MOVES = "replan_p95_ms"


def read(run):
    idle = P.idle_s(run.trace, P.find(run, "replan.full"))
    return P.median([1e3 * t for t in idle]) if idle else None

"""Share of the delta answers' time in which no operation ran on the
chip: device-idle seconds inside the program's `replan.delta` spans over
their summed length, from the trace."""
from bench import program_spans as P

LAYER = "fl.replan"
UNIT = "%"
MOVES = "replan_p50_ms"


def read(run):
    spans = P.find(run, "replan.delta")
    idle = P.idle_s(run.trace, spans)
    total = sum(s.seconds for s in spans)
    if not idle or total <= 0:
        return None
    return 100.0 * sum(idle) / total

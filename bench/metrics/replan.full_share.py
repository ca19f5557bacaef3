"""Share of the replan window's answers that were full rescans (cold,
status change, pool exhaustion), from the service's `last_mode`."""
LAYER = "fl.replan"
UNIT = "%"
MOVES = "replan_p95_ms"


def read(run):
    a = run.record["answers"]
    if not a:
        return None
    return 100.0 * sum(mode == "full" for _, mode, _ in a) / len(a)

"""Share of the replan window in which no operation ran on the chip:
1 - (union of device-operation intervals) / window, from the trace."""
LAYER = "device"
UNIT = "%"
MOVES = "replan_p50_ms"


def read(run):
    t = run.trace
    if t is None or not t.devices or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)

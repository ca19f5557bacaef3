"""Share of the replan window the chip spends in the utility forest's
inference (`_forest_predict_device`: the level-wise gathers over the
flattened trees), from the trace's program events."""
LAYER = "core.search"
UNIT = "%"
MOVES = "replan_p95_ms"


def read(run):
    t = run.trace
    sec = run.module_seconds(lambda n: "forest_predict_device" in n)
    if sec is None or t.window_s <= 0:
        return None
    return 100.0 * sec / t.window_s

"""Share of the federation window the chip spends in the jitted batched
client update (`update_many`: forward, backward and SGD of every client
of a group), from the trace's program events."""
LAYER = "fl.client"
UNIT = "%"
MOVES = "sim_windows_per_s"


def read(run):
    t = run.trace
    sec = run.module_seconds(lambda n: "update_many" in n)
    if sec is None or t.window_s <= 0:
        return None
    return 100.0 * sec / t.window_s

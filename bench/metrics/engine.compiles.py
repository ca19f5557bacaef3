"""Programs compiled or loaded from the persistent cache inside the
federation window (JAX's backend-compile events): 0 once the warm-up has
met every shape of a day."""
LAYER = "fl.engine"
UNIT = "count"
MOVES = "sim_windows_per_s"


def read(run):
    return run.record.get("compiles")

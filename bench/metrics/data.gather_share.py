"""Share of the federation window the host spends in the payload
adapter's batch gathers (`client_batch_many`, `client_batch`): the
`data.gather` spans of the benchmark's wrappers."""
LAYER = "data"
UNIT = "%"
MOVES = "sim_windows_per_s"


def read(run):
    lo, hi = run.record["window"]
    spans = [(s, e) for n, s, e in run.record["spans"] if n == "data.gather"]
    if not spans or hi <= lo:
        return None
    return 100.0 * sum(min(e, hi) - max(s, lo) for s, e in spans) \
        / (hi - lo)

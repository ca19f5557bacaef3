"""Roofline share of the eq.-4 aggregation kernel in the federation
window: the bytes each call must move (the (M, N) update stack, the
parameters in and out, the weights; M the event's buffer, N each leaf's
size) over HBM bandwidth, against the kernel's device time. The kernel is
memory-bound: its FLOPs over the peak are three orders of magnitude
smaller."""
from bench.flops import agg_event, roofline_share

LAYER = "kernels.agg"
UNIT = "%"
MOVES = "sim_windows_per_s"


def is_agg(name: str) -> bool:
    return "weighted_aggregate" in name or "_agg_kernel" in name


def read(run):
    r = run.record
    sec = run.device_seconds(is_agg)
    if sec is None or not r.get("events"):
        return None
    flops = nbytes = 0
    for m in r["events"]:
        f, b = agg_event(m, r["leaf_sizes"])
        flops, nbytes = flops + f, nbytes + b
    share, _ = roofline_share(flops, nbytes, sec, run.peaks["bf16_flops"],
                              run.peaks["hbm_bytes_per_s"])
    return share

"""Roofline share of the flash-attention forward kernel in the federation
window: the larger of its FLOPs over the bf16 peak and its bytes over HBM
bandwidth, for every call the window made (each client update's forward
passes over its padded batch bucket, each evaluation's forward), against
the kernel's device time. At head dim 8 and 8 positions the bytes bound
it."""
from bench.flops import roofline_share

LAYER = "kernels.flash_attention"
UNIT = "%"
MOVES = "sim_windows_per_s"


def is_flash(name: str) -> bool:
    return "flash" in name


def read(run):
    r = run.record
    sec = run.device_seconds(is_flash)
    if sec is None:
        return None
    P = r["payload"]
    rows = sum(r["train_buckets"]) * r["local_steps"] * r["batch_size"] \
        + r["eval_rows"]
    flops, nbytes = P.flash_forward(rows)
    share, _ = roofline_share(flops * P.layers, nbytes * P.layers, sec,
                              run.peaks["bf16_flops"],
                              run.peaks["hbm_bytes_per_s"])
    return share

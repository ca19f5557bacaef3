"""Model FLOP/s utilization of the payload's training in the federation
window: the forward and backward FLOPs of every real client row trained
(padding rows do not count), from shapes, over the window's seconds times
the chip's bf16 peak. The FL path's float32 matrix products run as one
bf16 pass at the default precision, so bf16 is the peak that applies."""
LAYER = "fl.client"
UNIT = "%"
MOVES = "sim_windows_per_s"


def read(run):
    r = run.record
    if not r.get("trained_rows") or r["window_s"] <= 0:
        return None
    samples = r["trained_rows"] * r["local_steps"] * r["batch_size"]
    flops = r["payload"].train_flops(samples)
    return 100.0 * flops / (r["window_s"] * run.peaks["bf16_flops"])

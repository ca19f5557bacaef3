"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's traffic file `bench/workloads/<cell>.json` names its
configuration (`bench/configs/<config>.json`) and its driver
(`bench/drivers/<driver>.py`). The driver sets up, warms up, measures for
`--seconds` and checks what the measured path produced against the plain
reference. With `--trace 0` the line carries the cell's end-to-end
metrics; with `--trace 1` the window is profiled and the line carries its
per-layer metrics, each read by `bench/metrics/<metric>.py`, with the
device's busy and window seconds and a breakdown of the trace.

The run needs the chips the cell asks for: without them it prints no
result and exits 1. The last line of standard output is one JSON object.
"""
from __future__ import annotations

import argparse
import importlib
import os
import sys
import time
import traceback
from dataclasses import dataclass, field
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_out" / "trace"
CACHE_DIR = ROOT / ".jax_cache"


@dataclass
class Context:
    """What a driver gets: the cell, its configuration, the run's
    arguments, the host spans to record into, and a tracer factory."""
    workload: dict
    config: dict
    seed: int
    seconds: float
    trace: bool
    chips: int
    t_start: float
    spans: object = None
    trace_dir: str = str(TRACE_DIR)
    extra: dict = field(default_factory=dict)

    def tracer(self):
        from bench.trace import capture
        return capture(os.path.join(self.trace_dir, self.workload["name"]))


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (the default of `repro.compile_cache`), whatever
    `JAX_COMPILATION_CACHE_DIR` says, so that two checkouts share nothing
    and a cell's second run compiles nothing. It keeps every program, the
    many sub-second ones too."""
    import jax
    path = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def per_layer(name: str, out: dict, trace, device: dict) -> dict:
    """Read every per-layer metric this cell reports."""
    from bench import common
    from bench.readers import Run
    run = Run(workload=name, record=out["record"], trace=trace,
              peaks=common.peaks(device["kind"]))
    metrics = {}
    for m in common.metrics_for(name, "per_layer"):
        reader = common.load_metric(m["name"])
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics


def prepare(workload: str, seed: int, seconds: float, trace: bool,
            t_start: float) -> Context:
    from bench import common
    from bench.spans import Spans
    wl = common.load_workload(workload)
    cfg = common.load_config(wl["config"])
    return Context(workload=wl, config=cfg, seed=seed, seconds=seconds,
                   trace=trace, chips=int(wl["chips"]), t_start=t_start,
                   spans=Spans())


def execute(ctx: Context, device: dict) -> tuple:
    """Run the cell's driver; returns (result line without checks,
    checks)."""
    from bench import common
    driver = importlib.import_module(f"bench.drivers.{ctx.workload['driver']}")
    out = driver.run(ctx)
    name = ctx.workload["name"]
    dev = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    if ctx.trace:
        from bench import trace as T
        tr = T.load(T.find_xplane(os.path.join(ctx.trace_dir, name)))
        tr.align(out["record"]["window"], out["record"]["spans"])
        metrics = per_layer(name, out, tr, device)
        dev.update(busy_s=tr.busy_s(), window_s=tr.window_s)
        extra = {"breakdown": {"device_ops": tr.top_ops(),
                               "idle_gaps": tr.idle_gaps()}}
    else:
        units = {m["name"]: m["unit"]
                 for m in common.metrics_for(name, "end_to_end")}
        metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in out["end_to_end"].items() if k in units}
        extra = {}
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": dev,
            **extra}
    print("info " + repr(out["info"]), file=sys.stderr)
    return line, out["checks"]


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    here = str(Path(__file__).resolve().parent)
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if p and Path(p).resolve() != Path(here)]
    try:
        from bench import common
        ctx = prepare(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start)
        device = common.require_chips(ctx.chips)
        enable_cache()
        line, checks = execute(ctx, device)
    except Exception:   # a run that cannot be made prints no result
        traceback.print_exc()
        return 1
    common.emit(line, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())

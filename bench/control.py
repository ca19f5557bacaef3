"""Readings that set a cell's correctness limits: the program's, and the
control's (the plain reference put in the program's place at the
precision below the configuration's), on many seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 3

Each seed runs the cell's driver at the cell's own size with a short
window, then prints one JSON line: the program's compared numbers and the
control's. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(workload: str, seed: int, seconds: float) -> dict:
    """One seed's program and control readings, on the chip."""
    import importlib
    from bench import common
    from bench.run import prepare
    ctx = prepare(workload, seed, seconds, False, time.perf_counter())
    common.require_chips(ctx.chips)
    ctx.extra["control"] = True
    driver = importlib.import_module(
        f"bench.drivers.{ctx.workload['driver']}")
    out = driver.run(ctx)
    return {"seed": seed, "correct": out["correct"],
            "program": {n: v for n, v, _ in out["checks"]},
            "control": {n: v for n, v, _ in out["control"]},
            "limits": {n: lim for n, _, lim in out["checks"]},
            "info": {k: v for k, v in out["info"].items()
                     if isinstance(v, (int, float, str, bool))}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    here = Path(__file__).resolve().parent
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if p and Path(p).resolve() != here]
    from bench.run import enable_cache
    enable_cache()
    for s in args.seeds.split(","):
        print(json.dumps(readings(args.workload, int(s), args.seconds)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

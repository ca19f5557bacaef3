"""What every part of the benchmark shares: finding a configuration, a
cell or a metric by its name, the character rules for names and units,
the table of peaks, the device check and the result line.

Everything is found under one root (the checkout): `BENCHMARK.json`,
`bench/configs/<config>.json`, `bench/workloads/<cell>.json`,
`bench/metrics/<metric>.py`. Adding a configuration, a cell or a metric is
adding such files and their `BENCHMARK.json` entries; nothing here names
one of them.
"""
from __future__ import annotations

import importlib.util
import json
import re
import sys
import time
from pathlib import Path
from typing import Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = "bench"

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class BenchError(RuntimeError):
    """A run that cannot be made: no chip, an unknown name, a bad file."""


def check_name(name: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise BenchError(f"not a valid name: {name!r}")
    return name


def check_unit(unit: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise BenchError(f"not a valid unit: {unit!r}")
    return unit


def _json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing file: {path}")
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return _json(Path(root) / "BENCHMARK.json")


def load_workload(name: str, root: Path = ROOT) -> dict:
    """The cell's traffic file, with its `name` filled in."""
    w = _json(Path(root) / BENCH / "workloads" / f"{check_name(name)}.json")
    return {**w, "name": name}


def load_config(name: str, root: Path = ROOT) -> dict:
    c = _json(Path(root) / BENCH / "configs" / f"{check_name(name)}.json")
    return {**c, "name": name}


def load_metric(name: str, root: Path = ROOT):
    """The reader module `bench/metrics/<name>.py`: it declares `LAYER`,
    `UNIT`, `MOVES` and `read(run) -> float | None`."""
    path = Path(root) / BENCH / "metrics" / f"{check_name(name)}.py"
    if not path.is_file():
        raise BenchError(f"no reader for metric {name}: {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for attr in ("LAYER", "UNIT", "MOVES", "read"):
        if not hasattr(mod, attr):
            raise BenchError(f"metric reader {path} lacks {attr}")
    check_unit(mod.UNIT)
    return mod


def metrics_for(workload: str, kind: str, root: Path = ROOT) -> list:
    """`BENCHMARK.json` entries of `kind` ("end_to_end" or "per_layer")
    that this cell reports: those that list it, and those that list no
    cells."""
    return [m for m in benchmark(root)[kind]
            if "workloads" not in m or workload in m["workloads"]]


def peaks(kind: str, root: Path = ROOT) -> dict:
    """The published peaks of one chip of `device_kind` `kind`. A device
    that is not in the table is an error, never a default."""
    table = _json(Path(root) / BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r}; known: "
                         f"{sorted(table)}")
    return table[kind]


def require_chips(n: int) -> dict:
    """The device record of this process; raises unless JAX sees at least
    `n` TPU chips. The benchmark never falls back to the CPU."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise BenchError(f"no TPU: JAX sees {len(devs)} {d.platform} "
                         "device(s)")
    if len(devs) < n:
        raise BenchError(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak_bytes(count: int) -> Optional[int]:
    """Peak bytes in use on the fullest of the first `count` devices."""
    import jax
    peaks_ = []
    for d in jax.devices()[:count]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks_.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_) if peaks_ else None


def log(msg: str) -> None:
    """A progress line on stderr, with the process's clock."""
    print(f"[bench {time.perf_counter():.3f}] {msg}", file=sys.stderr,
          flush=True)


def quantile(values, q: float) -> float:
    """The q-quantile of all values (linear between order statistics)."""
    xs = sorted(values)
    if not xs:
        raise BenchError("quantile of no values")
    if len(xs) == 1:
        return float(xs[0])
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def emit(result: dict, checks: list) -> None:
    """Print the compared numbers on stderr, then the result line as the
    last line of stdout. `checks` is [(name, value, limit)], printed last
    on stderr and under the last key of the line."""
    for name, value, limit in checks:
        print(f"check {name} = {value!r} (limit {limit!r})", file=sys.stderr)
    sys.stderr.flush()
    line = {**result,
            "checks": {n: {"value": v, "limit": lim}
                       for n, v, lim in checks}}
    print(json.dumps(line), flush=True)

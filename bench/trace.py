"""Reduce a profiler trace (`.xplane.pb`) to what the per-layer readers
need: the device's busy intervals, time per device operation and per
program, and the idle gaps, each attributed to the host span it fell in.

The host spans are the benchmark's own `TraceAnnotation`s (`bench.*`,
see `bench/spans.py`); the traced window is the span `bench.window`.
Device planes are those named `/device:TPU:<n>`; their `XLA Ops` line
holds one event per operation run on the chip, the `XLA Modules` line
one per compiled program.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import shutil
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from bench.spans import PREFIX

WINDOW = "window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@contextlib.contextmanager
def capture(log_dir: str):
    """Profile the enclosed block into `log_dir` (emptied first), host
    spans and device operations, without the Python tracer."""
    shutil.rmtree(log_dir, ignore_errors=True)
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(log_dir, profiler_options=opts):
        yield


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def union(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    out = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def gaps(merged, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The complement of merged intervals inside [lo, hi]."""
    out, t = [], lo
    for a, b in merged:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(idle, spans) -> Dict[str, float]:
    """Seconds of idle time per host span name: each idle stretch is split
    over the spans it overlaps (the innermost wins where spans nest: the
    shortest covering span is taken), and the rest goes to `host`."""
    out: Dict[str, float] = {}
    spans = sorted((s, e, n) for n, s, e in spans)
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0.0)
    for a, b in idle:
        near = [(s, e, n) for s, e, n in
                spans[bisect.bisect_left(starts, a - longest):
                      bisect.bisect_right(starts, b)] if e > a]
        cuts = sorted({a, b, *(x for s, e, _ in near for x in (s, e)
                               if a < x < b)})
        for s0, s1 in zip(cuts, cuts[1:]):
            mid = 0.5 * (s0 + s1)
            cover = [(e - s, n) for s, e, n in near if s <= mid <= e]
            name = min(cover)[1] if cover else "host"
            out[name] = out.get(name, 0.0) + (s1 - s0)
    return out


@dataclass
class Trace:
    """One traced window, in seconds on the trace's own clock."""
    window: Tuple[float, float]
    ops: Dict[int, List[Tuple[str, float, float]]]       # device -> events
    modules: Dict[int, List[Tuple[str, float, float]]]
    spans: List[Tuple[str, float, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def devices(self) -> List[int]:
        return sorted(self.ops)

    def busy_s(self, device: Optional[int] = None) -> float:
        """Seconds in which some operation ran, on one device or averaged
        over the devices."""
        devs = self.devices if device is None else [device]
        if not devs:
            return 0.0
        tot = 0.0
        for d in devs:
            tot += sum(b - a for a, b in union(
                [(s, e) for _, s, e in self.ops.get(d, [])], *self.window))
        return tot / len(devs)

    def op_seconds(self, match=None, device: int = None) -> float:
        """Device seconds of operations whose name `match(name)` accepts,
        inside the window (summed, averaged over devices)."""
        return self._sum(self.ops, match, device)

    def module_seconds(self, match=None, device: int = None) -> float:
        return self._sum(self.modules, match, device)

    def _sum(self, table, match, device):
        devs = self.devices if device is None else [device]
        if not devs:
            return 0.0
        lo, hi = self.window
        tot = 0.0
        for d in devs:
            tot += sum(max(0.0, min(e, hi) - max(s, lo))
                       for n, s, e in table.get(d, [])
                       if match is None or match(n))
        return tot / len(devs)

    def align(self, host_window, host_spans) -> None:
        """Put spans recorded on the host clock (`time.perf_counter`) on
        the trace's clock, by the window span both recorded."""
        off = self.window[0] - host_window[0]
        self.spans = [(n, s + off, e + off) for n, s, e in host_spans]

    def top_ops(self, n: int = 10, device: int = 0) -> List[list]:
        lo, hi = self.window
        per: Dict[str, float] = {}
        for name, s, e in self.ops.get(device, []):
            t = max(0.0, min(e, hi) - max(s, lo))
            if t > 0:
                per[name] = per.get(name, 0.0) + t
        return [[k, v] for k, v in sorted(per.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10, device: int = 0) -> List[list]:
        """The device's idle time in the window by what the host was doing
        (the benchmark's span around it), largest first."""
        merged = union([(s, e) for _, s, e in self.ops.get(device, [])],
                       *self.window)
        spans = [(nm, s, e) for nm, s, e in self.spans if nm != WINDOW]
        per = attribute(gaps(merged, *self.window), spans)
        return [[k, v] for k, v in sorted(per.items(),
                                          key=lambda kv: -kv[1])[:n]]


def _events(line):
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def short_module(name: str) -> str:
    """`jit_update_many(123...)` -> `jit_update_many`."""
    return name.split("(")[0]


def qualify(ops, modules):
    """Name each operation `<program>/<instruction>`: the program whose
    execution holds it, and the instruction's name without its HLO text
    (`%fusion.29 = f32[...] fusion(...)` -> `%fusion.29`)."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for name, s, e in ops:
        j = bisect.bisect_right(starts, s) - 1
        mod = short_module(mods[j][0]) if j >= 0 and mods[j][2] >= s \
            else "?"
        out.append((f"{mod}/{name.split(' = ')[0]}", s, e))
    return out


def load(path: str) -> Trace:
    """Read an `.xplane.pb`. The window is the `bench.window` span; where
    no such span was recorded, the whole extent of the host spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, spans = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = int(plane.name[len(DEVICE_PREFIX):].split()[0])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[dev] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[dev] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name.startswith(PREFIX):
                        spans.append((name[len(PREFIX):], s, e))
    ops = {d: qualify(ev, modules.get(d, [])) for d, ev in ops.items()}
    win = [(s, e) for n, s, e in spans if n == WINDOW]
    if win:
        window = max(win, key=lambda w: w[1] - w[0])
    elif spans:
        window = (min(s for _, s, _ in spans), max(e for _, _, e in spans))
    else:
        window = (0.0, 0.0)
    return Trace(window=window, ops=ops, modules=modules, spans=spans)

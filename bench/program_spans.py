"""The program's own spans in a traced run, and the device's idle time
inside them.

The program marks its host work with `repro.*` spans
(`src/repro/tracing.py`): while the profiler captures, each is an event
on a host thread of the trace, with its counts as stats, on the same
clock as the device's operations. This reads them from the cell's
`.xplane.pb` (the newest under `bench.run.TRACE_DIR/<cell>`, taken only
where its `bench.window` is the window `bench.trace.load` reduced), nests
each thread's spans by their intervals, and keeps those inside the
window. A program that records no such span gives none, and the readers
built on this then return None.

    python3 -m bench.program_spans <cell>

prints the summary of the cell's last traced run: each span's count,
median and total seconds, the device's idle seconds inside it, how much
of the delta answers' `replan.request` their child spans cover, and the
idle time inside the requests (first device) by innermost program span.
"""
from __future__ import annotations

import bisect
import functools
import json
import os
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from bench import run as R
from bench import trace as T
from bench.common import quantile
from bench.readers import Run
from bench.spans import PREFIX as BENCH_PREFIX

PREFIX = "repro."
REQUEST = "replan.request"


@dataclass
class Span:
    """One program span, in seconds on the trace's clock."""
    name: str
    start: float
    end: float
    counts: dict
    children: List["Span"] = field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()


def nest(spans) -> List[Span]:
    """The roots of one thread's spans, each span placed under the
    innermost span that holds its interval."""
    roots, stack = [], []
    for sp in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1].end < sp.end:
            stack.pop()
        (stack[-1].children if stack else roots).append(sp)
        stack.append(sp)
    return roots


def _seconds(e) -> Tuple[float, float]:
    return e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9


@functools.lru_cache(maxsize=4)
def _load(path: str, mtime_ns: int):
    from jax.profiler import ProfileData
    roots, bench = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            mine = []
            for e in line.events:
                if e.name.startswith(PREFIX):
                    mine.append(Span(e.name[len(PREFIX):], *_seconds(e),
                                     dict(e.stats)))
                elif e.name.startswith(BENCH_PREFIX):
                    bench.append((e.name[len(BENCH_PREFIX):],
                                  *_seconds(e)))
            roots += nest(mine)
    # the window as `bench.trace.load` takes it
    win = [(s, e) for n, s, e in bench if n == T.WINDOW]
    if win:
        window = max(win, key=lambda w: w[1] - w[0])
    elif bench:
        window = (min(s for _, s, _ in bench),
                  max(e for _, _, e in bench))
    else:
        window = (0.0, 0.0)
    return window, tuple(sorted(roots, key=lambda s: s.start))


def load(path: str):
    """(window, root spans) of one `.xplane.pb`, read once per file."""
    return _load(path, os.stat(path).st_mtime_ns)


def roots(run) -> List[Span]:
    """The root spans of the run's trace inside its window; [] where the
    trace is missing, is another run's or holds no program span."""
    if run.trace is None:
        return []
    try:
        path = T.find_xplane(os.path.join(R.TRACE_DIR, run.workload))
    except FileNotFoundError:
        return []
    window, spans = load(path)
    if window != tuple(run.trace.window):
        return []
    lo, hi = window
    return [s for s in spans if lo <= s.start and s.end <= hi]


def find(run, name: str) -> List[Span]:
    """Every span named `name` in the run's window, in time order."""
    return sorted((s for r in roots(run) for s in r.walk()
                   if s.name == name), key=lambda s: s.start)


def requests(run) -> List[Span]:
    """The window's `replan.request` spans, each with its children."""
    return find(run, REQUEST)


def idle_gaps(trace, spans, device: int) -> List[list]:
    """For each span, the stretches inside it in which no operation ran
    on `device`."""
    spans = list(spans)
    if not spans:
        return []
    busy = T.union([(a, b) for _, a, b in trace.ops.get(device, [])],
                   min(s.start for s in spans), max(s.end for s in spans))
    starts = [a for a, _ in busy]
    out = []
    for s in spans:
        j = max(bisect.bisect_right(starts, s.start) - 1, 0)
        near = busy[j:bisect.bisect_left(starts, s.end)]
        out.append(T.gaps(T.union(near, s.start, s.end), s.start, s.end))
    return out


def idle_s(trace, spans) -> Optional[List[float]]:
    """Seconds inside each span in which no operation ran on the device
    (averaged over the trace's devices), or None where the trace holds
    no device."""
    if trace is None or not trace.devices:
        return None
    spans = list(spans)
    out = [0.0] * len(spans)
    for d in trace.devices:
        for i, g in enumerate(idle_gaps(trace, spans, d)):
            out[i] += sum(b - a for a, b in g) / len(trace.devices)
    return out


def median(values) -> Optional[float]:
    return quantile(values, 0.5) if values else None


def delta_requests(run) -> List[Span]:
    return [r for r in requests(run)
            if any(c.name == "replan.delta" for c in r.children)]


def coverage(request: Span) -> float:
    """Share of a request's time inside the leaves of its span tree
    (`replan.check`, the delta's or the full rescan's steps, an inline
    `replan.maintain`)."""
    leaves = [s for s in request.walk() if s is not request and
              not s.children]
    return sum(s.seconds for s in leaves) / max(request.seconds, 1e-12)


def summary(run) -> Dict[str, object]:
    """What `python3 -m bench.program_spans` prints."""
    names = sorted({s.name for r in roots(run) for s in r.walk()})
    out: Dict[str, object] = {"spans": {}}
    for name in names:
        spans = find(run, name)
        idle = idle_s(run.trace, spans)
        out["spans"][name] = {
            "count": len(spans),
            "median_ms": 1e3 * median([s.seconds for s in spans]),
            "total_s": sum(s.seconds for s in spans),
            "idle_s": None if idle is None else sum(idle)}
    deltas = delta_requests(run)
    if deltas:
        cov = [coverage(r) for r in deltas]
        out["delta_coverage"] = {"min": min(cov), "median": median(cov)}
    reqs = requests(run)
    if reqs and run.trace is not None and run.trace.devices:
        idle = [g for gs in idle_gaps(run.trace, reqs, run.trace.devices[0])
                for g in gs]
        inner = [(s.name, s.start, s.end) for r in reqs for s in r.walk()
                 if s is not r]
        # idle time in a request that no child span holds stays its own
        split = T.attribute(idle, inner)
        split[REQUEST] = split.pop("host", 0.0)
        out["request_idle_s"] = dict(sorted(split.items(),
                                            key=lambda kv: -kv[1]))
    return out


def main(argv=None) -> int:
    cell = (argv if argv is not None else sys.argv[1:])[0]
    tr = T.load(T.find_xplane(os.path.join(R.TRACE_DIR, cell)))
    print(json.dumps(summary(Run(cell, {}, tr, {}))))
    return 0


if __name__ == "__main__":
    sys.exit(main())

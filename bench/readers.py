"""What a per-layer metric reader gets: one traced run, reduced."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from bench.trace import Trace


@dataclass
class Run:
    """`record` is the driver's record of the window (counts, spans on the
    host clock, shapes); `trace` the reduced profile of the same window,
    or None; `peaks` the chip's row of `bench/peaks.json`."""
    workload: str
    record: dict
    trace: Optional[Trace]
    peaks: dict

    def device_seconds(self, match) -> Optional[float]:
        """Device seconds of the operations `match` accepts, or None where
        the trace holds none of them."""
        if self.trace is None:
            return None
        t = self.trace.op_seconds(match)
        return t if t > 0 else None

    def module_seconds(self, match) -> Optional[float]:
        if self.trace is None:
            return None
        t = self.trace.module_seconds(match)
        return t if t > 0 else None

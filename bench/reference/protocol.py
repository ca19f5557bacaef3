"""Plain reference of the staleness protocol (paper Algorithm 1) and of the
FedBuff decision, in host numpy, one window at a time.

Independent of the program under test: it imports nothing from it. A
window is upload -> decide -> aggregate -> download over (K,) integer
columns; the counters are those the engine reports in
`SimResult.counters()`.
"""
from __future__ import annotations

import numpy as np


class Protocol:
    """Algorithm-1 state of K satellites, bootstrapped as the ground station
    does: every satellite holds version 0 with a pending round on it."""

    def __init__(self, K: int, s_max: int = 8):
        self.version = np.zeros(K, np.int64)
        self.pending = np.zeros(K, np.int64)
        self.buffered = np.full(K, -1, np.int64)
        self.ig = 0
        self.s_max = s_max
        self.counters = {"global_updates": 0, "aggregated_gradients": 0,
                         "idle_connections": 0, "total_connections": 0,
                         "staleness_hist": [0] * (s_max + 1),
                         "windows_run": 0}

    def upload(self, conn: np.ndarray) -> int:
        """Connected satellites hand their pending update to the buffer.
        Returns the buffer occupancy."""
        conn = np.asarray(conn, bool)
        has = self.pending >= 0
        idle = conn & ~has & (self.version == self.ig)
        up = conn & has
        self.buffered = np.where(up, self.pending, self.buffered)
        self.pending = np.where(up, -1, self.pending)
        self.counters["total_connections"] += int(conn.sum())
        self.counters["idle_connections"] += int(idle.sum())
        return int((self.buffered >= 0).sum())

    def aggregate(self):
        """Consume the buffer (eq. 4's index set). Returns the aggregated
        satellites, their base versions and their unclipped staleness."""
        ks = np.flatnonzero(self.buffered >= 0)
        base = self.buffered[ks].copy()
        stal = self.ig - base
        for s in np.clip(stal, 0, self.s_max):
            self.counters["staleness_hist"][int(s)] += 1
        self.counters["global_updates"] += 1
        self.counters["aggregated_gradients"] += len(ks)
        self.buffered[:] = -1
        self.ig += 1
        return ks, base, stal

    def download(self, conn: np.ndarray) -> None:
        """Connected satellites behind the global version fetch it and
        start a fresh round on it."""
        new = np.asarray(conn, bool) & (self.version < self.ig)
        self.version = np.where(new, self.ig, self.version)
        self.pending = np.where(new, self.ig, self.pending)

    def state(self) -> dict:
        return {"version": self.version.copy(),
                "pending": self.pending.copy(),
                "buffered": self.buffered.copy(), "ig": self.ig}


def run_fedbuff(C: np.ndarray, M: int, windows: int, *, s_max: int = 8,
                on_event=None) -> Protocol:
    """FedBuff (aggregate once the buffer holds M updates) over the first
    `windows` rows of the (W, K) connectivity, tiled by whole periods.
    `on_event(window, ks, base, stal)` sees each aggregation."""
    p = Protocol(C.shape[1], s_max)
    for i in range(windows):
        conn = C[i % C.shape[0]]
        n_buf = p.upload(conn)
        if n_buf >= M and n_buf > 0:
            ks, base, stal = p.aggregate()
            if on_event is not None:
                on_event(i, ks, base, stal)
        p.download(conn)
        p.counters["windows_run"] = i + 1
    return p

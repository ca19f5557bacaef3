"""Plain reference of the eq.-13 selection: every candidate schedule of a
pool simulated through the staleness protocol over the horizon, each
aggregation's staleness histogram featurized and scored by the utility
forest, the scores summed over the schedule's aggregation windows.

Host numpy, vectorized over the candidates; it imports nothing of the
program under test. `dtype=np.float32` is the reference; the control
computes features, utilities and sums in a lower precision, every
operation rounded to it.
"""
from __future__ import annotations

import numpy as np


def make_forest(rng: np.random.Generator, *, n_trees: int, depth: int,
                s_max: int, status_range) -> dict:
    """Random weights for a forest over the featurized histograms: full
    binary trees of `depth` levels (node n has children 2n+1, 2n+2), each
    split on a random feature at a threshold drawn over that feature's
    range, leaf utilities uniform on [0, 1). Counts split at half-integers,
    so no integer count lies on a threshold."""
    F = s_max + 5
    n_int = 2 ** depth - 1
    n_all = 2 ** (depth + 1) - 1
    feature = np.full((n_trees, n_all), -1, np.int32)
    thresh = np.zeros((n_trees, n_all), np.float32)
    feature[:, :n_int] = rng.integers(0, F, (n_trees, n_int))
    f = feature[:, :n_int]
    u = rng.random((n_trees, n_int))
    counts = np.floor(u * 24.0) + 0.5              # histogram bins, total
    t = np.where(f <= s_max + 1, counts, 0.0)
    t = np.where(f == s_max + 2, u * 16.0, t)       # fresh mass
    t = np.where(f == s_max + 3, u * s_max, t)      # mean staleness
    lo, hi = status_range
    t = np.where(f == s_max + 4, lo + u * (hi - lo), t)
    thresh[:, :n_int] = t.astype(np.float32)
    value = np.zeros((n_trees, n_all), np.float32)
    value[:, n_int:] = rng.random((n_trees, n_all - n_int))
    return {"feature": feature, "thresh": thresh, "value": value,
            "depth": depth}


def rounding(dtype):
    """Arithmetic in `dtype`, carried in float32: every result is rounded
    to `dtype` (round to nearest even) as that type's own operations
    round it. float32 rounds nothing."""
    if np.dtype(dtype) == np.float32:
        return lambda x: np.asarray(x, np.float32)
    return lambda x: np.asarray(x, np.float32).astype(dtype).astype(
        np.float32)


def featurize(hist: np.ndarray, status: float, q) -> np.ndarray:
    """Histogram counts, their total, the staleness-compensated mass
    sum_s h_s (s+1)^-1/2, the mean staleness, and the training status."""
    h = q(hist)
    s = np.arange(h.shape[-1], dtype=np.float32)
    c = q((s + 1) ** np.float32(-0.5))
    total = np.zeros(h.shape[:-1] + (1,), np.float32)
    fresh = np.zeros_like(total)
    wsum = np.zeros_like(total)
    for j in range(h.shape[-1]):
        total = q(total + h[..., j:j + 1])
        fresh = q(fresh + q(h[..., j:j + 1] * c[j]))
        wsum = q(wsum + q(h[..., j:j + 1] * s[j]))
    mean = q(wsum / np.maximum(total, 1.0))
    stat = q(np.full(total.shape, status, np.float32))
    return np.concatenate([h, total, fresh, mean, stat], -1)


def predict(forest: dict, X: np.ndarray, q) -> np.ndarray:
    """Mean leaf value over the trees; a row goes left where its feature is
    at or below the threshold."""
    feat = forest["feature"]
    thr = q(forest["thresh"])
    val = q(forest["value"])
    n_int = 2 ** forest["depth"] - 1
    rows = np.arange(X.shape[0])
    out = np.zeros(X.shape[0], np.float32)
    for t in range(feat.shape[0]):
        node = np.zeros(X.shape[0], np.int64)
        while True:
            inner = node < n_int
            if not inner.any():
                break
            at = np.minimum(node, n_int - 1)
            f = np.where(inner, feat[t, at], 0)
            left = X[rows, f] <= thr[t, at]
            node = np.where(inner, 2 * node + np.where(left, 1, 2), node)
        out = q(out + val[t, node])
    return q(out / np.float32(feat.shape[0]))


def scores(pool: np.ndarray, C_window: np.ndarray, state: dict, ig: int,
           forest: dict, status: float, *, s_max: int,
           dtype=np.float32) -> np.ndarray:
    """Summed predicted utility of every candidate (R, I0) from the
    protocol state (version, pending, buffered) and global version `ig`.
    An aggregation window with an empty buffer aggregates nothing but is
    still scored, on an empty histogram."""
    pool = np.asarray(pool)
    R, I0 = pool.shape
    K = C_window.shape[1]
    ver = np.broadcast_to(state["version"], (R, K)).astype(np.int64)
    pend = np.broadcast_to(state["pending"], (R, K)).astype(np.int64)
    buf = np.broadcast_to(state["buffered"], (R, K)).astype(np.int64)
    g = np.full(R, ig, np.int64)
    q = rounding(dtype)
    total = np.zeros(R, np.float32)
    for t in range(I0):
        conn = np.asarray(C_window[t], bool)[None, :]
        up = conn & (pend >= 0)
        buf = np.where(up, pend, buf)
        pend = np.where(up, -1, pend)
        a = pool[:, t] == 1
        inbuf = buf >= 0
        agg = a & inbuf.any(1)
        counted = inbuf & agg[:, None]
        stale = np.clip(g[:, None] - buf, 0, s_max)
        rr, kk = np.nonzero(counted)
        hist = np.bincount(rr * (s_max + 1) + stale[rr, kk],
                           minlength=R * (s_max + 1)).reshape(R, s_max + 1)
        rows = np.flatnonzero(a)
        if rows.size:
            u = predict(forest, featurize(hist[rows], status, q), q)
            total[rows] = q(total[rows] + u)
        buf = np.where(agg[:, None], -1, buf)
        g = g + agg
        new = conn & (ver < g[:, None])
        ver = np.where(new, g[:, None], ver)
        pend = np.where(new, g[:, None], pend)
    return total

"""Random search over aggregation-schedule candidates (paper §3.2, eq. 13).

The search space R ⊂ {0,1}^{I0} is restricted to schedules with
n_agg ∈ [N_min, N_max] aggregations (the paper infers the range from û and
uses |R| = 5000). Candidate evaluation is the vectorized protocol simulator
(repro.core.staleness.simulate_candidates) — one vmapped scan instead of the
paper's sequential Python loop.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import mesh as MM
from repro.core import staleness as SS
from repro.core.utility import featurize, featurize_jnp, forest_dense


def random_candidates(rng: np.random.Generator, I0: int, n_min: int,
                      n_max: int, R: int) -> np.ndarray:
    """(R, I0) binary matrix; row r has n_r ~ U[n_min, n_max] ones."""
    n_min = max(0, min(n_min, I0))
    n_max = max(n_min, min(n_max, I0))
    scores = rng.random((R, I0))
    n_agg = rng.integers(n_min, n_max + 1, R)
    order = np.argsort(scores, axis=1)
    ranks = np.empty_like(order)
    rows = np.arange(R)[:, None]
    ranks[rows, order] = np.arange(I0)[None, :]
    return (ranks < n_agg[:, None]).astype(np.int32)


def event_positions(candidates: np.ndarray):
    """Per-candidate aggregation-window indices, dense (host numpy).

    Returns (idx, mask): idx (R, n_cap) int32 holds each schedule's a=1
    window indices in increasing order (n_cap = max aggregation count over
    the batch, at least 1), 0-padded; mask (R, n_cap) bool flags the real
    entries. The eq.-13 objective only sums utility at a=1 windows, so the
    scorer evaluates û at these positions instead of all I0 windows.
    """
    cands = np.asarray(candidates)
    n = cands.sum(axis=1).astype(np.int64)
    n_cap = max(int(n.max()) if n.size else 0, 1)
    # stable argsort of (1 - a) lists the a=1 positions first, in order
    idx = np.argsort(1 - cands, axis=1, kind="stable")[:, :n_cap]
    mask = np.arange(n_cap)[None, :] < n[:, None]
    return idx.astype(np.int32), mask


@functools.partial(jax.jit, static_argnames=("s_max", "mesh"))
def _simulate_marks(C_window, candidates, state, ig, link, *, s_max: int,
                    mesh=None):
    """Jitted marks-collecting candidate simulation (the eager vmapped
    scan pays ~3x its own runtime in dispatch overhead at search shapes).
    `link` is an optional device `LinkGate` (grant (I0, K)) so candidates
    are scored against transfer-gated effective connectivity.

    `mesh` (static — meshes hash) shards the satellite axis of the
    vmapped scan under `shard_map`: state columns and the K axes of the
    connectivity/grant windows split across devices, candidates and
    scalars replicate, and the only cross-shard traffic is the
    empty-buffer psum inside `aggregate_step` (the marks themselves are
    per-satellite). The caller pads K to a device-count multiple
    (`score_candidates` does); `mesh=None` compiles the exact
    single-device program."""
    def run(Cw, cands, st, g, lk, axis=None):
        _, _, infos = SS.simulate_candidates(Cw, cands, st, g,
                                             s_max=s_max, collect="marks",
                                             link=lk, axis_name=axis)
        return infos["marks"]

    if mesh is None:
        return run(C_window, candidates, state, ig, link)
    ax = mesh.axis_names[0]
    P = jax.sharding.PartitionSpec
    sat, rep, col = P(ax), P(), P(None, ax)
    link_spec = rep if link is None else SS.LinkGate(col, rep, rep)
    return MM.shard_map(
        functools.partial(run, axis=ax), mesh,
        in_specs=(col, rep, sat, rep, link_spec),
        out_specs=P(None, None, ax))(C_window, candidates, state, ig,
                                     link)


@functools.partial(jax.jit, static_argnames=("s_max",))
def _simulate_marks_state(C_window, candidates, state, ig, link, *,
                          s_max: int):
    """`_simulate_marks` variant that also returns each candidate's final
    scan state and global version — the per-candidate frontier the
    incremental replanner (`repro.fl.replan.ReplanService`) caches so the
    next replan can simulate only the newly revealed window. The marks
    themselves are value-identical to `_simulate_marks` (same transitions,
    extra outputs), which is what keeps delta-scored schedules bit-equal
    to a full rescan. Single-device only: the replan cache is not built
    under a satellite-axis mesh (`score_candidates(mesh=...)` remains the
    sharded full-rescan path)."""
    fstate, fig, infos = SS.simulate_candidates(C_window, candidates,
                                                state, ig, s_max=s_max,
                                                collect="marks", link=link)
    return infos["marks"], fstate, fig


@functools.partial(jax.jit, static_argnames=("s_max",))
def step_candidates(states, igs, connected, bits, link, *, s_max: int):
    """One protocol window vmapped over per-candidate *states* — the
    delta-scan transition for a newly revealed window.

    `_simulate_marks` vmaps candidate schedules over one shared initial
    state; here every candidate carries its own frontier state/version
    (the scan state cached from the previous replan), takes its own
    aggregation bit for the revealed window, and shares the window's
    connectivity column and link gate. Built on the same
    `repro.core.staleness.step` composition as the scan, so the emitted
    marks — and the advanced states — are bit-identical to what a full
    rescan would compute at its last window.

    Args:
      states: stacked `SatState`, leading axis R (any signed-int dtype).
      igs: (R,) per-candidate global version, same dtype as the states.
      connected: (K,) bool — the revealed window's connectivity column.
      bits: (R,) {0,1} — each candidate's aggregation bit at that window.
      link: optional `LinkGate` with a (K,) grant shared by every
        candidate, or None.
      s_max: staleness clip (static).

    Returns (marks (R, K), new_states, new_igs).
    """
    def one(st, g, a):
        return SS.step(st, g, connected, a.astype(bool), s_max=s_max,
                       collect="marks", link=link)

    st, g, info = jax.vmap(one)(states, igs, bits)
    return info["marks"], st, g


@functools.partial(jax.jit, static_argnames=("s_max",))
def _event_features(marks, idx, status, *, s_max: int):
    """Gather the (R, I0, K) staleness marks at each candidate's
    aggregation windows, histogram them, and featurize: (R*n_cap, F)
    features for the utility regressor. The one-hot reduction runs once
    over the gathered events — n_agg of I0 windows — instead of inside the
    per-step scan, and accumulates in int16 (exact for K < 32768)."""
    g = jnp.take_along_axis(marks, idx[..., None], axis=1)  # (R, n_cap, K)
    hists = SS.hist_from_marks(g, s_max=s_max, dtype=jnp.int16)
    Rn, n_cap, F = hists.shape
    return featurize_jnp(hists.reshape(Rn * n_cap, F), status)


def _narrow_state(state: SS.SatState, ig: int, horizon: int):
    """int16 copy of (state, ig) when every version the window can produce
    fits — on CPU the narrowed vmapped scan moves half the bytes and runs
    ~3x faster, with bit-identical marks. Falls back to int32 otherwise.
    The `progress` and `relay` columns (if attached) stay int32: their
    arithmetic only meets int32 grant/need/hop scalars, never the version
    fields."""
    if ig + horizon < np.iinfo(np.int16).max - 1:
        dt = jnp.int16
    else:
        dt = jnp.int32
    return (SS.SatState(*(x.astype(dt) for x in state[:3]), state.progress,
                        state.relay),
            jnp.asarray(ig, dt))


def score_candidates(candidates: np.ndarray, C_window: np.ndarray,
                     state: SS.SatState, ig: int, regressor, status: float,
                     *, s_max: int = 8, chunk_rows: Optional[int] = None,
                     link: Optional[SS.LinkGate] = None,
                     mesh=None) -> np.ndarray:
    """Predicted summed utility per candidate (eq. 13).

    When the regressor exposes `predict_device` (both built-in regressors
    do), the whole pipeline stays on device and scatter/broadcast-free:
    the vmapped protocol scan carries only masked `jnp.where` updates over
    the dense per-satellite state (int16-narrowed) and emits compact
    staleness marks; histograms, featurization, and regression run once
    post-scan at each candidate's aggregation windows only (a=0 windows
    contribute exactly 0 to eq. 13). The only host transfer is the final
    (R,) score vector. Regressors with only `.predict` (e.g. test oracles)
    fall back to the legacy full-histogram host path.

    Args:
      candidates: (R, I0) {0,1} schedules to score.
      C_window: (I0, K) bool future connectivity.
      state, ig: post-upload protocol state at the window start.
      regressor: utility model û; `predict_device` selects the fast path.
      status: training status T fed to the featurizer.
      s_max: staleness clip — must match the regressor's feature width.
      chunk_rows: candidates simulated per device batch (None = auto-sized
        so the marks buffer stays ~64 MB); chunking only bounds memory,
        per-candidate results are unchanged.
      link: optional `LinkGate` (grant (I0, K), any array-like) gating the
        simulated transfers, so candidates are scored against effective —
        capacity-constrained — connectivity rather than raw visibility;
        `state.progress` must be attached when given.
      mesh: optional satellite-axis device mesh (`repro.core.mesh`): the
        fast path pads K to a device-count multiple with never-connected
        satellites (whose marks stay -1, invisible to the histograms) and
        shards the vmapped scan via `shard_map` — scores are bit-identical
        to `mesh=None`, which compiles the exact single-device program.
        The legacy `.predict` fallback ignores it.

    Returns: (R,) float32 predicted utility sums.
    """
    if link is not None:
        link = SS.LinkGate(jnp.asarray(np.asarray(link.grant), jnp.int32),
                           jnp.int32(link.need_up), jnp.int32(link.need_dn))
    predict_device = getattr(regressor, "predict_device", None)
    if predict_device is None:
        cands = jnp.asarray(candidates)
        Cw = jnp.asarray(C_window)
        # s_max must reach the simulator so the staleness histograms match
        # the regressor's feature width; only the histograms are consumed
        _, _, infos = SS.simulate_candidates(Cw, cands, state,
                                             jnp.int32(ig), s_max=s_max,
                                             lite=True, link=link)
        hist = np.asarray(infos["hist"])                 # (R, I0, s_max+1)
        Rn, I0, F = hist.shape
        feats = featurize(hist.reshape(Rn * I0, F), status)
        util = regressor.predict(feats).reshape(Rn, I0)
        agg_mask = np.asarray(candidates, np.float32)
        return (util * agg_mask).sum(axis=1)

    cands = np.asarray(candidates)
    R, I0 = cands.shape
    K = C_window.shape[1]
    idx, mask = event_positions(cands)
    C_window = np.asarray(C_window, bool)
    if mesh is not None:
        Kp = MM.padded_size(K, mesh)
        C_window = MM.pad_axis(C_window, Kp)
        state = MM.pad_state(state, Kp)
        if link is not None:
            link = link._replace(grant=jnp.asarray(
                MM.pad_axis(np.asarray(link.grant), Kp)))
    Cw = jnp.asarray(C_window)
    st, igd = _narrow_state(state, int(ig), I0)
    if chunk_rows is None:
        chunk_rows = max(256, (64 << 20) // max(I0 * K, 1))
    scores = np.empty(R, np.float32)
    for c0 in range(0, R, chunk_rows):
        rows = slice(c0, min(c0 + chunk_rows, R))
        marks = _simulate_marks(Cw, jnp.asarray(cands[rows]), st, igd,
                                link, s_max=s_max, mesh=mesh)
        feats = _event_features(marks, jnp.asarray(idx[rows]),
                                jnp.float32(status), s_max=s_max)
        util = predict_device(feats).reshape(-1, idx.shape[1])
        scores[rows] = np.asarray(
            (util * jnp.asarray(mask[rows], jnp.float32)).sum(axis=1))
    return scores


def scan_candidates(candidates: np.ndarray, C_window: np.ndarray,
                    state: SS.SatState, ig: int, regressor, status: float,
                    *, s_max: int = 8, chunk_rows: Optional[int] = None,
                    link: Optional[SS.LinkGate] = None):
    """`score_candidates`' device pipeline, additionally materializing the
    per-candidate scan artifacts the incremental replanner caches
    (`repro.fl.replan.ReplanService` — see `docs/replanning.md`).

    Scores are bit-identical to `score_candidates` on the same inputs:
    the marks come from the same transitions (`_simulate_marks_state` only
    adds outputs), the per-event utilities from the same
    histogram/featurize/predict pipeline, and the final masked reduction
    runs at the same (R, n_cap) shape. The regressor must expose
    `predict_device` (there is no legacy `.predict` fallback here — a
    host-path regressor has no cacheable device artifacts).

    Returns (scores (R,) float32, artifacts) where artifacts is a dict:
      win_util: (R, I0) float32 — each candidate's predicted per-event
        utility placed at its aggregation offsets (0 elsewhere; padded
        event slots land on a=0 offsets by construction, so real events
        are never overwritten).
      end_state: host-numpy stacked `SatState`, leading axis R — each
        candidate's scan state after the last window (the frontier the
        next delta step advances from).
      end_ig: (R,) per-candidate final global version (scan dtype).
      state_dtype: the narrowed scan dtype (np.int16 or np.int32) — the
        delta path's narrowing-guard check compares against it.
    """
    cands = np.asarray(candidates)
    R, I0 = cands.shape
    K = C_window.shape[1]
    if chunk_rows is None:
        chunk_rows = max(256, (64 << 20) // max(I0 * K, 1))
    with tracing.span("search.scan", rows=R, chunks=-(-R // chunk_rows),
                      forest_dense=forest_dense(regressor)):
        if link is not None:
            link = SS.LinkGate(
                jnp.asarray(np.asarray(link.grant), jnp.int32),
                jnp.int32(link.need_up), jnp.int32(link.need_dn))
        idx, mask = event_positions(cands)
        Cw = jnp.asarray(np.asarray(C_window, bool))
        st, igd = _narrow_state(state, int(ig), I0)
        scores = np.empty(R, np.float32)
        win_util = np.zeros((R, I0), np.float32)
        end_states, end_igs = [], []
        predict_device = regressor.predict_device
        for c0 in range(0, R, chunk_rows):
            rows = slice(c0, min(c0 + chunk_rows, R))
            # dispatch the chunk's programs; the wait for them lands in
            # the host reads that follow
            with tracing.span("search.chunk", rows=rows.stop - c0):
                marks, fstate, fig = _simulate_marks_state(
                    Cw, jnp.asarray(cands[rows]), st, igd, link,
                    s_max=s_max)
                feats = _event_features(marks, jnp.asarray(idx[rows]),
                                        jnp.float32(status), s_max=s_max)
                util = predict_device(feats).reshape(-1, idx.shape[1])
                masked = util * jnp.asarray(mask[rows], jnp.float32)
                total = masked.sum(axis=1)
            with tracing.span("search.fetch"):
                scores[rows] = np.asarray(total)
                np.put_along_axis(win_util[rows], idx[rows],
                                  np.asarray(masked), axis=1)
                end_states.append(jax.tree.map(np.asarray, fstate))
                end_igs.append(np.asarray(fig))
        end_state = jax.tree.map(lambda *xs: np.concatenate(xs),
                                 *end_states)
    return scores, {"win_util": win_util, "end_state": end_state,
                    "end_ig": np.concatenate(end_igs),
                    "state_dtype": np.dtype(np.int16)
                    if st.version.dtype == jnp.int16
                    else np.dtype(np.int32)}


def infer_n_range(regressor, uploads_per_window: float, I0: int,
                  status: float, *, s_max: int = 8, K: int = None,
                  halfwidth: int = 4):
    """Infer [N_min, N_max] from û, as the paper does: for each candidate
    aggregation count n, approximate the per-aggregation staleness histogram
    under even spacing (uploads split across n aggregations, mostly fresh),
    and pick the count maximizing n * û(hist(n), T)."""
    # Cap at one aggregation per two windows: beyond that per-aggregation
    # buffers thin out into the async regime the paper shows fails, and û
    # extrapolates badly at counts it never sampled.
    n_cap = max(1, I0 // 2)
    total_uploads = uploads_per_window * I0
    # f64 like the scalar loop this replaces (the f32 store happens once,
    # on assignment into hists), so the histogram features — and thus the
    # forest-split decisions — are bit-identical to the seed path
    ns = np.arange(1, n_cap + 1, dtype=np.float64)
    per = total_uploads / ns
    if K:
        per = np.minimum(per, K)
    hists = np.zeros((n_cap, s_max + 1), np.float32)
    hists[:, 0] = per * 0.7          # even spacing: gradients mostly fresh
    hists[:, 1] = per * 0.3
    u = ns * regressor.predict(featurize(hists, status)).astype(np.float64)
    best_n = 1 + int(np.argmax(u))
    return max(1, best_n - halfwidth), min(n_cap, best_n + halfwidth)


def fedspace_search(rng: np.random.Generator, C_window: np.ndarray,
                    state: SS.SatState, ig: int, regressor, status: float,
                    *, n_min: int = 4, n_max: int = 8, num_candidates: int
                    = 5000, s_max: int = 8,
                    link: Optional[SS.LinkGate] = None,
                    mesh=None) -> np.ndarray:
    I0 = C_window.shape[0]
    cands = random_candidates(rng, I0, n_min, n_max, num_candidates)
    scores = score_candidates(cands, C_window, state, ig, regressor, status,
                              s_max=s_max, link=link, mesh=mesh)
    return cands[select_candidate(cands, scores)]


def select_candidate(cands: np.ndarray, scores: np.ndarray) -> int:
    """Index of the winning candidate. Distinct-but-equivalent candidates
    (identical staleness histograms) tie at float level, and different
    scoring backends (host numpy vs on-device) break such ties differently
    by reduction-order jitter; so among candidates within float noise of
    the max, pick the lexicographically smallest schedule — deterministic
    and backend-stable."""
    best = float(np.max(scores))
    eps = 32 * float(np.finfo(np.float32).eps) * max(1.0, abs(best))
    near = np.flatnonzero(scores >= best - eps)
    if near.size > 1:
        near = sorted(near, key=lambda j: cands[j].tobytes())
    return int(near[0])

"""Incremental eq.-13 replanning as a service (ROADMAP north-star serving
story: ground-assisted scheduling, arXiv 2109.01348).

Every FedSpace aggregation event used to recompute the full candidate scan
over the I0 horizon, yet consecutive horizons overlap in all but one
window. `ReplanService` holds the marks/scan state of the previous replan
and scores only the delta:

* A **full plan** at window j draws a candidate pool, runs
  `repro.core.search.scan_candidates` (the cache-collecting twin of
  `score_candidates`) and keeps, per candidate, the predicted per-event
  utilities (`win_util`) and the final scan state/version (the frontier).
* A **delta replan** at window j+1 filters the pool to candidates whose
  window-j bit equals the realized action (their simulated trajectories
  coincide with reality on the overlap, so every cached per-event utility
  over [j+1, j+I0) is *bit-identical* to what a fresh rescan would
  compute), extends each survivor with a drawn bit for the newly revealed
  window j+I0, and simulates **only that window** — one vmapped
  `repro.core.search.step_candidates` step over the candidates that
  scheduled it — before re-reducing scores at the same (R, n_cap) shape a
  full rescan would use. Selection is therefore bit-identical to
  `score_candidates` + `select_candidate` on the same pool and state
  (gated by
  `tests/test_replan.py::test_delta_selection_bit_identical_to_full_rescan`).

The cache is invalidated — the service falls back to a full rescan — on:
  * **drift**: the caller's state is not the one the cached rollouts
    predicted (e.g. fault masking, an out-of-band aggregation, or a
    caller that executed a different action than the returned schedule);
  * **narrowing**: the global version grew past the int16 narrowing guard
    the cached frontier states were scanned under;
  * **horizon / window**: I0 or K changed, or the request is not the
    next consecutive window;
  * **link / connectivity view**: the overlapping connectivity or grant
    rows differ from the cached view (weather, outages, a new budget);
  * **status**: the training-status feature T changed (every cached
    utility was predicted at the old T);
  * **pool**: survivor filtering would drop the pool below `min_pool`;
  * **mesh**: the service runs sharded full rescans but never caches
    under a satellite-axis mesh.
Fallbacks are counted per reason in `ReplanService.stats`.

Forest transfer: the regressor is handed in once (`regressor=`) and the
serving path never refits — the histogram featurization is K-agnostic
(`repro.core.utility.transfer_ready`), so a forest fitted on flock191
serves starlink40/120/400/1000 unchanged. `examples/serve_replan.py`
wraps the service in a persistent-jit server loop (the
`examples/serve_decode.py` pattern): connectivity columns stream in,
replan requests are answered without recompilation.

Each step of a request is a `repro.tracing` span (`replan.request` and
its children), recorded while a profiler session captures; the span tree
and its counts are in `docs/replanning.md` (Tracing).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import staleness as SS
from repro.core.search import (event_positions, infer_n_range,
                               random_candidates, scan_candidates,
                               score_candidates, select_candidate,
                               step_candidates)
from repro.core.utility import (RandomForestRegressor, featurize,
                                featurize_jnp, forest_dense,
                                transfer_ready)

__all__ = ["ReplanService", "calibrate_forest", "rollout_histograms"]


class _Cache:
    """The previous replan's scan artifacts (see module docstring)."""

    def __init__(self, *, window, cands, Cw, grant, need_up, need_dn,
                 win_util, end_state, end_ig, state_dtype, pre_state,
                 pre_ig, winner_bit, status, density, n_max):
        self.window = window          # absolute window the plan answered
        self.cands = cands            # (R, I0) int32 pool
        self.Cw = Cw                  # (I0, K) bool horizon view
        self.grant = grant            # (I0, K) int grants or None
        self.need_up = need_up
        self.need_dn = need_dn
        self.win_util = win_util      # (R, I0) f32 per-event utilities
        self.end_state = end_state    # stacked SatState, frontier (host)
        self.end_ig = end_ig          # (R,) frontier versions
        self.state_dtype = state_dtype
        self.pre_state = pre_state    # int32 (K,) search state of the plan
        self.pre_ig = pre_ig
        self.winner_bit = winner_bit  # realized action the cache assumes
        self.status = status
        self.density = density        # pool aggregation density at draw
        self.n_max = n_max            # cap for extension bits
        self.pending = None           # (conn, gate) of an unadvanced window


def _np_state(state: SS.SatState) -> SS.SatState:
    """Host int32 copy of a (K,) SatState (progress/relay pass through)."""
    return SS.SatState(*(np.asarray(x, np.int32) for x in state[:3]),
                       None if state.progress is None
                       else np.asarray(state.progress, np.int32),
                       None if state.relay is None
                       else np.asarray(state.relay, np.int32))


def _rows(state: SS.SatState, sel) -> SS.SatState:
    """Index the leading (candidate) axis of a stacked SatState."""
    return jax.tree.map(lambda x: x[sel], state)


def _bucket(n: int) -> int:
    """Next power-of-two batch bucket. The one-window `step_candidates`
    kernel is jitted per batch shape, and the survivor pool decays across
    delta steps — bucketing keeps the serving loop at a handful of
    compiled shapes instead of one compile per request (which would dwarf
    the <100 ms answer budget). Padded rows duplicate a real row and are
    sliced off before use."""
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


@functools.partial(jax.jit, static_argnames=("columns", "s_max"))
def _drift(states, conns, scalars, grants, needs, *, columns, s_max: int):
    """The drift check as one program: realize the cached winner's bit
    from the cached pre-state (`SS.step`), re-upload both that prediction
    and the caller's state at the request's first window
    (`SS.upload_step`), and return a device bool, True when the versions
    or any column differ.

    The inputs come packed, so a call moves three host arrays (five under
    a link budget) to the device: `states` (2, len(columns), K) int32,
    the cached pre-state and the caller's state by `SatState` column;
    `conns` (2, K) bool, the cached plan's first window and the
    request's; `scalars` (3,) int32, the cached version, the caller's and
    the realized bit; `grants` (2, K) int32 and `needs` (2,) int32 (up,
    down) of the two gates, or None. Only the column names and `s_max`
    are static, so one trace serves every request of a K and gate or
    none."""
    pre, given = (SS.SatState(**dict(zip(columns, s))) for s in states)
    pre_gate = gate = None
    if grants is not None:
        pre_gate = SS.LinkGate(grants[0], needs[0], needs[1])
        gate = SS.LinkGate(grants[1], needs[0], needs[1])
    after, g_after, _ = SS.step(pre, scalars[0], conns[0], scalars[2] != 0,
                                s_max=s_max, collect="none", link=pre_gate)
    predicted, _ = SS.upload_step(after, g_after, conns[1], gate)
    rescanned, _ = SS.upload_step(given, scalars[1], conns[1], gate)
    differs = g_after != scalars[1]
    for x, y in zip(jax.tree.leaves(predicted), jax.tree.leaves(rescanned)):
        differs = differs | jnp.any(x != y)
    return differs


def rollout_histograms(C: np.ndarray, *, s_max: int = 8,
                       periods=(2, 3, 4, 6)) -> np.ndarray:
    """Per-window staleness histograms, (len(periods) * W, s_max + 1) f32,
    from protocol rollouts of the (W, K) connectivity `C` under periodic
    aggregation cadences (a spread of staleness mixes)."""
    state = SS.bootstrap_state(C.shape[1])
    hists = []
    for period in periods:
        a = (np.arange(C.shape[0]) % period == period - 1).astype(np.int32)
        _, _, infos = SS.simulate_window(
            jnp.asarray(C), jnp.asarray(a), state, jnp.int32(0),
            s_max=s_max, collect="hist")
        hists.append(np.asarray(infos["hist"]))
    return np.concatenate(hists).astype(np.float32)


def calibrate_forest(C: np.ndarray, *, s_max: int = 8, n_trees: int = 30,
                     seed: int = 0) -> RandomForestRegressor:
    """A transfer-ready utility forest for a `ReplanService` without
    FedSpace's phase-1 training: fitted on `rollout_histograms(C)` against
    the staleness-discounted aggregate-mass curve, a synthetic stand-in
    for the eq.-12 targets."""
    H = rollout_histograms(C, s_max=s_max)
    s = np.arange(s_max + 1, dtype=np.float32)
    y = ((H * (1.2 - 0.3 * s)).sum(1)
         / np.maximum(H.sum(1), 1.0)).astype(np.float32)
    return RandomForestRegressor(n_trees=n_trees, max_depth=6,
                                 seed=seed).fit(featurize(H, 1.0), y)


class ReplanService:
    """Persistent eq.-13 replanner with delta-window scoring.

    One service holds one regressor (the forest-transfer handoff: fit
    once, serve any constellation) and the scan cache of its latest plan.
    `replan` answers a request; `maintain` runs the deferred frontier
    advance between requests so answer latency stays at delta cost.

    Args:
      regressor: fitted utility model û; must pass
        `repro.core.utility.transfer_ready` for this `s_max` (the
        serving path never refits).
      I0: planning-horizon length (windows).
      num_candidates: pool size R of a full plan.
      n_min / n_max: aggregation-count range for candidate draws; None
        infers both from û per plan (paper §3.2, `infer_n_range`).
      s_max: staleness clip — must match the regressor's feature width.
      seed: service rng (extension bits + full-plan draws when the caller
        does not pass its own rng).
      min_pool: survivor floor below which a delta request full-rescans.
      mesh: optional satellite-axis device mesh for full rescans
        (`repro.core.mesh`); delta caching is disabled under a mesh.
    """

    def __init__(self, regressor, *, I0: int = 24,
                 num_candidates: int = 5000, n_min: Optional[int] = None,
                 n_max: Optional[int] = None, s_max: int = 8, seed: int = 0,
                 min_pool: int = 256, mesh=None):
        if not transfer_ready(regressor, s_max=s_max):
            raise ValueError(
                "regressor is not transfer-ready for s_max="
                f"{s_max}: it must expose predict_device and (if fitted "
                "through .fit) a matching feature width — see "
                "repro.core.utility.transfer_ready")
        self.regressor = regressor
        self.I0 = I0
        self.num_candidates = num_candidates
        self.n_min = n_min
        self.n_max = n_max
        self.s_max = s_max
        self.seed = seed
        self.min_pool = min_pool
        self.mesh = mesh
        self._rng = np.random.default_rng(seed)
        self._cache: Optional[_Cache] = None
        self.stats = {"full": 0, "delta": 0, "invalidated": {}}
        self.last_mode: Optional[str] = None
        self.last_reason: Optional[str] = None

    # -- cache management ---------------------------------------------------

    def invalidate(self, reason: str = "external"):
        """Drop the scan cache (the next request full-rescans). Engine
        `reset()` cascades here so re-runs never reuse a stale plan."""
        if self._cache is not None:
            self.stats["invalidated"][reason] = \
                self.stats["invalidated"].get(reason, 0) + 1
        self._cache = None

    @property
    def pool(self) -> Optional[np.ndarray]:
        """The current candidate pool (read-only copy), for parity gates."""
        return None if self._cache is None else self._cache.cands.copy()

    def _gate(self, grant_row, need_up, need_dn):
        if grant_row is None:
            return None
        return SS.LinkGate(jnp.asarray(np.asarray(grant_row), jnp.int32),
                           jnp.int32(need_up), jnp.int32(need_dn))

    def maintain(self):
        """Deferred delta-step bookkeeping: advance every cached frontier
        state through the window revealed by the last delta replan. The
        answer path only simulates the revealed window for candidates that
        *scheduled* it; this advances the full pool so the next request is
        again one-window work. Call it between requests (the server loop
        does); a replan arriving first runs it inline, trading latency for
        correctness."""
        c = self._cache
        if c is None or c.pending is None:
            return
        conn, gate = c.pending
        S = int(c.end_ig.shape[0])
        # `window` is the request this advance prepares: the next one
        with tracing.span("replan.maintain", window=c.window + 1, rows=S,
                          bucket=_bucket(S)):
            sel = np.concatenate([np.arange(S),
                                  np.zeros(_bucket(S) - S, np.int64)])
            _, st, g = step_candidates(
                jax.tree.map(jnp.asarray, _rows(c.end_state, sel)),
                jnp.asarray(c.end_ig[sel]), jnp.asarray(conn),
                jnp.asarray(c.cands[sel, -1]), gate, s_max=self.s_max)
            c.end_state = jax.tree.map(lambda x: np.asarray(x)[:S], st)
            c.end_ig = np.asarray(g)[:S]
            c.pending = None

    # -- request path -------------------------------------------------------

    def replan(self, window: int, C_window: np.ndarray, state: SS.SatState,
               ig: int, status: float, *, link: Optional[SS.LinkGate] = None,
               rng: Optional[np.random.Generator] = None,
               n_min: Optional[int] = None,
               n_max: Optional[int] = None) -> np.ndarray:
        """Answer one replan request: the winning (I0,) schedule for the
        horizon [window, window + I0).

        Arguments mirror `repro.core.search.fedspace_search`: `C_window`
        is the (I0, K) future connectivity (effective, capacity-resolved
        when budgets are modeled), `state`/`ig` the search-ready protocol
        state (post-upload at `window`, grant-inverted under a link budget
        — `FedSpaceScheduler._search_state`), `status` the training
        status T, `link` the horizon's `LinkGate` slice. `rng` drives the
        candidate draw of a full plan (the FedSpace scheduler passes its
        own so routed plans are bit-identical to unrouted ones);
        extension bits of delta steps always come from the service rng.

        Consecutive-window requests with an intact cache are answered by
        the delta path; anything else falls back to a full rescan (see
        the module docstring for the invalidation table).
        """
        with tracing.span("replan.request", window=window):
            C_window = np.asarray(C_window, bool)
            self.maintain()
            with tracing.span("replan.check", window=window) as sp:
                reason = self._delta_blocker(window, C_window, state, ig,
                                             status, link)
                # the drift check is the blocker's last test
                sp.set_metadata(drift_checked=int(reason in (None, "drift")))
            if reason is None:
                self.last_mode, self.last_reason = "delta", None
                self.stats["delta"] += 1
                return self._delta(window, C_window, state, ig, status,
                                   link)
            if self._cache is not None and reason != "cold":
                self.invalidate(reason)
            self.last_mode, self.last_reason = "full", reason
            self.stats["full"] += 1
            with tracing.span("replan.full", window=window, reason=reason):
                return self._full(window, C_window, state, ig, status,
                                  link, rng, n_min, n_max)

    # -- full plan ----------------------------------------------------------

    def _full(self, window, Cw, state, ig, status, link, rng, n_min,
              n_max):
        I0, K = Cw.shape
        rng = rng if rng is not None else self._rng
        n_min = n_min if n_min is not None else self.n_min
        n_max = n_max if n_max is not None else self.n_max
        with tracing.span("replan.full.draw", window=window,
                          candidates=self.num_candidates):
            if n_min is None or n_max is None:
                inf_min, inf_max = infer_n_range(
                    self.regressor, float(Cw.mean(axis=1).sum()) / I0 * K,
                    I0, status, s_max=self.s_max, K=K)
                n_min = n_min if n_min is not None else inf_min
                n_max = n_max if n_max is not None else inf_max
            cands = random_candidates(rng, I0, n_min, n_max,
                                      self.num_candidates)
        if self.mesh is not None:
            scores = score_candidates(cands, Cw, state, ig, self.regressor,
                                      status, s_max=self.s_max, link=link,
                                      mesh=self.mesh)
            art = None
        else:
            scores, art = scan_candidates(cands, Cw, state, ig,
                                          self.regressor, status,
                                          s_max=self.s_max, link=link)
        with tracing.span("replan.select", window=window):
            w = select_candidate(cands, scores)
            if art is not None:
                self._cache = _Cache(
                    window=window, cands=cands, Cw=Cw.copy(),
                    grant=None if link is None
                    else np.asarray(link.grant, np.int32).copy(),
                    need_up=0 if link is None else int(link.need_up),
                    need_dn=0 if link is None else int(link.need_dn),
                    win_util=art["win_util"], end_state=art["end_state"],
                    end_ig=art["end_ig"], state_dtype=art["state_dtype"],
                    pre_state=_np_state(state), pre_ig=int(ig),
                    winner_bit=int(cands[w, 0]), status=float(status),
                    density=float(cands.mean()), n_max=n_max)
            return cands[w].copy()

    # -- delta path ---------------------------------------------------------

    def _delta_blocker(self, window, Cw, state, ig, status, link):
        """None when the cached scan can answer this request, else the
        invalidation reason (module docstring)."""
        c = self._cache
        if c is None:
            return "cold"
        if self.mesh is not None:
            return "mesh"
        if window != c.window + 1:
            return "window"
        if Cw.shape != c.Cw.shape:
            return "horizon"
        if float(status) != c.status:
            return "status"
        if not np.array_equal(Cw[:-1], c.Cw[1:]):
            return "connectivity"
        if (link is None) != (c.grant is None):
            return "link"
        if link is not None:
            if (int(link.need_up) != c.need_up
                    or int(link.need_dn) != c.need_dn
                    or not np.array_equal(
                        np.asarray(link.grant, np.int32)[:-1],
                        c.grant[1:])):
                return "link"
        if (c.state_dtype == np.int16
                and not (int(ig) + self.I0 + 1
                         < np.iinfo(np.int16).max - 1)):
            return "narrowing"
        if np.count_nonzero(c.cands[:, 0] == c.winner_bit) < self.min_pool:
            return "pool"
        if self._drifted(Cw, state, ig, link):
            return "drift"
        return None

    def _drifted(self, Cw, state, ig, link) -> bool:
        """True when the caller's state is not the one the cached rollouts
        predicted. The cached scan entered the request's first window
        with the state produced by realizing the winner's bit at the
        window before; a fresh rescan would enter it by (idempotently)
        re-uploading the caller's search-ready state. The two coincide —
        and every cached mark stays valid — iff both post-upload states
        are equal, so that is the check: one compiled program (`_drift`),
        exact int32 compares, one read of its bool. States that differ in
        which optional columns they carry have drifted, decided here."""
        c = self._cache
        columns = tuple(f for f in SS.SatState._fields
                        if getattr(state, f) is not None)
        if columns != tuple(f for f in SS.SatState._fields
                            if getattr(c.pre_state, f) is not None):
            return True
        grants = needs = None
        if c.grant is not None:
            grants = np.stack([c.grant[0],
                               np.asarray(link.grant, np.int32)[0]])
            needs = np.array([c.need_up, c.need_dn], np.int32)
        return bool(_drift(
            np.array([[getattr(c.pre_state, f) for f in columns],
                      [getattr(state, f) for f in columns]], np.int32),
            np.stack([c.Cw[0], Cw[0]]),
            np.array([c.pre_ig, ig, c.winner_bit], np.int32),
            grants, needs, columns=columns, s_max=self.s_max))

    def _delta(self, window, Cw, state, ig, status, link):
        c = self._cache
        keep = c.cands[:, 0] == c.winner_bit
        S = int(np.count_nonzero(keep))
        with tracing.span("replan.delta", window=window, survivors=S,
                          bucket=_bucket(S)):
            with tracing.span("replan.delta.extend", window=window):
                base = c.cands[keep]
                # extend every survivor with a drawn bit for the revealed
                # window (service rng; capped so no candidate exceeds the
                # draw-time n_max)
                n_now = base[:, 1:].sum(axis=1)
                draw = (self._rng.random(S) < c.density).astype(np.int32)
                new_bits = np.where(n_now < c.n_max, draw, 0).astype(
                    np.int32)
                cands = np.concatenate([base[:, 1:], new_bits[:, None]],
                                       axis=1)
                win_util = np.concatenate(
                    [c.win_util[keep, 1:], np.zeros((S, 1), np.float32)],
                    axis=1)
                end_state = _rows(c.end_state, keep)
                end_ig = c.end_ig[keep]
                conn_new = Cw[-1]
                gate_new = self._gate(
                    None if link is None
                    else np.asarray(link.grant, np.int32)[-1],
                    c.need_up, c.need_dn)
                rows1 = np.flatnonzero(new_bits == 1)
            # simulate ONLY the newly revealed window, only for candidates
            # that scheduled it — same marks→hist→featurize→predict
            # pipeline as the full scan, from the cached frontier
            if rows1.size:
                m = rows1.size
                with tracing.span("replan.delta.score", window=window,
                                  scheduled=m, bucket=_bucket(m),
                                  forest_dense=forest_dense(
                                      self.regressor)):
                    sel = np.concatenate(
                        [rows1, np.full(_bucket(m) - m, rows1[0], np.int64)])
                    marks, _, _ = step_candidates(
                        jax.tree.map(jnp.asarray, _rows(end_state, sel)),
                        jnp.asarray(end_ig[sel]), jnp.asarray(conn_new),
                        jnp.asarray(new_bits[sel]), gate_new,
                        s_max=self.s_max)
                    hists = SS.hist_from_marks(marks, s_max=self.s_max,
                                               dtype=jnp.int16)
                    util = self.regressor.predict_device(
                        featurize_jnp(hists, jnp.float32(status)))
                    win_util[rows1, -1] = np.asarray(util)[:m]
            # re-reduce at the same per-row (n_cap,) shape a full rescan
            # would use, so the masked sum is bit-identical to
            # score_candidates. Rows are bucket-padded with zeros (per-row
            # sums unaffected) so the eager device reduction reuses a
            # handful of compiled shapes instead of recompiling for every
            # survivor count.
            with tracing.span("replan.delta.reduce", window=window,
                              rows=_bucket(S)) as sp:
                idx, mask = event_positions(cands)
                sp.set_metadata(width=idx.shape[1])
                util_ev = np.take_along_axis(win_util, idx, axis=1)
                pad = _bucket(S) - S
                if pad:
                    util_ev = np.concatenate(
                        [util_ev,
                         np.zeros((pad, util_ev.shape[1]), np.float32)])
                    mask = np.concatenate(
                        [mask, np.zeros((pad, mask.shape[1]), mask.dtype)])
                scores = np.asarray((jnp.asarray(util_ev)
                                     * jnp.asarray(mask, jnp.float32))
                                    .sum(axis=1))[:S]
            with tracing.span("replan.select", window=window):
                w = select_candidate(cands, scores)
                # roll the cache forward; the frontier advance is deferred
                # to maintain() so it stays off the answer path
                c.window = window
                c.cands = cands
                c.Cw = Cw.copy()
                if link is not None:
                    c.grant = np.asarray(link.grant, np.int32).copy()
                c.win_util = win_util
                c.end_state = end_state
                c.end_ig = end_ig
                c.pre_state = _np_state(state)
                c.pre_ig = int(ig)
                c.winner_bit = int(cands[w, 0])
                c.pending = (conn_new.copy(), gate_new)
                return cands[w].copy()

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
  window j+I0, and simulates **only that window**. One compiled program
  (`_delta_program`) does the device side: it gathers the survivors'
  rows of the frontier, takes one vmapped
  `repro.core.search.step_candidates` step (which is also the frontier's
  advance), predicts the new window's utility where a survivor scheduled
  it, slides `win_util` left by one window and sums each row. The marks and utilities are those a full
  rescan computes; the scores sum the same per-event utilities over the
  I0 axis instead of the event positions, so they equal
  `score_candidates`' up to float summation order, inside
  `select_candidate`'s 32-ulp tie band. Selection therefore equals
  `score_candidates` + `select_candidate` on the same pool and state,
  except where a candidate's score sits within a few ulp of that band's
  edge (gated on seeded streams by
  `tests/test_replan.py::test_delta_selection_bit_identical_to_full_rescan`).

The frontier (`end_state`, `end_ig`, `win_util`) has the full plan's R
rows. A full plan leaves it on the host, and it moves to the device once:
in `maintain`, which a caller runs between requests and which waits for
the copy to land so that it stays off the next answer, or else in the
first delta answer itself.
From there the delta program's output stays on the device and nothing is
read back. A caller that never answers by delta (a scheduler that
replans at I0 strides) never moves it. Survivors are
compacted to the front rows; the rows past them repeat a real row and are
never read, so the program's shapes depend only on (R, K, the state
dtype, gate or none) and it compiles once per service.

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
from repro.core.search import (infer_n_range, random_candidates,
                               scan_candidates, score_candidates,
                               select_candidate, step_candidates)
from repro.core.utility import (RandomForestRegressor, device_predictor,
                                featurize, featurize_jnp, forest_dense,
                                transfer_ready)

__all__ = ["ReplanService", "calibrate_forest", "rollout_histograms"]


class _Cache:
    """The previous replan's scan artifacts (see module docstring)."""

    def __init__(self, *, window, cands, Cw, grant, need_up, need_dn,
                 win_util, end_state, end_ig, state_dtype, pre_state,
                 pre_ig, winner_bit, status, density, n_max):
        self.window = window          # absolute window the plan answered
        self.cands = cands            # (S, I0) int32 pool, S <= R
        self.Cw = Cw                  # (I0, K) bool horizon view
        self.grant = grant            # (I0, K) int grants or None
        self.need_up = need_up
        self.need_dn = need_dn
        # the frontier, R rows: host arrays as a full plan leaves them,
        # device arrays once `maintain` or a delta answer has moved them;
        # row r < S belongs to cands[r], the rows past S repeat a real row
        self.win_util = win_util      # (R, I0) f32 per-event utilities
        self.end_state = end_state    # stacked SatState
        self.end_ig = end_ig          # (R,) frontier versions
        self.state_dtype = state_dtype
        self.pre_state = pre_state    # int32 (K,) search state of the plan
        self.pre_ig = pre_ig
        self.winner_bit = winner_bit  # realized action the cache assumes
        self.status = status
        self.density = density        # pool aggregation density at draw
        self.n_max = n_max            # cap for extension bits

    @property
    def on_host(self) -> bool:
        return isinstance(self.end_ig, np.ndarray)

    def to_device(self):
        """Move a full plan's host frontier onto the device."""
        self.end_state, self.end_ig, self.win_util = jax.device_put(
            (self.end_state, self.end_ig, self.win_util))


def _np_state(state: SS.SatState) -> SS.SatState:
    """Host int32 copy of a (K,) SatState (progress/relay pass through)."""
    return SS.SatState(*(np.asarray(x, np.int32) for x in state[:3]),
                       None if state.progress is None
                       else np.asarray(state.progress, np.int32),
                       None if state.relay is None
                       else np.asarray(state.relay, np.int32))


def _bucket(n: int) -> int:
    """Next power of two at or above n: the survivor pool's bucket, which
    the `replan.delta` span reports as its `bucket` count."""
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def _pack(survivors, bits, conn, grant, needs, status, R: int):
    """The delta program's one int32 input, laid out as `_unpack` reads
    it: the survivors' frontier rows padded to R with row 0, their new
    bits padded with 0, the revealed window's connectivity column, under
    a link budget its grant row and (need_up, need_down), and last the
    float32 status by its bits."""
    pad = np.zeros(R - survivors.size, np.int64)
    parts = [survivors, pad, bits, pad, conn]
    if grant is not None:
        parts += [grant, needs]
    parts.append(np.float32(status).view(np.int32)[None])
    return np.concatenate(parts).astype(np.int32)


def _unpack(packed, R: int, K: int, gated: bool):
    """(rows, bits, connected, link, status) of a `_pack`ed input."""
    rows, bits = packed[:R], packed[R:2 * R]
    connected = packed[2 * R:2 * R + K] != 0
    link = None
    if gated:
        g = packed[2 * R + K:]
        link = SS.LinkGate(g[:K], g[K], g[K + 1])
    status = jax.lax.bitcast_convert_type(packed[-1], jnp.float32)
    return rows, bits, connected, link, status


@functools.partial(jax.jit, static_argnames=("predict", "s_max", "gated"))
def _delta_program(states, igs, win_util, packed, forest, *, predict,
                   s_max: int, gated: bool):
    """A delta answer's device work as one program: gather the survivors'
    frontier rows (`packed`, see `_pack`), step every row through the
    revealed window (`step_candidates`), histogram, featurize and predict
    (`predict(*forest, X)`, the regressor's `device_predictor`), keep the
    utility where the row's new bit is 1 and 0 elsewhere, slide
    `win_util` left by one window with that column last, and sum each
    row. Returns the frontier advanced through the revealed window
    (states, igs), the new `win_util` and the (R,) scores. Shapes are the frontier's R rows whatever the
    survivor count, and every per-request value is an operand, so one
    trace serves a service's every delta answer."""
    R, K = igs.shape[0], states.version.shape[-1]
    rows, bits, connected, link, status = _unpack(packed, R, K, gated)
    states = jax.tree.map(lambda x: x[rows], states)
    igs, win_util = igs[rows], win_util[rows]
    marks, states, igs = step_candidates(states, igs, connected, bits,
                                         link, s_max=s_max)
    hists = SS.hist_from_marks(marks, s_max=s_max, dtype=jnp.int16)
    util = predict(*forest, featurize_jnp(hists, status))
    win_util = jnp.concatenate(
        [win_util[:, 1:], jnp.where(bits == 1, util, 0.0)[:, None]], axis=1)
    return states, igs, win_util, win_util.sum(axis=1)


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
    `replan` answers a request; `maintain`, between requests, moves a
    full plan's frontier to the device so that copy stays off the next
    answer.

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

    def maintain(self):
        """Frontier upkeep between requests: move a full plan's host
        frontier onto the device, so the next delta answer does not make
        that copy itself, and wait until the copy has landed, so it does
        not run on into the next answer's own transfers. A no-op once the
        frontier is there (a delta answer leaves it there). The request
        path never calls this, so a caller that only ever full-rescans
        never moves it."""
        c = self._cache
        if c is None or not c.on_host:
            return
        # `window` is the request this upkeep prepares: the next one
        with tracing.span("replan.maintain", window=c.window + 1,
                          rows=int(c.end_ig.shape[0])):
            c.to_device()
            jax.block_until_ready((c.end_state, c.end_ig, c.win_util))

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
        R = int(c.end_ig.shape[0])
        gated = c.grant is not None
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
                packed = _pack(
                    np.flatnonzero(keep), new_bits, Cw[-1],
                    None if not gated
                    else np.asarray(link.grant, np.int32)[-1],
                    (c.need_up, c.need_dn), status, R)
            # simulate ONLY the newly revealed window, from the cached
            # frontier: the full scan's marks -> hist -> featurize ->
            # predict pipeline in one program, over the frontier's R rows;
            # its step is also the frontier's advance
            with tracing.span("replan.delta.score", window=window,
                              scheduled=int(new_bits.sum()), rows=R,
                              forest_dense=forest_dense(self.regressor)):
                predict, forest = device_predictor(self.regressor)
                packed = jnp.asarray(packed)
                if c.on_host:         # no `maintain` since the full plan
                    c.to_device()
                end_state, end_ig, win_util, scores = _delta_program(
                    c.end_state, c.end_ig, c.win_util, packed, forest,
                    predict=predict, s_max=self.s_max, gated=gated)
            # the wait for the program lands in this read
            with tracing.span("replan.delta.reduce", window=window, rows=R,
                              width=Cw.shape[0]):
                scores = np.asarray(scores)[:S]
            with tracing.span("replan.select", window=window):
                w = select_candidate(cands, scores)
                # roll the cache forward, the frontier as the program
                # left it: on the device, advanced through the window
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
                return cands[w].copy()

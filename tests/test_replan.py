"""Incremental replanning service (`repro.fl.replan`): the delta-window
path must select what a full rescan of the same pool from the caller's
state selects, every invalidation rule must actually fire, and routing
the FedSpace scheduler through a service must not change a single
trajectory bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import staleness as SS
from repro.core.connectivity import connectivity_sets, constellation_preset
from repro.core.search import (event_positions, random_candidates,
                               scan_candidates, score_candidates,
                               select_candidate, step_candidates)
from repro.core.utility import (MLPRegressor, RandomForestRegressor,
                                featurize, featurize_jnp, n_features,
                                transfer_ready, transfer_report)
from repro.fl.replan import (ReplanService, _bucket,
                             _delta_program, _drift, calibrate_forest,
                             rollout_histograms)

S_MAX = 8


@pytest.fixture(scope="module", autouse=True)
def _drop_jit_caches():
    """This module compiles far more distinct executables than any other
    (the bucket ladder alone is a dozen shapes per jitted entry point);
    leaving them live for the rest of the suite has crashed XLA's CPU
    compiler deep in later, unrelated tests. Drop them on the way out."""
    yield
    jax.clear_caches()


def _forest(seed=0, n_trees=4):
    rng = np.random.default_rng(seed)
    hists = rng.integers(0, 20, (150, S_MAX + 1)).astype(np.float32)
    X = featurize(hists, 1.0)
    s = np.arange(S_MAX + 1, dtype=np.float32)
    y = ((hists * (1.0 - 0.1 * s)).sum(1)
         / np.maximum(hists.sum(1), 1.0)).astype(np.float32)
    return RandomForestRegressor(n_trees=n_trees, max_depth=4,
                                 seed=seed).fit(X, y)


def _world(K=24, T=64, p=0.3, seed=0):
    rng = np.random.default_rng(seed)
    C = rng.random((T, K)) < p
    state = jax.tree.map(np.asarray, SS.bootstrap_state(K))
    return C, state


def _advance(state, ig, conn, bit):
    """Realize one window of the true protocol (the engine's view)."""
    st, g, _ = SS.step(jax.tree.map(jnp.asarray, state), jnp.int32(ig),
                       jnp.asarray(conn), jnp.asarray(bool(bit)),
                       s_max=S_MAX, collect="none")
    return jax.tree.map(np.asarray, st), int(g)


# ---------------------------------------------------------------------------
# the tentpole invariant: delta == full rescan, bit for bit


def _flock191_world(T=64):
    """The first T windows of Planet Flock 191's propagated geometry."""
    C = connectivity_sets(constellation_preset("flock191"), days=1.0)[:T]
    return C, jax.tree.map(np.asarray, SS.bootstrap_state(C.shape[1]))


@pytest.mark.parametrize("explicit_maintain", [True, False])
@pytest.mark.parametrize("world", ["random", "flock191"])
def test_delta_selection_bit_identical_to_full_rescan(world,
                                                      explicit_maintain):
    """Across a stream of consecutive replans, every answer — delta or
    full — must equal `score_candidates` + `select_candidate` on the
    service's live pool from the caller's state, on a random K=24 world
    and on flock191's real geometry (K=191). With
    `explicit_maintain=False` the delta program takes the full plan's
    frontier from the host itself. A delta answer's scores sum the same
    per-event utilities as `score_candidates` in another order, so they
    agree within `select_candidate`'s tie band and not bit for bit: the
    selections are equal except for a candidate within a few ulp of the
    band's edge, which these streams do not hold."""
    rf = _forest()
    C, state = _world() if world == "random" else _flock191_world()
    svc = ReplanService(rf, I0=8, num_candidates=64, s_max=S_MAX, seed=7,
                        min_pool=8)
    ig, status = 0, 3.0
    modes = []
    for i in range(6):
        Cw = C[i:i + 8]
        plan = svc.replan(i, Cw, state, ig, status,
                          rng=np.random.default_rng(100 + i))
        modes.append(svc.last_mode)
        pool = svc.pool
        scores = score_candidates(pool, Cw, state, ig, rf, status,
                                  s_max=S_MAX)
        assert np.array_equal(plan, pool[select_candidate(pool, scores)])
        if explicit_maintain:
            svc.maintain()
        state, ig = _advance(state, ig, C[i], plan[0])
    assert modes[0] == "full" and "delta" in modes
    assert svc.stats["delta"] == modes.count("delta")


def test_scan_candidates_scores_match_score_candidates():
    """The cache-collecting scan twin must reproduce `score_candidates`
    bit for bit (same narrowed simulator, same device reduction)."""
    rf = _forest()
    C, state = _world(K=16, T=16)
    cands = random_candidates(np.random.default_rng(3), 10, 2, 5, 48)
    ref = np.asarray(score_candidates(cands, C[:10], state, 0, rf, 2.0,
                                      s_max=S_MAX))
    got, art = scan_candidates(cands, C[:10], state, 0, rf, 2.0,
                               s_max=S_MAX)
    assert np.array_equal(ref, np.asarray(got))
    assert art["win_util"].shape == (48, 10)
    assert art["end_ig"].shape == (48,)
    # per-event utilities land exactly at each candidate's event windows
    assert np.array_equal(art["win_util"] != 0.0,
                          (art["win_util"] * cands) != 0.0)


def test_pool_decays_and_winner_survives():
    rf = _forest()
    C, state = _world()
    svc = ReplanService(rf, I0=8, num_candidates=64, s_max=S_MAX, seed=7,
                        min_pool=4)
    ig = 0
    plan = svc.replan(0, C[0:8], state, ig, 1.0,
                      rng=np.random.default_rng(0))
    r0 = svc.pool.shape[0]
    state, ig = _advance(state, ig, C[0], plan[0])
    plan2 = svc.replan(1, C[1:9], state, ig, 1.0)
    assert svc.last_mode == "delta"
    assert svc.pool.shape[0] < r0           # survivors only
    # the previous winner's tail is still in the pool (it IS reality)
    assert any(np.array_equal(row[:7], plan[1:]) for row in svc.pool)
    assert plan2.shape == (8,)


# ---------------------------------------------------------------------------
# invalidation rules


def _primed(min_pool=4, K=24):
    """A service with a warm cache at window 0 plus the advanced state."""
    rf = _forest()
    C, state = _world(K=K)
    svc = ReplanService(rf, I0=8, num_candidates=64, s_max=S_MAX, seed=7,
                        min_pool=min_pool)
    plan = svc.replan(0, C[0:8], state, 0, 1.0,
                      rng=np.random.default_rng(0))
    state, ig = _advance(state, 0, C[0], plan[0])
    return svc, C, state, ig, plan


def test_invalidation_reasons_fire():
    svc, C, state, ig, plan = _primed()

    # non-consecutive window
    svc.replan(4, C[4:12], state, ig, 1.0, rng=np.random.default_rng(1))
    assert (svc.last_mode, svc.last_reason) == ("full", "window")

    # prime again, then: changed status invalidates every cached utility
    state2, ig2 = _advance(state, ig, C[4], svc.pool[0][0])
    svc.replan(5, C[5:13], state2, ig2, 9.0,
               rng=np.random.default_rng(2))
    assert (svc.last_mode, svc.last_reason) == ("full", "status")


def test_invalidation_horizon_and_connectivity():
    svc, C, state, ig, _ = _primed()
    svc.replan(1, C[1:7], state, ig, 1.0, rng=np.random.default_rng(1))
    assert (svc.last_mode, svc.last_reason) == ("full", "horizon")

    svc2, C2, state2, ig2, _ = _primed()
    Cw = C2[1:9].copy()
    Cw[2] = ~Cw[2]                          # overlap rows differ
    svc2.replan(1, Cw, state2, ig2, 1.0, rng=np.random.default_rng(1))
    assert (svc2.last_mode, svc2.last_reason) == ("full", "connectivity")


def test_invalidation_drift():
    """A caller whose state does not match the realized winner bit (e.g.
    an out-of-band aggregation) must force a full rescan."""
    svc, C, state, ig, plan = _primed()
    wrong_state, wrong_ig = _advance(state, ig, C[0], 1 - int(plan[0]))
    svc.replan(1, C[1:9], wrong_state, wrong_ig, 1.0,
               rng=np.random.default_rng(1))
    assert (svc.last_mode, svc.last_reason) == ("full", "drift")


def _eager_drifted(svc, Cw, state, ig, link):
    """The drift check as eager protocol steps and host compares of every
    leaf: the reference the compiled check answers like."""
    c = svc._cache

    def gate(row):
        return None if row is None else SS.LinkGate(
            jnp.asarray(np.asarray(row), jnp.int32), jnp.int32(c.need_up),
            jnp.int32(c.need_dn))

    after, g_after, _ = SS.step(
        jax.tree.map(jnp.asarray, c.pre_state), jnp.int32(c.pre_ig),
        jnp.asarray(c.Cw[0]), jnp.asarray(bool(c.winner_bit)),
        s_max=svc.s_max, collect="none",
        link=gate(None if c.grant is None else c.grant[0]))
    if int(g_after) != int(ig):
        return True
    gate0 = gate(None if link is None
                 else np.asarray(link.grant, np.int32)[0])
    conn0 = jnp.asarray(Cw[0])
    predicted, _ = SS.upload_step(after, g_after, conn0, gate0)
    given = jax.tree.map(lambda x: jnp.asarray(np.asarray(x), jnp.int32),
                         state)
    rescanned, _ = SS.upload_step(given, jnp.int32(int(ig)), conn0, gate0)
    la, lb = jax.tree.leaves(predicted), jax.tree.leaves(rescanned)
    return not (len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb)))


def _gated_link(grant, i, need_up=2, need_dn=1):
    return SS.LinkGate(jnp.asarray(grant[i:i + 8]), jnp.int32(need_up),
                       jnp.int32(need_dn))


def _absorbing_sat(C):
    return int(np.flatnonzero(~C[0] & C[1])[0])


def _primed_gated(K=24):
    """`_primed` under a link budget (need_up 2 > 0, `progress` column)."""
    rf = _forest()
    C, state = _world(K=K)
    grant = np.random.default_rng(4).integers(0, 3, (64, K)).astype(
        np.int32)
    # a satellite first in contact at window 1, granted a whole upload
    # there: its (pending) upload completes whatever progress it holds
    k = _absorbing_sat(C)
    grant[0, k], grant[1, k] = 0, 2
    state = SS.SatState(*state[:3], np.zeros(K, np.int32), None)
    svc = ReplanService(rf, I0=8, num_candidates=64, s_max=S_MAX, seed=7,
                        min_pool=4)
    plan = svc.replan(0, C[0:8], state, 0, 1.0, link=_gated_link(grant, 0),
                      rng=np.random.default_rng(0))
    g0 = _gated_link(grant, 0)
    st, g, _ = SS.step(jax.tree.map(jnp.asarray, state), jnp.int32(0),
                       jnp.asarray(C[0]), jnp.asarray(bool(plan[0])),
                       s_max=S_MAX, collect="none",
                       link=SS.LinkGate(g0.grant[0], g0.need_up,
                                        g0.need_dn))
    return svc, C, jax.tree.map(np.asarray, st), int(g), grant


def _drift_case(case):
    """(service, Cw, state, ig, link, drifted) of one parity case."""
    if case.startswith("gate"):
        svc, C, state, ig, grant = _primed_gated()
        if case != "gate":
            # out of contact, a satellite keeps its progress through the
            # upload, so the change shows; the absorbing satellite's
            # upload completes and resets it, so the change is no drift
            k = (int(np.flatnonzero(~C[1])[0]) if case == "gate, progress"
                 else _absorbing_sat(C))
            progress = state.progress.copy()
            progress[k] += 1
            state = state._replace(progress=progress)
        return (svc, C[1:9], state, ig, _gated_link(grant, 1),
                case == "gate, progress")
    svc, C, state, ig, plan = _primed()
    if case == "other action":
        state, ig = _advance(state, 0, C[0], 1 - int(plan[0]))
        # (aggregating an empty buffer would be no drift)
        assert np.any(C[0] & (svc._cache.pre_state.pending >= 0))
    elif case == "out-of-band aggregation":
        ig = ig + 1
    elif case == "pending changed":
        pending = state.pending.copy()
        pending[3] = pending[3] + 1 if pending[3] >= 0 else ig
        state = state._replace(pending=pending)
    elif case == "progress on one side":
        state = state._replace(progress=np.zeros(C.shape[1], np.int32))
    return svc, C[1:9], state, ig, None, case != "no drift"


@pytest.mark.parametrize("case", [
    "no drift", "other action", "out-of-band aggregation",
    "pending changed", "gate", "gate, progress",
    "gate, progress absorbed", "progress on one side"])
def test_compiled_drift_check_matches_eager_steps(case):
    """The compiled drift check answers as the eager protocol steps and
    leaf compares it replaced, in each way a caller's state can drift or
    not."""
    svc, Cw, state, ig, link, drifted = _drift_case(case)
    want = _eager_drifted(svc, Cw, state, ig, link)
    assert svc._drifted(Cw, state, ig, link) == want == drifted
    svc.replan(1, Cw, state, ig, 1.0, link=link,
               rng=np.random.default_rng(1))
    assert svc.last_reason == ("drift" if drifted else None)


def test_drift_reasons_match_eager_check():
    """A seeded stream with one injected drift (the caller realizes the
    other action at window 3): the service answers and gives the same
    reasons as one whose drift check is the eager reference."""
    rf = _forest()
    C, state0 = _world()

    def drive(svc):
        state, ig, out = state0, 0, []
        for i in range(8):
            plan = svc.replan(i, C[i:i + 8], state, ig, 1.0,
                              rng=np.random.default_rng(100 + i))
            out.append((svc.last_mode, svc.last_reason, plan))
            bit = int(plan[0]) if i != 3 else 1 - int(plan[0])
            state, ig = _advance(state, ig, C[i], bit)
        return out

    def service():
        return ReplanService(rf, I0=8, num_candidates=256, s_max=S_MAX,
                             seed=7, min_pool=8)

    ref = service()
    ref._drifted = lambda Cw, state, ig, link: _eager_drifted(
        ref, Cw, state, ig, link)
    got, want = drive(service()), drive(ref)
    assert [(m, r) for m, r, _ in got] == [(m, r) for m, r, _ in want]
    assert all(np.array_equal(a, b) for (_, _, a), (_, _, b)
               in zip(got, want))
    assert got[4][:2] == ("full", "drift")
    assert [m for m, _, _ in got].count("delta") >= 4


def test_drift_check_compiles_once_across_delta_answers():
    """Ten consecutive delta answers, over which the versions and the
    realized bits change, run the drift program from one trace: every
    per-request value is an argument, none is static."""
    rf = _forest(seed=1)
    C, state = _world(p=0.5)
    svc = ReplanService(rf, I0=8, num_candidates=2048, s_max=S_MAX, seed=7,
                        min_pool=4)
    _drift.clear_cache()
    plan = svc.replan(0, C[0:8], state, 0, 1.0,
                      rng=np.random.default_rng(0))
    ig, bits, igs = 0, [], []
    for i in range(1, 11):
        bits.append(int(plan[0]))
        state, ig = _advance(state, ig, C[i - 1], plan[0])
        igs.append(ig)
        plan = svc.replan(i, C[i:i + 8], state, ig, 1.0)
        assert svc.last_mode == "delta", (i, svc.last_reason)
    assert len(set(bits)) == 2 and len(set(igs)) >= 3
    assert _drift._cache_size() == 1


# ---------------------------------------------------------------------------
# the delta answer as one compiled program


def _rows(state, sel):
    return jax.tree.map(lambda x: x[sel], state)


def _eager_delta(svc, window, Cw, state, ig, status, link):
    """The delta answer as eager steps on a host-held frontier: survivor
    rows copied on the host, the one-window step, histogram, features and
    forest of the rows that scheduled the new window at a power-of-two
    batch, and the event-position reduce a full rescan uses. The
    reference the compiled program answers like."""
    c = svc._cache
    keep = c.cands[:, 0] == c.winner_bit
    S = int(np.count_nonzero(keep))
    base = c.cands[keep]
    n_now = base[:, 1:].sum(axis=1)
    draw = (svc._rng.random(S) < c.density).astype(np.int32)
    new_bits = np.where(n_now < c.n_max, draw, 0).astype(np.int32)
    cands = np.concatenate([base[:, 1:], new_bits[:, None]], axis=1)
    win_util = np.concatenate(
        [c.win_util[keep, 1:], np.zeros((S, 1), np.float32)], axis=1)
    end_state = _rows(c.end_state, keep)
    end_ig = c.end_ig[keep]
    conn_new = Cw[-1]
    gate_new = None if link is None else SS.LinkGate(
        jnp.asarray(np.asarray(link.grant, np.int32)[-1]),
        jnp.int32(c.need_up), jnp.int32(c.need_dn))
    rows1 = np.flatnonzero(new_bits == 1)
    if rows1.size:
        m = rows1.size
        sel = np.concatenate(
            [rows1, np.full(_bucket(m) - m, rows1[0], np.int64)])
        marks, _, _ = step_candidates(
            jax.tree.map(jnp.asarray, _rows(end_state, sel)),
            jnp.asarray(end_ig[sel]), jnp.asarray(conn_new),
            jnp.asarray(new_bits[sel]), gate_new, s_max=svc.s_max)
        hists = SS.hist_from_marks(marks, s_max=svc.s_max, dtype=jnp.int16)
        util = svc.regressor.predict_device(
            featurize_jnp(hists, jnp.float32(status)))
        win_util[rows1, -1] = np.asarray(util)[:m]
    idx, mask = event_positions(cands)
    util_ev = np.take_along_axis(win_util, idx, axis=1)
    pad = _bucket(S) - S
    util_ev = np.concatenate([util_ev, np.zeros((pad, idx.shape[1]),
                                                np.float32)])
    mask = np.concatenate([mask, np.zeros((pad, idx.shape[1]), bool)])
    scores = np.asarray((jnp.asarray(util_ev)
                         * jnp.asarray(mask, jnp.float32)).sum(axis=1))[:S]
    w = select_candidate(cands, scores)
    c.window, c.cands, c.Cw = window, cands, Cw.copy()
    if link is not None:
        c.grant = np.asarray(link.grant, np.int32).copy()
    c.win_util, c.end_state, c.end_ig = win_util, end_state, end_ig
    c.pre_state = jax.tree.map(
        lambda x: None if x is None else np.asarray(x, np.int32), state)
    c.pre_ig, c.winner_bit = int(ig), int(cands[w, 0])
    c.pending = (conn_new.copy(), gate_new)
    return cands[w].copy()


def _eager_maintain(svc):
    """The host-held frontier's advance through the window the last
    `_eager_delta` revealed (its `pending`), which the eager service ran
    between requests or, failing that, before its next answer."""
    c = svc._cache
    if c is None or getattr(c, "pending", None) is None:
        return
    conn, gate = c.pending
    S = int(c.end_ig.shape[0])
    sel = np.concatenate([np.arange(S), np.zeros(_bucket(S) - S, np.int64)])
    _, st, g = step_candidates(
        jax.tree.map(jnp.asarray, _rows(c.end_state, sel)),
        jnp.asarray(c.end_ig[sel]), jnp.asarray(conn),
        jnp.asarray(c.cands[sel, -1]), gate, s_max=svc.s_max)
    c.end_state = jax.tree.map(lambda x: np.asarray(x)[:S], st)
    c.end_ig = np.asarray(g)[:S]
    c.pending = None


def _stream(world, gated, K=24):
    """(C, state, grant or None) of a seeded request stream."""
    C, state = _world(K=K) if world == "random" else _flock191_world()
    if not gated:
        return C, state, None
    grant = np.random.default_rng(4).integers(0, 3, C.shape).astype(
        np.int32)
    state = SS.SatState(*state[:3], np.zeros(C.shape[1], np.int32), None)
    return C, state, grant


def _drive_stream(svc, C, state, grant, *, explicit_maintain, n=14):
    """n consecutive requests, the status changing at request 7, each
    answer's first action realized (under the grant when gated):
    [(mode, reason, plan)]."""
    ig, out = 0, []
    for i in range(n):
        link = None if grant is None else _gated_link(grant, i)
        status = 3.0 if i < 7 else 2.5
        plan = svc.replan(i, C[i:i + 8], state, ig, status, link=link,
                          rng=np.random.default_rng(100 + i))
        out.append((svc.last_mode, svc.last_reason, plan))
        if explicit_maintain:
            svc.maintain()
        gate = None if link is None else SS.LinkGate(
            link.grant[0], link.need_up, link.need_dn)
        st, g, _ = SS.step(jax.tree.map(jnp.asarray, state), jnp.int32(ig),
                           jnp.asarray(C[i]), jnp.asarray(bool(plan[0])),
                           s_max=S_MAX, collect="none", link=gate)
        state, ig = jax.tree.map(np.asarray, st), int(g)
    return out


@pytest.mark.parametrize("explicit_maintain", [True, False])
@pytest.mark.parametrize("gated", [False, True])
@pytest.mark.parametrize("world", ["random", "flock191"])
def test_delta_program_answers_as_eager_delta(world, gated,
                                              explicit_maintain):
    """A seeded stream of 14 requests: the service answers, in the same
    modes and for the same reasons, as one whose delta answer and frontier
    upkeep are the eager host-frontier reference."""
    rf = _forest()
    C, state, grant = _stream(world, gated)

    def service():
        return ReplanService(rf, I0=8, num_candidates=128, s_max=S_MAX,
                             seed=7, min_pool=8)

    def eager_answer(*a):
        _eager_maintain(ref)
        return _eager_delta(ref, *a)

    ref = service()
    ref._delta = eager_answer
    ref.maintain = lambda: _eager_maintain(ref)
    got, want = (_drive_stream(svc, C, state, grant,
                               explicit_maintain=explicit_maintain)
                 for svc in (service(), ref))
    assert [(m, r) for m, r, _ in got] == [(m, r) for m, r, _ in want]
    assert all(np.array_equal(a, b) for (_, _, a), (_, _, b)
               in zip(got, want))
    assert [m for m, _, _ in got].count("delta") >= 8


def test_delta_program_compiles_once_across_buckets_and_widths():
    """Delta answers whose survivor pool falls through several
    power-of-two buckets, and whose event width (the pool's largest
    aggregation count) changes with a second full plan's draw range, run
    the delta program from one trace, whether `maintain` moved the
    frontier to the device (the first plan) or the answer did (the
    second)."""
    rf = _forest(seed=1)
    C, state = _world(p=0.5)
    svc = ReplanService(rf, I0=8, num_candidates=2048, s_max=S_MAX, seed=7,
                        min_pool=4)
    _delta_program.clear_cache()
    ig, plan, buckets, widths, modes = 0, None, set(), set(), []
    for i in range(13):
        if plan is not None:
            state, ig = _advance(state, ig, C[i - 1], plan[0])
        n = 2 if i < 7 else 4           # a status change at 7: full plan
        plan = svc.replan(i, C[i:i + 8], state, ig, 1.0 if i < 7 else 2.0,
                          rng=np.random.default_rng(i), n_min=n, n_max=n)
        modes.append(svc.last_mode)
        if svc.last_mode == "delta":
            buckets.add(_bucket(len(svc.pool)))
            widths.add(int(svc.pool.sum(axis=1).max()))
        if i < 7:
            svc.maintain()
    assert modes == ["full"] + ["delta"] * 6 + ["full"] + ["delta"] * 5
    assert len(buckets) >= 3 and len(widths) >= 2
    assert _delta_program._cache_size() == 1


@pytest.mark.parametrize("gated", [False, True])
def test_delta_answer_advances_the_device_frontier_as_step_candidates(
        gated):
    """`maintain` moves a full plan's frontier to the device unchanged,
    and a delta answer leaves each survivor's frontier row on the device
    and equal, read back, to `step_candidates` of its host copy through
    the revealed window by its new bit."""
    rf = _forest()
    C, state, grant = _stream("random", gated)
    svc = ReplanService(rf, I0=8, num_candidates=128, s_max=S_MAX, seed=7,
                        min_pool=8)

    def frontier():
        c = svc._cache
        return jax.tree.map(np.asarray, (c.end_state, c.end_ig))

    link = None if grant is None else _gated_link(grant, 0)
    plan = svc.replan(0, C[0:8], state, 0, 3.0, link=link,
                      rng=np.random.default_rng(0))
    host = frontier()
    svc.maintain()
    assert isinstance(svc._cache.end_ig, jax.Array)
    assert jax.tree.all(jax.tree.map(np.array_equal, frontier(), host))
    gate = None if link is None else SS.LinkGate(
        link.grant[0], link.need_up, link.need_dn)
    st, g, _ = SS.step(jax.tree.map(jnp.asarray, state), jnp.int32(0),
                       jnp.asarray(C[0]), jnp.asarray(bool(plan[0])),
                       s_max=S_MAX, collect="none", link=gate)
    state, ig = jax.tree.map(np.asarray, st), int(g)
    link = None if grant is None else _gated_link(grant, 1)
    c = svc._cache
    keep = np.flatnonzero(c.cands[:, 0] == c.winner_bit)
    rows, igs = jax.tree.map(lambda x: x[keep], frontier())
    svc.replan(1, C[1:9], state, ig, 3.0, link=link)
    assert svc.last_mode == "delta"
    S = len(svc.pool)
    assert S == keep.size
    gate = None if link is None else SS.LinkGate(
        jnp.asarray(link.grant[-1]), link.need_up, link.need_dn)
    _, want, want_ig = step_candidates(
        jax.tree.map(jnp.asarray, rows), jnp.asarray(igs),
        jnp.asarray(C[8]), jnp.asarray(svc.pool[:, -1]), gate,
        s_max=S_MAX)
    assert isinstance(svc._cache.end_ig, jax.Array)
    got, got_ig = jax.tree.map(lambda x: x[:S], frontier())
    assert np.array_equal(got_ig, np.asarray(want_ig))
    assert jax.tree.all(jax.tree.map(
        lambda a, b: np.array_equal(a, np.asarray(b)), got, want))


def test_invalidation_link_view():
    svc, C, state, ig, _ = _primed()
    K = C.shape[1]
    # the gated rescan needs the in-progress-transfer column attached
    state = SS.SatState(state.version, state.pending, state.buffered,
                        np.zeros(K, np.int32), None)
    gate = SS.LinkGate(jnp.ones((8, K), jnp.int32), jnp.int32(1),
                       jnp.int32(1))
    svc.replan(1, C[1:9], state, ig, 1.0, link=gate,
               rng=np.random.default_rng(1))
    assert (svc.last_mode, svc.last_reason) == ("full", "link")


def test_external_invalidate_and_pool_floor():
    svc, C, state, ig, _ = _primed(min_pool=64)
    svc.invalidate("reset")
    assert svc.pool is None
    svc.replan(1, C[1:9], state, ig, 1.0, rng=np.random.default_rng(1))
    assert (svc.last_mode, svc.last_reason) == ("full", "cold")
    assert svc.stats["invalidated"]["reset"] == 1

    # min_pool=64 == R: the first consecutive request trips the floor
    state, ig = _advance(state, ig, C[1], svc.pool[0][0])
    svc.replan(2, C[2:10], state, ig, 1.0, rng=np.random.default_rng(2))
    assert (svc.last_mode, svc.last_reason) == ("full", "pool")


def test_transfer_ready_gatekeeps_service():
    class NoDevice:
        def predict(self, X):
            return np.zeros(len(X), np.float32)

    with pytest.raises(ValueError, match="transfer-ready"):
        ReplanService(NoDevice())

    rf = _forest()
    rf.n_features_ = 99                     # fitted at a different s_max
    with pytest.raises(ValueError, match="transfer-ready"):
        ReplanService(rf)


def test_calibrated_forest_serves_the_service():
    """`calibrate_forest` fits on one histogram row per window and
    cadence, and its forest passes the service's transfer gate."""
    C, state = _world(K=24, T=16)
    H = rollout_histograms(C, s_max=S_MAX)
    assert H.shape == (4 * 16, S_MAX + 1)
    assert H.sum() > 0
    rf = calibrate_forest(C, s_max=S_MAX, n_trees=4)
    assert transfer_ready(rf, s_max=S_MAX)
    svc = ReplanService(rf, I0=8, num_candidates=64, s_max=S_MAX, seed=0)
    plan = svc.replan(0, C[:8], state, 0, 1.0,
                      rng=np.random.default_rng(0))
    assert plan.shape == (8,) and svc.last_mode == "full"


# ---------------------------------------------------------------------------
# forest transfer metadata


def test_fit_records_envelope_and_transfer_report():
    rf = _forest()
    assert rf.n_features_ == n_features(S_MAX)
    assert rf.feature_low_.shape == (n_features(S_MAX),)
    assert transfer_ready(rf, s_max=S_MAX)
    assert not transfer_ready(rf, s_max=4)  # width mismatch

    mlp = MLPRegressor(hidden=8, steps=5, seed=0).fit(
        np.random.default_rng(0).random((32, n_features(S_MAX))).astype(
            np.float32),
        np.zeros(32, np.float32))
    assert mlp.n_features_ == n_features(S_MAX)

    inside = transfer_report(rf, rf.feature_low_[None, :])
    assert inside["in_envelope"] == 1.0 and inside["out_features"] == []
    outside = transfer_report(rf, rf.feature_high_[None, :] + 1000.0)
    assert outside["in_envelope"] < 1.0 and outside["out_features"]
    assert outside["pred_finite"]           # trees saturate, never explode


# ---------------------------------------------------------------------------
# engine routing: a service-backed FedSpace run is the unrouted run


def test_fedspace_routed_through_service_is_bit_identical():
    from repro.fl.api import (AdapterConfig, ConstellationConfig,
                              DatasetConfig, FLExperiment, PartitionConfig,
                              SchedulerConfig)
    from repro.fl.api import Federation
    from repro.fl.engine import EngineConfig

    rf = _forest()
    W = 10
    exp = FLExperiment(
        constellation=ConstellationConfig(preset="starlink40", days=0.125),
        dataset=DatasetConfig(num_train=240, num_val=60),
        partition=PartitionConfig(kind="iid"),
        adapter=AdapterConfig(kind="mlp", params={"hidden": 8}),
        scheduler=SchedulerConfig(kind="fedspace",
                                  params={"regressor": rf, "I0": 5,
                                          "n_min": 1, "n_max": 2,
                                          "num_candidates": 16}),
        train=EngineConfig(max_windows=W, eval_every=W, local_steps=1,
                           batch_size=8))
    fed = Federation.from_experiment(exp)
    plain = fed.run()

    svc = ReplanService(rf, I0=5, num_candidates=16, s_max=S_MAX, seed=0)
    routed = fed.with_scheduler(SchedulerConfig(
        kind="fedspace",
        params={"regressor": rf, "I0": 5, "n_min": 1, "n_max": 2,
                "num_candidates": 16, "service": svc})).run()

    assert plain.accuracy == routed.accuracy
    assert plain.num_global_updates == routed.num_global_updates
    assert np.array_equal(plain.staleness_hist, routed.staleness_hist)
    assert plain.replan_stats is None
    assert routed.replan_stats is not None
    assert routed.replan_stats["full"] + routed.replan_stats["delta"] > 0
    assert routed.summary()["replan_stats"] == routed.replan_stats


def test_maintain_returns_once_the_frontier_upload_has_landed(monkeypatch):
    """`maintain` waits on the frontier it moved to the device before it
    returns, so the copy does not run on into the next answer's own
    transfers; with the frontier already there it waits on nothing."""
    rf = _forest()
    C, state, _ = _stream("random", False)
    svc = ReplanService(rf, I0=8, num_candidates=128, s_max=S_MAX, seed=7,
                        min_pool=8)
    svc.replan(0, C[0:8], state, 0, 3.0, rng=np.random.default_rng(0))
    waited, real = [], jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: real(waited.append(x) or x))
    svc.maintain()
    c = svc._cache
    assert len(waited) == 1
    leaves = jax.tree.leaves(waited[0])
    assert [id(x) for x in leaves] == [
        id(x) for x in jax.tree.leaves((c.end_state, c.end_ig, c.win_util))]
    assert all(isinstance(x, jax.Array) and x.is_ready() for x in leaves)
    svc.maintain()
    assert len(waited) == 1


def test_stride_routed_scheduler_never_moves_the_frontier(monkeypatch):
    """The FedSpace scheduler replans at I0 strides, so a routed run's
    every answer is a full rescan ('cold', then 'window'), and no request
    uploads a frontier that the next full plan would throw away."""
    from repro.core.scheduler import make_scheduler
    from repro.fl.replan import _Cache
    uploads = []
    monkeypatch.setattr(_Cache, "to_device",
                        lambda self: uploads.append(self.window))
    rf = _forest()
    C, state = _world()
    I0 = 8
    svc = ReplanService(rf, I0=I0, num_candidates=32, s_max=S_MAX, seed=0)
    sched = make_scheduler("fedspace", regressor=rf, I0=I0, n_min=1,
                           n_max=3, num_candidates=32, s_max=S_MAX,
                           service=svc)
    ig = 0
    for i in range(3 * I0):
        act = sched.decide(i, n_in_buffer=1, K=C.shape[1], state=state,
                           ig=ig, connectivity=C, status=3.0)
        state, ig = _advance(state, ig, C[i], act)
    assert svc.stats["delta"] == 0 and svc.stats["full"] == 3
    assert svc.stats["invalidated"] == {"window": 2}
    assert uploads == []
    assert isinstance(svc._cache.end_ig, np.ndarray)


def test_scheduler_service_knob_mismatch_rejected():
    from repro.core.scheduler import make_scheduler
    rf = _forest()
    svc = ReplanService(rf, I0=6, num_candidates=32, s_max=S_MAX)
    with pytest.raises(ValueError, match="service"):
        make_scheduler("fedspace", regressor=rf, I0=8, num_candidates=32,
                       service=svc)

"""Link-budget layer: station-level contact accounting, deterministic
contention at shared ground stations, multi-window transfer gating, and
the parity contracts — infinite capacity must equal raw geometry, and the
schedule search must pick identical schedules under a trivial gate."""
import numpy as np
import jax.numpy as jnp

from repro.core import connectivity as CN
from repro.core import staleness as SS
from repro.core.search import fedspace_search
from repro.core.utility import RandomForestRegressor, featurize


# ---------------------------------------------------------------------------
# physics: transfer_windows / station_windows / resolve_contention


def test_transfer_windows_arithmetic():
    # 600 MB at 20 Mbit/s = 240 s = 4 sixty-second substeps
    assert CN.transfer_windows(20.0, 600.0, 60.0) == 4
    assert CN.transfer_windows(100.0, 600.0, 60.0) == 1   # ceil(48/60)
    # unconstrained sentinels
    assert CN.transfer_windows(0.0, 600.0) == 0
    assert CN.transfer_windows(20.0, 0.0) == 0


def test_station_windows_matches_connectivity_sets():
    """Collapsing the per-station contact counts must reproduce the
    geometry-only connectivity matrix bit-for-bit."""
    spec = CN.ConstellationSpec(num_satellites=16)
    C = CN.connectivity_sets(spec, days=0.25)
    counts = CN.station_windows(spec, days=0.25)
    assert counts.shape == (C.shape[0], 16, len(spec.ground_stations))
    np.testing.assert_array_equal((counts > 0).any(axis=-1), C)


def test_resolve_contention_unlimited_serves_all():
    counts = np.array([[[3, 0], [2, 5], [0, 0]]], np.int32)  # (1, K=3, G=2)
    assign = CN.resolve_contention(counts, 0)
    # longest-contact station wins; invisible satellite unserved
    assert assign.tolist() == [[0, 1, -1]]


def test_resolve_contention_capacity_and_order():
    # one station, capacity 1, both sats visible: longest contact first
    counts = np.array([[[2], [5]]], np.int32)                # (1, K=2, G=1)
    assert CN.resolve_contention(counts, 1).tolist() == [[-1, 0]]
    # tie on contact length -> lowest satellite index
    counts = np.array([[[4], [4]]], np.int32)
    assert CN.resolve_contention(counts, 1).tolist() == [[0, -1]]
    # stations claim in index order: station 0 takes sat 1 (its longest),
    # then station 1 still serves sat 0
    counts = np.array([[[2, 9], [5, 9]]], np.int32)
    assert CN.resolve_contention(counts, 1).tolist() == [[1, 0]]


def test_link_budget_unlimited_is_geometry():
    spec = CN.ConstellationSpec(num_satellites=12)
    C = CN.connectivity_sets(spec, days=0.25)
    b = CN.link_budget(spec, days=0.25)
    np.testing.assert_array_equal(b.served, C)
    np.testing.assert_array_equal(b.visible, C)
    assert b.need_up == 0 and b.need_dn == 0
    assert b.blocked_fraction() == 0.0
    assert (b.grants[b.served] > 0).all()
    assert (b.grants[~b.served] == 0).all()


def test_link_budget_capacity_blocks_contacts():
    spec = CN.constellation_preset("flock191", ground="sparse1")
    b = CN.link_budget(spec, days=0.25, gs_capacity=2)
    assert (b.served <= b.visible).all()
    assert b.blocked_fraction() > 0.1     # a single station saturates
    # never more than `capacity` sats on one station per window
    for i in range(b.num_windows):
        served = b.assign[i][b.assign[i] >= 0]
        _, n = np.unique(served, return_counts=True)
        assert (n <= 2).all()


# ---------------------------------------------------------------------------
# protocol gating: multi-window uploads/downloads


def test_multi_window_upload_and_download():
    """need_up=2 at 1 unit/window: the upload enters the buffer on the
    second contact; need_dn=2: the new model arrives two contacts after
    the aggregation."""
    K, I = 1, 7
    C = np.ones((I, K), bool)
    a = jnp.asarray(np.array([0, 0, 1, 0, 0, 0, 0], np.int32))
    gate = SS.LinkGate(jnp.ones((I, K), jnp.int32), jnp.int32(2),
                       jnp.int32(2))
    st = SS.bootstrap_state(K, progress=True)
    ig = jnp.int32(0)
    hist = []
    for i in range(I):
        st, ig, _ = SS.step(st, ig, jnp.asarray(C[i]), a[i].astype(bool),
                            s_max=8, link=SS.LinkGate(gate.grant[i],
                                                      gate.need_up,
                                                      gate.need_dn))
        hist.append((int(st.pending[0]), int(st.buffered[0]),
                     int(st.version[0]), int(ig), int(st.progress[0])))
    assert hist == [
        (0, -1, 0, 0, 1),    # w0: uploading, 1/2 units
        (-1, 0, 0, 0, 0),    # w1: upload complete -> buffer
        (-1, -1, 0, 1, 1),   # w2: aggregation; download starts, 1/2
        (1, -1, 1, 1, 0),    # w3: download complete -> new local round
        (1, -1, 1, 1, 1),    # w4: uploading the new round, 1/2
        (-1, 1, 1, 1, 0),    # w5: second upload complete
        (-1, 1, 1, 1, 0),    # w6: nothing to do (idle contact)
    ]


def test_upload_drains_before_download():
    """A satellite mid-upload does not accumulate download progress even
    when a newer global model exists."""
    st = SS.SatState(version=jnp.zeros((1,), jnp.int32),
                     pending=jnp.zeros((1,), jnp.int32),
                     buffered=jnp.full((1,), -1, jnp.int32),
                     progress=jnp.zeros((1,), jnp.int32))
    gate = SS.LinkGate(jnp.ones((1,), jnp.int32), jnp.int32(3),
                       jnp.int32(1))
    conn = jnp.ones((1,), bool)
    # ig=2 (model is ahead): the sat uploads for 3 windows before any
    # download despite need_dn=1
    ig = jnp.int32(2)
    for w in range(2):
        st, _, _ = SS.step(st, ig, conn, jnp.bool_(False), s_max=8,
                           link=gate)
        assert int(st.version[0]) == 0 and int(st.pending[0]) == 0, w
    st, _, _ = SS.step(st, ig, conn, jnp.bool_(False), s_max=8, link=gate)
    # upload completed on window 3; download then starts fresh and
    # completes immediately (need_dn=1, same-window grant)
    assert int(st.buffered[0]) == 0
    assert int(st.version[0]) == 2 and int(st.pending[0]) == 2


def test_progress_persists_across_contact_gaps():
    """A partial transfer resumes at the next contact instead of
    restarting."""
    C = np.array([[True], [False], [False], [True]], bool)
    gate = SS.LinkGate(jnp.asarray(np.where(C, 1, 0).astype(np.int32)),
                       jnp.int32(2), jnp.int32(0))
    st, ig, _ = SS.simulate_window(
        jnp.asarray(C), jnp.asarray(np.zeros(4, np.int32)),
        SS.bootstrap_state(1, progress=True), jnp.int32(0), link=gate)
    # 1 unit at w0 + 1 unit at w3 completes the 2-unit upload
    assert int(st.buffered[0]) == 0 and int(st.pending[0]) == -1


# ---------------------------------------------------------------------------
# search parity and experiment wiring


def _forest(s_max=8, seed=0):
    rng = np.random.default_rng(seed)
    hists = rng.integers(0, 25, (300, s_max + 1)).astype(np.float32)
    X = featurize(hists, 1.0)
    y = (hists.sum(1) + rng.normal(size=300)).astype(np.float32)
    return RandomForestRegressor(n_trees=10, max_depth=5, seed=seed).fit(
        X, y)


def test_search_trivial_gate_identical_schedule():
    """fedspace_search under the zero-need gate must select the identical
    schedule to the geometry-only search (both scoring backends share the
    candidate stream and the selection rule)."""
    rng = np.random.default_rng(0)
    K, I0 = 16, 12
    C = rng.random((I0, K)) < 0.2
    rf = _forest()
    base = fedspace_search(np.random.default_rng(7), C,
                           SS.bootstrap_state(K), 0, rf, 1.0,
                           num_candidates=256, s_max=8)
    gate = SS.LinkGate(np.ones((I0, K), np.int32) * C, 0, 0)
    gated = fedspace_search(np.random.default_rng(7), C,
                            SS.bootstrap_state(K, progress=True), 0, rf,
                            1.0, num_candidates=256, s_max=8, link=gate)
    np.testing.assert_array_equal(base, gated)


def test_fedspace_search_state_undoes_boundary_upload():
    """The search receives the post-upload state at a re-plan boundary
    and its rollout re-simulates that window's upload. With link gating
    the re-run is NOT idempotent (progress already holds the window's
    grant), so FedSpace must hand the search an inverted state —
    re-applying the gated upload_step on it must land exactly back on the
    engine's state, for in-flight and completed uploads alike."""
    from repro.core.scheduler import FedSpaceScheduler
    K = 4
    conn = jnp.asarray(np.array([True, True, True, False]))
    grants = np.array([[2, 2, 2, 2]], np.int32)
    gate = SS.LinkGate(jnp.asarray(grants[0]), jnp.int32(3), jnp.int32(1))
    # sat0 mid-upload, sat1 completes this window (progress 1 + 2 >= 3),
    # sat2 starts fresh, sat3 disconnected mid-upload
    pre = SS.SatState(version=jnp.zeros((K,), jnp.int32),
                      pending=jnp.asarray([0, 0, 0, 0], jnp.int32),
                      buffered=jnp.full((K,), -1, jnp.int32),
                      progress=jnp.asarray([0, 1, 0, 1], jnp.int32))
    post, _ = SS.upload_step(pre, jnp.int32(0), conn, gate)
    assert np.asarray(post.progress).tolist() == [2, 0, 2, 1]
    assert np.asarray(post.pending).tolist() == [0, -1, 0, 0]
    run_link = SS.LinkGate(grants, 3, 1)
    undone = FedSpaceScheduler._search_state(
        post, 0, connectivity=np.asarray(conn)[None, :], link=run_link)
    # the rollout's own upload_step for window 0 must reproduce `post`
    redo, _ = SS.upload_step(undone, jnp.int32(0), conn, gate)
    for a, b in zip(redo, post):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and without the inversion it would not (the double-count this pins)
    redo2, _ = SS.upload_step(post, jnp.int32(0), conn, gate)
    assert np.asarray(redo2.progress).tolist() != \
        np.asarray(post.progress).tolist()


def test_search_host_and_device_backends_agree_under_gate():
    """The `.predict`-only fallback path and the on-device marks path must
    select the same schedule for the same finite link gate."""

    class HostOnly:
        def __init__(self, rf):
            self.predict = rf.predict

    rng = np.random.default_rng(3)
    K, I0 = 10, 12
    C = rng.random((I0, K)) < 0.3
    grants = (rng.integers(0, 3, (I0, K)) * C).astype(np.int32)
    gate = SS.LinkGate(grants, 2, 1)
    rf = _forest(seed=3)
    st = SS.bootstrap_state(K, progress=True)
    dev = fedspace_search(np.random.default_rng(7), C, st, 0, rf, 1.0,
                          num_candidates=128, s_max=8, link=gate)
    host = fedspace_search(np.random.default_rng(7), C, st, 0,
                           HostOnly(rf), 1.0, num_candidates=128, s_max=8,
                           link=gate)
    np.testing.assert_array_equal(dev, host)


def test_linkconfig_constrained_predicate():
    from repro.fl.api import LinkConfig
    assert not LinkConfig().constrained
    assert not LinkConfig(uplink_topk=0.1).constrained
    assert not LinkConfig(model_mb=100).constrained        # no rate
    assert not LinkConfig(uplink_mbps=20).constrained      # no size
    assert LinkConfig(gs_capacity=2).constrained
    assert LinkConfig(model_mb=100, uplink_mbps=20).constrained
    assert LinkConfig(model_mb=100, downlink_mbps=50).constrained


def test_federation_builds_and_runs_link_budget():
    from repro.fl.api import (ConstellationConfig, DatasetConfig,
                              FLExperiment, Federation, LinkConfig)
    from repro.fl.engine import EngineConfig
    exp = FLExperiment(
        constellation=ConstellationConfig(num_satellites=10, days=0.25),
        dataset=DatasetConfig(num_train=200, num_val=80),
        train=EngineConfig(eval_every=12, max_windows=24, local_steps=2),
        link=LinkConfig(uplink_mbps=20, downlink_mbps=100, model_mb=300,
                        gs_capacity=1),
    )
    fed = Federation.from_experiment(exp)
    assert fed.link_budget is not None
    assert fed.link_budget.need_up == 2 and fed.link_budget.need_dn == 1
    eng = fed.engine()
    res = eng.run()
    assert res.windows_run == 24
    assert eng.transfer_progress is not None
    # the default (unconstrained) LinkConfig builds no budget
    exp2 = FLExperiment(
        constellation=ConstellationConfig(num_satellites=10, days=0.25),
        dataset=DatasetConfig(num_train=200, num_val=80),
        train=EngineConfig(eval_every=12, max_windows=24, local_steps=2))
    fed2 = Federation.from_experiment(exp2)
    assert fed2.link_budget is None
    assert fed2.engine().run().windows_run == 24


def test_with_scheduler_shares_link_budget():
    from repro.fl.api import (ConstellationConfig, DatasetConfig,
                              FLExperiment, Federation, LinkConfig)
    from repro.fl.engine import EngineConfig
    exp = FLExperiment(
        constellation=ConstellationConfig(num_satellites=8, days=0.25),
        dataset=DatasetConfig(num_train=160, num_val=60),
        train=EngineConfig(eval_every=12, max_windows=12, local_steps=2),
        link=LinkConfig(gs_capacity=1),
    )
    fed = Federation.from_experiment(exp)
    fed2 = fed.with_scheduler("async")
    assert fed2.link_budget is fed.link_budget
    assert fed2.run().windows_run == 12

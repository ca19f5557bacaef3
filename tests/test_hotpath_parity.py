"""Parity tests for the vectorized simulation hot paths.

Strict-parity contract of the vectorization PRs:
  * the structure-of-arrays numpy forest predict bit-matches the per-row
    node-walk reference;
  * the jit/JAX forest predict and featurize match to XLA reduction-order
    tolerance, and the end-to-end `fedspace_search` still selects the
    identical schedule;
  * the device-resident engine (chunked jitted window scans, device
    SatState, checkpoint ring, batched `on_aggregate`) reproduces the seed
    host-loop engine's trajectory bit-identically — and its own per-window
    host fallback exactly — including under the FedSpace scheduler's
    re-planning;
  * `aggregate_params_tree` agrees between the Pallas interpreter and the
    jnp tensordot oracle, and the default off-TPU dispatch is bit-identical
    to the oracle.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import connectivity as CN
from repro.core import staleness as SS
from repro.core.search import fedspace_search, infer_n_range
from repro.core.utility import (DENSE_MAX_DEPTH, RandomForestRegressor,
                                _forest_predict_gather, _Node, featurize,
                                featurize_jnp, forest_dense)
from repro.data.fmow import FmowSpec, SyntheticFmow
from repro.data.partition import iid_partition
from repro.data.pipeline import make_clients
from repro.fl.adapters import MlpFmowAdapter
from repro.fl.compression import roundtrip
from repro.fl.engine import EngineConfig, SimulationEngine
from repro.core.scheduler import make_scheduler
from repro.kernels import on_tpu
from repro.kernels.agg.ops import aggregate_params_tree


def _fit_forest(seed, *, n_trees=15, max_depth=5, n=300, F=13):
    rng = np.random.default_rng(seed)
    X = rng.random((n, F)).astype(np.float32)
    y = (2 * X[:, 0] + np.sin(6 * X[:, 3])
         + 0.1 * rng.normal(size=n)).astype(np.float32)
    rf = RandomForestRegressor(n_trees=n_trees, max_depth=max_depth,
                               seed=seed).fit(X, y)
    return rf, rng


def _fit_hist_forest(seed, *, s_max=8, n=400):
    """Forest over the search feature space (staleness histograms)."""
    rng = np.random.default_rng(seed)
    hists = rng.integers(0, 25, (n, s_max + 1)).astype(np.float32)
    X = featurize(hists, 1.0)
    s = np.arange(s_max + 1, dtype=np.float32)
    y = ((hists * (1.2 - 0.3 * s)).sum(1)
         / np.maximum(hists.sum(1), 1.0)
         + 0.05 * rng.normal(size=n)).astype(np.float32)
    return RandomForestRegressor(n_trees=20, max_depth=6, seed=seed
                                 ).fit(X, y)


class _NodeWalkHost:
    """Seed-style regressor facade: pure-Python node walk, host featurize
    (no predict_device => score_candidates takes the host path)."""

    def __init__(self, rf):
        self._rf = rf

    def predict(self, X):
        return self._rf.predict_reference(X)


# ---------------------------------------------------------------------------
# forest inference


@pytest.mark.parametrize("seed,depth,trees", [(0, 5, 15), (1, 6, 30),
                                              (2, 2, 5), (3, 8, 10)])
def test_soa_predict_bitmatches_node_walk(seed, depth, trees):
    rf, rng = _fit_forest(seed, n_trees=trees, max_depth=depth)
    X = rng.random((500, 13)).astype(np.float32)
    ref = rf.predict_reference(X)
    fast = rf.predict(X)
    assert np.array_equal(ref, fast)


def test_device_predict_matches_node_walk():
    rf, rng = _fit_forest(0)
    X = rng.random((500, 13)).astype(np.float32)
    ref = rf.predict_reference(X)
    dev = np.asarray(rf.predict_device(jnp.asarray(X)))
    np.testing.assert_allclose(dev, ref, rtol=1e-5, atol=1e-6)


def _gather_walk(rf, X):
    """The gather traversal over the forest's flattened arrays: the form
    `predict_device` served before the heap walk, and still serves past
    `DENSE_MAX_DEPTH`."""
    fa = rf.arrays()
    T, M = fa.feature.shape
    flat = [jnp.asarray(a.reshape(-1))
            for a in (fa.feature, fa.thresh, fa.left, fa.right, fa.value)]
    offsets = jnp.asarray((np.arange(T, dtype=np.int32) * M)[:, None])
    return np.asarray(_forest_predict_gather(*flat, offsets, jnp.asarray(X),
                                             depth=fa.depth))


def _heap_forest(seed, *, depth=6, n_trees=30, F=13):
    """A complete heap-shaped forest (inner nodes 0..2^D - 2, children
    2n + 1 and 2n + 2), the layout of a forest drawn rather than fitted."""
    rng = np.random.default_rng(seed)
    n_inner = 2 ** depth - 1
    trees = [[_Node(feature=int(rng.integers(F)) if n < n_inner else -1,
                    thresh=float(rng.random()),
                    left=2 * n + 1 if n < n_inner else -1,
                    right=2 * n + 2 if n < n_inner else -1,
                    value=float(rng.normal()))
              for n in range(2 * n_inner + 1)] for _ in range(n_trees)]
    rf = RandomForestRegressor(n_trees=n_trees, max_depth=depth)
    rf.trees = trees
    return rf, rng


@functools.lru_cache(maxsize=None)
def _forest_case(kind, depth):
    if kind == "heap":
        return _heap_forest(depth, depth=depth)[0]
    # enough samples that the deepest levels are reached, unevenly
    return _fit_forest(depth, n_trees=10, max_depth=depth,
                       n=250 * depth)[0]


def _rows_on_thresholds(rf, rng, n):
    """(n, 13) rows in [0, 1), the first half with one feature set exactly
    to a split threshold of the forest."""
    fa = rf.arrays()
    X = rng.random((n, 13)).astype(np.float32)
    trees, nodes = np.nonzero(fa.feature >= 0)
    pick = rng.integers(len(trees), size=n // 2)
    X[np.arange(n // 2), fa.feature[trees[pick], nodes[pick]]] = \
        fa.thresh[trees[pick], nodes[pick]]
    return X


FOREST_CASES = [("fit", 2), ("fit", 5), ("fit", 6), ("fit", 8), ("heap", 6)]


@pytest.mark.parametrize("kind,depth", FOREST_CASES)
def test_heap_walk_bitmatches_gather_walk(kind, depth):
    """The gather-free heap walk returns the gather traversal's bits, on
    unbalanced fitted forests, a complete heap, and rows that sit exactly
    on a split threshold (`x <= thresh` goes left in both)."""
    rf, rng = _forest_case(kind, depth), np.random.default_rng(depth)
    assert rf.dense and forest_dense(rf) == 1
    X = _rows_on_thresholds(rf, rng, 5_000)
    dev = np.asarray(rf.predict_device(jnp.asarray(X)))
    assert np.array_equal(dev, _gather_walk(rf, X))
    np.testing.assert_allclose(dev[:300], rf.predict_reference(X[:300]),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kind,depth", FOREST_CASES)
def test_heap_walk_row_is_batch_invariant(kind, depth):
    """A row's prediction does not depend on its batch: alone, in a
    1,024-row delta bucket and in a 5,000 x 12 full-rescan batch. The
    replan service's delta == full contract rests on this."""
    rf, rng = _forest_case(kind, depth), np.random.default_rng(depth)
    probe = _rows_on_thresholds(rf, rng, 16)
    alone = np.concatenate([np.asarray(rf.predict_device(jnp.asarray(x)))
                            for x in probe[:, None]])
    for n in (1_024, 5_000 * 12):
        X = rng.random((n, 13)).astype(np.float32)
        at = rng.choice(n, len(probe), replace=False)
        X[at] = probe
        batch = np.asarray(rf.predict_device(jnp.asarray(X)))
        assert np.array_equal(batch[at], alone), n


def test_deep_forest_keeps_gather_walk():
    """Past `DENSE_MAX_DEPTH` the select walk's O(2^depth) cost is not
    paid: a depth-9 forest serves the gather traversal, with the same
    values alone as in a batch."""
    rf, rng = _fit_forest(9, n_trees=3, max_depth=DENSE_MAX_DEPTH + 1,
                          n=2_500)
    assert not rf.dense and forest_dense(rf) == 0
    X = _rows_on_thresholds(rf, rng, 1_024)
    dev = np.asarray(rf.predict_device(jnp.asarray(X)))
    assert rf._device_arrays[0].func is _forest_predict_gather
    assert np.array_equal(dev, _gather_walk(rf, X))
    np.testing.assert_allclose(dev[:300], rf.predict_reference(X[:300]),
                               rtol=1e-5, atol=1e-6)
    alone = np.concatenate([np.asarray(rf.predict_device(jnp.asarray(x)))
                            for x in X[:8, None]])
    assert np.array_equal(alone, dev[:8])


def test_forest_dense_counts_only_the_heap_walk():
    assert forest_dense(_NodeWalkHost(None)) == 0
    assert forest_dense(_heap_forest(0, depth=DENSE_MAX_DEPTH)[0]) == 1


def test_featurize_jnp_matches_host():
    rng = np.random.default_rng(0)
    hist = rng.integers(0, 30, (128, 9)).astype(np.float32)
    host = featurize(hist, 0.7)
    dev = np.asarray(featurize_jnp(jnp.asarray(hist), 0.7))
    assert dev.shape == host.shape
    np.testing.assert_allclose(dev, host, rtol=1e-6, atol=1e-5)
    # integer-exact features are bit-exact
    assert np.array_equal(dev[:, :9], host[:, :9])       # raw histogram
    assert np.array_equal(dev[:, 9], host[:, 9])         # total count


def _search_world(name, I0=24):
    """A random K=24 world, or the first I0 windows of a constellation
    preset's real geometry (a quarter day of propagated visibility)."""
    if name == "random24":
        return np.random.default_rng(5).random((I0, 24)) < 0.2, 512
    C = CN.connectivity_sets(CN.constellation_preset(name), days=0.25)
    return C[:I0], 256


@pytest.mark.parametrize("world", ["random24", "starlink40", "starlink120",
                                   "flock191", "starlink400",
                                   "starlink1000"])
def test_fedspace_search_selects_identical_schedule(world):
    """The acceptance gate: same rng seed => same selected schedule on the
    device path as on the seed node-walk/host path, on a random world and
    on every constellation preset's geometry (K = 40 ... 1000)."""
    rf = _fit_hist_forest(0)
    C, R = _search_world(world)
    state = SS.bootstrap_state(C.shape[1])
    ref = fedspace_search(np.random.default_rng(7), C, state, 0,
                          _NodeWalkHost(rf), 1.0, num_candidates=R)
    opt = fedspace_search(np.random.default_rng(7), C, state, 0, rf, 1.0,
                          num_candidates=R)
    assert np.array_equal(ref, opt)


def test_infer_n_range_matches_loop_reference():
    rf = _fit_hist_forest(1)

    def reference(regressor, uploads_per_window, I0, status, *, s_max=8,
                  K=None, halfwidth=4):
        best_n, best_u = 1, -np.inf
        n_cap = max(1, I0 // 2)
        total = uploads_per_window * I0
        for n in range(1, n_cap + 1):
            per = total / n
            if K:
                per = min(per, K)
            hist = np.zeros(s_max + 1, np.float32)
            hist[0] = per * 0.7
            hist[1] = per * 0.3
            u = n * float(regressor.predict(featurize(hist[None],
                                                      status))[0])
            if u > best_u:
                best_n, best_u = n, u
        return max(1, best_n - halfwidth), min(n_cap, best_n + halfwidth)

    rng = np.random.default_rng(2)
    upws = [0.5, 2.0, 5.0, 11.0] + list(rng.uniform(0.1, 20.0, 40))
    for upw in upws:
        for K in (None, 16):
            assert infer_n_range(rf, upw, 24, 1.0, K=K) \
                == reference(rf, upw, 24, 1.0, K=K), (upw, K)


# ---------------------------------------------------------------------------
# batched aggregation round


class _SeedHostEngine:
    """The pre-refactor engine, transcribed as the parity oracle: numpy
    protocol arrays rebuilt into a SatState every window, a host-pytree
    CheckpointStore, one jitted client update + checkpoint fetch per
    buffered satellite, sequential compression roundtrip, and a
    stack-tensordot-add aggregation."""

    def __init__(self, C, adapter, scheduler, config):
        self.config = dataclasses.replace(
            config, seed=0 if config.seed is None else config.seed,
            uplink_topk=(0.0 if config.uplink_topk is None
                         else config.uplink_topk))
        self.C = np.asarray(C, bool)
        self.adapter = adapter
        self.scheduler = scheduler
        self.num_windows = self.C.shape[0]
        if self.config.max_windows:
            self.num_windows = min(self.num_windows,
                                   self.config.max_windows)
        self.K = self.C.shape[1]

    def run(self):
        from repro.ckpt.checkpoint import CheckpointStore
        from repro.core.staleness import staleness_compensation
        from repro.fl.client import make_client_update
        from repro.fl.engine import SimResult
        cfg = self.config
        self.scheduler.reset()
        params = self.adapter.init(jax.random.PRNGKey(cfg.seed))
        mask = self.adapter.trainable_mask(params) \
            if hasattr(self.adapter, "trainable_mask") else None
        client_update = make_client_update(
            self.adapter, local_steps=cfg.local_steps, lr=cfg.client_lr,
            trainable_mask=mask)
        store = CheckpointStore(keep_in_memory=cfg.s_max + 26)
        store.put(0, params)
        ig = 0
        version = np.zeros(self.K, np.int64)
        pending = np.zeros(self.K, np.int64)
        buffered = np.full(self.K, -1, np.int64)
        res = SimResult(scheme=self.scheduler.name,
                        target_acc=cfg.target_acc)
        res.staleness_hist = np.zeros(cfg.s_max + 1, np.int64)
        status = float(self.adapter.val_loss(params))
        for i in range(self.num_windows):
            conn = self.C[i]
            res.total_connections += int(conn.sum())
            has_pending = conn & (pending >= 0)
            res.idle_connections += int(
                (conn & ~has_pending & (version == ig)).sum())
            buffered[has_pending] = pending[has_pending]
            pending[has_pending] = -1
            n_buf = int((buffered >= 0).sum())
            state = SS.SatState(jnp.asarray(version, jnp.int32),
                                jnp.asarray(pending, jnp.int32),
                                jnp.asarray(buffered, jnp.int32))
            a = self.scheduler.decide(
                i, n_in_buffer=n_buf, K=self.K, state=state, ig=ig,
                connectivity=self.C, status=status)
            if a and n_buf > 0:
                ks = np.flatnonzero(buffered >= 0)
                stal = ig - buffered[ks]
                updates = []
                for k in ks:
                    base = store.get(int(buffered[k]))
                    u = client_update(base, int(k), round_rng=i,
                                      batch_size=cfg.batch_size)
                    if cfg.uplink_topk > 0.0:
                        u, _ = roundtrip(u, cfg.uplink_topk)
                    updates.append(u)
                stack = jax.tree.map(lambda *xs: jnp.stack(xs), *updates)
                c = staleness_compensation(jnp.asarray(stal), cfg.alpha)
                wv = c / jnp.maximum(jnp.sum(c), 1e-12) * cfg.server_lr
                delta = jax.tree.map(
                    lambda u_: jnp.tensordot(wv.astype(jnp.float32),
                                             u_.astype(jnp.float32),
                                             axes=1), stack)
                params = jax.tree.map(
                    lambda p, d: (p.astype(jnp.float32) + d).astype(p.dtype),
                    params, delta)
                ig += 1
                store.put(ig, params)
                refs = np.concatenate([pending, buffered])
                refs = refs[refs >= 0]
                store.prune(int(refs.min()) if refs.size else ig)
                res.num_global_updates += 1
                res.num_aggregated_gradients += len(ks)
                np.add.at(res.staleness_hist, np.clip(stal, 0, cfg.s_max), 1)
                buffered[:] = -1
            behind = conn & (version < ig)
            version[behind] = ig
            pending[behind] = ig
            res.windows_run = i + 1
            stop = False
            if (i + 1) % cfg.eval_every == 0 or i == self.num_windows - 1:
                acc = self.adapter.accuracy(params)
                status = float(self.adapter.val_loss(params))
                res.accuracy.append(acc)
                res.val_loss.append(status)
                res.eval_windows.append(i)
                if (cfg.target_acc is not None and acc >= cfg.target_acc
                        and res.time_to_target_days is None):
                    res.time_to_target_days = res.days(i)
                    if cfg.stop_at_target:
                        stop = True
            if stop:
                break
        self.params = params
        return res


@pytest.fixture(scope="module")
def tiny_world():
    spec = CN.ConstellationSpec(num_satellites=16)
    C = CN.connectivity_sets(spec, days=1.0)
    data = SyntheticFmow(FmowSpec(num_train=800, num_val=200))
    adapter = MlpFmowAdapter(data, make_clients(iid_partition(800, 16, 0)))
    return C, adapter


def test_batched_aggregate_bit_identical_trajectory(tiny_world):
    """The protocol trajectory (every integer counter) is bit-identical
    to the seed's host engine; accuracy, loss and final parameters agree
    within float tolerance, not bit for bit."""
    C, adapter = tiny_world
    cfg = dict(eval_every=16, max_windows=64)
    ref_eng = _SeedHostEngine(C, adapter, make_scheduler("fedbuff", M=4),
                              EngineConfig(**cfg))
    ref = ref_eng.run()
    new_eng = SimulationEngine(C, adapter, make_scheduler("fedbuff", M=4),
                               EngineConfig(**cfg))
    new = new_eng.run()
    # Integer protocol counters must match exactly. The float trajectory
    # is compared under a tolerance: the vmapped batched update and the
    # seed's per-satellite jitted calls are different XLA programs, whose
    # float reductions may round differently in the last ulp (they do on
    # jax 0.9 CPU, and a TPU is not bit-identical to the CPU either).
    # One eval sample of 200 is 0.005 accuracy; ulp-level parameter noise
    # may flip at most that one prediction.
    floats = ("final_acc", "best_acc")
    strip = lambda d: {k: v for k, v in d.items() if k not in floats}
    assert strip(new.summary()) == strip(ref.summary())
    np.testing.assert_allclose(new.accuracy, ref.accuracy, atol=0.005)
    np.testing.assert_allclose(new.val_loss, ref.val_loss, rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=1e-5, atol=1e-6),
        new_eng.params, ref_eng.params)


def test_batched_aggregate_with_fused_compression(tiny_world):
    """Compressed-uplink mode: the fused in-jit roundtrip matches the
    sequential eager one to ~1 ulp (XLA strength-reduces the /127 dequant
    constant inside the fused program), so the trajectory agrees to float
    noise; all integer protocol counters are exact."""
    C, adapter = tiny_world
    cfg = dict(eval_every=16, max_windows=64, uplink_topk=0.25)
    ref_eng = _SeedHostEngine(C, adapter, make_scheduler("fedbuff", M=4),
                              EngineConfig(**cfg))
    ref = ref_eng.run()
    new_eng = SimulationEngine(C, adapter, make_scheduler("fedbuff", M=4),
                               EngineConfig(**cfg))
    new = new_eng.run()
    assert new.num_global_updates == ref.num_global_updates
    assert new.num_aggregated_gradients == ref.num_aggregated_gradients
    assert new.staleness_hist.tolist() == ref.staleness_hist.tolist()
    assert new.windows_run == ref.windows_run
    np.testing.assert_allclose(new.val_loss, ref.val_loss, atol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a),
                                                np.asarray(b), atol=1e-7),
        new_eng.params, ref_eng.params)


def test_batched_aggregate_handles_empty_shards():
    """Satellites with empty shards contribute exact-zero updates, batched
    alongside trained ones."""
    K = 8
    rng = np.random.default_rng(0)
    C = rng.random((32, K)) < 0.4
    data = SyntheticFmow(FmowSpec(num_train=200, num_val=50))
    parts = iid_partition(200, K - 2, 0) + [np.array([], np.int64)] * 2
    adapter = MlpFmowAdapter(data, make_clients(parts))
    cfg = dict(eval_every=16, max_windows=32)
    ref = _SeedHostEngine(C, adapter, make_scheduler("async"),
                          EngineConfig(**cfg)).run()
    new = SimulationEngine(C, adapter, make_scheduler("async"),
                           EngineConfig(**cfg)).run()
    assert new.summary() == ref.summary()
    assert new.accuracy == ref.accuracy


@pytest.mark.parametrize("scheme,kw", [("fedbuff", {"M": 6}),
                                       ("async", {})])
def test_batched_aggregate_mixed_event_matches_seed(scheme, kw):
    """Events whose buffer mixes base versions, a satellite whose shard is
    smaller than the batch (an off-modal batch width) and one with an
    empty shard train as one batch of rows and match the seed's
    per-satellite engine: integer counters exactly, floats within the
    tolerances of `test_batched_aggregate_bit_identical_trajectory`."""
    K = 12
    C = np.random.default_rng(1).random((48, K)) < 0.35
    data = SyntheticFmow(FmowSpec(num_train=400, num_val=200))
    parts = iid_partition(360, K - 3, 0) + [
        np.arange(360, 370), np.arange(370, 375), np.array([], np.int64)]
    adapter = MlpFmowAdapter(data, make_clients(parts))
    cfg = EngineConfig(eval_every=16, max_windows=48)
    ref_eng = _SeedHostEngine(C, adapter, make_scheduler(scheme, **kw), cfg)
    ref = ref_eng.run()
    new_eng = SimulationEngine(C, adapter, make_scheduler(scheme, **kw),
                               cfg)
    aggregate, mixed = new_eng.on_aggregate, []

    def spy(i):
        b = np.asarray(new_eng.state.buffered)
        mixed.append(len(set(b[b >= 0])) > 1 and b[K - 1] >= 0
                     and max(b[K - 3], b[K - 2]) >= 0)
        aggregate(i)

    new_eng.on_aggregate = spy
    new = new_eng.run()
    assert any(mixed)
    floats = ("final_acc", "best_acc")
    strip = lambda d: {k: v for k, v in d.items() if k not in floats}
    assert strip(new.summary()) == strip(ref.summary())
    np.testing.assert_allclose(new.accuracy, ref.accuracy, atol=0.005)
    np.testing.assert_allclose(new.val_loss, ref.val_loss, rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=1e-5, atol=1e-6),
        new_eng.params, ref_eng.params)


# ---------------------------------------------------------------------------
# chunked fast loop vs per-window host loop


@pytest.mark.parametrize("scheme,kw", [("async", {}), ("fedbuff", {"M": 4}),
                                       ("periodic", {"period": 3})])
def test_fast_loop_matches_host_loop(tiny_world, scheme, kw):
    """The engine's two execution strategies — chunked jitted scans vs
    per-window protocol-step calls — must produce identical results and
    bit-identical parameters."""
    C, adapter = tiny_world
    cfg = dict(eval_every=16, max_windows=64)
    fast_eng = SimulationEngine(C, adapter, make_scheduler(scheme, **kw),
                                EngineConfig(**cfg))
    fast = fast_eng.run()
    assert fast_eng._fast_ok            # took the chunked path
    host_eng = SimulationEngine(C, adapter, make_scheduler(scheme, **kw),
                                EngineConfig(fast_loop=False, **cfg))
    host = host_eng.run()
    assert not host_eng._fast_ok
    assert fast.summary() == host.summary()
    assert fast.accuracy == host.accuracy
    np.testing.assert_array_equal(fast_eng.version, host_eng.version)
    np.testing.assert_array_equal(fast_eng.pending, host_eng.pending)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        fast_eng.params, host_eng.params)


def test_fedspace_fast_loop_matches_host_loop(tiny_world):
    """FedSpace re-plans every I0 windows from the live protocol state;
    the chunked loop must hand `fedspace_search` the identical post-upload
    state (and consume the scheduler rng identically), so the schedules —
    and hence the whole trajectory — match the per-window loop exactly."""
    from repro.core.scheduler import FedSpaceScheduler
    C, adapter = tiny_world
    rf = _fit_hist_forest(3)
    cfg = dict(eval_every=8, max_windows=48)
    fast_eng = SimulationEngine(
        C, adapter,
        FedSpaceScheduler(rf, I0=8, num_candidates=64, seed=11),
        EngineConfig(**cfg))
    fast = fast_eng.run()
    assert fast_eng._fast_ok
    host_eng = SimulationEngine(
        C, adapter,
        FedSpaceScheduler(rf, I0=8, num_candidates=64, seed=11),
        EngineConfig(fast_loop=False, **cfg))
    host = host_eng.run()
    assert fast.summary() == host.summary()
    assert fast.accuracy == host.accuracy
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        fast_eng.params, host_eng.params)


def test_fast_loop_respects_early_stop_and_target(tiny_world):
    """Chunk boundaries align with eval windows, so target-accuracy stops
    fire at the same window on both strategies."""
    C, adapter = tiny_world
    cfg = dict(eval_every=8, max_windows=96, target_acc=0.1)
    fast = SimulationEngine(C, adapter, make_scheduler("async"),
                            EngineConfig(**cfg)).run()
    host = SimulationEngine(C, adapter, make_scheduler("async"),
                            EngineConfig(fast_loop=False, **cfg)).run()
    assert fast.windows_run == host.windows_run
    assert fast.time_to_target_days == host.time_to_target_days


# ---------------------------------------------------------------------------
# vectorized utility-sample generation (eq. 12)


def test_vectorized_utility_samples_match_loop(tiny_world):
    """The batched sample generator (grouped vmapped client training +
    vmapped loss over perturbed checkpoints) shares the loop path's rng
    stream: features — integer staleness histograms + T — are
    bit-identical, targets agree to reduction-order tolerance."""
    from repro.core.utility import generate_utility_samples
    from repro.fl.client import (make_batched_client_update,
                                 make_client_update)
    from repro.fl.fedspace_setup import pretrain_trajectory
    _, adapter = tiny_world
    traj = pretrain_trajectory(adapter, rounds=6, clients_per_round=6,
                               local_steps=2, client_lr=0.3, seed=0)
    cu = make_client_update(adapter, local_steps=2, lr=0.3)

    def upd_fn(base, ci, r):
        return cu(base, ci, round_rng=int(r))

    common = dict(num_clients=16, n_samples=24, s_max=8,
                  clients_per_sample=8, seed=5)
    X_loop, y_loop = generate_utility_samples(
        jax.random.PRNGKey(0), traj, upd_fn,
        lambda p: adapter.val_loss(p), **common)
    val_batch = adapter.eval_batch()
    X_vec, y_vec = generate_utility_samples(
        jax.random.PRNGKey(0), traj, upd_fn,
        lambda p: adapter.val_loss(p),
        batch_fn=lambda ci, r: adapter.client_batch(ci, int(r), 32, 2),
        batched_update_fn=make_batched_client_update(
            adapter, local_steps=2, lr=0.3),
        batched_loss_fn=jax.jit(jax.vmap(
            lambda p: adapter.loss(p, val_batch))),
        **common)
    assert np.array_equal(X_loop, X_vec)
    np.testing.assert_allclose(y_vec, y_loop, atol=1e-5)


# ---------------------------------------------------------------------------
# aggregation kernel routing


def _rand_tree(rng, M):
    params = {"w": jnp.asarray(rng.normal(size=(17, 23)).astype(np.float32)),
              "b": jnp.asarray(rng.normal(size=(11,)).astype(np.float32))}
    upds = jax.tree.map(
        lambda p: jnp.asarray(
            rng.normal(size=(M,) + p.shape).astype(np.float32)), params)
    w = jnp.asarray(rng.random(M).astype(np.float32))
    return params, upds, w


def test_aggregate_params_tree_interpret_matches_tensordot():
    rng = np.random.default_rng(3)
    params, upds, w = _rand_tree(rng, 6)
    ref = jax.tree.map(
        lambda p, u: p + jnp.tensordot(w, u.astype(jnp.float32), axes=1),
        params, upds)
    interp = aggregate_params_tree(params, upds, w, interpret=True)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5), interp, ref)


@pytest.mark.skipif(on_tpu(), reason="off-TPU dispatch contract")
def test_aggregate_params_tree_default_bitmatches_tensordot_off_tpu():
    """The engine's default dispatch must stay bit-identical to the eager
    tensordot reduction the seed engine used."""
    rng = np.random.default_rng(4)
    params, upds, w = _rand_tree(rng, 9)
    ref = jax.tree.map(
        lambda p, u: p + jnp.tensordot(w, u.astype(jnp.float32), axes=1),
        params, upds)
    out = aggregate_params_tree(params, upds, w)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), out, ref)

"""End-to-end FL system tests: data partitioning, simulation semantics,
optimizers, checkpointing, aggregation (eq. 4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import connectivity as CN
from repro.core.aggregation import aggregation_weights, apply_aggregation
from repro.core.scheduler import make_scheduler
from repro.data.fmow import FmowSpec, SyntheticFmow
from repro.data.partition import (iid_partition, noniid_partition,
                                  partition_stats)
from repro.data.pipeline import make_clients
from repro.fl.adapters import MlpFmowAdapter
from repro.fl.simulation import run_simulation
from repro.optim import (adamw_init, adamw_update, apply_updates,
                         clip_by_global_norm, sgd_init, sgd_update)
from repro.ckpt.checkpoint import (CheckpointStore, DeviceCheckpointStore,
                                   load_pytree, save_pytree)


@pytest.fixture(scope="module")
def small_world():
    spec = CN.ConstellationSpec(num_satellites=24)
    C = CN.connectivity_sets(spec, days=1.0)
    data = SyntheticFmow(FmowSpec(num_train=2400, num_val=600))
    parts = iid_partition(2400, 24, 0)
    adapter = MlpFmowAdapter(data, make_clients(parts))
    return spec, C, data, adapter


# ---------------------------------------------------------------------------
# data


def test_iid_partition_exact_cover():
    parts = iid_partition(1000, 7, 0)
    allidx = np.sort(np.concatenate(parts))
    assert (allidx == np.arange(1000)).all()


def test_noniid_partition_cover_and_skew(small_world):
    spec, _, data, _ = small_world
    parts = noniid_partition(data.train_zones, 24, spec, days=1.0)
    allidx = np.sort(np.concatenate(parts))
    assert (allidx == np.arange(data.spec.num_train)).all()
    st_iid = partition_stats(iid_partition(data.spec.num_train, 24, 0),
                             data.train_labels)
    st_non = partition_stats(parts, data.train_labels)
    assert st_non["tv_mean"] > st_iid["tv_mean"] + 0.05, \
        "non-IID partition is not skewed vs IID"


# ---------------------------------------------------------------------------
# optimizers / checkpoint


def test_sgd_matches_manual(key):
    p = {"w": jnp.ones((4,))}
    g = {"w": jnp.full((4,), 0.5)}
    upd, st = sgd_update(g, sgd_init(p), p, lr=0.1)
    p2 = apply_updates(p, upd)
    np.testing.assert_allclose(np.asarray(p2["w"]), 1.0 - 0.05, rtol=1e-6)


def test_adamw_converges_quadratic(key):
    p = {"w": jnp.asarray([5.0, -3.0])}
    opt = adamw_init(p)
    for _ in range(300):
        g = {"w": 2 * p["w"]}
        upd, opt = adamw_update(g, opt, p, lr=0.05, weight_decay=0.0)
        p = apply_updates(p, upd)
    assert float(jnp.abs(p["w"]).max()) < 0.1


def test_clip_by_global_norm():
    g = {"a": jnp.full((3,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) > 1.0
    n2 = jnp.sqrt(jnp.sum(jnp.square(clipped["a"])))
    np.testing.assert_allclose(float(n2), 1.0, rtol=1e-5)


def test_checkpoint_roundtrip(tmp_path, key):
    tree = {"a": jax.random.normal(key, (4, 5)),
            "b": [jnp.arange(3), {"c": jnp.float32(2.5)}]}
    path = str(tmp_path / "ck.npz")
    save_pytree(path, tree)
    back = load_pytree(path, tree)
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        np.asarray(x), np.asarray(y)), tree, back)


def test_checkpoint_store_prune():
    st = CheckpointStore(keep_in_memory=3)
    for v in range(8):
        st.put(v, {"w": jnp.full((2,), float(v))})
    st.prune(min_referenced=6)
    assert 6 in st._mem and 7 in st._mem
    with pytest.raises(KeyError):
        st.get(0)


@pytest.mark.parametrize("cls,kw", [
    (CheckpointStore, {"keep_in_memory": 2}),
    (DeviceCheckpointStore, {"ring": 2}),
])
def test_checkpoint_store_prune_unlinks_disk_spill(tmp_path, cls, kw):
    """Regression: prune used to leave spilled .npz files (and `_disk`
    entries) behind forever, growing disk unboundedly on long runs."""
    st = cls(directory=str(tmp_path), spill_every=1, **kw)
    for v in range(10):
        st.put(v, {"w": jnp.full((2,), float(v))})
    assert len(list(tmp_path.glob("*.npz"))) == 10
    st.prune(min_referenced=9)       # cutoff = newest - keep + 1 = 8
    assert sorted(st._disk) == [8, 9]
    assert sorted(p.name for p in tmp_path.glob("*.npz")) == \
        ["w_000008.npz", "w_000009.npz"]


def test_device_checkpoint_store_contract():
    """Ring hits return device arrays with the put values; ring-evicted
    versions spill to host and stay readable until pruned; `get_many`
    gathers a stacked pytree; misses raise the same KeyError contract."""
    st = DeviceCheckpointStore(ring=4)
    for v in range(9):
        st.put(v, {"w": jnp.full((3,), float(v)), "b": jnp.arange(2) + v})
    assert st.versions() == list(range(9))
    for v in range(9):                        # 5..8 in ring, 0..4 spilled
        got = st.get(v)
        assert isinstance(got["w"], jax.Array)
        assert float(got["w"][0]) == v and int(got["b"][1]) == v + 1
    stacked = st.get_many([6, 8, 5])
    assert np.asarray(stacked["w"])[:, 0].tolist() == [6.0, 8.0, 5.0]
    st.prune(min_referenced=7)       # cutoff = min(7, newest - ring + 1)
    assert st.versions() == [5, 6, 7, 8]
    with pytest.raises(KeyError):
        st.get(4)


def test_device_checkpoint_store_spilled_bases_compile_nothing():
    """Once the ring exists, spilling versions off it and gathering
    per-row bases of which some have spilled (stacked on the host and
    uploaded once) compile no program, however many leaves the model
    has."""
    st = DeviceCheckpointStore(ring=2)
    trees = [{"a": jnp.full(3, v), "b": jnp.full((2, 2), v),
              "c": jnp.full(5, v)} for v in range(4)]
    st.put(0, trees[0])
    compiles = []

    def listen(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for v in range(1, 4):                 # 2, 3 in ring, 0, 1 spilled
            st.put(v, trees[v])
        stacked = st.get_many([0, 1, 2, 3, 3])
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []
    assert np.asarray(stacked["b"])[:, 1, 1].tolist() == [0, 1, 2, 3, 3]


def test_device_checkpoint_store_overwrites_in_place():
    """Re-putting a version replaces the slot content (no stale host
    copy resurfacing)."""
    st = DeviceCheckpointStore(ring=3)
    st.put(0, {"w": jnp.zeros(2)})
    st.put(0, {"w": jnp.ones(2)})
    assert float(st.get(0)["w"][0]) == 1.0
    assert st.versions() == [0]


# ---------------------------------------------------------------------------
# aggregation (eq. 4)


def test_aggregation_weights_normalized():
    w = aggregation_weights(jnp.asarray([0, 1, 4, 8]), alpha=0.5)
    np.testing.assert_allclose(float(jnp.sum(w)), 1.0, rtol=1e-6)
    assert float(w[0]) > float(w[3])     # fresher => heavier


def test_apply_aggregation_matches_manual(key):
    params = {"w": jnp.zeros((5,))}
    upds = {"w": jnp.stack([jnp.ones(5), 2 * jnp.ones(5)])}
    stal = jnp.asarray([0, 1])
    out = apply_aggregation(params, upds, stal, alpha=1.0)
    c = np.array([1.0, 0.5])
    expect = (c / c.sum()) @ np.stack([np.ones(5), 2 * np.ones(5)])
    np.testing.assert_allclose(np.asarray(out["w"]), expect, rtol=1e-5)


def test_apply_aggregation_kernel_path_matches(key):
    params = {"w": jax.random.normal(key, (3, 7)),
              "b": jax.random.normal(jax.random.fold_in(key, 1), (11,))}
    upds = jax.tree.map(
        lambda p: jax.random.normal(jax.random.fold_in(key, 2),
                                    (4,) + p.shape), params)
    stal = jnp.asarray([0, 1, 2, 3])
    a = apply_aggregation(params, upds, stal)               # jnp off-TPU
    b = apply_aggregation(params, upds, stal, interpret=True)   # kernel

    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        np.asarray(x), np.asarray(y), atol=1e-5), a, b)


# ---------------------------------------------------------------------------
# simulation semantics


def test_sync_zero_staleness(small_world):
    _, C, _, adapter = small_world
    res = run_simulation(C, adapter, make_scheduler("sync"), eval_every=24,
                         max_windows=96)
    assert res.staleness_hist[1:].sum() == 0
    assert res.num_global_updates >= 1


def test_async_no_idle(small_world):
    _, C, _, adapter = small_world
    res = run_simulation(C, adapter, make_scheduler("async"), eval_every=24,
                         max_windows=96)
    assert res.idle_connections == 0
    assert res.staleness_hist.sum() == res.num_aggregated_gradients


def test_fedbuff_buffer_threshold(small_world):
    _, C, _, adapter = small_world
    res = run_simulation(C, adapter, make_scheduler("fedbuff", M=8),
                         eval_every=24, max_windows=96)
    # every aggregation consumed >= M gradients
    assert res.num_aggregated_gradients >= 8 * res.num_global_updates


def test_learning_happens(small_world):
    _, C, _, adapter = small_world
    res = run_simulation(C, adapter, make_scheduler("fedbuff", M=8),
                         eval_every=16, max_windows=96)
    assert res.accuracy[-1] > 2.0 / 62.0, "no learning signal"

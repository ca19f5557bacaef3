"""Uplink-compression extension tests (DESIGN.md §5b / paper §5: gradient
compression is orthogonal to scheduling and combinable): property tests of
the top-k / dense-int8 round-trips, the analytic bytes-ratio accounting,
and the compression-aware link-budget coupling
(`LinkConfig` -> `uplink_bytes_ratio` -> `LinkBudget.need_up`)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.fl.compression import (compress_int8, compress_topk_int8,
                                  decompress, decompress_int8, roundtrip,
                                  roundtrip_int8, uplink_bytes_ratio)


def test_roundtrip_keeps_topk_exactly_shaped(key):
    tree = {"a": jax.random.normal(key, (64, 32)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (100,))}
    out, ratio = roundtrip(tree, k_frac=0.25)
    assert ratio > 3.0
    jax.tree.map(lambda a, b: None if a.shape == b.shape else
                 pytest.fail("shape"), tree, out)


def test_topk_preserves_largest_entries(key):
    x = {"w": jnp.asarray([10.0, -8.0, 0.1, 0.01, 6.0, -0.2, 0.0, 0.3])}
    out, _ = roundtrip(x, k_frac=0.375)   # keep 3 of 8
    w = np.asarray(out["w"])
    # the three largest-magnitude entries survive (int8-quantized)
    np.testing.assert_allclose(w[[0, 1, 4]], [10.0, -8.0, 6.0], rtol=0.02)
    assert (w[[2, 3, 5, 6, 7]] == 0).all()


@settings(max_examples=25, deadline=None)
@given(st.integers(8, 200), st.floats(0.05, 1.0))
def test_quantization_error_bounded(n, k_frac):
    rng = np.random.default_rng(n)
    x = {"w": jnp.asarray(rng.normal(0, 1, n).astype(np.float32))}
    out, ratio = roundtrip(x, k_frac=float(k_frac))
    w0, w1 = np.asarray(x["w"]), np.asarray(out["w"])
    kept = w1 != 0
    # int8 symmetric quantization: relative error on kept entries < 1%
    # of the max magnitude
    assert np.abs(w1[kept] - w0[kept]).max() <= \
        np.abs(w0).max() / 127.0 + 1e-6
    assert ratio >= 0.79   # int8+idx vs f32 never worse than 0.8x


# ---------------------------------------------------------------------------
# property tests: round-trip guarantees and bytes accounting


@settings(max_examples=25, deadline=None)
@given(st.integers(4, 150), st.floats(0.05, 1.0), st.integers(0, 10_000))
def test_topk_keeps_exact_index_set(n, k_frac, seed):
    """With distinct magnitudes the kept index set is exactly the top-k by
    |value|, each kept value is quantized to its nearest int8 level
    (round-to-nearest of the float32 quotient v / scale), and `decompress`
    returns exactly level * scale in float32."""
    rng = np.random.default_rng(seed)
    mags = rng.permutation(np.arange(1, n + 1)).astype(np.float32)
    vals = mags * rng.choice([-1.0, 1.0], n).astype(np.float32)
    comp, b_c, b_r = compress_topk_int8({"w": jnp.asarray(vals)},
                                        float(k_frac))
    leaf = comp["w"]
    k = max(1, int(n * k_frac))
    expect = set(np.argsort(np.abs(vals))[-k:].tolist())
    assert set(np.asarray(leaf.indices).tolist()) == expect
    assert leaf.values.shape == (k,)
    assert b_c == k * 5 and b_r == n * 4
    idx, q = np.asarray(leaf.indices), np.asarray(leaf.values)
    scale = np.float32(leaf.scale)
    np.testing.assert_array_equal(q, np.round(vals[idx] / scale))
    deq = np.asarray(decompress(comp)["w"])[idx]
    np.testing.assert_array_equal(deq, q.astype(np.float32) * scale)


@settings(max_examples=15, deadline=None)
@given(st.integers(8, 200))
def test_topk_bytes_monotone_in_k_frac(n):
    """Measured compressed bytes grow monotonically in k_frac, the raw
    bytes don't move, and the analytic ratio tracks the same ordering."""
    x = {"w": jnp.asarray(
        np.random.default_rng(n).normal(size=n).astype(np.float32))}
    fracs = [0.05, 0.1, 0.25, 0.5, 0.75, 1.0]
    sizes = [compress_topk_int8(x, f)[1:] for f in fracs]
    comp_bytes = [c for c, _ in sizes]
    assert all(a <= b for a, b in zip(comp_bytes, comp_bytes[1:]))
    assert all(r == n * 4 for _, r in sizes)
    ratios = [uplink_bytes_ratio(f) for f in fracs]
    assert all(a < b for a, b in zip(ratios, ratios[1:]))


@settings(max_examples=20, deadline=None)
@given(st.integers(10, 120), st.integers(0, 10_000))
def test_roundtrip_idempotent_on_already_sparse(n, seed):
    """decompress∘compress is exact on an update that already went through
    one round-trip: the surviving entries are int8-representable at the
    same scale, so a second pass reproduces them bit-for-bit."""
    rng = np.random.default_rng(seed)
    k = max(1, int(n * 0.25))
    dense = np.zeros(n, np.float32)
    pos = rng.choice(n, size=k, replace=False)
    dense[pos] = rng.normal(0, 1, k).astype(np.float32)
    once = roundtrip({"w": jnp.asarray(dense)}, 0.25)[0]
    twice = roundtrip(once, 0.25)[0]
    np.testing.assert_array_equal(np.asarray(once["w"]),
                                  np.asarray(twice["w"]))


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 200), st.integers(0, 10_000))
def test_int8_dense_roundtrip(n, seed):
    """Dense int8: shape-preserving, error <= scale/2 on EVERY entry,
    bytes = one per entry + a per-leaf scale, and idempotent on an
    already-quantized tree."""
    rng = np.random.default_rng(seed)
    x = rng.normal(0, 3, n).astype(np.float32)
    comp, b_c, b_r = compress_int8({"w": jnp.asarray(x)})
    assert b_r == 4 * n and b_c == n + 4
    deq = np.asarray(decompress_int8(comp)["w"])
    assert deq.shape == x.shape
    scale = float(comp["w"].scale)
    assert np.abs(deq - x).max() <= scale / 2 + 1e-6
    again = np.asarray(roundtrip_int8({"w": jnp.asarray(deq)})[0]["w"])
    np.testing.assert_array_equal(again, deq)


def test_uplink_bytes_ratio_accounting():
    """The analytic ratio matches the measured per-leaf accounting in the
    large-leaf limit: 5 bytes per kept top-k entry, 1 byte per dense-int8
    entry, 4 bytes per raw f32 entry; off = 1.0."""
    assert uplink_bytes_ratio() == 1.0
    assert uplink_bytes_ratio(0.0, int8=False) == 1.0
    assert uplink_bytes_ratio(None) == 1.0
    assert uplink_bytes_ratio(0.1) == pytest.approx(0.125)
    assert uplink_bytes_ratio(0.0, int8=True) == 0.25
    x = {"w": jnp.zeros(4000)}
    _, b_c, b_r = compress_topk_int8(x, 0.1)
    assert b_c / b_r == pytest.approx(uplink_bytes_ratio(0.1))
    _, b_c8, b_r8 = compress_int8(x)
    assert b_c8 / b_r8 == pytest.approx(uplink_bytes_ratio(int8=True),
                                        rel=0.01)


def test_simulation_with_compressed_uplink():
    from repro.core import connectivity as CN
    from repro.core.scheduler import make_scheduler
    from repro.data.fmow import FmowSpec, SyntheticFmow
    from repro.data.partition import iid_partition
    from repro.data.pipeline import make_clients
    from repro.fl.adapters import MlpFmowAdapter
    from repro.fl.simulation import run_simulation
    spec = CN.ConstellationSpec(num_satellites=16)
    C = CN.connectivity_sets(spec, days=0.5)
    data = SyntheticFmow(FmowSpec(num_train=800, num_val=200))
    adapter = MlpFmowAdapter(data, make_clients(iid_partition(800, 16, 0)))
    res = run_simulation(C, adapter, make_scheduler("fedbuff", M=4),
                         eval_every=16, max_windows=48, uplink_topk=0.25)
    assert res.num_global_updates >= 1
    assert res.accuracy[-1] > 1.0 / 62.0   # still learns through compression


# ---------------------------------------------------------------------------
# config validation and the compression-aware link budget


def test_engine_config_uplink_topk_validated():
    from repro.fl.engine import EngineConfig
    for bad in (-0.2, 1.0001, 7.0):
        with pytest.raises(ValueError,
                           match=r"EngineConfig\.uplink_topk must be in "
                                 r"\(0, 1\]"):
            EngineConfig(uplink_topk=bad)
    # the off sentinels and the bounds stay constructible (the engine
    # resolves None -> 0.0 through dataclasses.replace, which re-runs
    # __post_init__)
    assert EngineConfig().uplink_topk is None
    assert EngineConfig(uplink_topk=0.0).uplink_topk == 0.0
    assert EngineConfig(uplink_topk=1.0).uplink_topk == 1.0


def test_link_config_uplink_topk_validated():
    from repro.fl.api import LinkConfig
    with pytest.raises(ValueError,
                       match=r"LinkConfig\.uplink_topk must be in \[0, 1\], "
                             r"got 1\.5"):
        LinkConfig(uplink_topk=1.5)
    with pytest.raises(ValueError,
                       match=r"LinkConfig\.uplink_topk must be >= 0"):
        LinkConfig(uplink_topk=-0.1)
    assert LinkConfig(uplink_topk=1.0).uplink_topk == 1.0


def _payload_experiment(*, topk=0.0, int8=False, fast_loop=True,
                        train_topk=None):
    from repro.fl.api import (AdapterConfig, ConstellationConfig,
                              DatasetConfig, FLExperiment, LinkConfig,
                              SchedulerConfig)
    from repro.fl.engine import EngineConfig
    return FLExperiment(
        constellation=ConstellationConfig(num_satellites=10, days=0.25),
        dataset=DatasetConfig(num_train=240, num_val=80),
        adapter=AdapterConfig(kind="transformer",
                              params={"d_model": 16, "num_layers": 1,
                                      "num_heads": 2, "num_kv_heads": 1,
                                      "d_ff": 32}),
        scheduler=SchedulerConfig(kind="fedbuff", params={"M": 2}),
        train=EngineConfig(eval_every=12, max_windows=24, local_steps=2,
                           fast_loop=fast_loop, uplink_topk=train_topk),
        link=LinkConfig(uplink_topk=topk, uplink_int8=int8,
                        uplink_mbps=20.0, downlink_mbps=100.0,
                        model_mb=300.0, gs_capacity=1),
    )


def test_compression_off_bit_identical_both_strategies():
    """`uplink_topk=None` (unset) and an explicit 0.0 must produce the
    same trajectory as each other, bit for bit, under the fast loop AND
    the per-window host loop — the parity contract of the payload path."""
    from repro.fl.api import Federation

    def run(topk_train, fast):
        fed = Federation.from_experiment(_payload_experiment(
            fast_loop=fast, train_topk=topk_train))
        eng = fed.engine()
        res = eng.run()
        return eng, res

    e_ref, r_ref = run(None, True)
    for topk_train, fast in ((0.0, True), (None, False), (0.0, False)):
        e, r = run(topk_train, fast)
        assert np.array_equal(e.version, e_ref.version)
        assert np.array_equal(e.pending, e_ref.pending)
        assert np.array_equal(e.buffered_base, e_ref.buffered_base)
        assert e.ig == e_ref.ig
        assert r.accuracy == r_ref.accuracy
        assert r.val_loss == r_ref.val_loss
        assert r.summary() == r_ref.summary()
        for a, b in zip(jax.tree.leaves(e.params),
                        jax.tree.leaves(e_ref.params)):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def test_compression_reduces_need_up():
    """A non-trivial compression ratio rescales the effective uplink
    payload: 300 MB at 20 Mbit/s needs 2 contact units raw, 1 at top-k
    0.25 (ratio 0.3125) or dense int8 (0.25); the downlink (full model)
    is untouched."""
    from repro.fl.api import Federation
    f_raw = Federation.from_experiment(_payload_experiment())
    f_tk = Federation.from_experiment(_payload_experiment(topk=0.25))
    f_i8 = Federation.from_experiment(_payload_experiment(int8=True))
    assert f_raw.link_budget.need_up == 2
    assert f_tk.link_budget.need_up == 1
    assert f_i8.link_budget.need_up == 1
    assert f_raw.link_budget.need_dn == f_tk.link_budget.need_dn == 1
    # train-level EngineConfig.uplink_topk wins over LinkConfig's
    f_override = Federation.from_experiment(
        _payload_experiment(topk=0.25, train_topk=1.0))
    assert f_override.link_budget.need_up == 3   # ratio 1.25 -> 375 MB

"""Compile the FL path's kernels for one described TPU v5e chip, with no
chip attached: what the TPU compiler refuses here (VMEM overruns, block
shapes off the tiling, a kernel that cannot be differentiated) fails in
tier-1 instead of on the chip.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file. The kernel dispatch (`on_tpu`) is steered per test, since the CPU
backend this process runs on would otherwise pick the jnp oracles."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.data.fmow import FmowSpec, SyntheticFmow
from repro.data.partition import iid_partition
from repro.data.pipeline import make_clients
from repro.fl.adapters import MlpFmowAdapter, TransformerFmowAdapter
from repro.kernels.agg import ops as agg_ops
from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.rmsnorm import ops as rmsnorm_ops


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def kernels_on(monkeypatch):
    """Route the ops dispatch to the compiled Pallas kernels."""
    for mod in (agg_ops, rmsnorm_ops, flash_ops):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)


@pytest.fixture(scope="module")
def adapters():
    data = SyntheticFmow(FmowSpec(num_train=64, num_val=16))
    clients = make_clients(iid_partition(64, 2, 0))
    return {"mlp": MlpFmowAdapter(data, clients, hidden=48),
            "transformer": TransformerFmowAdapter(data, clients)}


def _spec(x, sharding, dtype=None):
    return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return compiled


@pytest.mark.parametrize("kind", ["mlp", "transformer"])
@pytest.mark.parametrize("M", [96, 191])
def test_agg_compiles_at_fl_buffer_sizes(topo, one_chip, kernels_on,
                                         adapters, kind, M):
    """FedBuff's default buffer (96) and the whole flock191
    constellation (191) over each FL payload's parameter tree."""
    params = jax.eval_shape(adapters[kind].init, jax.random.PRNGKey(0))
    p = jax.tree.map(lambda x: _spec(x, one_chip), params)
    u = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        (M,) + x.shape, x.dtype, sharding=one_chip), params)
    w = jax.ShapeDtypeStruct((M,), jnp.float32, sharding=one_chip)
    _compile(agg_ops.aggregate_params_tree, p, u, w)


@pytest.mark.parametrize("M", [96, 191])
def test_agg_compiles_for_a_large_flat_model(topo, one_chip, M):
    """A 4M-parameter flat model: many blocks, a partial last one. A
    fixed (M, 16,384) panel ran out of VMEM here at M = 96 and 191."""
    from repro.kernels.agg.kernel import weighted_aggregate
    n = 4_000_037
    _compile(lambda p, u, w: weighted_aggregate(p, u, w),
             jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip),
             jax.ShapeDtypeStruct((M, n), jnp.float32, sharding=one_chip),
             jax.ShapeDtypeStruct((M,), jnp.float32, sharding=one_chip))


def test_rmsnorm_compiles_at_transformer_shapes(topo, one_chip, kernels_on,
                                                adapters):
    cfg = adapters["transformer"].cfg
    S = adapters["transformer"].seq_len
    x = jax.ShapeDtypeStruct((32, S, cfg.d_model), jnp.float32,
                             sharding=one_chip)
    s = jax.ShapeDtypeStruct((cfg.d_model,), jnp.float32, sharding=one_chip)
    _compile(lambda x, s: rmsnorm_ops.rmsnorm(x, s, cfg.norm_eps), x, s)


def test_flash_compiles_at_transformer_shapes(topo, one_chip, kernels_on,
                                              adapters):
    ad = adapters["transformer"]
    cfg, S = ad.cfg, ad.seq_len
    hd = cfg.resolved_head_dim
    q = jax.ShapeDtypeStruct((32, S, cfg.num_heads, hd), jnp.float32,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((32, S, cfg.num_kv_heads, hd), jnp.float32,
                              sharding=one_chip)
    _compile(lambda q, k, v: flash_ops.flash_attention_bshd(
        q, k, v, causal=True, bq=S, bk=S), q, kv, kv)


def test_transformer_grad_compiles_through_kernels(topo, one_chip,
                                                   kernels_on, adapters):
    """The client update's `jax.grad(adapter.loss)`: the kernels' custom
    VJP is what lets reverse mode through the `pallas_call`s."""
    ad = adapters["transformer"]
    params = jax.eval_shape(ad.init, jax.random.PRNGKey(0))
    p = jax.tree.map(lambda x: _spec(x, one_chip), params)
    X = jax.ShapeDtypeStruct((32, ad._X_train.shape[1]), jnp.float32,
                             sharding=one_chip)
    y = jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one_chip)
    _compile(jax.grad(ad.loss), p, (X, y))


@pytest.mark.parametrize("rows", [60_000, 1_024])
def test_forest_walk_compiles_without_gathers(topo, one_chip, rows):
    """The eq.-13 forest at the replan pool's size (5,000 candidates by
    up to 12 events) and a delta bucket: 30 trees of depth 6 over 13
    features. The heap walk must lower to selects alone; the gather
    traversal deeper forests keep shows the probe finds a gather."""
    import functools
    from repro.core.utility import (_forest_predict_device,
                                    _forest_predict_gather)
    T, D, F, M = 30, 6, 13, 2 ** 7 - 1

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    X = spec((rows, F), jnp.float32)
    dense = _forest_predict_device.lower(
        spec((T, 2 ** D - 1), jnp.int32), spec((T, 2 ** D - 1), jnp.float32),
        spec((T, 2 ** D), jnp.float32), X).compile()
    assert " gather(" not in dense.as_text()
    flat = [spec((T * M,), dt) for dt in (jnp.int32, jnp.float32, jnp.int32,
                                         jnp.int32, jnp.float32)]
    walk = jax.jit(functools.partial(_forest_predict_gather, depth=D)).lower(
        *flat, spec((T, 1), jnp.int32), X).compile()
    assert " gather(" in walk.as_text()

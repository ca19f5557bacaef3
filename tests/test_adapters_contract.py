"""Shared adapter-contract tests.

The engine treats adapters as interchangeable: anything registered in
`repro.fl.registry.ADAPTERS` must provide init/apply/loss, deterministic
client batches (with the batched path bit-identical to per-client calls),
a deterministic eval batch, and updates whose pytree matches the
parameter pytree. These tests run the same contract over EVERY registered
adapter — MLP, the paper's DenseNet, and the transformer payload — so a
new adapter gets the full battery by registering (and adding its small
test config to `_PARAMS` below).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.fl.adapters  # noqa: F401 — registers the built-in adapters
from repro.data.fmow import FmowSpec, SyntheticFmow
from repro.data.partition import iid_partition
from repro.data.pipeline import make_clients, row_bucket
from repro.fl.client import make_batched_client_update
from repro.fl.registry import ADAPTERS

K = 6

# one deliberately tiny configuration per registered adapter; the pin
# test below forces additions here when a new adapter registers
_PARAMS = {
    "mlp": {"hidden": 16},
    # channel counts must stay divisible by the group-norm group count (8)
    "densenet": {"growth": 8, "blocks": (1, 1), "stem": 8, "val_n": 64},
    "transformer": {"d_model": 16, "num_layers": 1, "num_heads": 2,
                    "num_kv_heads": 1, "d_ff": 32},
}


def test_every_registered_adapter_is_covered():
    assert set(ADAPTERS.names()) == set(_PARAMS), (
        "a registered adapter has no contract-test config; add a tiny "
        "_PARAMS entry in tests/test_adapters_contract.py")


@pytest.fixture(scope="module")
def world():
    data = SyntheticFmow(FmowSpec(num_train=240, num_val=80))
    clients = make_clients(iid_partition(data.spec.num_train, K, 0))
    return data, clients


@pytest.fixture(scope="module", params=sorted(_PARAMS))
def adapter(request, world):
    data, clients = world
    return ADAPTERS.build(request.param, data, clients,
                          **_PARAMS[request.param])


def _tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


# --------------------------------------------------------------------------
# client batches


def test_client_batch_many_bit_identical_to_per_client(adapter):
    """The stacked fast-path batch must reproduce the sequential
    `client_batch` calls bit for bit for every included row — the engine's
    seed-trajectory guarantee rests on this. The stack is padded to the
    next power of two at or above the clients asked for, with copies of
    its first row."""
    for round_rng in (3, 17):
        stacked, rows = adapter.client_batch_many(list(range(K)), round_rng,
                                                  16, 2)
        assert rows == sorted(rows)
        assert set(rows) <= set(range(K))
        assert len(rows) > 0
        M = row_bucket(K)
        for leaf in jax.tree.leaves(stacked):
            assert leaf.shape[0] == M
        for pos, cid in enumerate(rows + rows[:1] * (M - len(rows))):
            single = adapter.client_batch(cid, round_rng, 16, 2)
            assert single is not None
            got = jax.tree.map(lambda s: s[pos], stacked)
            assert _tree_equal(got, single)


def test_client_batch_grouping_is_deterministic(adapter):
    a = adapter.client_batch_many(list(range(K)), 11, 16, 2)
    b = adapter.client_batch_many(list(range(K)), 11, 16, 2)
    assert a[1] == b[1]
    assert _tree_equal(a[0], b[0])


# --------------------------------------------------------------------------
# evaluation


def test_eval_batch_deterministic_and_labeled(adapter):
    X1, y1 = adapter.eval_batch(64)
    X2, y2 = adapter.eval_batch(64)
    assert np.array_equal(np.asarray(X1), np.asarray(X2))
    assert np.array_equal(np.asarray(y1), np.asarray(y2))
    assert jnp.issubdtype(y1.dtype, jnp.integer)
    assert X1.shape[0] == y1.shape[0] <= 64


def test_accuracy_and_val_loss_are_finite(adapter):
    params = adapter.init(jax.random.PRNGKey(0))
    acc = adapter.accuracy(params, 64)
    vl = adapter.val_loss(params, 64)
    assert 0.0 <= acc <= 1.0
    assert np.isfinite(vl)
    # evaluation is pure: same params, same numbers
    assert adapter.accuracy(params, 64) == acc
    assert adapter.val_loss(params, 64) == vl


# --------------------------------------------------------------------------
# update pytrees


def test_batched_update_matches_param_pytree(adapter):
    """Client updates are deltas over the parameter pytree, each row over
    its own base: identical treedef, and per-leaf shapes/dtypes with the
    stacked leading axis M — what the staleness aggregation and the
    compression roundtrip both assume."""
    params = adapter.init(jax.random.PRNGKey(1))
    mask = (adapter.trainable_mask(params)
            if hasattr(adapter, "trainable_mask") else None)
    if mask is not None:
        assert (jax.tree.structure(mask) == jax.tree.structure(params))
    update_many = make_batched_client_update(
        adapter, local_steps=2, lr=0.1, trainable_mask=mask)
    stacked, rows = adapter.client_batch_many(list(range(K)), 5, 16, 2)
    M = jax.tree.leaves(stacked)[0].shape[0]
    bases = jax.tree.map(lambda p: jnp.broadcast_to(p, (M,) + p.shape),
                         params)
    u = update_many(bases, stacked)
    assert jax.tree.structure(u) == jax.tree.structure(params)
    for du, p in zip(jax.tree.leaves(u), jax.tree.leaves(params)):
        assert du.shape == (M,) + p.shape
        assert du.dtype == p.dtype
        assert np.isfinite(np.asarray(du)).all()

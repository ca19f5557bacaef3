"""Per-kernel validation (deliverable c): shape/dtype sweeps asserting
allclose against the pure-jnp oracles, interpret=True on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.agg.kernel import weighted_aggregate
from repro.kernels.agg.ops import aggregate_params_tree
from repro.kernels.agg.ref import weighted_aggregate_ref
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rmsnorm.kernel import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref


def _tol(dtype):
    return dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# aggregation kernel


@pytest.mark.parametrize("m", [1, 7, 64, 191])
@pytest.mark.parametrize("n", [128, 5000, 40_000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_agg_sweep(m, n, dtype, key):
    upd = jax.random.normal(key, (m, n), dtype)
    p = jax.random.normal(jax.random.fold_in(key, 1), (n,), dtype)
    w = jax.random.uniform(jax.random.fold_in(key, 2), (m,), jnp.float32)
    w = w / w.sum()
    out = weighted_aggregate(p, upd, w, block=4096, interpret=True)
    ref = weighted_aggregate_ref(p, upd, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_agg_tree_paths(key):
    tree = {"a": jax.random.normal(key, (5, 16, 8)),
            "b": {"c": jax.random.normal(jax.random.fold_in(key, 1),
                                         (5, 33))}}
    w = jnp.asarray([0.5, 0.2, 0.1, 0.1, 0.1])
    params = jax.tree.map(lambda u: u[0], tree)
    got = aggregate_params_tree(params, tree, w, interpret=True)
    ref = jax.tree.map(lambda p, u: p + jnp.tensordot(w, u, axes=1),
                       params, tree)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=1e-5), got, ref)


# ---------------------------------------------------------------------------
# rmsnorm kernel


@pytest.mark.parametrize("shape", [(4, 128), (3, 5, 256), (37, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype, key):
    x = jax.random.normal(key, shape, dtype)
    s = jax.random.normal(jax.random.fold_in(key, 1), (shape[-1],), dtype)
    out = rmsnorm(x, s, rows=8, interpret=True)
    ref = rmsnorm_ref(x, s)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


# ---------------------------------------------------------------------------
# flash attention kernel


@pytest.mark.parametrize("h,k", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 32),
                                           (False, 0)])
def test_flash_gqa_mask_sweep(h, k, causal, window, key):
    B, S, hd = 2, 128, 64
    q = jax.random.normal(key, (B, h, S, hd))
    kk = jax.random.normal(jax.random.fold_in(key, 1), (B, k, S, hd))
    vv = jax.random.normal(jax.random.fold_in(key, 2), (B, k, S, hd))
    out = flash_attention(q, kk, vv, causal=causal, window=window, bq=32,
                          bk=32, interpret=True)
    ref = attention_ref(q, kk, vv, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("sq,sk", [(64, 64), (100, 200), (64, 192)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_shape_dtype_sweep(sq, sk, dtype, key):
    B, H, hd = 1, 2, 128
    q = jax.random.normal(key, (B, H, sq, hd), dtype)
    kk = jax.random.normal(jax.random.fold_in(key, 1), (B, H, sk, hd), dtype)
    vv = jax.random.normal(jax.random.fold_in(key, 2), (B, H, sk, hd), dtype)
    out = flash_attention(q, kk, vv, causal=False, bq=32, bk=64,
                          interpret=True)
    ref = attention_ref(q, kk, vv, causal=False)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_block_shape_invariance(key):
    """Output must not depend on the BlockSpec tiling."""
    B, H, S, hd = 1, 2, 256, 64
    q = jax.random.normal(key, (B, H, S, hd))
    kk = jax.random.normal(jax.random.fold_in(key, 1), (B, H, S, hd))
    vv = jax.random.normal(jax.random.fold_in(key, 2), (B, H, S, hd))
    outs = [flash_attention(q, kk, vv, causal=True, bq=bq, bk=bk,
                            interpret=True)
            for bq, bk in [(32, 32), (64, 128), (256, 64)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=2e-5)


# ---------------------------------------------------------------------------
# FL-payload shapes + ops-layer dispatch (the transformer adapter's hot
# path: tiny sequences, narrow heads — far off the LLM-shaped sweeps above)


def test_rmsnorm_fl_shape_parity(key):
    """TransformerFmowAdapter hidden states: (B, S, d_model) = (32, 8, 32)."""
    x = jax.random.normal(key, (32, 8, 32))
    s = jax.random.normal(jax.random.fold_in(key, 1), (32,))
    out = rmsnorm(x, s, rows=8, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(rmsnorm_ref(x, s)),
                               atol=2e-5)


def test_flash_fl_shape_parity(key):
    """Adapter attention shapes: B=32 clients*batch, H=4, K=2 (GQA),
    S=8 tokens, hd=8 — the kernel must clamp its tiles to the tiny
    sequence and still match the oracle."""
    B, H, K, S, hd = 32, 4, 2, 8, 8
    q = jax.random.normal(key, (B, H, S, hd))
    kk = jax.random.normal(jax.random.fold_in(key, 1), (B, K, S, hd))
    vv = jax.random.normal(jax.random.fold_in(key, 2), (B, K, S, hd))
    out = flash_attention(q, kk, vv, causal=True, bq=S, bk=S, interpret=True)
    ref = attention_ref(q, kk, vv, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ops_dispatch_bit_identical_to_oracle_off_tpu(key):
    """`interpret=None` (the FL default) must BE the jnp oracle off-TPU —
    bit-identical, not allclose — so simulation trajectories through the
    transformer adapter stay reproducible on CPU CI."""
    from repro.kernels import on_tpu
    from repro.kernels.flash_attention.ops import flash_attention_bshd
    from repro.kernels.rmsnorm.ops import rmsnorm as rmsnorm_op
    if on_tpu():
        pytest.skip("off-TPU dispatch path")
    x = jax.random.normal(key, (32, 8, 32))
    s = jax.random.normal(jax.random.fold_in(key, 1), (32,))
    assert np.array_equal(np.asarray(rmsnorm_op(x, s)),
                          np.asarray(rmsnorm_ref(x, s)))
    B, H, K, S, hd = 4, 4, 2, 8, 8
    # ops layer takes the model's (B, S, H, hd) layout
    q = jax.random.normal(key, (B, S, H, hd))
    kk = jax.random.normal(jax.random.fold_in(key, 2), (B, S, K, hd))
    vv = jax.random.normal(jax.random.fold_in(key, 3), (B, S, K, hd))
    got = flash_attention_bshd(q, kk, vv, causal=True)
    ref = jnp.moveaxis(attention_ref(jnp.moveaxis(q, 2, 1),
                                     jnp.moveaxis(kk, 2, 1),
                                     jnp.moveaxis(vv, 2, 1), causal=True),
                       1, 2)
    assert got.shape == q.shape
    assert np.array_equal(np.asarray(got), np.asarray(ref))


def test_ops_interpret_true_close_to_oracle(key):
    """Explicit `interpret=True` routes through the Pallas interpreter:
    numerically close to — though not bit-identical with — the oracle."""
    from repro.kernels.flash_attention.ops import flash_attention_bshd
    from repro.kernels.rmsnorm.ops import rmsnorm as rmsnorm_op
    x = jax.random.normal(key, (16, 8, 32))
    s = jax.random.normal(jax.random.fold_in(key, 1), (32,))
    np.testing.assert_allclose(np.asarray(rmsnorm_op(x, s, interpret=True)),
                               np.asarray(rmsnorm_ref(x, s)), atol=2e-5)
    B, H, K, S, hd = 2, 4, 2, 8, 8
    q = jax.random.normal(key, (B, S, H, hd))
    kk = jax.random.normal(jax.random.fold_in(key, 2), (B, S, K, hd))
    vv = jax.random.normal(jax.random.fold_in(key, 3), (B, S, K, hd))
    got = flash_attention_bshd(q, kk, vv, causal=True, bq=S, bk=S,
                               interpret=True)
    ref = jnp.moveaxis(attention_ref(jnp.moveaxis(q, 2, 1),
                                     jnp.moveaxis(kk, 2, 1),
                                     jnp.moveaxis(vv, 2, 1), causal=True),
                       1, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------------------
# reverse mode through the kernel paths: a custom VJP whose backward pass is
# the oracle's, so gradients match `jax.grad` of the oracle up to the
# forward kernel's rounding


def test_rmsnorm_kernel_path_grad_matches_oracle(key):
    from repro.kernels.rmsnorm.ops import rmsnorm as rmsnorm_op
    x = jax.random.normal(key, (16, 8, 32))
    s = jax.random.normal(jax.random.fold_in(key, 1), (32,))
    r = jax.random.normal(jax.random.fold_in(key, 2), x.shape)

    def loss(fn):
        return lambda x, s: jnp.sum(jnp.sin(fn(x, s)) * r)

    got = jax.grad(loss(lambda x, s: rmsnorm_op(x, s, interpret=True)),
                   argnums=(0, 1))(x, s)
    ref = jax.grad(loss(rmsnorm_ref), argnums=(0, 1))(x, s)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 3)])
def test_flash_kernel_path_grad_matches_oracle(key, causal, window):
    from repro.kernels.flash_attention.ops import flash_attention_bshd
    B, H, K, S, hd = 2, 4, 2, 8, 8
    q = jax.random.normal(key, (B, S, H, hd))
    kk = jax.random.normal(jax.random.fold_in(key, 1), (B, S, K, hd))
    vv = jax.random.normal(jax.random.fold_in(key, 2), (B, S, K, hd))
    r = jax.random.normal(jax.random.fold_in(key, 3), q.shape)

    def ref_bshd(q, k, v):
        t = lambda a: jnp.moveaxis(a, 2, 1)
        return t(attention_ref(t(q), t(k), t(v), causal=causal,
                               window=window))

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.tanh(fn(q, k, v)) * r)

    got = jax.grad(loss(lambda q, k, v: flash_attention_bshd(
        q, k, v, causal=causal, window=window, bq=S, bk=S,
        interpret=True)), argnums=(0, 1, 2))(q, kk, vv)
    ref = jax.grad(loss(ref_bshd), argnums=(0, 1, 2))(q, kk, vv)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_kernel_path_grad_under_vmap_and_scan(key):
    """The client update's shape of use: grad inside a scan over local
    steps, vmapped over satellites."""
    from repro.kernels.rmsnorm.ops import rmsnorm as rmsnorm_op
    x = jax.random.normal(key, (3, 4, 8, 32))      # (M, steps, rows, D)
    s0 = jnp.ones((32,))

    def update(fn):
        def one(xs):
            def body(s, x):
                g = jax.grad(lambda s: jnp.sum(fn(x, s) ** 2))(s)
                return s - 0.01 * g, None
            return jax.lax.scan(body, s0, xs)[0]
        return jax.jit(jax.vmap(one))

    got = update(lambda x, s: rmsnorm_op(x, s, interpret=True))(x)
    ref = update(rmsnorm_ref)(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

"""Plain reference of the transformer payload, its client update (paper
eq. 3) and the staleness-weighted aggregation (eq. 4).

Straightforward `jax.numpy`: no kernels, no cache, dense causal softmax
attention, every matrix product at `Precision.HIGHEST` in float32. With
`dtype=jnp.bfloat16` the same code computes in bfloat16 throughout: that
is the control, the precision below the float32 the configuration states.
It imports nothing of the program under test; it draws its own weights
from the seed, with the same key tree and scales as the payload.

The model: a feature vector of F values is read as S tokens of F/S values
and embedded by `w_in`; L pre-norm blocks, each RMSNorm -> grouped-query
causal attention with rotary positions -> residual, RMSNorm -> SwiGLU ->
residual; a final RMSNorm and a linear head on the last position; mean
cross-entropy over the batch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.flops import Payload

EPS = 1e-6
ROPE_THETA = 10000.0
HIGHEST = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("P",))
def init(key, P: Payload):
    """The payload's weights from one PRNG key, as a nested dict whose
    paths name the same leaves as the payload's own parameter tree."""
    d, hd, H, K, f = P.d_model, P.head_dim, P.heads, P.kv_heads, P.d_ff
    tok = P.features // P.seq
    normal = jax.random.normal
    ks = jax.random.split(key, 3)

    def layer(k):
        blk = jax.random.split(jax.random.split(k, 1)[0], 3)
        a = jax.random.split(blk[0], 6)
        m = jax.random.split(blk[1], 3)
        return {"attn": {"norm": {"scale": jnp.ones(d)},
                         "wq": normal(a[0], (d, H * hd)) * d ** -0.5,
                         "wk": normal(a[1], (d, K * hd)) * d ** -0.5,
                         "wv": normal(a[2], (d, K * hd)) * d ** -0.5,
                         "wo": normal(a[3], (H * hd, d)) * (H * hd) ** -0.5},
                "ffn": {"norm": {"scale": jnp.ones(d)},
                        "mlp": {"w_gate": normal(m[0], (d, f)) * d ** -0.5,
                                "w_up": normal(m[1], (d, f)) * d ** -0.5,
                                "w_down": normal(m[2], (f, d)) * f ** -0.5}}}

    layers = [layer(k) for k in jax.random.split(ks[1], P.layers)]
    stage = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return {"w_in": normal(ks[0], (tok, d)) * tok ** -0.5,
            "stage": {"pos0": stage},
            "final_norm": {"scale": jnp.ones(d)},
            "head_w": normal(ks[2], (d, P.classes)) * d ** -0.5,
            "head_b": jnp.zeros(P.classes)}


def _rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + EPS) * scale


def _rope(x, pos):
    """x: (B, S, heads, hd); rotate pairs (i, i + hd/2)."""
    hd = x.shape[-1]
    inv = 1.0 / (ROPE_THETA ** (jnp.arange(0, hd, 2, dtype=jnp.float32)
                                / hd))
    ang = (pos[:, None, None].astype(jnp.float32) * inv).astype(x.dtype)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(params, X, P: Payload):
    """Logits (B, classes) of feature vectors X (B, F), in X's dtype."""
    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    B, S, H, K, hd = X.shape[0], P.seq, P.heads, P.kv_heads, P.head_dim
    G = H // K
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    h = mm(X.reshape(B, S, -1), params["w_in"])
    st = params["stage"]["pos0"]
    for li in range(P.layers):
        a = jax.tree.map(lambda t: t[li], st["attn"])
        f = jax.tree.map(lambda t: t[li], st["ffn"])
        x = _rmsnorm(h, a["norm"]["scale"])
        q = _rope(mm(x, a["wq"]).reshape(B, S, H, hd), pos)
        k = _rope(mm(x, a["wk"]).reshape(B, S, K, hd), pos)
        v = mm(x, a["wv"]).reshape(B, S, K, hd)
        q = q.reshape(B, S, K, G, hd)
        s = jnp.einsum("bqkgd,btkd->bkgqt", q, k, precision=HIGHEST) \
            * jnp.asarray(hd ** -0.5, X.dtype)
        s = jnp.where(causal, s, jnp.asarray(-1e30, X.dtype))
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqt,btkd->bqkgd", p, v, precision=HIGHEST)
        h = h + mm(o.reshape(B, S, H * hd), a["wo"])
        x = _rmsnorm(h, f["norm"]["scale"])
        m = f["mlp"]
        h = h + mm(jax.nn.silu(mm(x, m["w_gate"])) * mm(x, m["w_up"]),
                   m["w_down"])
    h = _rmsnorm(h, params["final_norm"]["scale"])
    return mm(h[:, -1, :], params["head_w"]) + params["head_b"]


def loss(params, X, y, P: Payload):
    logits = forward(params, X, P)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(logits, y[:, None], -1)[:, 0])


@functools.partial(jax.jit, static_argnames=("P", "lr"))
def client_updates(bases, X, y, *, P: Payload, lr: float):
    """Each client's update w^E - w^0 after one SGD step per batch.
    bases: params stacked per client; X (M, E, b, F); y (M, E, b)."""
    def one(p0, Xs, ys):
        def step(p, xy):
            g = jax.grad(loss)(p, xy[0], xy[1], P)
            return jax.tree.map(lambda w, g_: w - jnp.asarray(lr, w.dtype)
                                * g_, p, g), None
        p, _ = jax.lax.scan(step, p0, (Xs, ys))
        return jax.tree.map(lambda a, b: a - b, p, p0)
    return jax.vmap(one)(bases, X, y)


def weights(staleness, alpha: float, dtype):
    """Normalized c(s)/C with c(s) = (s + 1)^-alpha (eq. 4)."""
    c = (jnp.asarray(staleness, jnp.float32) + 1.0) ** -alpha
    return (c / jnp.sum(c)).astype(dtype)


def aggregate(params, updates, w, server_lr: float = 1.0):
    """params + server_lr * sum_k w_k updates_k, leaf by leaf."""
    return jax.tree.map(
        lambda p, u: p + jnp.asarray(server_lr, p.dtype)
        * jnp.tensordot(w, u, axes=1, precision=HIGHEST), params, updates)


def cast(tree, dtype):
    return jax.tree.map(lambda x: jnp.asarray(x).astype(dtype), tree)

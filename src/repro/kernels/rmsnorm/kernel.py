"""Pallas TPU kernel: RMSNorm over the last (model) dimension.

    y = x / sqrt(mean(x^2) + eps) * scale

Memory-bound elementwise+reduction op; tiled as (BR, D) row panels so each
grid step keeps one panel and the (1, D) scale row in VMEM. A block spans
the whole model dim, so D needs no lane alignment (the FL transformer
payload has D = 32). `eps` is static: it is a model constant.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_ROWS = 256


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                  # (BR, D)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "rows", "interpret"))
def rmsnorm(x, scale, eps: float = 1e-6, *, rows: int = DEFAULT_ROWS,
            interpret: bool = False):
    """x: (..., D); scale: (D,)."""
    orig_shape = x.shape
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    n = xf.shape[0]
    br = min(rows, n)
    pad = (-n) % br
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    grid = (xf.shape[0] // br,)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=float(eps)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),        # x panel
            pl.BlockSpec((1, d), lambda i: (0, 0)),         # scale
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        interpret=interpret,
    )(xf, scale.reshape(1, d))
    if pad:
        out = out[:n]
    return out.reshape(orig_shape)

"""Public API for flash attention in the model's (B, S, H, hd) layout,
differentiable on every path."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, bq, bk, interpret):
    return flash_attention(q, k, v, causal=causal, window=window, bq=bq,
                           bk=bk, interpret=interpret)


def _flash_fwd(q, k, v, causal, window, bq, bk, interpret):
    return _flash(q, k, v, causal, window, bq, bk, interpret), (q, k, v)


def _flash_bwd(causal, window, bq, bk, interpret, res, g):
    # the oracle's VJP, recomputed from the saved inputs (not a kernel)
    _, vjp = jax.vjp(functools.partial(attention_ref, causal=causal,
                                       window=window), *res)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_bshd(q, k, v, *, causal=True, window=0, bq=512, bk=512,
                         interpret=None):
    """q: (B, S, H, hd); k, v: (B, T, K, hd) — the transformer-stack layout.
    Transposes to (B, H, S, hd) for the kernel.

    Dispatch mirrors `repro.kernels.agg.ops`: with `interpret=None` (the
    default) the compiled Pallas kernel runs on TPU and the pure-jnp
    oracle (`attention_ref`) everywhere else, keeping off-TPU FL runs
    bit-reproducible; an explicit `interpret=True` forces the Pallas
    interpreter (kernel debugging — close to, not bit-identical with, the
    oracle). The kernel paths carry a custom VJP whose backward pass is
    the oracle's, so `jax.grad` passes through."""
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if interpret is None:
        if on_tpu():
            interpret = False
        else:
            return jnp.moveaxis(
                attention_ref(qt, kt, vt, causal=causal, window=window),
                1, 2)
    out = _flash(qt, kt, vt, causal, window, bq, bk, interpret)
    return jnp.moveaxis(out, 1, 2)

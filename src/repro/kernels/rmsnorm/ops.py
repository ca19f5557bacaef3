"""Public API for the RMSNorm kernel, differentiable on every path."""
from __future__ import annotations

import functools

import jax

from repro.kernels import on_tpu
from repro.kernels.rmsnorm.kernel import rmsnorm as _kernel
from repro.kernels.rmsnorm.ref import rmsnorm_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rmsnorm(x, scale, eps, interpret):
    return _kernel(x, scale, eps, interpret=interpret)


def _rmsnorm_fwd(x, scale, eps, interpret):
    return _rmsnorm(x, scale, eps, interpret), (x, scale)


def _rmsnorm_bwd(eps, interpret, res, g):
    # the oracle's VJP, recomputed from the saved inputs (not a kernel)
    _, vjp = jax.vjp(lambda x, s: rmsnorm_ref(x, s, eps), *res)
    return vjp(g)


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def rmsnorm(x, scale, eps: float = 1e-6, *, interpret=None):
    """Dispatch mirrors `repro.kernels.agg.ops`: `interpret=None` (the
    default) runs the compiled Pallas kernel on TPU and the pure-jnp
    oracle (`rmsnorm_ref`) everywhere else; explicit `interpret=True`
    forces the Pallas interpreter. The kernel paths carry a custom VJP
    whose backward pass is the oracle's, so `jax.grad` passes through."""
    if interpret is None:
        if on_tpu():
            interpret = False
        else:
            return rmsnorm_ref(x, scale, eps)
    return _rmsnorm(x, scale, eps, interpret)

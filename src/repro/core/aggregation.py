"""Staleness-compensated buffered aggregation (paper eq. 4):

    w^{i+1} = w^i + sum_k  c(s_k)/C * g_k,    C = sum_k c(s_k)

Operates on pytrees of per-satellite update stacks. The hot spot — the
weighted reduction over the update buffer at full model size — routes
through `repro.kernels.agg.ops.aggregate_params_tree`: the Pallas TPU
kernel on TPU, the bit-identical pure-jnp reduction elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.staleness import staleness_compensation
from repro.kernels.agg.ops import aggregate_params_tree


@functools.partial(jax.jit, static_argnames=("alpha",))
def aggregation_weights(staleness, alpha: float = 0.5, server_lr=1.0):
    """Normalized c(s_k)/C weights times `server_lr`, one program per
    length. staleness: (M,) int array; rows of negative staleness are
    padding and weigh 0."""
    s = jnp.asarray(staleness)
    c = jnp.where(s >= 0, staleness_compensation(jnp.maximum(s, 0), alpha),
                  0.0)
    return c / jnp.maximum(jnp.sum(c), 1e-12) * server_lr


def apply_aggregation(global_params, update_stack, staleness, *,
                      alpha: float = 0.5, server_lr: float = 1.0,
                      interpret=None):
    """global_params: pytree; update_stack: pytree with leading buffer dim M
    (stacked g_k); staleness: (M,) int32.

    Returns updated params. `interpret` forwards to the kernel dispatch
    (None = kernel on TPU, jnp reduction elsewhere).
    """
    w = aggregation_weights(staleness, alpha, server_lr)
    return aggregate_params_tree(global_params, update_stack, w,
                                 interpret=interpret)

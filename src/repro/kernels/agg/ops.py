"""jit'd public API for the aggregation kernel over a parameter pytree.

Dispatch policy (`interpret=None`, the default): on TPU the compiled Pallas
kernel runs; off TPU the pure-jnp oracle runs instead. The oracle is
bit-identical to the eager tensordot reduction the FL engine historically
used (the Pallas *interpreter* is not — its per-block elementwise reduce
accumulates in a different order), so CPU trajectories stay reproducible
while TPU gets the kernel. Pass `interpret=True` explicitly to run the
kernel through the Pallas interpreter (tests do, to validate the kernel
logic off-TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu
from repro.kernels.agg.kernel import weighted_aggregate
from repro.kernels.agg.ref import weighted_sum_ref


def aggregate_params_tree(params, update_stack, weights, *, interpret=None):
    """params + sum_m w_m * updates[m] per leaf, through the kernel: one
    jitted program for each (M, tree structure)."""
    if interpret is None and on_tpu():
        interpret = False
    return _aggregate_tree(params, update_stack, weights,
                           interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _aggregate_tree(params, update_stack, weights, *, interpret):
    """(new params, the oracle's weighted sums): `interpret` None runs the
    oracle, a bool the Pallas kernel (no sums). The sums leave the
    program so that XLA's CPU backend cannot fold `p + dot` into one
    fusion, which rounds differently from the eager reduction."""
    def one(p, u):
        flat = p.reshape(-1).astype(jnp.float32)
        u = u.reshape(u.shape[0], -1)
        if interpret is None:
            acc = weighted_sum_ref(u, weights)
            return (flat + acc).reshape(p.shape).astype(p.dtype), acc
        out = weighted_aggregate(flat, u, weights, interpret=interpret)
        return out.reshape(p.shape).astype(p.dtype), None

    leaves, tree = jax.tree.flatten(params)
    outs, sums = zip(*map(one, leaves, jax.tree.leaves(update_stack)))
    return jax.tree.unflatten(tree, outs), sums

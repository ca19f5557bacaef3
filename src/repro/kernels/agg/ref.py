"""Pure-jnp oracle for the aggregation kernel."""
from __future__ import annotations

import jax.numpy as jnp


def weighted_sum_ref(updates, weights):
    return jnp.tensordot(weights.astype(jnp.float32),
                         updates.astype(jnp.float32), axes=1)


def weighted_aggregate_ref(params_flat, updates, weights):
    acc = weighted_sum_ref(updates, weights)
    return (params_flat.astype(jnp.float32) + acc).astype(params_flat.dtype)

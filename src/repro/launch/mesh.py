"""Production mesh construction.

Defined as functions (not module constants) so importing this module never
touches jax device state — required because the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init,
while tests/benches must see the single real CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """`jax.make_mesh` with `Auto` axes: the step functions place data with
    `with_sharding_constraint` hints, which `Explicit` axes (the default
    of `jax.make_mesh`) would turn into assertions."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """1x1 mesh on the real devices — for smoke-scale runs of the same
    pjit code paths on CPU."""
    n = len(jax.devices())
    return _mesh((n, 1), ("data", "model"))

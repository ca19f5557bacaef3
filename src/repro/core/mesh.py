"""Device-mesh layer for the simulation itself: the satellite axis of the
Algorithm-1 protocol state sharded across devices.

It has two jobs:

  * **the simulation mesh** — `sim_mesh` builds the 1-D ``"sat"`` mesh the
    engine (`repro.fl.engine.SimulationEngine(mesh=...)`) and the eq.-13
    search (`repro.core.search.score_candidates(mesh=...)`) shard the
    satellite axis over. The protocol transitions are embarrassingly
    parallel over K between aggregation events: the only cross-satellite
    contractions are the scalar counters/any-buffer reductions (exact
    integer `psum`s — see the ``axis_name`` threading in
    `repro.core.staleness`) and the (K,)-sized ISL neighbour/sink gathers
    (`all_gather` of one bool/int row per window).
  * **padding** — device counts rarely divide K, so `padded_size` /
    `pad_axis` / `pad_state` extend the satellite axis with never-connected
    satellites (connectivity False, grants 0, state "never existed"). A
    satellite with no contact ever uploads, downloads, gossips, idles, or
    enters the buffer, so every counter and every real satellite's
    trajectory is bit-identical to the unpadded run — that is the parity
    contract `docs/scaling.md` spells out and the mesh tests/benchmark
    gate enforce.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import staleness as SS

SAT_AXIS = "sat"


def shard_map(f, mesh, in_specs, out_specs, check: bool = False):
    """`jax.shard_map` with the replication check (``check_vma``) off by
    default: the protocol scans emit psum-replicated outputs from inside
    `lax.scan`, which the static checker mis-tracks — parity with the
    single-device program is asserted by tests instead."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


# ---------------------------------------------------------------------------
# the simulation mesh


def sim_mesh(num_devices: Optional[int] = None, *,
             axis: str = SAT_AXIS) -> jax.sharding.Mesh:
    """1-D device mesh over the satellite axis. All visible devices by
    default (`num_devices` clips — e.g. to benchmark scaling curves);
    a single-device mesh is valid and compiles the shard_map path with
    trivial collectives, which is how the mesh code stays exercised on
    1-device CI runners."""
    devs = jax.devices()
    n = len(devs) if num_devices is None else max(1, min(int(num_devices),
                                                         len(devs)))
    return jax.sharding.Mesh(np.asarray(devs[:n]), (axis,))


def mesh_size(mesh) -> int:
    """Total device count of a mesh (the satellite-axis shard count)."""
    return int(np.prod([mesh.shape[a] for a in mesh.axis_names]))


def sat_sharding(mesh) -> jax.sharding.NamedSharding:
    """NamedSharding placing a (..., K)-last-axis-leading (K,) array along
    the mesh's satellite axis."""
    return jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(mesh.axis_names[0]))


# ---------------------------------------------------------------------------
# satellite-axis padding (never-connected satellites: trajectory-inert)


def padded_size(K: int, mesh) -> int:
    """Smallest multiple of the mesh's device count >= K."""
    n = mesh_size(mesh)
    return -(-int(K) // n) * n


def pad_axis(arr, total: int, *, axis: int = -1, fill=0):
    """Pad `arr` with `fill` along `axis` up to length `total` (host
    numpy). The fill values model satellites that do not exist: False
    connectivity/alive rows, zero grants, self-loop neighbour indices."""
    arr = np.asarray(arr)
    pad = total - arr.shape[axis]
    if pad <= 0:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis if axis >= 0 else arr.ndim + axis] = (0, pad)
    return np.pad(arr, widths, constant_values=fill)


def pad_state(state: SS.SatState, total: int) -> SS.SatState:
    """Extend a (K,) `SatState` to `total` satellites that were never
    seeded (version/pending/buffered -1, zero progress/relay). Combined
    with all-False connectivity columns the padding is trajectory-inert:
    no upload (nothing pending), no download (never connected), no idle
    or buffer contribution, no fault revive, and self-loop ISL entries
    neither offer nor adopt anything."""
    K = state.version.shape[-1]
    pad = total - K
    if pad <= 0:
        return state

    def ext(x, fill):
        return jnp.concatenate(
            [x, jnp.full(x.shape[:-1] + (pad,), fill, x.dtype)], axis=-1)

    return SS.SatState(
        version=ext(state.version, -1),
        pending=ext(state.pending, -1),
        buffered=ext(state.buffered, -1),
        progress=None if state.progress is None else ext(state.progress, 0),
        relay=None if state.relay is None else ext(state.relay, 0))

"""Pytree checkpointing on npz (no external deps).

Flattens a pytree to path-keyed arrays; restores with the original treedef.
Also provides the bounded in-memory/off-memory trajectory store the utility
estimator consumes ({w^0..w^Imax}, paper §3.2) and its device-resident
sibling `DeviceCheckpointStore` — a stacked-pytree ring buffer the FL
engine reads base checkpoints from without a host→device transfer.
"""
from __future__ import annotations

import functools
import json
import os
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


def _flatten_with_paths(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        out[key] = np.asarray(leaf)
    return out


def save_pytree(path: str, tree) -> None:
    """Save a pytree of arrays to `path` as an npz of path-keyed leaves
    (parent directories are created; see `load_pytree` to restore)."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    np.savez(path, **_flatten_with_paths(tree))


def load_pytree(path: str, like) -> Any:
    """Restore into the structure of `like` (shapes must match)."""
    data = np.load(path)
    flat = jax.tree_util.tree_flatten_with_path(like)
    leaves = []
    for p, leaf in flat[0]:
        key = "/".join(str(getattr(q, "key", getattr(q, "idx", q)))
                       for q in p)
        arr = data[key]
        leaves.append(np.asarray(arr, dtype=leaf.dtype).reshape(leaf.shape))
    return jax.tree_util.tree_unflatten(flat[1], leaves)


class CheckpointStore:
    """Version-indexed global-model store. Keeps the newest `keep_in_memory`
    versions in RAM and (optionally) spills every `spill_every`-th version to
    disk — the utility estimator needs w^{i-s} for s <= s_max, the FL server
    needs old bases for stale satellites."""

    def __init__(self, directory: Optional[str] = None,
                 keep_in_memory: int = 32, spill_every: int = 0):
        self.dir = directory
        self.keep = keep_in_memory
        self.spill_every = spill_every
        self._mem: Dict[int, Any] = {}
        self._disk: Dict[int, str] = {}
        self._like = None

    def put(self, version: int, params) -> None:
        """Store `params` (a pytree) under integer `version`; spills to
        disk as well when the version hits the `spill_every` stride."""
        self._like = params
        self._mem[version] = params
        if self.dir and self.spill_every and version % self.spill_every == 0:
            p = os.path.join(self.dir, f"w_{version:06d}.npz")
            save_pytree(p, params)
            self._disk[version] = p

    def prune(self, min_referenced: int) -> None:
        """Drop versions older than the oldest still-referenced base
        (callers pass min over satellites' pending/buffered bases), but
        never shrink below `keep` recent versions. The cutoff applies to
        the disk spill too — spilled ``.npz`` files are unlinked, so long
        runs with `spill_every` set stay disk-bounded."""
        if not self._mem:
            return
        newest = max(self._mem)
        cutoff = min(min_referenced, newest - self.keep + 1)
        for v in [v for v in self._mem if v < cutoff]:
            del self._mem[v]
        for v in [v for v in self._disk if v < cutoff]:
            try:
                os.unlink(self._disk[v])
            except OSError:
                pass
            del self._disk[v]

    def get(self, version: int):
        """Fetch the stored pytree for `version` (memory first, then the
        disk spill). Raises KeyError for evicted/unknown versions."""
        if version in self._mem:
            return self._mem[version]
        if version in self._disk:
            return load_pytree(self._disk[version], self._like)
        raise KeyError(f"version {version} evicted "
                       f"(have {sorted(self._mem)[:4]}..)")

    def versions(self) -> List[int]:
        """Sorted list of every retrievable version (memory + disk)."""
        return sorted(set(self._mem) | set(self._disk))


# ---------------------------------------------------------------------------
# Device-resident store


@functools.partial(jax.jit, donate_argnums=(0,))
def _ring_write(ring, params, slot):
    """Write `params` into ring slot `slot` (traced, so one compiled
    program serves every slot). The ring argument is donated: XLA aliases
    the output to the input buffer, so the write is in place — no
    O(ring · model) copy per put."""
    return jax.tree.map(
        lambda b, l: jax.lax.dynamic_update_index_in_dim(
            b, l.astype(b.dtype), slot, 0), ring, params)


@jax.jit
def _ring_read(ring, slot):
    return jax.tree.map(lambda b: jax.lax.dynamic_index_in_dim(
        b, slot, 0, keepdims=False), ring)


@jax.jit
def _ring_gather(ring, slots):
    return jax.tree.map(lambda b: jnp.take(b, slots, axis=0), ring)


class DeviceCheckpointStore:
    """Device-resident `CheckpointStore`: the newest `ring` versions live
    as one stacked pytree on device (leading axis = ring slot) and are
    gathered by version index there, so `get()` of a recent version — the
    FL server fetching w^{i-s} for a stale satellite — returns device
    arrays with no host→device transfer. Versions evicted from the ring
    while still retained spill to host memory (and optionally disk, same
    `spill_every` policy), behind the same put/get/prune/versions contract.

    Size the ring to s_max plus margin: Algorithm 1 references bases at
    most `prune`'s retention window deep, so in steady state every
    `get` is served from device."""

    def __init__(self, ring: int = 34, directory: Optional[str] = None,
                 spill_every: int = 0):
        self.keep = ring
        self.dir = directory
        self.spill_every = spill_every
        self._ring = None                       # stacked pytree, axis0=ring
        self._slot_ver: List[Optional[int]] = [None] * ring
        self._ver_slot: Dict[int, int] = {}
        self._host: Dict[int, Any] = {}         # spilled host pytrees
        self._disk: Dict[int, str] = {}
        self._like = None

    def put(self, version: int, params) -> None:
        """Write `params` into the ring slot for `version` (an in-place
        donated device write); a still-retained version occupying the slot
        is spilled to host first. Disk spill follows `spill_every`."""
        params = jax.tree.map(jnp.asarray, params)
        self._like = params
        if self._ring is None:
            self._ring = jax.tree.map(
                lambda l: jnp.zeros((self.keep,) + l.shape, l.dtype),
                params)
            # compile the spill read with the ring, not at the first spill
            _ring_read(self._ring, jnp.int32(0))
        slot = version % self.keep
        evicted = self._slot_ver[slot]
        if evicted is not None and evicted != version \
                and evicted in self._ver_slot:
            # still retained (not pruned): spill to host before overwrite
            self._host[evicted] = jax.tree.map(
                np.asarray, _ring_read(self._ring, jnp.int32(slot)))
            del self._ver_slot[evicted]
        self._ring = _ring_write(self._ring, params, jnp.int32(slot))
        self._ver_slot[version] = slot
        self._slot_ver[slot] = version
        self._host.pop(version, None)
        if self.dir and self.spill_every and version % self.spill_every == 0:
            p = os.path.join(self.dir, f"w_{version:06d}.npz")
            save_pytree(p, params)
            self._disk[version] = p

    def get(self, version: int):
        """Fetch `version` as device arrays: a device gather when it is
        still in the ring, else re-upload from the host/disk spill.
        Raises KeyError for evicted/unknown versions."""
        slot = self._ver_slot.get(version)
        if slot is not None:
            return _ring_read(self._ring, jnp.int32(slot))
        if version in self._host:
            return jax.tree.map(jnp.asarray, self._host[version])
        if version in self._disk:
            return jax.tree.map(jnp.asarray,
                                load_pytree(self._disk[version], self._like))
        raise KeyError(f"version {version} evicted "
                       f"(have {self.versions()[:4]}..)")

    def get_many(self, versions):
        """Stacked device gather of several in-ring versions (leading axis
        = len(versions), one program per length); when any requested
        version has spilled off the ring, the per-version `get`s are
        stacked on the host and uploaded once, which compiles nothing."""
        slots = [self._ver_slot.get(v) for v in versions]
        if all(s is not None for s in slots):
            return _ring_gather(self._ring, np.asarray(slots, np.int32))
        return jax.tree.map(lambda *ls: jnp.asarray(np.stack(ls)),
                            *[self.get(v) for v in versions])

    def prune(self, min_referenced: int) -> None:
        """Same retention rule as `CheckpointStore.prune`, applied to ring
        bookkeeping, host spill, and disk spill (files unlinked)."""
        known = list(self._ver_slot) + list(self._host)
        if not known:
            return
        newest = max(known)
        cutoff = min(min_referenced, newest - self.keep + 1)
        for v in [v for v in self._ver_slot if v < cutoff]:
            self._slot_ver[self._ver_slot.pop(v)] = None
        for v in [v for v in self._host if v < cutoff]:
            del self._host[v]
        for v in [v for v in self._disk if v < cutoff]:
            try:
                os.unlink(self._disk[v])
            except OSError:
                pass
            del self._disk[v]

    def versions(self) -> List[int]:
        """Sorted list of every retrievable version (ring + spills)."""
        return sorted(set(self._ver_slot) | set(self._host)
                      | set(self._disk))

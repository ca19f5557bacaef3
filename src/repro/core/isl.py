"""Inter-satellite links (ISLs): intra-plane ring topology derived from the
constellation geometry, sink-satellite election, and the device-resident
relay/gossip transitions that compose with the Algorithm-1 protocol steps.

FedSpace's satellites talk only to ground stations; the strongest related
work closes exactly that gap with ISLs. This module implements the two
mechanisms the engine's schedulers build on:

  * **intra-plane propagation with sink satellites** (Razmi et al., arXiv
    2302.13447): satellites in one orbital plane form a ring over
    intra-plane ISLs; per planning epoch, each plane elects the member
    with the earliest (tie: longest) upcoming ground contact as its *sink*,
    every member relays its trained update around the ring toward the
    sink, and the sink uplinks the plane's partial aggregate in one pass.
    Here that is a `relay` hop counter on `repro.core.staleness.SatState`
    (`relay_step`) plus sink-indexed effective connectivity: a member's
    upload becomes GS-visible once its update has accumulated enough hop
    units to have reached the sink, and the whole plane uploads/downloads
    through the sink's contacts.
  * **asynchronous gossip over ISLs** (Razmi et al., arXiv 2206.00307):
    between ground contacts, ring neighbours (optionally grid neighbours
    across planes) exchange models and a satellite that sees a newer
    global version adopts it and restarts local training on it
    (`gossip_step` — the ISL analogue of `download_step`'s
    restart-on-newer-model rule). Uploads still happen at each
    satellite's own physical ground contacts.

`ISLConfig` mirrors `repro.fl.api.LinkConfig`'s zero sentinels: rate or
model size 0 means instantaneous one-window hops; otherwise one ring hop
takes `transfer_windows(isl_mbps, model_mb, T0)` protocol windows. With
``isl=None`` (the default everywhere) none of this exists in the compiled
programs — the `relay` column stays an empty pytree node and every
trajectory is bit-identical to the ground-only protocol.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import staleness as SS
from repro.core.connectivity import (ConstellationSpec, satellite_elements,
                                     transfer_windows)

T0_S = 900.0     # protocol window length (15 min), the hop-latency unit


@dataclass(frozen=True)
class ISLConfig:
    """Declarative ISL options, resolved by `Federation.from_experiment`.

    Zero sentinels mirror `LinkConfig`: `isl_mbps` or `model_mb` 0 makes a
    ring hop instantaneous (one update crosses any ring distance within
    the window it was trained in); both positive make one hop take
    ``transfer_windows(isl_mbps, model_mb, T0)`` windows, so an update
    `d` hops from its sink arrives after ``d * relay_windows`` windows.
    `epoch` is the sink re-election period in windows (2302.13447 re-picks
    the sink per visiting period); `cross_plane` adds grid links to the
    neighbouring planes of the same shell (used by gossip mode, where it
    lets model versions cross planes that never see a ground station)."""
    isl_mbps: float = 0.0      # inter-satellite link rate; 0 = instantaneous
    model_mb: float = 0.0      # model transfer size; 0 = instantaneous
    cross_plane: bool = False  # grid links to adjacent planes (gossip)
    epoch: int = 24            # sink re-election period, windows

    def __post_init__(self):
        if self.isl_mbps < 0:
            raise ValueError(
                f"ISLConfig.isl_mbps must be >= 0, got {self.isl_mbps}")
        if self.model_mb < 0:
            raise ValueError(
                f"ISLConfig.model_mb must be >= 0, got {self.model_mb}")
        if int(self.epoch) < 1:
            raise ValueError(
                f"ISLConfig.epoch must be >= 1, got {self.epoch}")

    @property
    def relay_windows(self) -> int:
        """Windows one ring hop takes (0 = instantaneous sentinel)."""
        return transfer_windows(self.isl_mbps, self.model_mb, T0_S)


@dataclass(frozen=True)
class ISLTopology:
    """Ring (and optional grid) adjacency over a constellation.

    All arrays are (K,) int32, host-side (the engine moves what it needs
    to device once per run). Planes are *physical* orbital planes: the
    satellites sharing a shell, RAAN, inclination, and altitude — so
    single-shell Planet-Flock specs split into sun-synchronous planes and
    ISS-orbit planes exactly as the geometry dictates, and shells never
    mix. Within a plane, satellites are ordered by along-track phase; the
    ring closes over that order. Degenerate planes are self-loops
    (``nxt == prv == self``), which make every ISL transition a no-op for
    them. `left`/`right` are the same-slot members of the adjacent planes
    of the same shell (self when the shell has a single plane)."""
    plane: np.ndarray    # plane id per satellite
    pos: np.ndarray      # ring position within the plane (phase order)
    nxt: np.ndarray      # ring successor (self when alone)
    prv: np.ndarray      # ring predecessor (self when alone)
    left: np.ndarray     # same-slot member of previous plane in shell
    right: np.ndarray    # same-slot member of next plane in shell

    @property
    def num_planes(self) -> int:
        return int(self.plane.max()) + 1 if self.plane.size else 0

    def plane_sizes(self) -> np.ndarray:
        """(num_planes,) member count per plane."""
        return np.bincount(self.plane, minlength=self.num_planes)

    def ring_distance(self, target: np.ndarray) -> np.ndarray:
        """(K,) minimal ring hop count from each satellite to `target[k]`
        (an array of per-satellite targets in the same plane, e.g. the
        elected sinks)."""
        n = self.plane_sizes()[self.plane]
        d = (self.pos - self.pos[target]) % n
        return np.minimum(d, n - d).astype(np.int32)


def _shell_ids(spec: ConstellationSpec) -> np.ndarray:
    """(K,) shell index per satellite (all 0 for single-shell specs)."""
    if spec.shells:
        return np.concatenate(
            [np.full(s.num_satellites, i, np.int32)
             for i, s in enumerate(spec.shells)])
    return np.zeros(spec.num_satellites, np.int32)


def ring_topology(spec: ConstellationSpec) -> ISLTopology:
    """Derive the intra-plane ring (+ cross-plane grid) adjacency from the
    spec's deterministic orbital elements.

    Satellites are grouped into physical planes by (shell, RAAN,
    inclination, altitude) — on the legacy single-shell path this puts the
    ISS-orbit satellites (different inclination/altitude) in their own
    planes, never ringed with the sun-synchronous ones, and multi-shell
    Walker specs decompose into their per-shell planes. The grouping is a
    pure function of the spec, like everything else in the geometry layer.
    """
    raan, inc, phase, alt = satellite_elements(spec)
    shell = _shell_ids(spec)
    key = np.stack([shell.astype(np.float64), np.round(raan, 9),
                    np.round(inc, 9), np.round(alt, 3)], axis=1)
    _, plane = np.unique(key, axis=0, return_inverse=True)
    plane = plane.astype(np.int32)
    K = plane.shape[0]
    pos = np.zeros(K, np.int32)
    nxt = np.arange(K, dtype=np.int32)
    prv = np.arange(K, dtype=np.int32)
    members = {}                     # plane id -> members in ring order
    for p in np.unique(plane):
        m = np.flatnonzero(plane == p)
        order = m[np.lexsort((m, phase[m]))]
        members[int(p)] = order
        pos[order] = np.arange(order.size)
        if order.size > 1:
            nxt[order] = np.roll(order, -1)
            prv[order] = np.roll(order, 1)
    left, right = _grid_neighbors(shell, plane, raan, members)
    return ISLTopology(plane=plane, pos=pos, nxt=nxt, prv=prv,
                       left=left, right=right)


def _grid_neighbors(shell, plane, raan, members):
    """Same-slot links to the adjacent planes of the same shell (RAAN
    order, wrapping), self where the shell has a single plane. Slot r of a
    plane maps to slot ``r % n`` of a differently-sized neighbour."""
    K = plane.shape[0]
    left = np.arange(K, dtype=np.int32)
    right = np.arange(K, dtype=np.int32)
    for s in np.unique(shell):
        pids = np.unique(plane[shell == s])
        order = pids[np.argsort([raan[members[int(p)][0]] for p in pids],
                                kind="stable")]
        if order.size < 2:
            continue
        for j, p in enumerate(order):
            mine = members[int(p)]
            for arr, q in ((left, order[(j - 1) % order.size]),
                           (right, order[(j + 1) % order.size])):
                other = members[int(q)]
                arr[mine] = other[np.arange(mine.size) % other.size]
    return left, right


def identity_topology(K: int) -> ISLTopology:
    """The degenerate no-ISL topology — every satellite its own singleton
    plane, every link a self-loop. Under it, sink election picks each
    satellite as its own sink and every relay arrives in place, so an
    ISL-enabled run must reproduce the plain ground-only protocol
    bit-for-bit (the parity gate
    `tests/test_isl.py::test_identity_topology_parity_with_fedbuff` runs
    exactly this)."""
    idx = np.arange(K, dtype=np.int32)
    return ISLTopology(plane=idx.copy(), pos=np.zeros(K, np.int32),
                       nxt=idx.copy(), prv=idx.copy(), left=idx.copy(),
                       right=idx.copy())


@dataclass(frozen=True)
class ISL:
    """Resolved ISL runtime handed to the engine and the schedulers:
    the derived topology plus the `ISLConfig`-resolved hop latency and
    election period. Built by `build_isl` (via
    `repro.fl.api.Federation.from_experiment` when `FLExperiment.isl`
    is set)."""
    topology: ISLTopology
    relay_windows: int = 0
    epoch: int = 24
    cross_plane: bool = False

    def sink_plan(self, C_epoch: np.ndarray, *, alive=None):
        """Sinks and per-satellite hop needs for one election epoch:
        returns ``(sink (K,), need_hops (K,))`` from the epoch's effective
        connectivity slice (`elect_sinks` + ring distances scaled by the
        hop latency; instantaneous hops need 0). `alive` (fault runs)
        restricts the election to satellites alive at some point in the
        epoch — a deorbited member must not be elected sink."""
        sink = elect_sinks(C_epoch, self.topology, alive=alive)
        need = self.topology.ring_distance(sink) * self.relay_windows
        return sink, need.astype(np.int32)


def build_isl(spec: ConstellationSpec, config: ISLConfig) -> ISL:
    """Resolve an `ISLConfig` against a constellation spec."""
    return ISL(topology=ring_topology(spec),
               relay_windows=config.relay_windows,
               epoch=max(int(config.epoch), 1),
               cross_plane=config.cross_plane)


def elect_sinks(C_epoch: np.ndarray, topo: ISLTopology, *,
                alive=None) -> np.ndarray:
    """Per-plane sink election (2302.13447 §III): the member whose next
    ground contact in the epoch comes earliest wins; ties go to the member
    with the most contact windows in the epoch, then the lowest satellite
    index. Planes with no contact in the epoch elect their lowest-index
    member (their ring still relays, it just never reaches ground until a
    later epoch's election sees a contact).

    Args:
      C_epoch: (W, K) bool — the epoch's (effective) connectivity slice.
      topo: the ring topology whose `plane` grouping scopes the election.
      alive: optional (K,) bool candidate mask (`repro.core.faults`):
        dead satellites are never elected; an all-dead plane falls back to
        the full membership (its election is moot — no member can act).

    Returns (K,) int32: each satellite's elected sink (same plane always).
    """
    C_epoch = np.asarray(C_epoch, bool)
    W = C_epoch.shape[0]
    has = C_epoch.any(axis=0)
    first = np.where(has, C_epoch.argmax(axis=0), W)     # W = "never"
    total = C_epoch.sum(axis=0)
    sink = np.empty(topo.plane.shape[0], np.int32)
    alive = None if alive is None else np.asarray(alive, bool)
    for p in np.unique(topo.plane):
        m = np.flatnonzero(topo.plane == p)
        cand = m if alive is None else m[alive[m]]
        if cand.size == 0:
            cand = m
        best = cand[np.lexsort((cand, -total[cand], first[cand]))][0]
        sink[m] = best
    return sink


# ---------------------------------------------------------------------------
# Device-resident ISL transitions. Pure jnp over SatState, composable with
# upload_step/aggregate_step/download_step inside the engine's jitted scan
# (repro.fl.engine._scan_windows) and its per-window host-loop wrappers —
# one transition semantics for both execution strategies, like the rest of
# the protocol.


def relay_step(state: SS.SatState, need_hops):
    """Advance the intra-ring relay by one window: every satellite holding
    a pending update accumulates one hop unit toward its sink. Returns
    ``(state, arrived)`` where ``arrived[k]`` means k's update has covered
    its ring distance (``relay >= need_hops``; distance-0 satellites —
    sinks, and everyone under instantaneous hops — arrive immediately).

    The counter resets on download (`reset_relay`) when the satellite
    starts its next local round, so `relay` measures transit of the
    *current* pending update. Re-elections mid-transit keep the
    accumulated units (the partial aggregate is already moving along the
    ring; 2302.13447 re-targets it rather than restarting)."""
    relay = state.relay + (state.pending >= 0).astype(state.relay.dtype)
    return state._replace(relay=relay), relay >= need_hops


def reset_relay(state: SS.SatState, downloads):
    """Zero the relay counter where a download started a fresh local round
    (the new pending update begins its ring transit from scratch)."""
    return state._replace(
        relay=jnp.where(downloads, 0, state.relay))


def sink_connectivity(conn, sink, arrived, pending, *, axis_name=None):
    """Effective connectivity under sink relaying: satellite k can reach
    the GS this window iff its plane's sink has a (served) contact AND
    k's update has arrived at the sink — or k has nothing in transit
    (idle / download-only contacts ride the sink's pass directly, the
    ring broadcast of the global model being pipelined within the
    window).

    `sink` holds *global* satellite indices; when the satellite axis is
    sharded (`axis_name`, see `repro.core.mesh`) the connectivity row is
    `all_gather`ed — one (K,) bool row per window — so each shard can
    look up its plane's sink wherever it lives."""
    if axis_name is not None:
        conn = jax.lax.all_gather(conn, axis_name, tiled=True)
    return conn[sink] & (arrived | (pending < 0))


def gossip_step(state: SS.SatState, nxt, prv, left, right, do_hop,
                alive=None, *, axis_name=None):
    """One asynchronous intra-ring gossip exchange (2206.00307): each
    satellite looks at its ring neighbours (and grid neighbours, which are
    self-loops unless cross-plane links are configured) and, when `do_hop`
    is set and a neighbour holds a newer global version, adopts it and
    restarts local training on it — exactly `download_step`'s
    restart-on-newer-model rule, with the neighbour in place of the GS.
    Version-equal neighbours exchange nothing the protocol state can see
    (model *averaging* between same-version peers does not change
    version/pending/staleness bookkeeping), so the transition tracks
    propagation, which is what staleness/idleness accounting needs.

    `alive` (fault runs, (K,) bool) removes dead satellites from the
    exchange entirely: they offer nothing to their neighbours (their
    version reads as -1, below any live version) and adopt nothing
    themselves. `alive=None` compiles the exact prior program.

    The neighbour arrays hold *global* satellite indices; when the
    satellite axis is sharded (`axis_name`) the masked version vector is
    `all_gather`ed — one (K,) int row per hop — before the four gathers,
    so ring/grid neighbours resolve across shard boundaries.

    Returns ``(state, adopted)`` with the adoption mask."""
    v = state.version
    vn = v if alive is None else jnp.where(alive, v, SS._m1(v))
    if axis_name is not None:
        vn = jax.lax.all_gather(vn, axis_name, tiled=True)
    nbv = jnp.maximum(jnp.maximum(vn[nxt], vn[prv]),
                      jnp.maximum(vn[left], vn[right]))
    adopted = do_hop & (nbv > v)
    if alive is not None:
        adopted = adopted & alive
    return state._replace(version=jnp.where(adopted, nbv, v),
                          pending=jnp.where(adopted, nbv, state.pending)), \
        adopted


def reachable_count(topo: ISLTopology, C: np.ndarray) -> int:
    """Number of satellites in planes with at least one (effective) ground
    contact over the run — the natural sync threshold for sink-relay
    scheduling (planes that never see a station can never contribute, so
    waiting for all K would deadlock e.g. mid-inclination Starlink shells
    over a polar-only ground network)."""
    has = np.asarray(C, bool).any(axis=0)
    reach = np.unique(topo.plane[has])
    return int(np.isin(topo.plane, reach).sum())

"""Event-driven FL simulation engine over the connectivity sequence
(Algorithm 1), decomposed into overridable protocol steps.

Time advances in T0 windows (15 min each). At window i the GS:
  receives pending updates from connected satellites (`on_uploads`), asks
  the scheduler whether to aggregate a^i (`on_decide`), applies the
  staleness-compensated update of eq. 4 when a^i = 1 (`on_aggregate`), and
  broadcasts the current model (`on_downloads`).

The per-satellite protocol state is the device-resident
`repro.core.staleness.SatState`, advanced through the SAME jitted
sub-transitions (`upload_step` / `aggregate_step` / `download_step`) the
schedule-search simulator scans — one Algorithm-1 implementation shared by
the engine, the search, and the utility sampler. The former numpy arrays
(`version` / `pending` / `buffered_base`) survive as read-only host
mirrors, materialized only at diagnostic points.

Two execution strategies, same trajectory bit-for-bit:
  * fast loop (default): when no protocol step is overridden and the
    scheduler provides `device_plan`, windows run in chunked jitted scans
    (`_scan_windows`) that stop at the first aggregation event — per-window
    Python dispatch and device→host transfers disappear from the hot loop;
  * host loop: per-window `on_uploads`/`on_decide`/`on_aggregate`/
    `on_downloads` calls through the same transitions, taken automatically
    for subclassed steps or schedulers without a device plan.

Finite link budgets (`repro.core.connectivity.LinkBudget`, built by the
`Federation` layer from `LinkConfig`) slot into the same transitions: the
engine then runs on capacity-resolved effective connectivity and gates
every upload/download on accumulated per-window transfer grants, under
both execution strategies.

Subclass and override a step to model protocol variants (ISL propagation,
sink satellites, lossy links); attach `repro.fl.callbacks.Callback`s for
cross-cutting concerns (metric streaming, checkpointing, early stop).
`repro.fl.simulation.run_simulation` is a thin back-compat wrapper.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass, field
from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt.checkpoint import DeviceCheckpointStore
from repro.core import faults as FT
from repro.core import isl as ISL
from repro.core import mesh as MM
from repro.core import staleness as SS
from repro.core.aggregation import aggregation_weights
from repro.core.scheduler import Scheduler
from repro.data.pipeline import row_bucket
from repro.fl.client import make_batched_client_update
from repro.kernels.agg.ops import aggregate_params_tree

T0_MINUTES = 15.0

# Upper bound on windows per jitted scan: chunks are bucketed to powers of
# two up to this, so the scan compiles O(log) shapes per scheduler kind.
_MAX_CHUNK = 128


# ---------------------------------------------------------------------------
# an aggregation event's rows


@jax.jit
def _take_rows(params, stacks, src):
    """Rows `src` of the update stacks laid end to end; the index just past
    their end is an exact-zero row."""
    return jax.tree.map(
        lambda p, *us: jnp.concatenate(
            us + (jnp.zeros((1,) + p.shape, p.dtype),))[src],
        params, *stacks)


def _client_batch_many(adapter, client_ids, *args):
    """`client_batch_many` for an adapter with only `client_batch`: the
    clients whose batch has the first batch's shapes, stacked on the host
    and padded to `row_bucket(len(client_ids))` rows."""
    got = [adapter.client_batch(int(k), *args) for k in client_ids]
    shapes = [None if b is None else [np.shape(x) for x in jax.tree.leaves(b)]
              for b in got]
    first = next((g for g in shapes if g is not None), None)
    rows = [r for r, g in enumerate(shapes) if g is not None and g == first]
    if not rows:
        return None, []
    pad = row_bucket(len(client_ids)) - len(rows)
    return jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)), *[
        got[r] for r in rows + rows[:1] * pad]), rows


# ---------------------------------------------------------------------------
# jitted protocol-transition wrappers (shared by both execution strategies)


@jax.jit
def _upload(state, ig, conn, gate):
    state, info = SS.upload_step(state, ig, conn, gate)
    return state, jnp.stack([info["n_connected"], info["n_idle"],
                             info["n_buffered"]])


@functools.partial(jax.jit, static_argnames=("s_max",))
def _aggregate_state(state, ig, *, s_max):
    # collect="none": the engine computes its own staleness bookkeeping on
    # host in `on_aggregate`, so the per-step histogram never enters the
    # compiled program at all
    state, _, _ = SS.aggregate_step(state, ig, jnp.bool_(True), s_max=s_max,
                                    collect="none")
    return state


@jax.jit
def _download(state, ig, conn, gate):
    state, _ = SS.download_step(state, ig, conn, gate)
    return state


def _sink_gate(gate, sink, axis_name=None):
    """Gather the link gate at each satellite's sink: the plane's shared
    transfer rides the sink's contact units (None passes through). `sink`
    holds global indices, so a sharded satellite axis (`axis_name`)
    gathers the full grant row first."""
    if gate is None:
        return None
    grant = gate.grant
    if axis_name is not None:
        grant = jax.lax.all_gather(grant, axis_name, tiled=True)
    return gate._replace(grant=grant[..., sink])


@jax.jit
def _isl_upload(state, ig, conn, gate, sink, need, alive=None):
    """Sink-relay upload transition (host loop): advance the ring relay
    one window, then run the shared `upload_step` on sink-indexed
    effective connectivity — a member uploads once its update has hopped
    to its plane's sink and the sink has a (served, grant-sufficient)
    contact. `alive` (fault runs) removes dead satellites from the
    sink-routed path — a dead member must not ride its sink's contact."""
    state, arrived = ISL.relay_step(state, need)
    eff = ISL.sink_connectivity(conn, sink, arrived, state.pending)
    if alive is not None:
        eff = eff & alive
    state, info = SS.upload_step(state, ig, eff, _sink_gate(gate, sink))
    return state, jnp.stack([info["n_connected"], info["n_idle"],
                             info["n_buffered"]])


@jax.jit
def _isl_download(state, ig, conn, gate, sink, need, alive=None):
    """Sink-relay download transition (host loop): the plane fetches the
    global model through the sink's contact (no relay advance — uploads
    advanced it this window already); satellites starting a fresh round
    reset their relay counter."""
    arrived = state.relay >= need
    eff = ISL.sink_connectivity(conn, sink, arrived, state.pending)
    if alive is not None:
        eff = eff & alive
    state, dn = SS.download_step(state, ig, eff, _sink_gate(gate, sink))
    return ISL.reset_relay(state, dn["downloads"])


@jax.jit
def _gossip(state, nxt, prv, left, right, do_hop, alive=None):
    state, _ = ISL.gossip_step(state, nxt, prv, left, right, do_hop,
                               alive=alive)
    return state


_fault_reset = jax.jit(FT.fault_reset)


def _tree_where(pred, a, b):
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def _scan_impl(state, ig, C_dev, i0, n_valid, ind_args, link_dev,
               isl_dev=None, faults_dev=None, *, indicator, horizon,
               isl_mode=None, axis=None):
    """Advance the protocol over up to `horizon` windows starting at
    absolute window i0, freezing at the first window whose aggregation
    indicator fires (post-upload, pre-aggregation — the engine trains and
    aggregates on host, then resumes). `ig` is constant throughout: no
    aggregation happens inside the scan. Windows at offset >= n_valid are
    padding (bucketed horizon) and leave the state untouched.

    `link_dev` is None (instantaneous transfers) or ``(G_dev, need_up,
    need_dn)`` — the padded device grants matrix plus unit needs — in which
    case the scanned upload/download transitions are gated per window
    through the shared `repro.core.staleness.LinkGate` semantics.

    `isl_mode`/`isl_dev` thread the ISL transitions (`repro.core.isl`)
    into the same scan: ``"sink"`` takes ``(sink, need_hops)`` — one
    election, valid for the whole chunk (the engine clips chunks to
    election epochs) — and runs relay advance + sink-indexed effective
    connectivity around the shared transitions; ``"gossip"`` takes
    ``(nxt, prv, left, right, period)`` and applies the neighbour
    version-exchange before each window's upload. ``None`` (the default)
    compiles the exact ground-only program of previous releases.

    `faults_dev` is None (no fault injection — the exact prior program)
    or ``(revive_dev, alive_dev)`` padded device masks: each window first
    applies `repro.core.faults.fault_reset` to reviving satellites (forced
    re-download on re-entry), and the alive mask additionally gates the
    ISL paths (dead satellites neither gossip nor ride their sink's
    contact — plain connectivity is already masked in `C_dev` by the
    engine).

    `axis` names the mesh axis when the satellite dimension of every
    array here is a shard (`_scan_windows` wraps this body in
    `shard_map`): the transition counters become exact integer psums and
    the ISL sink/neighbour lookups gather the one (K,) row they index —
    everything else runs embarrassingly parallel over the shard.

    Returns (state, counters (horizon, 4) int32) with per-window
    [n_connected, n_idle, n_buffered, a]; counter rows after the event row
    are garbage the caller must ignore.
    """
    xs = {"t": i0 + jnp.arange(horizon),
          "conn": jax.lax.dynamic_slice_in_dim(C_dev, i0, horizon, axis=0)}
    if link_dev is not None:
        G_dev, need_up, need_dn = link_dev
        xs["grant"] = jax.lax.dynamic_slice_in_dim(G_dev, i0, horizon,
                                                   axis=0)
    if faults_dev is not None:
        R_dev, A_dev = faults_dev
        xs["revive"] = jax.lax.dynamic_slice_in_dim(R_dev, i0, horizon,
                                                    axis=0)
        xs["alive"] = jax.lax.dynamic_slice_in_dim(A_dev, i0, horizon,
                                                   axis=0)

    def body(carry, inp):
        st, done = carry
        t, conn = inp["t"], inp["conn"]
        gate = None if link_dev is None \
            else SS.LinkGate(inp["grant"], need_up, need_dn)
        live = (~done) & (t - i0 < n_valid)
        alive = inp["alive"] if faults_dev is not None else None
        stf = st if faults_dev is None else FT.fault_reset(st,
                                                           inp["revive"])
        if isl_mode == "sink":
            sink, need = isl_dev
            st2, arrived = ISL.relay_step(stf, need)
            up_conn = ISL.sink_connectivity(conn, sink, arrived,
                                            st2.pending, axis_name=axis)
            if alive is not None:
                up_conn = up_conn & alive
            gate = _sink_gate(gate, sink, axis)
            up_st, info = SS.upload_step(st2, ig, up_conn, gate,
                                         axis_name=axis)
            dn_conn = ISL.sink_connectivity(conn, sink, arrived,
                                            up_st.pending, axis_name=axis)
            if alive is not None:
                dn_conn = dn_conn & alive
        elif isl_mode == "gossip":
            g_nxt, g_prv, g_left, g_right, period = isl_dev
            do_hop = (period <= 1) | (t % period == 0)
            st2, _ = ISL.gossip_step(stf, g_nxt, g_prv, g_left, g_right,
                                     do_hop, alive=alive, axis_name=axis)
            up_st, info = SS.upload_step(st2, ig, conn, gate,
                                         axis_name=axis)
            dn_conn = conn
        else:
            up_st, info = SS.upload_step(stf, ig, conn, gate,
                                         axis_name=axis)
            dn_conn = conn
        n_buf = info["n_buffered"]
        a = live & indicator(t, n_buf, ind_args) & (n_buf > 0)
        dl_st, dn = SS.download_step(up_st, ig, dn_conn, gate)
        if isl_mode == "sink":
            dl_st = ISL.reset_relay(dl_st, dn["downloads"])
        new_st = _tree_where(live, _tree_where(a, up_st, dl_st), st)
        counters = jnp.stack([info["n_connected"], info["n_idle"], n_buf,
                              a.astype(jnp.int32)])
        return (new_st, done | a), counters

    (state, _), counters = jax.lax.scan(body, (state, jnp.bool_(False)), xs)
    return state, counters


@functools.partial(jax.jit, static_argnames=("indicator", "horizon",
                                             "isl_mode", "mesh"))
def _scan_windows(state, ig, C_dev, i0, n_valid, ind_args, link_dev,
                  isl_dev=None, faults_dev=None, *, indicator, horizon,
                  isl_mode=None, mesh=None):
    """`_scan_impl`, jitted — and, when `mesh` is given (a
    `jax.sharding.Mesh`, static: meshes hash), wrapped in `shard_map`
    along the satellite axis. Satellite-sized inputs (state columns, the
    connectivity/grant/fault matrices, ISL index arrays) shard; window
    indices, `ig`, the indicator args, and the link needs replicate; the
    counters come back replicated because every cross-shard quantity
    inside is an exact integer psum — so the host-side event loop reads
    identical values from any shard and `mesh=None` compiles the exact
    single-device program of previous releases."""
    impl = functools.partial(_scan_impl, indicator=indicator,
                             horizon=horizon, isl_mode=isl_mode)
    if mesh is None:
        return impl(state, ig, C_dev, i0, n_valid, ind_args, link_dev,
                    isl_dev, faults_dev)
    ax = mesh.axis_names[0]
    P = jax.sharding.PartitionSpec
    sat, rep, col = P(ax), P(), P(None, ax)
    link_spec = rep if link_dev is None else (col, rep, rep)
    if isl_mode == "sink":
        isl_spec = (sat, sat)
    elif isl_mode == "gossip":
        isl_spec = (sat, sat, sat, sat, rep)
    else:
        isl_spec = rep
    faults_spec = rep if faults_dev is None else (col, col)
    sharded = MM.shard_map(
        functools.partial(impl, axis=ax), mesh,
        in_specs=(sat, rep, col, rep, rep, rep, link_spec, isl_spec,
                  faults_spec),
        out_specs=(sat, rep))
    return sharded(state, ig, C_dev, i0, n_valid, ind_args, link_dev,
                   isl_dev, faults_dev)


@dataclass
class SimResult:
    """Outcome of one simulated federated run.

    Fields: `scheme` (scheduler name), `accuracy`/`val_loss`/
    `eval_windows` (one entry per eval checkpoint), `staleness_hist`
    (aggregated-gradient counts per clipped staleness),
    `idle_connections`/`total_connections` (eq.-10 idleness accounting),
    `num_global_updates` (aggregations), `num_aggregated_gradients`,
    `windows_run`, and `time_to_target_days`/`target_acc` when a target
    accuracy was set. `replan_stats` carries the `ReplanService` counters
    (full vs delta replans, invalidation reasons) when the scheduler
    routes eq.-13 searches through one. `days(window)` converts a window
    index to simulated days; `summary()` returns the JSON-friendly
    digest."""
    scheme: str
    accuracy: List[float] = field(default_factory=list)
    val_loss: List[float] = field(default_factory=list)
    eval_windows: List[int] = field(default_factory=list)
    staleness_hist: Optional[np.ndarray] = None
    idle_connections: int = 0
    total_connections: int = 0
    num_global_updates: int = 0
    num_aggregated_gradients: int = 0
    windows_run: int = 0
    time_to_target_days: Optional[float] = None
    target_acc: Optional[float] = None
    replan_stats: Optional[dict] = None

    def days(self, window: int) -> float:
        """Simulated days elapsed at `window` (T0 = 15-minute windows)."""
        return window * T0_MINUTES / 60.0 / 24.0

    def summary(self) -> dict:
        """JSON-friendly digest (final/best accuracy, counters, hist)."""
        return {
            "scheme": self.scheme,
            "final_acc": self.accuracy[-1] if self.accuracy else None,
            "best_acc": max(self.accuracy) if self.accuracy else None,
            "time_to_target_days": self.time_to_target_days,
            "global_updates": self.num_global_updates,
            "aggregated_gradients": self.num_aggregated_gradients,
            "idle_connections": self.idle_connections,
            "total_connections": self.total_connections,
            "staleness_hist": (self.staleness_hist.tolist()
                               if self.staleness_hist is not None else None),
            "replan_stats": self.replan_stats,
        }

    def counters(self) -> dict:
        """The integer protocol counters (no float result among them)."""
        return {"global_updates": self.num_global_updates,
                "aggregated_gradients": self.num_aggregated_gradients,
                "idle_connections": self.idle_connections,
                "total_connections": self.total_connections,
                "staleness_hist": [int(c) for c in self.staleness_hist],
                "windows_run": self.windows_run}


@dataclass
class EngineConfig:
    """Protocol/training knobs of one simulated run (the former
    `run_simulation` keyword soup, as data)."""
    local_steps: int = 4
    batch_size: int = 32
    client_lr: float = 0.05
    server_lr: float = 1.0
    alpha: float = 0.5
    eval_every: int = 8
    target_acc: Optional[float] = None
    max_windows: Optional[int] = None
    repeat_connectivity: int = 1   # 0: auto-tile C to cover max_windows
    s_max: int = 8
    # None = unset: lets experiment-level settings (FLExperiment.seed,
    # LinkConfig.uplink_topk) apply without 0 doubling as a sentinel
    seed: Optional[int] = None           # unset -> 0
    stop_at_target: bool = True
    uplink_topk: Optional[float] = None  # >0: compressed uplink; unset -> 0
    # dense int8 uplink quantization (ignored when uplink_topk > 0, whose
    # kept values are already int8); unset -> False / LinkConfig fallback
    uplink_int8: Optional[bool] = None
    # False forces the per-window host loop even when the chunked jitted
    # fast loop would apply — e.g. for callbacks that must observe the
    # device state at every single window boundary
    fast_loop: bool = True

    def __post_init__(self):
        # 0.0 stays legal alongside None: the engine resolves the unset
        # sentinel to 0.0 via dataclasses.replace, which re-runs this hook
        v = self.uplink_topk
        if v is not None and v != 0.0 and not 0.0 < v <= 1.0:
            raise ValueError(
                f"EngineConfig.uplink_topk must be in (0, 1], got {v}")


class RunArtifacts(NamedTuple):
    """The resolved world arrays one run executes on: the effective
    connectivity/grants (`C`/`grants`), the scheduler-facing planning view
    (`plan_C`/`plan_grants` — the same objects unless a blind fault trace
    splits them), and the horizon-extended `FaultTrace`."""
    C: np.ndarray
    grants: Optional[np.ndarray]
    plan_C: np.ndarray
    plan_grants: Optional[np.ndarray]
    trace: Optional[FT.FaultTrace]


def resolve_run_artifacts(C, cfg: EngineConfig, *, link_budget=None,
                          faults=None) -> RunArtifacts:
    """Resolve raw world inputs into `RunArtifacts`: substitute the link
    budget's capacity-resolved `served` matrix, tile the connectivity (and
    grants) to the requested horizon per `cfg.repeat_connectivity`, extend
    the fault trace over the tiled length, and split the plan view from
    the executed view (clean-vs-masked under a blind trace, identical
    under none/oracle). One resolution semantics shared by the engine and
    the batched sweep (`repro.fl.sweep`)."""
    grants = assign = None
    if link_budget is not None:
        C = link_budget.served
        grants = np.asarray(link_budget.grants, np.int32)
        assign = np.asarray(link_budget.assign, np.int32)
    repeat = cfg.repeat_connectivity
    if repeat == 0:    # auto: tile C up to the requested horizon
        need = cfg.max_windows or C.shape[0]
        repeat = max(1, -(-int(need) // C.shape[0]))
    if repeat > 1:
        C = np.concatenate([C] * repeat, axis=0)
        if grants is not None:
            grants = np.concatenate([grants] * repeat, axis=0)
            assign = np.concatenate([assign] * repeat, axis=0)
    C = np.asarray(C, bool)
    # plan view (what schedulers see) vs executed view (what the run
    # applies): the same objects without faults or under an oracle
    # trace, clean-vs-masked under a blind one
    plan_C, plan_grants = C, grants
    trace = None if faults is None else faults.extended(C.shape[0])
    if trace is None:
        exec_C, exec_grants = C, grants
    elif link_budget is not None:
        exec_C, exec_grants = FT.mask_served(C, grants, assign, trace)
    else:
        exec_C = C & trace.mask[:C.shape[0]]
        exec_grants = None
    if trace is not None and trace.oracle:
        plan_C, plan_grants = exec_C, exec_grants
    return RunArtifacts(exec_C, exec_grants, plan_C, plan_grants, trace)


class SimulationEngine:
    """One federated run: connectivity x adapter x scheduler -> SimResult.

    Protocol steps (`on_uploads`, `on_decide`, `on_aggregate`,
    `on_downloads`) are methods so scenario variants override exactly the
    step they change; callbacks observe the run without touching it.

    Execution-strategy selection (both strategies are bit-identical):
      * the chunked **fast loop** (`_scan_windows`) runs when ALL of —
        `EngineConfig.fast_loop` is True (default), no protocol step is
        overridden in a subclass, and `Scheduler.device_plan` returns a
        plan for the current window;
      * otherwise each window goes through the per-window **host loop**
        (`_run_window`) — one `on_uploads`/`on_decide`/`on_aggregate`/
        `on_downloads` cycle per window through the same jitted
        transitions.
    Fast-loop chunks are clipped to eval boundaries (where `status`
    changes), the scheduler's plan horizon, and `_MAX_CHUNK`, then
    bucketed to powers of two so jit compiles O(log) scan shapes.

    Args:
      C: (num_windows, K) bool connectivity matrix (tiled per
        `EngineConfig.repeat_connectivity`).
      adapter: model adapter (init/loss/client_batch/accuracy/val_loss).
      scheduler: aggregation policy (`repro.core.scheduler.Scheduler`).
      config: `EngineConfig`; keyword `overrides` replace single fields.
      callbacks: `repro.fl.callbacks` observers.
      init_params: optional initial global model (default: adapter.init).
      link_budget: optional `repro.core.connectivity.LinkBudget`. When
        given, the engine runs on its capacity-resolved `served` matrix
        (the `C` argument is replaced — schedulers then plan against
        effective connectivity), satellites carry the in-progress-transfer
        column, and every upload/download is gated on accumulated contact
        units through the shared `LinkGate` transitions — in the fast loop
        and the host loop alike. A trivial budget (unlimited capacity,
        zero needs) is bit-identical to `link_budget=None`.
      isl: optional `repro.core.isl.ISL` runtime (topology + hop latency +
        election period, resolved by `Federation.from_experiment` from
        `FLExperiment.isl`). It only takes effect when the scheduler also
        declares an `isl_mode` ("sink": intra-plane relay toward elected
        sink satellites; "gossip": asynchronous neighbour version
        exchange) — ground-only schedulers under the same experiment run
        the unmodified protocol, so with/without-ISL comparisons share one
        world. `isl=None` (default) leaves every code path bit-identical
        to previous releases.
      faults: optional `repro.core.faults.FaultTrace` (resolved by
        `Federation.from_experiment` from `FLExperiment.faults`). The
        engine then *executes* on the fault-masked artifacts — dead
        satellites lose every contact (and ISL participation), grants are
        weather-rescaled, reviving satellites re-enter through
        `fault_reset`'s forced re-download — while schedulers *plan* on
        the clean connectivity/link view unless the trace is `oracle`
        (the blind/oracle split that measures how each policy degrades
        when its plan is wrong). `faults=None` (default) keeps every
        compiled program and trajectory bit-identical to previous
        releases.
      mesh: optional `jax.sharding.Mesh` (see `repro.core.mesh.sim_mesh`)
        sharding the satellite axis of the protocol state and every
        satellite-sized artifact across devices. K is padded up to a
        multiple of the device count with trajectory-inert
        never-connected satellites (`repro.core.mesh.pad_state`), the
        fast loop's window scans run under `shard_map` with exact
        integer psums as the only cross-shard traffic, and the host-side
        mirrors/event path strip the padding — so any mesh run is
        trajectory-bit-identical to `mesh=None` (the default, which
        compiles the exact single-device program of previous releases).
    """

    def __init__(self, C: np.ndarray, adapter, scheduler: Scheduler,
                 config: Optional[EngineConfig] = None, *,
                 callbacks: Sequence = (), init_params=None,
                 link_budget=None, isl=None, faults=None, mesh=None,
                 **overrides):
        cfg = config if config is not None else EngineConfig()
        if overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        cfg = dataclasses.replace(
            cfg, seed=0 if cfg.seed is None else cfg.seed,
            uplink_topk=(0.0 if cfg.uplink_topk is None
                         else cfg.uplink_topk),
            uplink_int8=bool(cfg.uplink_int8))
        self.config = cfg
        self.link_budget = link_budget
        self.isl = isl
        self.faults = faults
        self.mesh = mesh
        art = resolve_run_artifacts(C, cfg, link_budget=link_budget,
                                    faults=faults)
        self.C, self._grants = art.C, art.grants
        self._plan_C, self._plan_grants = art.plan_C, art.plan_grants
        self._trace = art.trace
        self.adapter = adapter
        self.scheduler = scheduler
        self.callbacks = list(callbacks)
        self._init_params = init_params
        self._stop_requested = False

        self.num_windows = self.C.shape[0]
        if cfg.max_windows:
            self.num_windows = min(self.num_windows, cfg.max_windows)
        self.K = self.C.shape[1]

    # ------------------------------------------------------------------ API

    def request_stop(self) -> None:
        """Ask the engine to stop after the current window (callbacks use
        this for early stopping)."""
        self._stop_requested = True

    @property
    def version(self) -> np.ndarray:
        """Host mirror of the last global version each satellite received.
        Read-only diagnostic — the authoritative state is `self.state`
        (mesh padding, when any, is stripped from every mirror)."""
        return np.asarray(self.state.version)[:self.K]

    @property
    def pending(self) -> np.ndarray:
        """Host mirror of each satellite's pending-update base version."""
        return np.asarray(self.state.pending)[:self.K]

    @property
    def buffered_base(self) -> np.ndarray:
        """Host mirror of the GS buffer's per-satellite base versions."""
        return np.asarray(self.state.buffered)[:self.K]

    @property
    def transfer_progress(self):
        """Host mirror of per-satellite in-progress transfer units (None
        unless the run models a link budget)."""
        return None if self.state.progress is None \
            else np.asarray(self.state.progress)[:self.K]

    @property
    def relay_units(self):
        """Host mirror of per-satellite accumulated ISL hop units (None
        unless the run relays through sink satellites)."""
        return None if self.state.relay is None \
            else np.asarray(self.state.relay)[:self.K]

    def prepare(self) -> None:
        """Initialize run state (model, client-update programs, checkpoint
        ring, device-resident protocol state). `run` calls this; benchmarks
        and tests call it directly to drive individual protocol steps."""
        cfg = self.config
        # ISL activates only when BOTH the runtime and a scheduler-declared
        # mode are present; the scheduler reads the runtime (topology) via
        # its `isl` attribute, bound before reset()
        mode = getattr(self.scheduler, "isl_mode", None)
        self._isl = self.isl if (self.isl is not None
                                 and mode is not None) else None
        self._isl_mode = mode if self._isl is not None else None
        self.scheduler.isl = self._isl
        # schedulers that run device-side simulation (fedspace's eq.-13
        # search) shard it over the same mesh as the run
        self.scheduler.mesh = self.mesh
        self.scheduler.reset()
        self._stop_requested = False
        # mesh runs pad K up to a device-count multiple with
        # trajectory-inert never-connected satellites; _Kp is the padded
        # satellite count every device-side artifact uses
        self._Kp = self.K if self.mesh is None \
            else MM.padded_size(self.K, self.mesh)

        key = jax.random.PRNGKey(cfg.seed)
        self.params = (self.adapter.init(key) if self._init_params is None
                       else self._init_params)
        mask = self.adapter.trainable_mask(self.params) \
            if hasattr(self.adapter, "trainable_mask") else None
        self._batched_update = make_batched_client_update(
            self.adapter, local_steps=cfg.local_steps, lr=cfg.client_lr,
            trainable_mask=mask, uplink_topk=cfg.uplink_topk,
            uplink_int8=bool(cfg.uplink_int8))

        self.store = DeviceCheckpointStore(ring=cfg.s_max + 26)
        self.store.put(0, self.params)
        self.ig = 0
        # every satellite holds w^0 with a pending round on it (Alg. 1
        # init); link-budget runs carry the in-progress-transfer column,
        # sink-relay runs the ISL relay column
        linked = self.link_budget is not None
        self.state = SS.bootstrap_state(self.K, progress=linked,
                                        relay=self._isl_mode == "sink")
        if self.mesh is not None:
            self.state = jax.device_put(
                MM.pad_state(self.state, self._Kp),
                MM.sat_sharding(self.mesh))
        if linked:
            b = self.link_budget
            self._need_up = jnp.int32(b.need_up)
            self._need_dn = jnp.int32(b.need_dn)
            # run-level gates handed to schedulers: exec grants drive the
            # run; blind-fault runs plan on the clean grants view
            self._link = SS.LinkGate(self._grants, int(b.need_up),
                                     int(b.need_dn))
            self._plan_link = self._link \
                if self._plan_grants is self._grants \
                else SS.LinkGate(self._plan_grants, int(b.need_up),
                                 int(b.need_dn))
        else:
            self._link = None
            self._plan_link = None
        self._fast_ok = cfg.fast_loop and all(
            getattr(type(self), m) is getattr(SimulationEngine, m)
            for m in ("on_uploads", "on_decide", "on_aggregate",
                      "on_downloads"))
        # device copy of the run's connectivity (and grants), padded with
        # _MAX_CHUNK all-false/zero rows so a bucketed scan slice never
        # clamps (columns padded to _Kp under a mesh)
        self._C_dev = jnp.asarray(np.concatenate(
            [MM.pad_axis(self.C[:self.num_windows], self._Kp),
             np.zeros((_MAX_CHUNK, self._Kp), bool)])) \
            if self._fast_ok else None
        self._link_dev = None
        if self._fast_ok and linked:
            G_dev = jnp.asarray(np.concatenate(
                [MM.pad_axis(self._grants[:self.num_windows], self._Kp),
                 np.zeros((_MAX_CHUNK, self._Kp), np.int32)]))
            self._link_dev = (G_dev, self._need_up, self._need_dn)
        # fault masks: host rows feed the per-window host loop, padded
        # device copies feed the scans (None everywhere without a trace)
        self._faults_dev = None
        if self._trace is None:
            self._alive = self._revive = None
        else:
            self._alive = np.asarray(
                self._trace.alive[:self.num_windows], bool)
            self._revive = np.asarray(
                self._trace.revive[:self.num_windows], bool)
            if self._fast_ok:
                pad = np.zeros((_MAX_CHUNK, self._Kp), bool)
                self._faults_dev = (
                    jnp.asarray(np.concatenate(
                        [MM.pad_axis(self._revive, self._Kp), pad])),
                    jnp.asarray(np.concatenate(
                        [MM.pad_axis(self._alive, self._Kp), pad])))
        # ISL device state: sink elections are cached per epoch (sink
        # mode); the gossip neighbour arrays are run constants — padded
        # satellites are their own (inert) neighbours/sinks
        self._sink_cache = {}
        self._gossip_dev = None
        if self._isl_mode == "gossip":
            topo = self._isl.topology
            idx = np.arange(self._Kp, dtype=np.int32)
            cross = self._isl.cross_plane

            def nbr(a):
                return jnp.asarray(np.concatenate(
                    [np.asarray(a, np.int32), idx[self.K:]]))

            self._gossip_dev = (
                nbr(topo.nxt), nbr(topo.prv),
                nbr(topo.left) if cross else jnp.asarray(idx),
                nbr(topo.right) if cross else jnp.asarray(idx),
                jnp.int32(max(self._isl.relay_windows, 1)))

        self.result = SimResult(scheme=self.scheduler.name,
                                target_acc=cfg.target_acc)
        self.result.staleness_hist = np.zeros(cfg.s_max + 1, np.int64)
        self.status = float(self.adapter.val_loss(self.params))

    def run(self) -> SimResult:
        """Execute the run: `prepare()`, then advance windows under the
        selected strategy until the horizon, a stop request, or the
        target accuracy. Returns the populated `SimResult`."""
        self.prepare()
        try:
            self._emit("on_run_begin")
            i = 0
            while i < self.num_windows:
                chunk = self._fast_chunk_plan(i) if self._fast_ok else None
                if chunk is None:
                    i, stop = self._run_window(i)
                else:
                    i, stop = self._run_chunk(i, *chunk)
                if stop or self._stop_requested:
                    break
        finally:
            service = getattr(self.scheduler, "service", None)
            if service is not None:
                self.result.replan_stats = {
                    "full": service.stats["full"],
                    "delta": service.stats["delta"],
                    "invalidated": dict(service.stats["invalidated"]),
                }
            # always emitted (even on a mid-run exception) so callbacks
            # holding resources — open files, sockets — can release them
            self._emit("on_run_end", self.result)
        return self.result

    # ---------------------------------------------------- host window loop

    def _run_window(self, i: int):
        """One window through the overridable protocol-step methods.
        Returns (next window, stop)."""
        cfg = self.config
        conn = self.C[i]
        n_buf = self.on_uploads(i, conn)
        a = self.on_decide(i, n_buf)
        if a and n_buf > 0:
            self.on_aggregate(i)
        self.on_downloads(i, conn)
        self.result.windows_run = i + 1
        stop = False
        if (i + 1) % cfg.eval_every == 0 or i == self.num_windows - 1:
            stop = self.evaluate(i)
        self._emit("on_window_end", i)
        return i + 1, stop

    # --------------------------------------------------- chunked fast loop

    def _pad_row(self, row, fill=0):
        """Pad a host (K,) row to the mesh-padded satellite count (no-op
        without a mesh)."""
        return MM.pad_axis(row, self._Kp, fill=fill)

    def _plan_state(self):
        """The scheduler-facing (K,) view of the protocol state — mesh
        padding stripped so `device_plan`/`decide` see the world at its
        declared satellite count."""
        if self._Kp == self.K:
            return self.state
        return jax.tree.map(lambda x: x[..., :self.K], self.state)

    def _gate(self, i: int):
        """Device `LinkGate` for window i (None when no link budget)."""
        if self._link is None:
            return None
        return SS.LinkGate(jnp.asarray(self._pad_row(self._grants[i])),
                           self._need_up, self._need_dn)

    def _sink_plan(self, i: int):
        """Device (sink, need_hops) arrays for window i's election epoch,
        elected once per epoch from the run's effective connectivity.
        Mesh-padded satellites are their own zero-distance sinks — their
        connectivity is all-False, so they stay inert."""
        ep = self._isl.epoch
        e = i // ep
        if e not in self._sink_cache:
            alive_e = None if self._alive is None else \
                self._alive[e * ep:(e + 1) * ep].any(axis=0)
            sink, need = self._isl.sink_plan(self.C[e * ep:(e + 1) * ep],
                                             alive=alive_e)
            if self._Kp != self.K:
                sink = np.concatenate(
                    [np.asarray(sink, np.int32),
                     np.arange(self.K, self._Kp, dtype=np.int32)])
                need = self._pad_row(np.asarray(need, np.int32))
            self._sink_cache[e] = (jnp.asarray(sink), jnp.asarray(need))
        return self._sink_cache[e]

    def _fast_chunk_plan(self, i: int):
        """Ask the scheduler for a device-side indicator valid from window
        i; clip the chunk to eval boundaries (where `status` changes) and
        the scan-size bucket cap. Returns (indicator, args, end) or None."""
        if self._trace is not None:
            # reviving satellites re-enter before planning (idempotent —
            # the scan re-applies the same reset at this window)
            self.state = _fault_reset(
                self.state, jnp.asarray(self._pad_row(self._revive[i])))
        extra = {} if self._trace is None else {
            "exec_connectivity": self.C, "exec_link": self._link}
        plan = self.scheduler.device_plan(
            i, K=self.K, state=self._plan_state(), ig=self.ig,
            connectivity=self._plan_C, status=self.status,
            link=self._plan_link, **extra)
        if plan is None:
            return None
        fn, args, horizon = plan
        end = i + (int(horizon) if horizon is not None
                   else self.num_windows - i)
        ev = self.config.eval_every
        end = min(end, self.num_windows, (i // ev + 1) * ev, i + _MAX_CHUNK)
        if self._isl_mode == "sink":
            # one sink election per scan: clip chunks to election epochs
            ep = self._isl.epoch
            end = min(end, (i // ep + 1) * ep)
        return fn, args, end

    def _run_chunk(self, i: int, fn, args, end: int):
        """Advance windows [i, end) through jitted scans, dropping back to
        host exactly at aggregation events. One device→host transfer of the
        per-window counters per scan; protocol ints and model trajectory
        are bit-identical to the per-window loop. Returns (next, stop)."""
        cfg, res = self.config, self.result
        w = i
        while w < end:
            H = end - w
            bucket = 1 << (H - 1).bit_length()
            if self._isl_mode == "sink":
                isl_dev = self._sink_plan(w)
            elif self._isl_mode == "gossip":
                isl_dev = self._gossip_dev
            else:
                isl_dev = None
            prev_state = self.state
            self.state, counters = _scan_windows(
                self.state, jnp.int32(self.ig), self._C_dev, jnp.int32(w),
                jnp.int32(H), args, self._link_dev, isl_dev,
                self._faults_dev, indicator=fn, horizon=bucket,
                isl_mode=self._isl_mode, mesh=self.mesh)
            counters = np.asarray(counters)
            advanced = H
            for j in range(H):
                n_conn, n_idle, _, a = (int(x) for x in counters[j])
                res.total_connections += n_conn
                res.idle_connections += n_idle
                res.windows_run = w + j + 1
                if a:
                    self.on_aggregate(w + j)
                    self.on_downloads(w + j, self.C[w + j])
                stop = False
                if (w + j + 1) % cfg.eval_every == 0 \
                        or w + j == self.num_windows - 1:
                    stop = self.evaluate(w + j)
                self._emit("on_window_end", w + j)
                if stop or self._stop_requested:
                    if not a and j + 1 < H:
                        # a stop mid-chunk: the scan already advanced the
                        # state past this window — replay the prefix (no
                        # event fired in it, so the rescan is an exact
                        # deterministic replay) so the run freezes one
                        # window after the request, not at the chunk end
                        self.state, _ = _scan_windows(
                            prev_state, jnp.int32(self.ig), self._C_dev,
                            jnp.int32(w), jnp.int32(j + 1), args,
                            self._link_dev, isl_dev, self._faults_dev,
                            indicator=fn, horizon=bucket,
                            isl_mode=self._isl_mode, mesh=self.mesh)
                    return w + j + 1, True
                if a:        # scan froze at the event; rescan from w+j+1
                    advanced = j + 1
                    break
            w += advanced
        return w, False

    # -------------------------------------------------------- protocol steps

    def on_uploads(self, i: int, conn: np.ndarray) -> int:
        """Connected satellites hand their pending update to the GS buffer
        (shared `upload_step` transition on device; under an active ISL
        mode the sink-relay or gossip transition composes in front of it,
        identically to the fast loop's scan body). Returns the buffer
        occupancy."""
        res = self.result
        conn_dev = jnp.asarray(self._pad_row(np.asarray(conn, bool)))
        alive = None
        if self._trace is not None:
            self.state = _fault_reset(
                self.state, jnp.asarray(self._pad_row(self._revive[i])))
            alive = jnp.asarray(self._pad_row(self._alive[i]))
        if self._isl_mode == "sink":
            sink, need = self._sink_plan(i)
            self.state, counters = _isl_upload(
                self.state, jnp.int32(self.ig), conn_dev, self._gate(i),
                sink, need, alive)
        else:
            if self._isl_mode == "gossip":
                per = int(self._gossip_dev[4])
                self.state = _gossip(
                    self.state, *self._gossip_dev[:4],
                    jnp.bool_(per <= 1 or i % per == 0), alive)
            self.state, counters = _upload(self.state, jnp.int32(self.ig),
                                           conn_dev, self._gate(i))
        n_conn, n_idle, n_buf = (int(x) for x in np.asarray(counters))
        res.total_connections += n_conn
        res.idle_connections += n_idle
        return n_buf

    def on_decide(self, i: int, n_buf: int) -> bool:
        """Ask the scheduler for the aggregation indicator a^i. The
        device-resident SatState is handed over as-is — no per-window
        host-array rebuild."""
        return self.scheduler.decide(
            i, n_in_buffer=n_buf, K=self.K, state=self._plan_state(),
            ig=self.ig, connectivity=self._plan_C, status=self.status,
            link=self._plan_link)

    def on_aggregate(self, i: int) -> None:
        """Apply the staleness-compensated buffered update (eq. 4).

        The n buffered satellites train as one batch of B rows, B the next
        power of two at or above n, each row on its own base model
        (`_train_event`). The staleness vector is padded to B with rows
        that weigh 0, and the reduction routes through the aggregation
        kernel (`aggregate_params_tree`: Pallas on TPU, bit-identical jnp
        elsewhere). Every program of an event is shaped by B alone, so an
        event compiles nothing once its bucket has been met. The buffer
        is read to host once, for the data gather and the bookkeeping.
        """
        cfg = self.config
        buffered = np.asarray(self.state.buffered)
        ks = np.flatnonzero(buffered >= 0)
        stal = (self.ig - buffered[ks]).astype(np.int32)
        stack = self._train_event(ks, buffered[ks], round_rng=i)
        padded = np.full(jax.tree.leaves(stack)[0].shape[0], -1, np.int32)
        padded[:len(ks)] = stal
        w = aggregation_weights(padded, cfg.alpha, cfg.server_lr)
        self.params = aggregate_params_tree(self.params, stack, w)
        self.state = _aggregate_state(self.state, jnp.int32(self.ig),
                                      s_max=cfg.s_max)
        self.ig += 1
        self.store.put(self.ig, self.params)
        refs = np.concatenate([np.asarray(self.state.pending), buffered])
        refs = refs[refs >= 0]
        self.store.prune(int(refs.min()) if refs.size else self.ig)
        res = self.result
        res.num_global_updates += 1
        res.num_aggregated_gradients += len(ks)
        np.add.at(res.staleness_hist, np.clip(stal, 0, cfg.s_max), 1)
        self._emit("on_aggregate_end", i,
                   {"ig": self.ig, "n_aggregated": len(ks),
                    "staleness": stal.tolist()})

    def _train_event(self, ks: np.ndarray, versions: np.ndarray, *,
                     round_rng: int):
        """The updates of satellites `ks`, trained on base `versions`, as
        one stack of B = `row_bucket(len(ks))` rows: row r is ks[r]'s
        update, rows past len(ks) are exact zeros.

        One `client_batch_many` call serves the satellites at the modal
        batch width; the rest keep a call at their own width (fixed per
        shard), and an empty shard gets an exact-zero row. A call's batch
        comes padded to its `row_bucket`, its rows' bases out of the device
        ring (`store.get_many`), and it trains in one vmapped program
        (`make_batched_client_update`, uplink compression fused in): one
        program per (bucket, width)."""
        cfg = self.config
        many = getattr(self.adapter, "client_batch_many", None) \
            or functools.partial(_client_batch_many, self.adapter)
        rest, stacks, off = np.arange(len(ks)), [], 0
        src = np.full(row_bucket(len(ks)), -1, np.int32)
        while rest.size:
            batches, used = many(ks[rest], round_rng, cfg.batch_size,
                                 cfg.local_steps)
            if not used:                  # only empty shards are left
                break
            m = jax.tree.leaves(batches)[0].shape[0]
            rows = rest[used + used[:1] * (m - len(used))]
            bases = self.store.get_many(versions[rows].tolist())
            stacks.append(self._batched_update(bases, batches))
            src[rows[:len(used)]] = off + np.arange(len(used))
            off += m
            rest = np.delete(rest, used)
        src[src < 0] = off                # the exact-zero row
        return _take_rows(self.params, tuple(stacks), src)

    def on_downloads(self, i: int, conn: np.ndarray) -> None:
        """Connected satellites fetch the current global model and start a
        fresh local round on it (shared `download_step` transition),
        link-gated on accumulated downlink progress when a budget is
        modeled. Under sink relaying the plane downloads through its
        sink's contact and fresh rounds reset the relay counter (the fast
        loop's scan body does the same at non-event windows)."""
        conn_dev = jnp.asarray(self._pad_row(np.asarray(conn, bool)))
        if self._isl_mode == "sink":
            sink, need = self._sink_plan(i)
            alive = None if self._trace is None \
                else jnp.asarray(self._pad_row(self._alive[i]))
            self.state = _isl_download(self.state, jnp.int32(self.ig),
                                       conn_dev, self._gate(i), sink, need,
                                       alive)
        else:
            self.state = _download(self.state, jnp.int32(self.ig),
                                   conn_dev, self._gate(i))

    # --------------------------------------------------------------- eval

    def evaluate(self, i: int) -> bool:
        """Eval checkpoint; returns True when the run should stop (target
        accuracy reached and stop_at_target is set)."""
        cfg, res = self.config, self.result
        acc = self.adapter.accuracy(self.params)
        self.status = float(self.adapter.val_loss(self.params))
        res.accuracy.append(acc)
        res.val_loss.append(self.status)
        res.eval_windows.append(i)
        self._emit("on_eval", i, {
            "window": i, "day": res.days(i), "accuracy": acc,
            "val_loss": self.status,
            "global_updates": res.num_global_updates,
            "aggregated_gradients": res.num_aggregated_gradients,
        })
        if (cfg.target_acc is not None and acc >= cfg.target_acc
                and res.time_to_target_days is None):
            res.time_to_target_days = res.days(i)
            if cfg.stop_at_target:
                return True
        return False

    # ------------------------------------------------------------ callbacks

    def _emit(self, event: str, *args) -> None:
        for cb in self.callbacks:
            handler = getattr(cb, event, None)
            if handler is not None:
                handler(self, *args)


def protocol_mismatches(a: SimulationEngine,
                        b: SimulationEngine) -> List[str]:
    """Names of the protocol quantities in which two finished engines
    differ: their last runs' `SimResult.counters`, the global version
    `ig`, and the final `SatState` mirrors. Empty when the trajectories
    are identical."""
    ca, cb = a.result.counters(), b.result.counters()
    out = [k for k in ca if ca[k] != cb[k]]
    if a.ig != b.ig:
        out.append("ig")
    return out + [f for f in ("version", "pending", "buffered_base")
                  if not np.array_equal(getattr(a, f), getattr(b, f))]

"""Batched experiment sweeps: many protocol runs as ONE device dispatch.

FedSpace's evaluation — and every study in `examples/` — is a grid of
variants over a shared world: scheduler hyperparameters, fault scenarios,
link knobs, seeds. Sequentially that is hundreds of engine runs, each
paying per-chunk dispatch and host transfers for a protocol whose windows
are already fully vectorized. This module runs the *entire* fast-loop
trajectory of every variant in a single `jit(vmap(...))` over a leading
variant axis: one compile per variant *shape* (same scheduler indicator,
same horizon/K, same optional columns), one dispatch per group.

The sweep body (`_sweep_run`) mirrors `repro.fl.engine._scan_impl`'s
window body exactly — same fault re-entry, ISL pre-steps, and
upload/download gating through the shared `repro.core.staleness`
transitions — but with the aggregation transition inlined
(`aggregate_step(collect="hist")`) instead of dropping to host, because a
sweep tracks the *protocol* trajectory (versions, staleness histograms,
idleness — everything `SimResult` carries except accuracy): models are
not trained, which is also what makes whole runs vmappable. Each
variant's outcome is bit-identical to its sequential
`SimulationEngine.run()` — the lockstep property tests
(`tests/test_sweep_and_mesh.py`) enforce it.

What is sweepable: any engine whose scheduler `device_plan` is valid for
the rest of the run (``horizon=None`` — sync/async/fedbuff/periodic/
intra_plane/isl_async), with base protocol steps and no early-stop
target. FedSpace replans mid-run against training status, so it is
inherently sequential — `sweep_engines` raises a clear error rather than
silently diverging (run those variants via `.run()` alongside, as
`examples/fault_study.py` does).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults as FT
from repro.core import isl as ISL
from repro.core import staleness as SS
from repro.fl.engine import SimResult, SimulationEngine, _sink_gate


@dataclass
class SweepOutcome:
    """One variant's outcome: the protocol-level `SimResult` (accuracy
    empty — sweeps do not train models) plus host mirrors of the final
    per-satellite state, matching `SimulationEngine`'s properties."""
    result: SimResult
    version: np.ndarray
    pending: np.ndarray
    buffered: np.ndarray
    ig: int


def _not_sweepable(eng, why: str) -> ValueError:
    return ValueError(
        f"scheduler '{eng.scheduler.name}' is not sweepable: {why} — "
        "run this variant sequentially via SimulationEngine.run()")


def _sweep_run(cols, *, indicator, isl_mode, s_max):
    """One variant's full trajectory, pure jnp (vmapped over variants by
    `_run_group`). `cols` carries the per-variant arrays; scheduler kind,
    ISL mode, and the optional-column layout are static per group."""
    W, K = cols["C"].shape
    linked = "grant" in cols
    state = SS.bootstrap_state(K, progress=linked,
                               relay=isl_mode == "sink")
    xs = {"t": jnp.arange(W), "conn": cols["C"]}
    for k in ("grant", "revive", "alive", "sink", "need_hops"):
        if k in cols:
            xs[k] = cols[k]

    def body(carry, inp):
        st, ig, total, idle, hist, nagg = carry
        t, conn = inp["t"], inp["conn"]
        gate = None if not linked else SS.LinkGate(
            inp["grant"], cols["need_up"], cols["need_dn"])
        stf = st if "revive" not in cols \
            else FT.fault_reset(st, inp["revive"])
        alive = inp["alive"] if "alive" in cols else None
        if isl_mode == "sink":
            sink = inp["sink"]
            st2, arrived = ISL.relay_step(stf, inp["need_hops"])
            up_conn = ISL.sink_connectivity(conn, sink, arrived,
                                            st2.pending)
            if alive is not None:
                up_conn = up_conn & alive
            gate = _sink_gate(gate, sink)
            up_st, info = SS.upload_step(st2, ig, up_conn, gate)
            dn_conn = ISL.sink_connectivity(conn, sink, arrived,
                                            up_st.pending)
            if alive is not None:
                dn_conn = dn_conn & alive
        elif isl_mode == "gossip":
            period = cols["period"]
            do_hop = (period <= 1) | (t % period == 0)
            st2, _ = ISL.gossip_step(stf, cols["nxt"], cols["prv"],
                                     cols["left"], cols["right"], do_hop,
                                     alive=alive)
            up_st, info = SS.upload_step(st2, ig, conn, gate)
            dn_conn = conn
        else:
            up_st, info = SS.upload_step(stf, ig, conn, gate)
            dn_conn = conn
        n_buf = info["n_buffered"]
        a = indicator(t, n_buf, cols["args"]) & (n_buf > 0)
        # the engine drops to host here to train and aggregate; the sweep
        # inlines the same transition — aggregate_step's hist/count
        # diagnostics are exactly the engine's host-side bookkeeping
        ag_st, new_ig, agg = SS.aggregate_step(up_st, ig, a, s_max=s_max,
                                               collect="hist")
        dl_st, dn = SS.download_step(ag_st, new_ig, dn_conn, gate)
        if isl_mode == "sink":
            dl_st = ISL.reset_relay(dl_st, dn["downloads"])
        carry = (dl_st, new_ig, total + info["n_connected"],
                 idle + info["n_idle"], hist + agg["hist"],
                 nagg + agg["n_aggregated"])
        return carry, ()

    zero = jnp.int32(0)
    (state, ig, total, idle, hist, nagg), _ = jax.lax.scan(
        body, (state, zero, zero, zero,
               jnp.zeros(s_max + 1, jnp.int32), zero), xs)
    return {"version": state.version, "pending": state.pending,
            "buffered": state.buffered, "ig": ig, "total": total,
            "idle": idle, "hist": hist, "nagg": nagg}


@functools.partial(jax.jit, static_argnames=("indicator", "isl_mode",
                                             "s_max"))
def _run_group(cols, *, indicator, isl_mode, s_max):
    return jax.vmap(functools.partial(_sweep_run, indicator=indicator,
                                      isl_mode=isl_mode, s_max=s_max)
                    )(cols)


def _variant_columns(eng: SimulationEngine):
    """Resolve one engine into (static group signature, per-variant column
    dict) — mirroring exactly what `SimulationEngine.prepare()` would
    execute — or raise for inherently sequential variants."""
    if any(getattr(type(eng), m) is not getattr(SimulationEngine, m)
           for m in ("on_uploads", "on_decide", "on_aggregate",
                     "on_downloads")):
        raise _not_sweepable(eng, "subclassed protocol steps")
    cfg = eng.config
    if cfg.target_acc is not None and cfg.stop_at_target:
        raise _not_sweepable(
            eng, "stop-at-target runs end at a training-dependent window")
    W, K = eng.num_windows, eng.K
    sched = eng.scheduler
    mode = getattr(sched, "isl_mode", None)
    isl_rt = eng.isl if (eng.isl is not None and mode is not None) \
        else None
    mode = mode if isl_rt is not None else None
    sched.isl = isl_rt
    sched.mesh = None
    sched.reset()

    linked = eng.link_budget is not None
    state0 = SS.bootstrap_state(K, progress=linked, relay=mode == "sink")
    extra = {} if eng._trace is None else {
        "exec_connectivity": eng.C,
        "exec_link": None if not linked else SS.LinkGate(
            eng._grants, int(eng.link_budget.need_up),
            int(eng.link_budget.need_dn))}
    plan_link = None if not linked else SS.LinkGate(
        eng._plan_grants, int(eng.link_budget.need_up),
        int(eng.link_budget.need_dn))
    plan = sched.device_plan(0, K=K, state=state0, ig=0,
                             connectivity=eng._plan_C, status=0.0,
                             link=plan_link, **extra)
    if plan is None:
        raise _not_sweepable(eng, "no device plan")
    fn, args, horizon = plan
    if horizon is not None:
        raise _not_sweepable(
            eng, "its device plan replans mid-run (finite horizon)")

    cols = {"C": np.asarray(eng.C[:W], bool), "args": args}
    if linked:
        cols["grant"] = np.asarray(eng._grants[:W], np.int32)
        cols["need_up"] = np.int32(eng.link_budget.need_up)
        cols["need_dn"] = np.int32(eng.link_budget.need_dn)
    if eng._trace is not None:
        cols["revive"] = np.asarray(eng._trace.revive[:W], bool)
        cols["alive"] = np.asarray(eng._trace.alive[:W], bool)
    if mode == "sink":
        # expand the per-epoch elections into per-window rows (the engine
        # clips scan chunks to epochs instead; the sweep scans all W)
        ep = isl_rt.epoch
        sink = np.empty((W, K), np.int32)
        need = np.empty((W, K), np.int32)
        alive_rows = None if eng._trace is None \
            else np.asarray(eng._trace.alive[:W], bool)
        for e0 in range(0, W, ep):
            e1 = min(e0 + ep, W)
            alive_e = None if alive_rows is None \
                else alive_rows[e0:e1].any(axis=0)
            s, n = isl_rt.sink_plan(eng.C[e0:e1], alive=alive_e)
            sink[e0:e1] = np.asarray(s, np.int32)
            need[e0:e1] = np.asarray(n, np.int32)
        cols["sink"], cols["need_hops"] = sink, need
    elif mode == "gossip":
        topo = isl_rt.topology
        idx = np.arange(K, dtype=np.int32)
        cross = isl_rt.cross_plane
        cols["nxt"] = np.asarray(topo.nxt, np.int32)
        cols["prv"] = np.asarray(topo.prv, np.int32)
        cols["left"] = np.asarray(topo.left, np.int32) if cross else idx
        cols["right"] = np.asarray(topo.right, np.int32) if cross else idx
        cols["period"] = np.int32(max(isl_rt.relay_windows, 1))

    leaves = jax.tree.leaves(args)
    args_sig = (jax.tree.structure(args),
                tuple((jnp.asarray(x).shape, str(jnp.asarray(x).dtype))
                      for x in leaves))
    key = (fn, mode, W, K, cfg.s_max, linked, eng._trace is not None,
           args_sig)
    return key, cols


def sweep_engines(engines: Sequence[SimulationEngine]
                  ) -> List[SweepOutcome]:
    """Run every engine's full protocol trajectory in batched dispatches.

    Engines are grouped by static shape — scheduler indicator, ISL mode,
    horizon, K, and which optional columns (link grants, fault masks) they
    carry — and each group runs as one `jit(vmap)` call; a 32-variant
    fedbuff×faults grid is one dispatch. Outcomes come back in input
    order, each bit-identical to that engine's own `run()` (protocol
    counters and final state; `accuracy` is empty — sweeps do not train).

    Raises ValueError for inherently sequential variants (FedSpace's
    replanning, subclassed steps, stop-at-target runs).
    """
    keyed = [_variant_columns(e) for e in engines]
    groups = {}
    for i, (key, cols) in enumerate(keyed):
        groups.setdefault(key, []).append((i, cols))

    outcomes: List[SweepOutcome] = [None] * len(engines)
    for (fn, mode, W, K, s_max, *_rest), members in groups.items():
        batched = jax.tree.map(
            lambda *xs: jnp.asarray(np.stack(xs)),
            *[cols for _, cols in members])
        out = _run_group(batched, indicator=fn, isl_mode=mode,
                         s_max=s_max)
        out = jax.tree.map(np.asarray, out)
        for v, (i, _) in enumerate(members):
            eng = engines[i]
            res = SimResult(scheme=eng.scheduler.name,
                            target_acc=eng.config.target_acc)
            res.staleness_hist = out["hist"][v].astype(np.int64)
            res.idle_connections = int(out["idle"][v])
            res.total_connections = int(out["total"][v])
            res.num_global_updates = int(out["ig"][v])
            res.num_aggregated_gradients = int(out["nagg"][v])
            res.windows_run = W
            outcomes[i] = SweepOutcome(
                result=res, version=out["version"][v],
                pending=out["pending"][v], buffered=out["buffered"][v],
                ig=int(out["ig"][v]))
    return outcomes


def run_sweep(worlds: Sequence) -> List[SimResult]:
    """Batched counterpart of ``[w.run() for w in worlds]`` over
    `Federation` variants (`with_scheduler`/`with_faults` clones or any
    mix): builds each world's engine, dispatches them through
    `sweep_engines`, and returns the per-variant `SimResult`s in input
    order."""
    return [o.result for o in
            sweep_engines([w.engine() for w in worlds])]

"""Hot-path benchmark: the simulation bottlenecks, seed path vs
vectorized/device-resident path, with machine-readable output.

1. **Schedule-search re-plan** (eq. 13): one `fedspace_search` call at the
   paper's shapes — `num_candidates` schedules over an I0-window horizon,
   every (candidate, window) histogram scored by the utility forest. The
   seed path walks forest nodes per row in pure Python and featurizes on
   host; the optimized path runs structure-of-arrays forest inference
   on-device with jnp featurization (no host round-trip after the protocol
   simulator).
2. **Aggregation round** (eq. 4): one `on_aggregate` with a buffer of
   satellite updates. The seed path dispatched one jitted client update
   per satellite, each with its own checkpoint fetch, then reduced via
   stack+tensordot; the optimized path groups satellites by base version,
   trains each group under a single vmapped jitted call, and routes the
   reduction through the aggregation kernel dispatch.
3. **Window loop** (Algorithm 1): the engine's protocol loop at
   K ∈ {34, 191, 1000}. The seed path kept per-satellite state in numpy
   and rebuilt a device SatState for the scheduler every window; the
   device-resident engine holds SatState on device and advances whole
   chunks of windows per jitted scan (`repro.fl.engine._scan_windows`),
   with a parity check of every protocol counter and the final state.
4. **Utility sampler** (eq. 12): `generate_utility_samples` per-sample
   loop vs the vectorized path (client updates grouped by base checkpoint
   and vmapped, perturbed checkpoints evaluated in vmapped loss calls).
5. **Search scaling** (mega-constellations): the full re-plan across the
   constellation scenario suite — K ∈ {40, 191, 400, 1000} satellites
   (starlink40 / flock191 / starlink400 / starlink1000 presets) x
   R ∈ {5000, 20000} candidates. The PR-3 pipeline (per-step histogram
   broadcast inside the vmapped scan, û over all R*I0 windows) is
   transcribed below as the frozen reference; the current path scans
   scatter-free int16 state emitting compact staleness marks and
   evaluates û only at each candidate's aggregation windows. Selected
   schedules must be identical cell by cell.
6. **Link budget** (capacity-constrained transfers): (a) the parity gate —
   an engine run under the trivial budget (unlimited station capacity,
   zero-latency transfers) must reproduce the geometry-only trajectory
   bit-for-bit, and the link-gated schedule search must select the
   identical schedule under the zero-need gate; (b) the downlink-capacity
   study the scenario suite was built for — the same constellation over
   `dense12` vs `sparse1` ground networks under finite rates and
   per-station capacity, reporting idle/blocked/staleness statistics that
   geometry-only contact models cannot distinguish.

7. **Inter-satellite links** (ISL subsystem): (a) the parity gate — the
   degenerate identity topology (all self-loops) run through the sink
   scheduler must reproduce the ground-only fedbuff trajectory
   bit-for-bit under both engine strategies; (b) the idle-time study —
   the sparse-ground starlink40 preset under a finite link budget,
   FedSpace / fedbuff vs the intra-plane sink scheduler and ISL gossip,
   gated on sink relaying actually reducing the eq.-10 idle share.
8. **Fault injection** (robustness layer): (a) the parity gate — an
   all-alive fault trace must reproduce the ``faults=None`` trajectory
   bit-for-bit under both engine strategies on the geometry and
   link-budget paths; (b) the degradation study — sync / fedbuff /
   fedspace / intra-plane on starlink40 over dense12 under *blind*
   satellite churn, a total station blackout, and weather-degraded
   links, gated on churn measurably reducing aggregated gradients.
9. **Real payloads** (transformer clients + compression-aware links):
   (a) the parity gate — a transformer federation (Pallas-dispatch
   forward, finite link budget) with `uplink_topk` unset, explicitly
   0.0, and under both engine strategies must produce one bit-identical
   trajectory and final model; (b) the bytes-on-the-wire study —
   starlink40 over sparse1 sweeping model family x compression ratio x
   scheduler, gated on compression cutting `need_up` and shifting the
   aggregated-gradient counts.
10. **Replan service** (incremental eq.-13 replanning): (a) the parity
   gate — at every consecutive-window request the schedule selected by
   `repro.fl.replan.ReplanService` (delta-window scoring over the cached
   scan) must be bit-identical to a full `score_candidates` +
   `select_candidate` rescan of the service's live pool, with at least
   one request answered by the delta path; (b) the latency study — warm
   delta answer time vs the full-rescan time at the serving shapes
   (K=1000 satellites, R=20000 candidates, I0=24), plus the deferred
   `maintain()` cost the delta path keeps off the answer path.

Every section registers itself in `SECTIONS`; the runner iterates the
registry and fails if a registered section is missing from the report, so
parity gates cannot rot by silent omission. Writes results to
``BENCH_hotpaths.json`` at the repo root (``--smoke`` writes
``BENCH_hotpaths.smoke.json`` instead so CI runs never clobber the
committed baseline; CI uploads the smoke report as a build artifact).
Regenerate the baseline with:

    PYTHONPATH=src python -m benchmarks.hotpaths

Run a named subset against the existing report with ``--sections``, e.g.
``python -m benchmarks.hotpaths --sections faults,isl``.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.core import staleness as SS
from repro.core.scheduler import make_scheduler
from repro.core.search import fedspace_search
from repro.core.staleness import staleness_compensation
from repro.core.utility import (RandomForestRegressor, featurize,
                                featurize_jnp)
from repro.data.fmow import FmowSpec, SyntheticFmow
from repro.data.partition import iid_partition
from repro.data.pipeline import make_clients
from repro.fl.adapters import MlpFmowAdapter
from repro.fl.compression import roundtrip
from repro.fl.engine import (EngineConfig, SimulationEngine,
                             protocol_mismatches)

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


# ---------------------------------------------------------------------------
# section registry: the runner iterates this, so a section cannot be
# silently dropped from the report (and with it, its parity gate)

SECTIONS: dict = {}    # name -> (bench_fn, parity_fn or None)


def section(name: str, parity=None):
    """Register a benchmark section. `bench_fn(smoke) -> dict` produces
    the section's report entry (and prints its own summary line);
    `parity(result) -> bool` extracts the section's parity verdict —
    any False fails the whole run with a nonzero exit."""
    def deco(fn):
        SECTIONS[name] = (fn, parity)
        return fn
    return deco


# ---------------------------------------------------------------------------
# 1. schedule-search re-plan


def _fit_search_regressor(s_max=8, n_trees=40, seed=0):
    """Forest over the search feature space (simulator staleness
    histograms), fitted on a synthetic count-utility curve."""
    rng = np.random.default_rng(seed)
    hists = rng.integers(0, 25, (600, s_max + 1)).astype(np.float32)
    X = featurize(hists, 1.0)
    s = np.arange(s_max + 1, dtype=np.float32)
    y = ((hists * (1.2 - 0.3 * s)).sum(1)
         / np.maximum(hists.sum(1), 1.0)
         + 0.05 * rng.normal(size=len(X))).astype(np.float32)
    return RandomForestRegressor(n_trees=n_trees, max_depth=6,
                                 seed=seed).fit(X, y)


def _seed_step(state, ig, connected, aggregate, *, s_max):
    """The seed protocol step, with the histogram built by scatter-add
    (the pre-vectorization `repro.core.staleness.step`)."""
    has_pending = state.pending >= 0
    uploads = connected & has_pending
    buffered = jnp.where(uploads, state.pending, state.buffered)
    pending = jnp.where(uploads, -1, state.pending)
    idle = connected & (~has_pending) & (state.version == ig)
    n_idle = jnp.sum(idle.astype(jnp.int32))
    in_buffer = buffered >= 0
    aggregate = jnp.logical_and(aggregate, jnp.any(in_buffer))
    stale = jnp.where(in_buffer, ig - buffered, 0)
    stale_c = jnp.clip(stale, 0, s_max)
    hist = jnp.zeros((s_max + 1,), jnp.int32).at[stale_c].add(
        (in_buffer & aggregate).astype(jnp.int32))
    n_agg = jnp.sum((in_buffer & aggregate).astype(jnp.int32))
    max_stale = jnp.max(jnp.where(in_buffer & aggregate, stale, 0))
    new_ig = ig + aggregate.astype(jnp.int32)
    buffered = jnp.where(aggregate, -1, buffered)
    gets_new = connected & (state.version < new_ig)
    version = jnp.where(gets_new, new_ig, state.version)
    pending = jnp.where(gets_new, new_ig, pending)
    info = {"hist": hist, "n_aggregated": n_agg, "n_idle": n_idle,
            "max_staleness": max_stale}
    return SS.SatState(version, pending, buffered), new_ig, info


def _seed_replan(rng, C, state, ig, rf, status, *, num_candidates, s_max):
    """The seed re-plan pipeline end-to-end: scatter-add protocol
    simulator, hist to host, host featurize, pure-Python node-walk forest.
    (Candidate selection uses the shared `select_candidate` rule so the
    before/after comparison isolates the scoring pipeline.)"""
    from repro.core.search import random_candidates, select_candidate
    I0 = C.shape[0]
    cands = random_candidates(rng, I0, 4, 8, num_candidates)

    def sim_window(a):
        def body(carry, inp):
            st, g = carry
            c, ai = inp
            st, g, info = _seed_step(st, g, c, ai.astype(bool),
                                     s_max=s_max)
            return (st, g), info
        (st, g), infos = jax.lax.scan(
            body, (state, jnp.int32(ig)),
            (jnp.asarray(C), a.astype(jnp.int32)))
        return st, g, infos

    _, _, infos = jax.vmap(sim_window)(jnp.asarray(cands))
    hist = np.asarray(infos["hist"])
    Rn, I0_, F = hist.shape
    feats = featurize(hist.reshape(Rn * I0_, F), status)
    util = rf.predict_reference(feats).reshape(Rn, I0_)
    scores = (util * cands.astype(np.float32)).sum(axis=1)
    return cands[select_candidate(cands, scores)]


@section("search_replan", parity=lambda r: r["schedule_identical"])
def bench_search(smoke: bool) -> dict:
    K = 16 if smoke else 191          # fig.-2 constellation scale
    R = 64 if smoke else 5000         # |R| from the paper
    I0 = 8 if smoke else 24
    s_max = 8
    rng = np.random.default_rng(0)
    C = rng.random((I0, K)) < 0.15
    state = SS.bootstrap_state(K)
    rf = _fit_search_regressor(s_max=s_max)

    def replan_opt():
        t0 = time.perf_counter()
        sched = fedspace_search(np.random.default_rng(7), C, state, 0, rf,
                                1.0, num_candidates=R, s_max=s_max)
        return time.perf_counter() - t0, sched

    def replan_ref():
        t0 = time.perf_counter()
        sched = _seed_replan(np.random.default_rng(7), C, state, 0, rf,
                             1.0, num_candidates=R, s_max=s_max)
        return time.perf_counter() - t0, sched

    # both paths: one cold run (pays jit compile), then min-of-3 warm runs
    # (matching how re-plans recur every I0 windows)
    t_opt_cold, sched_opt = replan_opt()
    t_opt_warm = min(replan_opt()[0] for _ in range(3))
    _, sched_ref = replan_ref()
    t_ref = min(replan_ref()[0] for _ in range(3))

    print(f"search_replan: reference {t_ref:.3f}s, optimized warm "
          f"{t_opt_warm:.3f}s ({t_ref / t_opt_warm:.1f}x), "
          f"schedule_identical="
          f"{bool(np.array_equal(sched_ref, sched_opt))}", flush=True)
    return {
        "num_candidates": R, "I0": I0, "K": K,
        "n_trees": rf.n_trees, "max_depth": rf.max_depth,
        "rows_scored": R * I0,
        "t_reference_s": t_ref,
        "t_optimized_cold_s": t_opt_cold,
        "t_optimized_warm_s": t_opt_warm,
        "speedup_cold": t_ref / t_opt_cold,
        "speedup_warm": t_ref / t_opt_warm,
        "schedule_identical": bool(np.array_equal(sched_ref, sched_opt)),
    }


# ---------------------------------------------------------------------------
# 1b. search scaling across the constellation scenario suite


def _pr3_replan(rng, C, state, ig, rf, status, *, num_candidates, s_max):
    """The PR-3 re-plan pipeline, transcribed: full-histogram protocol
    simulation (per-step (R, K, s_max+1) compare+reduce inside the vmapped
    scan, int32 state) and û evaluated at every one of the R*I0 windows,
    masked by the schedule afterwards. Candidate generation and selection
    are shared with the current path so the comparison isolates scoring."""
    from repro.core.search import random_candidates, select_candidate
    I0 = C.shape[0]
    cands = random_candidates(rng, I0, 4, 8, num_candidates)
    cs = jnp.asarray(cands)
    _, _, infos = SS.simulate_candidates(jnp.asarray(C), cs, state,
                                         jnp.int32(ig), s_max=s_max,
                                         lite=True)
    hist = infos["hist"]                                 # (R, I0, s_max+1)
    Rn, I0_, F = hist.shape
    feats = featurize_jnp(hist.reshape(Rn * I0_, F), status)
    util = rf.predict_device(feats).reshape(Rn, I0_)
    scores = np.asarray((util * cs.astype(jnp.float32)).sum(axis=1))
    return cands[select_candidate(cands, scores)]


@section("search_scaling",
         parity=lambda r: all(c["schedule_identical"] for c in r["cells"]))
def bench_search_scaling(smoke: bool) -> dict:
    """fedspace_search wall time over the scenario-suite grid, current
    scatter-free path vs the transcribed PR-3 pipeline, parity-gated on
    the selected schedule in every cell."""
    from repro.core.connectivity import connectivity_sets, \
        constellation_preset
    s_max = 8
    rf = _fit_search_regressor(s_max=s_max)
    if smoke:
        I0 = 8
        rng = np.random.default_rng(0)
        grid = [("random16", rng.random((I0, 16)) < 0.15, 64)]
    else:
        I0 = 24
        presets = ["starlink40", "flock191", "starlink400", "starlink1000"]
        grid = [(p, connectivity_sets(constellation_preset(p), days=0.25),
                 R) for p in presets for R in (5000, 20000)]

    out = {"I0": I0, "s_max": s_max, "n_trees": rf.n_trees, "cells": []}
    for name, C, R in grid:
        K = C.shape[1]
        state = SS.bootstrap_state(K)

        def replan_new():
            t0 = time.perf_counter()
            sched = fedspace_search(np.random.default_rng(7), C, state, 0,
                                    rf, 1.0, num_candidates=R, s_max=s_max)
            return time.perf_counter() - t0, sched

        def replan_pr3():
            t0 = time.perf_counter()
            sched = _pr3_replan(np.random.default_rng(7), C, state, 0, rf,
                                1.0, num_candidates=R, s_max=s_max)
            return time.perf_counter() - t0, sched

        t_new_cold, sched_new = replan_new()
        t_new = min(replan_new()[0] for _ in range(3))
        t_pr3_cold, sched_pr3 = replan_pr3()
        t_pr3 = min(replan_pr3()[0] for _ in range(2))
        cell = {
            "preset": name, "K": K, "num_candidates": R,
            "t_pr3_s": t_pr3,
            "t_current_s": t_new,
            "t_current_cold_s": t_new_cold,
            "speedup": t_pr3 / t_new,
            "schedule_identical": bool(np.array_equal(sched_pr3,
                                                      sched_new)),
        }
        out["cells"].append(cell)
        print(f"search_scaling {name} K={K} R={R}: pr3 {t_pr3:.3f}s, "
              f"current {t_new:.3f}s ({cell['speedup']:.1f}x), "
              f"schedule_identical={cell['schedule_identical']}",
              flush=True)
    return out


# ---------------------------------------------------------------------------
# 2. aggregation round


def _seed_aggregate(eng, i: int, client_update):
    """The seed engine's `on_aggregate` hot loop (one dispatch + checkpoint
    fetch per satellite, sequential compression, stack-tensordot-add),
    without the bookkeeping; returns the new global params."""
    cfg = eng.config
    buffered = eng.buffered_base
    ks = np.flatnonzero(buffered >= 0)
    stal = eng.ig - buffered[ks]
    updates = []
    for k in ks:
        base = eng.store.get(int(buffered[k]))
        u = client_update(base, int(k), round_rng=i,
                          batch_size=cfg.batch_size)
        if cfg.uplink_topk > 0.0:
            u, _ = roundtrip(u, cfg.uplink_topk)
        updates.append(u)
    stack = jax.tree.map(lambda *xs: jnp.stack(xs), *updates)
    c = staleness_compensation(jnp.asarray(stal), cfg.alpha)
    w = c / jnp.maximum(jnp.sum(c), 1e-12) * cfg.server_lr
    delta = jax.tree.map(
        lambda u_: jnp.tensordot(w.astype(jnp.float32),
                                 u_.astype(jnp.float32), axes=1), stack)
    return jax.tree.map(
        lambda p, d: (p.astype(jnp.float32) + d).astype(p.dtype),
        eng.params, delta)


def _batched_aggregate(eng, i: int):
    """The optimized path (`SimulationEngine.on_aggregate` compute body)."""
    from repro.core.aggregation import aggregation_weights
    from repro.kernels.agg.ops import aggregate_params_tree
    cfg = eng.config
    buffered = eng.buffered_base
    ks = np.flatnonzero(buffered >= 0)
    stack = eng._train_event(ks, buffered[ks], round_rng=i)
    stal = np.full(jax.tree.leaves(stack)[0].shape[0], -1, np.int32)
    stal[:len(ks)] = eng.ig - buffered[ks]
    w = aggregation_weights(stal, cfg.alpha, cfg.server_lr)
    return aggregate_params_tree(eng.params, stack, w)


def _block(params):
    jax.tree.map(lambda x: x.block_until_ready()
                 if hasattr(x, "block_until_ready") else x, params)


@section("aggregation_round", parity=lambda r: r["params_bit_equal"])
def bench_aggregation(smoke: bool) -> dict:
    K = 8 if smoke else 191           # buffered satellites per round
    num_train = 400 if smoke else 7640
    n_versions = 2 if smoke else 4    # distinct base versions in buffer
    hidden = 64
    reps = 2 if smoke else 5
    data = SyntheticFmow(FmowSpec(num_train=num_train, num_val=200))
    adapter = MlpFmowAdapter(data, make_clients(
        iid_partition(num_train, K, 0)), hidden=hidden)
    C = np.ones((4, K), bool)
    eng = SimulationEngine(C, adapter, make_scheduler("async"),
                           EngineConfig())
    eng.prepare()
    # a buffer where every satellite holds an update, spread over
    # n_versions base versions (stale + fresh mix, as under FedSpace)
    rng = np.random.default_rng(0)
    for v in range(1, n_versions):
        eng.store.put(v, eng.params)
    eng.ig = n_versions - 1
    eng.state = SS.SatState(
        jnp.full((K,), eng.ig, jnp.int32),
        jnp.asarray(eng.pending, jnp.int32),
        jnp.asarray(rng.integers(0, n_versions, K), jnp.int32))

    def timed(fn):
        fn(eng, 3)                    # warm the jit caches
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn(eng, 3)
            _block(out)
            ts.append(time.perf_counter() - t0)
        return min(ts), out

    t_opt, p_opt = timed(_batched_aggregate)
    from repro.fl.client import make_client_update
    cu = make_client_update(adapter, local_steps=eng.config.local_steps,
                            lr=eng.config.client_lr)
    t_ref, p_ref = timed(lambda e, i: _seed_aggregate(e, i, cu))
    bit_equal = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_opt)))

    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(
        eng.params))
    print(f"aggregation_round: reference {t_ref:.3f}s, batched "
          f"{t_opt:.3f}s ({t_ref / t_opt:.1f}x), params_bit_equal="
          f"{bool(bit_equal)}", flush=True)
    return {
        "n_buffered": K, "n_base_versions": n_versions,
        "model_params": n_params, "local_steps": eng.config.local_steps,
        "t_reference_s": t_ref,
        "t_batched_s": t_opt,
        "speedup": t_ref / t_opt,
        "params_bit_equal": bool(bit_equal),
    }


# ---------------------------------------------------------------------------
# 3. window loop


class _NullAdapter:
    """Protocol-isolating adapter: tiny model, zero-gradient loss, so the
    engine's window loop is what gets measured, not client training."""

    def __init__(self, K):
        self.clients = list(range(K))

    def init(self, key):
        return {"w": jnp.zeros((2,))}

    def loss(self, params, batch):
        return jnp.sum(params["w"]) * 0.0 + jnp.sum(batch) * 0.0

    def client_batch(self, ci, round_rng, batch_size, num_batches):
        return jnp.zeros((num_batches, 1))

    def accuracy(self, params):
        return 0.0

    def val_loss(self, params):
        return 0.0


def _seed_window_loop(C, num_windows, decide, *, s_max=8):
    """The seed engine's host window loop (protocol only): per-satellite
    numpy arrays, a device SatState rebuilt for the scheduler EVERY window
    (the PR-2 `fl/engine.py` behavior the device-resident engine retired).
    Returns the final protocol state and counters for the parity check."""
    K = C.shape[1]
    version = np.zeros(K, np.int64)
    pending = np.zeros(K, np.int64)
    buffered = np.full(K, -1, np.int64)
    ig = total = idle = n_agg = 0
    hist = np.zeros(s_max + 1, np.int64)
    for i in range(num_windows):
        conn = C[i]
        total += int(conn.sum())
        has_pending = conn & (pending >= 0)
        idle += int((conn & ~has_pending & (version == ig)).sum())
        buffered[has_pending] = pending[has_pending]
        pending[has_pending] = -1
        n_buf = int((buffered >= 0).sum())
        state = SS.SatState(jnp.asarray(version, jnp.int32),
                            jnp.asarray(pending, jnp.int32),
                            jnp.asarray(buffered, jnp.int32))
        if decide(i, n_buf, state, ig) and n_buf > 0:
            ks = np.flatnonzero(buffered >= 0)
            np.add.at(hist, np.clip(ig - buffered[ks], 0, s_max), 1)
            n_agg += len(ks)
            ig += 1
            buffered[:] = -1
        behind = conn & (version < ig)
        version[behind] = ig
        pending[behind] = ig
    return {"version": version, "pending": pending, "ig": ig,
            "total": total, "idle": idle, "n_agg": n_agg, "hist": hist}


@section("window_loop",
         parity=lambda r: all(c["state_and_counters_identical"]
                              for c in r["per_K"].values()))
def bench_window_loop(smoke: bool) -> dict:
    Ks = [16] if smoke else [34, 191, 1000]
    W = 64 if smoke else 2048
    Wp = 48 if smoke else 256         # parity run (with aggregations)
    out = {"windows": W, "per_K": {}}
    for K in Ks:
        rng = np.random.default_rng(0)
        C = rng.random((W, K)) < 0.08
        adapter = _NullAdapter(K)

        # throughput: no aggregations => the loop is pure protocol
        M_never = K + 1
        cfg = EngineConfig(eval_every=W, max_windows=W)

        def run_device():
            eng = SimulationEngine(C, adapter,
                                   make_scheduler("fedbuff", M=M_never),
                                   cfg)
            t0 = time.perf_counter()
            eng.run()
            return time.perf_counter() - t0, eng

        def run_seed():
            t0 = time.perf_counter()
            fin = _seed_window_loop(C, W,
                                    lambda i, nb, st, ig: nb >= M_never)
            return time.perf_counter() - t0, fin

        t_dev_cold, eng = run_device()
        assert eng._fast_ok
        t_dev = min(run_device()[0] for _ in range(3))
        t_seed = min(run_seed()[0] for _ in range(3))

        # parity: aggregation-bearing schedule, every protocol counter and
        # the final state must match the seed loop exactly
        M = max(2, K // 8)
        Cp = np.random.default_rng(1).random((Wp, K)) < 0.08
        fin = _seed_window_loop(Cp, Wp, lambda i, nb, st, ig: nb >= M)
        peng = SimulationEngine(Cp, adapter,
                                make_scheduler("fedbuff", M=M),
                                EngineConfig(eval_every=Wp, max_windows=Wp))
        pres = peng.run()
        parity = (
            np.array_equal(peng.version, fin["version"])
            and np.array_equal(peng.pending, fin["pending"])
            and peng.ig == fin["ig"]
            and pres.total_connections == fin["total"]
            and pres.idle_connections == fin["idle"]
            and pres.num_aggregated_gradients == fin["n_agg"]
            and pres.staleness_hist.tolist() == fin["hist"].tolist())

        out["per_K"][str(K)] = {
            "t_seed_loop_s": t_seed,
            "t_device_loop_s": t_dev,
            "t_device_loop_cold_s": t_dev_cold,
            "windows_per_s_seed": W / t_seed,
            "windows_per_s_device": W / t_dev,
            "speedup": t_seed / t_dev,
            "state_and_counters_identical": bool(parity),
        }
        print(f"window_loop K={K}: seed {W / t_seed:.0f} win/s, device "
              f"{W / t_dev:.0f} win/s ({t_seed / t_dev:.1f}x), parity="
              f"{bool(parity)}", flush=True)
    return out


# ---------------------------------------------------------------------------
# 4. utility sampler


@section("utility_sampler",
         parity=lambda r: r["features_identical"] and r["targets_close"])
def bench_utility_sampler(smoke: bool) -> dict:
    from repro.core.utility import generate_utility_samples
    from repro.fl.client import (make_batched_client_update,
                                 make_client_update)
    from repro.fl.fedspace_setup import pretrain_trajectory
    num_train = 400 if smoke else 2000
    K = 12 if smoke else 40
    n_samples = 12 if smoke else 150
    cps = 8 if smoke else 32
    local_steps = 2 if smoke else 4
    data = SyntheticFmow(FmowSpec(num_train=num_train, num_val=200))
    adapter = MlpFmowAdapter(data, make_clients(
        iid_partition(num_train, K, 0)), hidden=48)
    traj = pretrain_trajectory(adapter, rounds=8, clients_per_round=8,
                               local_steps=local_steps, client_lr=0.3,
                               seed=0)
    cu = make_client_update(adapter, local_steps=local_steps, lr=0.3)

    def upd_fn(base, ci, r):
        return cu(base, ci, round_rng=int(r))

    common = dict(num_clients=K, n_samples=n_samples, s_max=8,
                  clients_per_sample=cps, seed=3)
    val_batch = adapter.eval_batch()
    vec_kw = dict(
        batch_fn=lambda ci, r: adapter.client_batch(ci, int(r), 32,
                                                    local_steps),
        batched_update_fn=make_batched_client_update(
            adapter, local_steps=local_steps, lr=0.3),
        batched_loss_fn=jax.jit(jax.vmap(
            lambda p: adapter.loss(p, val_batch))))

    def run(kw):
        t0 = time.perf_counter()
        X, y = generate_utility_samples(
            jax.random.PRNGKey(0), traj, upd_fn,
            lambda p: adapter.val_loss(p), **common, **kw)
        return time.perf_counter() - t0, X, y

    t_vec_cold, Xv, yv = run(vec_kw)
    t_vec = min(run(vec_kw)[0] for _ in range(2))
    t_loop, Xl, yl = run({})
    t_loop = min(t_loop, run({})[0])
    print(f"utility_sampler: loop {t_loop:.3f}s, vectorized {t_vec:.3f}s "
          f"({t_loop / t_vec:.1f}x), features_identical="
          f"{bool(np.array_equal(Xl, Xv))}, targets_close="
          f"{bool(np.allclose(yl, yv, atol=1e-5))}", flush=True)
    return {
        "n_samples": n_samples, "clients_per_sample": cps,
        "num_clients": K, "local_steps": local_steps,
        "t_loop_s": t_loop,
        "t_vectorized_s": t_vec,
        "t_vectorized_cold_s": t_vec_cold,
        "speedup": t_loop / t_vec,
        "features_identical": bool(np.array_equal(Xl, Xv)),
        "targets_max_abs_diff": float(np.abs(yl - yv).max()),
        "targets_close": bool(np.allclose(yl, yv, atol=1e-5)),
    }


# ---------------------------------------------------------------------------
# 6. link budget: trivial-budget parity gate + the downlink-capacity study


def _protocol_run(C, budget, *, M, windows, eval_every=None):
    """One protocol-isolated engine run (NullAdapter, fedbuff M); returns
    (engine, result, wall seconds)."""
    K = C.shape[1]
    eng = SimulationEngine(
        C, _NullAdapter(K), make_scheduler("fedbuff", M=M),
        EngineConfig(eval_every=eval_every or windows, max_windows=windows),
        link_budget=budget)
    t0 = time.perf_counter()
    res = eng.run()
    return eng, res, time.perf_counter() - t0


def _capacity_cell(spec, *, days, windows, link_kw, M):
    """Run one ground-network cell of the capacity study and digest the
    idle/blocked/staleness statistics."""
    from repro.core.connectivity import link_budget
    budget = link_budget(spec, days=days, **link_kw)
    eng, res, t = _protocol_run(budget.served, budget, M=M,
                                windows=windows)
    hist = res.staleness_hist
    n_agg = int(hist.sum())
    return {
        "stations": len(spec.ground_stations),
        "visible_contacts": int(budget.visible[:windows].sum()),
        "served_contacts": int(budget.served[:windows].sum()),
        "blocked_fraction": float(
            (budget.visible[:windows] & ~budget.served[:windows]).sum()
            / max(budget.visible[:windows].sum(), 1)),
        "idle_fraction": res.idle_connections
        / max(res.total_connections, 1),
        "global_updates": res.num_global_updates,
        "aggregated_gradients": res.num_aggregated_gradients,
        "mean_staleness": float((hist * np.arange(len(hist))).sum()
                                / max(n_agg, 1)),
        "t_run_s": t,
    }


@section("link_budget",
         parity=lambda r: r["trivial_trajectory_identical"]
         and r["trivial_schedule_identical"] and r["capacity_stats_differ"])
def bench_link_budget(smoke: bool) -> dict:
    """(a) Parity gate: the trivial budget — unlimited station capacity,
    zero-latency transfers — must reproduce the geometry-only engine
    trajectory and the geometry-only search schedule bit-for-bit (the
    contract every link-budget code path is gated on). (b) Capacity
    study: identical constellation and protocol over dense12 vs sparse1
    ground networks under finite rates and per-station capacity — the
    idle/blocked/staleness statistics must differ measurably, which is
    exactly what the geometry-only contact model could not show."""
    from repro.core.connectivity import (ConstellationSpec, link_budget,
                                         resolve_spec, transfer_windows)
    K = 16 if smoke else 191
    days = 0.25 if smoke else 1.0
    windows = int(days * 96)
    # smoke: a wide 10-deg visibility cone + capacity 1, so even 16
    # satellites over a quarter day produce real shared-station contention
    base = ConstellationSpec() if not smoke \
        else ConstellationSpec(num_satellites=K, min_elevation_deg=10.0)
    capacity = 2 if not smoke else 1
    M = max(2, K // 8)

    # (a) trivial-budget parity: same trajectory, bit for bit
    trivial = link_budget(base, days=days)    # all sentinels: gates nothing
    C = trivial.visible
    e0, r0, t_geom = _protocol_run(C, None, M=M, windows=windows,
                                   eval_every=windows // 2)
    e1, r1, t_gated = _protocol_run(C, trivial, M=M, windows=windows,
                                    eval_every=windows // 2)
    traj_ok = (
        np.array_equal(e0.version, e1.version)
        and np.array_equal(e0.pending, e1.pending)
        and np.array_equal(e0.buffered_base, e1.buffered_base)
        and e0.ig == e1.ig
        and r0.total_connections == r1.total_connections
        and r0.idle_connections == r1.idle_connections
        and r0.staleness_hist.tolist() == r1.staleness_hist.tolist())

    rf = _fit_search_regressor()
    I0 = 8 if smoke else 24
    Cw = C[:I0]
    R = 64 if smoke else 5000
    sched0 = fedspace_search(np.random.default_rng(7), Cw,
                             SS.bootstrap_state(K), 0, rf, 1.0,
                             num_candidates=R, s_max=8)
    gate = SS.LinkGate((np.ones_like(Cw, np.int32) * Cw), 0, 0)
    sched1 = fedspace_search(np.random.default_rng(7), Cw,
                             SS.bootstrap_state(K, progress=True), 0, rf,
                             1.0, num_candidates=R, s_max=8, link=gate)
    sched_ok = bool(np.array_equal(sched0, sched1))

    # (b) capacity study: dense12 vs sparse1, finite rates + station caps
    link_kw = dict(uplink_mbps=20.0, downlink_mbps=100.0, model_mb=600.0,
                   gs_capacity=capacity)
    cells = {g: _capacity_cell(resolve_spec(base, g, None), days=days,
                               windows=windows, link_kw=link_kw, M=M)
             for g in ("dense12", "sparse1")}
    d12, sp1 = cells["dense12"], cells["sparse1"]
    stats_differ = bool(
        sp1["blocked_fraction"] > d12["blocked_fraction"]
        and sp1["aggregated_gradients"] < d12["aggregated_gradients"])

    print(f"link_budget: trivial gate {t_gated:.3f}s vs geometry "
          f"{t_geom:.3f}s, trajectory_identical={traj_ok}, "
          f"schedule_identical={sched_ok}", flush=True)
    for g, c in cells.items():
        print(f"link_budget {g}: blocked {c['blocked_fraction']:.2f}, "
              f"idle {c['idle_fraction']:.2f}, "
              f"agg_gradients {c['aggregated_gradients']}, "
              f"mean_staleness {c['mean_staleness']:.2f}", flush=True)
    return {
        "K": K, "windows": windows,
        "need_up": transfer_windows(link_kw["uplink_mbps"],
                                    link_kw["model_mb"]),
        "need_dn": transfer_windows(link_kw["downlink_mbps"],
                                    link_kw["model_mb"]),
        "gs_capacity": link_kw["gs_capacity"],
        "t_geometry_run_s": t_geom,
        "t_trivial_gated_run_s": t_gated,
        "trivial_trajectory_identical": bool(traj_ok),
        "trivial_schedule_identical": sched_ok,
        "capacity_cells": cells,
        "capacity_stats_differ": stats_differ,
    }


# ---------------------------------------------------------------------------
# 7. inter-satellite links: identity-topology parity gate + idle-time study


def _isl_run(C, scheduler, *, windows, isl=None, budget=None, fast=True,
             faults=None):
    """One protocol-isolated engine run under an optional ISL runtime and
    fault trace; returns (engine, result, wall seconds)."""
    K = C.shape[1]
    eng = SimulationEngine(
        C, _NullAdapter(K), scheduler,
        EngineConfig(eval_every=windows, max_windows=windows,
                     fast_loop=fast),
        link_budget=budget, isl=isl, faults=faults)
    t0 = time.perf_counter()
    res = eng.run()
    return eng, res, time.perf_counter() - t0


def _same_trajectory(a, b, ra, rb):
    return (np.array_equal(a.version, b.version)
            and np.array_equal(a.pending, b.pending)
            and np.array_equal(a.buffered_base, b.buffered_base)
            and a.ig == b.ig
            and ra.idle_connections == rb.idle_connections
            and ra.total_connections == rb.total_connections
            and ra.staleness_hist.tolist() == rb.staleness_hist.tolist())


@section("isl",
         parity=lambda r: r["identity_trajectory_identical"]
         and r.get("idle_reduced", True))
def bench_isl(smoke: bool) -> dict:
    """(a) Parity gate: the degenerate identity topology (every satellite
    its own singleton plane, all links self-loops) run through the sink
    scheduler must reproduce the ground-only fedbuff trajectory
    bit-for-bit under BOTH engine strategies — the contract that `isl`
    only changes what the topology says it changes. (b) Idle-time study
    (full runs only): the sparse-ground starlink preset under a finite
    link budget, FedSpace / fedbuff / intra-plane sinks / ISL gossip —
    the regime arXiv 2302.13447 targets, where relaying whole planes
    through their best-placed contact must cut the eq.-10 idle share
    below the ground-only schedulers'."""
    from repro.core import isl as ISL
    from repro.core.connectivity import (connectivity_sets,
                                         constellation_preset, link_budget)
    K = 16 if smoke else 40
    windows = 48 if smoke else 96
    M = max(2, K // 8)

    # (a) identity-topology parity, both strategies
    if smoke:
        C = np.random.default_rng(0).random((windows, K)) < 0.08
    else:
        C = connectivity_sets(constellation_preset("starlink40"), days=1.0)
    ident = ISL.ISL(topology=ISL.identity_topology(K), relay_windows=0,
                    epoch=24)
    e0, r0, t_ground = _isl_run(C, make_scheduler("fedbuff", M=M),
                                windows=windows)
    parity = True
    t_fast = t_host = 0.0
    for fast in (True, False):
        e1, r1, t1 = _isl_run(C, make_scheduler("intra_plane", M=M),
                              windows=windows, isl=ident, fast=fast)
        parity = parity and _same_trajectory(e0, e1, r0, r1)
        if fast:
            t_fast = t1
        else:
            t_host = t1
    print(f"isl: identity-parity ground {t_ground:.3f}s, sink fast "
          f"{t_fast:.3f}s, sink host {t_host:.3f}s, "
          f"trajectory_identical={bool(parity)}", flush=True)
    out = {
        "K": K, "windows": windows, "M": M,
        "t_ground_run_s": t_ground,
        "t_sink_fast_s": t_fast,
        "t_sink_host_s": t_host,
        "identity_trajectory_identical": bool(parity),
    }
    if smoke:
        return out

    # (b) idle-time study: starlink40 over the single Svalbard station
    # with finite rates and station capacity; the 53-deg shells never see
    # the station, so ground-only policies leave the polar shell carrying
    # everything while sink relaying pulls whole planes into each pass.
    # FedSpace plans at the paper's schedule density (n in [4, 8] per
    # I0 = 24); the sink threshold matches fedbuff's M so the comparison
    # isolates the relay mechanism, not the aggregation cadence.
    spec = constellation_preset("starlink40", ground="sparse1")
    days = 2.0
    study_windows = int(days * 96)
    budget = link_budget(spec, days=days, uplink_mbps=20.0,
                         downlink_mbps=100.0, model_mb=600.0,
                         gs_capacity=2)
    runtime = ISL.build_isl(spec, ISL.ISLConfig(isl_mbps=100.0,
                                                model_mb=600.0, epoch=24))
    reach = ISL.reachable_count(runtime.topology,
                                budget.served[:study_windows])
    M_study = max(2, reach // 4)
    rf = _fit_search_regressor()
    scheds = {
        "fedspace": make_scheduler("fedspace", regressor=rf, I0=24,
                                   n_min=4, n_max=8, num_candidates=512,
                                   seed=0),
        "fedbuff": make_scheduler("fedbuff", M=M_study),
        "intra_plane": make_scheduler("intra_plane", M=M_study),
        "isl_async": make_scheduler("isl_async"),
    }
    cells = {}
    for name, sched in scheds.items():
        eng, res, t = _isl_run(budget.served, sched, windows=study_windows,
                               isl=runtime, budget=budget)
        cells[name] = {
            "idle_fraction": res.idle_connections
            / max(res.total_connections, 1),
            "idle_connections": res.idle_connections,
            "total_connections": res.total_connections,
            "global_updates": res.num_global_updates,
            "aggregated_gradients": res.num_aggregated_gradients,
            "t_run_s": t,
        }
        print(f"isl {name}: idle {cells[name]['idle_fraction']:.2f} "
              f"({res.idle_connections}/{res.total_connections}), "
              f"updates {res.num_global_updates}, grads "
              f"{res.num_aggregated_gradients}", flush=True)
    out.update({
        "study_preset": "starlink40", "study_ground": "sparse1",
        "study_windows": study_windows, "study_M": M_study,
        "reachable_satellites": reach,
        "study_cells": cells,
        "idle_reduced": bool(cells["intra_plane"]["idle_fraction"]
                             < cells["fedspace"]["idle_fraction"]),
    })
    return out


# ---------------------------------------------------------------------------
# 8. fault injection: all-alive parity gate + the churn/blackout study


@section("faults",
         parity=lambda r: r["all_alive_trajectory_identical"]
         and r.get("degradation_observed", True))
def bench_faults(smoke: bool) -> dict:
    """(a) Parity gate: an all-alive fault trace — no deorbits, every
    station up, unit weather — must reproduce the ``faults=None``
    trajectory bit-for-bit under BOTH engine strategies, on the
    geometry-only path and the link-budget path (the contract that fault
    injection is a pure mask over the clean artifacts, and that the
    inactive masks add nothing to the compiled programs). (b) Degradation
    study (full runs only): sync / fedbuff / fedspace / intra-plane sinks
    on starlink40 over the dense12 ground network under *blind* faults —
    escalating satellite churn, a total ground-network blackout, and
    weather-degraded links — reporting the idle/staleness/aggregated-
    gradient curves each scheduler traces as the planned and executed
    worlds diverge."""
    from repro.core import isl as ISL
    from repro.core.connectivity import (LinkBudget, constellation_preset,
                                         link_budget)
    from repro.core.faults import (FaultConfig, fault_trace, random_churn,
                                   station_blackout)

    # (a) all-alive parity, geometry and budget paths, both strategies
    Kp, Wp = 16, 64
    rng = np.random.default_rng(0)
    Cp = rng.random((Wp, Kp)) < 0.2
    grants = (rng.integers(1, 4, Cp.shape) * Cp).astype(np.int32)
    assign = np.where(Cp, rng.integers(0, 3, Cp.shape), -1).astype(np.int32)
    bp = LinkBudget(visible=Cp, served=Cp, assign=assign, grants=grants,
                    need_up=2, need_dn=1)
    alive_trace = fault_trace(FaultConfig(), Wp, K=Kp, num_stations=3)
    M = max(2, Kp // 8)
    parity = True
    t_none = t_alive = 0.0
    for budget in (None, bp):
        e0, r0, t0 = _isl_run(Cp, make_scheduler("fedbuff", M=M),
                              windows=Wp, budget=budget)
        t_none += t0
        for fast in (True, False):
            e1, r1, t1 = _isl_run(Cp, make_scheduler("fedbuff", M=M),
                                  windows=Wp, budget=budget, fast=fast,
                                  faults=alive_trace)
            parity = parity and _same_trajectory(e0, e1, r0, r1)
            if budget is not None:
                parity = parity and np.array_equal(e0.transfer_progress,
                                                   e1.transfer_progress)
            if fast:
                t_alive += t1
    print(f"faults: all-alive gate none {t_none:.3f}s, traced "
          f"{t_alive:.3f}s, trajectory_identical={bool(parity)}",
          flush=True)
    out = {
        "gate_K": Kp, "gate_windows": Wp,
        "t_none_runs_s": t_none,
        "t_all_alive_runs_s": t_alive,
        "all_alive_trajectory_identical": bool(parity),
    }
    if smoke:
        return out

    # (b) degradation study: starlink40 over dense12 under blind faults.
    # The schedulers plan on the clean connectivity the search was promised
    # (§3.1's determinism premise) while the engine executes the faulted
    # world — the curves measure how gracefully each policy degrades when
    # that premise breaks. Churn fractions share one seed so the fault
    # sets nest and the curves are comparable.
    spec = constellation_preset("starlink40")
    days = 2.0
    W = int(days * 96)
    G = len(spec.ground_stations)
    K = spec.num_satellites
    budget = link_budget(spec, days=days, uplink_mbps=20.0,
                         downlink_mbps=100.0, model_mb=600.0,
                         gs_capacity=2)
    runtime = ISL.build_isl(spec, ISL.ISLConfig(isl_mbps=100.0,
                                                model_mb=600.0, epoch=24))
    reach = ISL.reachable_count(runtime.topology, budget.served[:W])
    M_study = max(2, reach // 4)
    rf = _fit_search_regressor()
    sched_fns = {
        "sync": lambda: make_scheduler("sync"),
        "fedbuff": lambda: make_scheduler("fedbuff", M=M_study),
        "fedspace": lambda: make_scheduler(
            "fedspace", regressor=rf, I0=24, n_min=4, n_max=8,
            num_candidates=512, seed=0),
        "intra_plane": lambda: make_scheduler("intra_plane", M=M_study),
    }
    scenarios = {
        "clean": None,
        "churn20": FaultConfig(deorbit=random_churn(K, W, 0.20, seed=0)),
        "churn40": FaultConfig(deorbit=random_churn(K, W, 0.40, seed=0)),
        "blackout": FaultConfig(
            outages=station_blackout(G, W // 3, 2 * W // 3)),
        "weather": FaultConfig(rate_scale_min=0.25, rate_scale_max=1.0,
                               seed=1),
    }
    traces = {n: None if c is None
              else fault_trace(c, W, K=K, num_stations=G)
              for n, c in scenarios.items()}
    cells = {}
    for sname, make in sched_fns.items():
        cells[sname] = {}
        for scen, trace in traces.items():
            eng, res, t = _isl_run(budget.served, make(), windows=W,
                                   isl=runtime, budget=budget,
                                   faults=trace)
            hist = res.staleness_hist
            n_agg = int(hist.sum())
            cells[sname][scen] = {
                "idle_fraction": res.idle_connections
                / max(res.total_connections, 1),
                "total_connections": res.total_connections,
                "global_updates": res.num_global_updates,
                "aggregated_gradients": res.num_aggregated_gradients,
                "mean_staleness": float(
                    (hist * np.arange(len(hist))).sum() / max(n_agg, 1)),
                "t_run_s": t,
            }
        curve = " ".join(
            f"{scen}={c['aggregated_gradients']}"
            for scen, c in cells[sname].items())
        print(f"faults {sname}: agg_gradients {curve}", flush=True)

    def agg(s, scen):
        return cells[s][scen]["aggregated_gradients"]

    degradation = bool(all(
        agg(s, "churn40") < agg(s, "clean")
        for s in ("fedbuff", "fedspace")))
    out.update({
        "study_preset": "starlink40", "study_ground": "dense12",
        "study_windows": W, "study_M": M_study,
        "churn_fractions": [0.0, 0.2, 0.4],
        "blackout_windows": [W // 3, 2 * W // 3],
        "study_cells": cells,
        "degradation_observed": degradation,
    })
    return out


# ---------------------------------------------------------------------------
# 9. sweep scaling: batched whole-experiment dispatch + the sharded-K gate


class _NullAdapter:
    """Trains nothing: the mesh gate compares protocol trajectories only."""

    def __init__(self, K):
        self.clients = list(range(K))

    def init(self, key):
        return {"w": jnp.zeros((2,))}

    def loss(self, params, batch):
        return jnp.sum(params["w"]) * 0.0 + jnp.sum(batch) * 0.0

    def client_batch(self, ci, round_rng, batch_size, num_batches):
        return jnp.zeros((num_batches, 1))

    def accuracy(self, params):
        return 0.0

    def val_loss(self, params):
        return 0.0


def _mesh_parity(*, K, W, M) -> dict:
    """A fedbuff run on `sim_mesh()` over every visible device against the
    same run on one device: trajectory identity plus best-of-2 wall
    times."""
    from repro.core import mesh as MM

    C = np.random.default_rng(0).random((W, K)) < 0.08

    def run(mesh):
        eng = SimulationEngine(C, _NullAdapter(K),
                               make_scheduler("fedbuff", M=M),
                               EngineConfig(eval_every=W, max_windows=W),
                               mesh=mesh)
        t0 = time.perf_counter()
        eng.run()
        return eng, time.perf_counter() - t0

    mesh = MM.sim_mesh()
    e0, _ = run(None)
    t_single = min(run(None)[1] for _ in range(2))
    e1, _ = run(mesh)
    t_mesh = min(run(mesh)[1] for _ in range(2))
    return {"K": K, "windows": W, "devices": MM.mesh_size(mesh),
            "backend": jax.default_backend(),
            "t_single_device_s": t_single, "t_mesh_s": t_mesh,
            "trajectory_identical": not protocol_mismatches(e0, e1)}


_MESH_GATE_SCRIPT = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path[:0] = [{root!r}, {src!r}]
from benchmarks.hotpaths import _mesh_parity
print("MESH_GATE " + json.dumps(_mesh_parity(K={K}, W={W}, M={M})))
"""


def _mesh_gate(*, K, W, M):
    """The sharded-K parity gate. On an accelerator it runs in this
    process over the real devices (a child could not open the chip this
    process holds), and is reported as not run on a single device. On
    the CPU it runs in a child on a forced 8-virtual-device mesh, since
    the device count locks at the first jax init."""
    if jax.default_backend() != "cpu":
        if len(jax.devices()) < 2:
            return {"ran": False, "devices": 1,
                    "backend": jax.default_backend()}
        return {"ran": True, **_mesh_parity(K=K, W=W, M=M)}
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    script = _MESH_GATE_SCRIPT.format(
        root=_ROOT, src=os.path.join(_ROOT, "src"), K=K, W=W, M=M)
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=1200,
                       cwd=_ROOT, env=env)
    if r.returncode != 0:
        raise SystemExit(f"mesh gate subprocess failed:\n{r.stderr[-2000:]}")
    line = [l for l in r.stdout.splitlines()
            if l.startswith("MESH_GATE ")][-1]
    return {"ran": True, **json.loads(line[len("MESH_GATE "):])}


@section("sweep_scaling",
         parity=lambda r: r["per_variant_identical"]
         and (not r["mesh_gate"]["ran"]
              or r["mesh_gate"]["trajectory_identical"]))
def bench_sweep_scaling(smoke: bool) -> dict:
    """(a) Batched dispatch: a fedbuff-M x churn-fraction x seed grid of
    whole experiment variants over one world, run once as V sequential
    engine runs and once as a single `jit(vmap)` sweep dispatch
    (`repro.fl.sweep.sweep_engines`), parity-gated on every variant's
    protocol counters and final state being bit-identical. (b) Sharded-K
    gate: a fedbuff run at starlink1000 scale under `mesh=sim_mesh()` on
    a forced 8-virtual-device CPU mesh must be trajectory-bit-identical
    to the single-device run (subprocess, since the device count locks at
    first jax init)."""
    from repro.core.faults import FaultConfig, fault_trace, random_churn
    from repro.fl import sweep as SW
    if smoke:
        K, W = 12, 48
        Ms, fracs, seeds = (2, 4), (0.1, 0.2), (0, 1)        # V = 8
    else:
        K, W = 40, 192
        Ms, fracs, seeds = (2, 3, 4, 6), (0.1, 0.2, 0.3, 0.4), (0, 1)
    C = np.random.default_rng(0).random((W, K)) < 0.08
    adapter = _NullAdapter(K)
    grid = [(M, f, s) for M in Ms for f in fracs for s in seeds]
    traces = {(f, s): fault_trace(
        FaultConfig(deorbit=random_churn(K, W, f, seed=s)), W, K=K)
        for _, f, s in grid}

    def build():
        return [SimulationEngine(
            C, adapter, make_scheduler("fedbuff", M=M),
            EngineConfig(eval_every=W, max_windows=W),
            faults=traces[(f, s)]) for M, f, s in grid]

    # every variant shares the fedbuff indicator and column layout, so the
    # whole grid is ONE vmapped dispatch — count the groups to prove it
    groups = {SW._variant_columns(e)[0] for e in build()}

    def run_sequential():
        t0 = time.perf_counter()
        out = [(e, e.run()) for e in build()]
        return time.perf_counter() - t0, out

    def run_batched():
        engines = build()
        t0 = time.perf_counter()
        outs = SW.sweep_engines(engines)
        return time.perf_counter() - t0, outs

    _, seq = run_sequential()               # cold: pays the jit compiles
    t_seq = min(run_sequential()[0] for _ in range(2))
    t_swp_cold, outs = run_batched()
    t_swp = min(run_batched()[0] for _ in range(2))

    identical = all(
        np.array_equal(e.version, o.version)
        and np.array_equal(e.pending, o.pending)
        and np.array_equal(e.buffered_base, o.buffered)
        and e.ig == o.ig
        and r.staleness_hist.tolist() == o.result.staleness_hist.tolist()
        and r.idle_connections == o.result.idle_connections
        and r.total_connections == o.result.total_connections
        and r.num_global_updates == o.result.num_global_updates
        and r.num_aggregated_gradients
        == o.result.num_aggregated_gradients
        for (e, r), o in zip(seq, outs))

    print(f"sweep_scaling: {len(grid)} variants sequential {t_seq:.3f}s, "
          f"batched {t_swp:.3f}s ({t_seq / t_swp:.1f}x), "
          f"dispatch_groups={len(groups)}, per_variant_identical="
          f"{bool(identical)}", flush=True)

    gate = _mesh_gate(K=100 if smoke else 1000, W=48 if smoke else 96,
                      M=12)
    if gate["ran"]:
        print(f"sweep_scaling mesh gate: K={gate['K']} on "
              f"{gate['devices']} devices, single "
              f"{gate['t_single_device_s']:.3f}s, mesh "
              f"{gate['t_mesh_s']:.3f}s, trajectory_identical="
              f"{gate['trajectory_identical']}", flush=True)
    else:
        print("sweep_scaling mesh gate: not run (one device)", flush=True)
    return {
        "num_variants": len(grid), "K": K, "windows": W,
        "dispatch_groups": len(groups),
        "t_sequential_s": t_seq,
        "t_batched_s": t_swp,
        "t_batched_cold_s": t_swp_cold,
        "speedup": t_seq / t_swp,
        "per_variant_identical": bool(identical),
        "mesh_gate": gate,
    }


# ---------------------------------------------------------------------------
# 10. real payloads: compression-off parity gate + bytes-on-the-wire study


def _payload_exp(*, preset="", num_satellites=10, ground="", days,
                 adapter_kind="transformer", adapter_params=None,
                 scheduler="fedbuff", sched_params=None, model_mb=300.0,
                 topk=0.0, int8=False, train_topk=None, fast=True,
                 windows, eval_every, num_train=240, num_val=80,
                 local_steps=2):
    from repro.fl.api import (AdapterConfig, ConstellationConfig,
                              DatasetConfig, FLExperiment, LinkConfig,
                              SchedulerConfig)
    return FLExperiment(
        constellation=ConstellationConfig(num_satellites=num_satellites,
                                          days=days, preset=preset,
                                          ground=ground),
        dataset=DatasetConfig(num_train=num_train, num_val=num_val),
        adapter=AdapterConfig(kind=adapter_kind,
                              params=dict(adapter_params or {})),
        scheduler=SchedulerConfig(kind=scheduler,
                                  params=dict(sched_params or {})),
        train=EngineConfig(eval_every=eval_every, max_windows=windows,
                           local_steps=local_steps, fast_loop=fast,
                           uplink_topk=train_topk),
        link=LinkConfig(uplink_topk=topk, uplink_int8=int8,
                        uplink_mbps=20.0, downlink_mbps=100.0,
                        model_mb=model_mb, gs_capacity=1),
    )


def _payload_run(exp):
    from repro.fl.api import Federation
    fed = Federation.from_experiment(exp)
    eng = fed.engine()
    t0 = time.perf_counter()
    res = eng.run()
    return fed, eng, res, time.perf_counter() - t0


@section("payloads",
         parity=lambda r: r["compression_off_trajectory_identical"]
         and r.get("need_up_reduced", True)
         and r.get("agg_gradients_shift", True))
def bench_payloads(smoke: bool) -> dict:
    """(a) Parity gate: a transformer federation — Pallas-dispatch forward,
    real client batches, a finite link budget — run with `uplink_topk`
    unset (None), an explicit 0.0, and under both engine strategies must
    produce one bit-identical trajectory AND bit-identical final model
    parameters: compression off is the absence of the feature, not a
    cheap approximation of it. (b) Bytes-on-the-wire study (full runs
    only): starlink40 over the single sparse1 station under a finite
    budget, sweeping model family (mlp vs transformer, with their wire
    sizes) x compression (off / top-k 0.25 / dense int8) x scheduler
    (fedbuff / async) — gated on compression measurably cutting
    `need_up` and shifting the aggregated-gradient counts, the coupling
    a bytes-blind contact model cannot express."""
    from repro.fl.compression import uplink_bytes_ratio

    # (a) compression-off parity, both sentinels x both strategies
    gate_kw = dict(num_satellites=10, days=0.25, windows=24, eval_every=12)
    gp = {"d_model": 16, "num_layers": 1, "num_heads": 2,
          "num_kv_heads": 1, "d_ff": 32}
    _, e0, r0, t_ref = _payload_run(_payload_exp(
        adapter_params=gp, train_topk=None, fast=True, **gate_kw))
    parity = True
    t_variants = 0.0
    for train_topk, fast in ((0.0, True), (None, False), (0.0, False)):
        _, e1, r1, t1 = _payload_run(_payload_exp(
            adapter_params=gp, train_topk=train_topk, fast=fast, **gate_kw))
        t_variants += t1
        parity = (parity and _same_trajectory(e0, e1, r0, r1)
                  and r0.accuracy == r1.accuracy
                  and all(np.array_equal(np.asarray(a), np.asarray(b))
                          for a, b in zip(jax.tree.leaves(e0.params),
                                          jax.tree.leaves(e1.params))))
    print(f"payloads: compression-off gate ref {t_ref:.3f}s, variants "
          f"{t_variants:.3f}s, trajectory_identical={bool(parity)}",
          flush=True)
    out = {
        "gate_K": 10, "gate_windows": 24,
        "t_gate_ref_s": t_ref,
        "t_gate_variants_s": t_variants,
        "compression_off_trajectory_identical": bool(parity),
    }
    if smoke:
        return out

    # (b) the study: one constellation/station world, model x compression
    # x scheduler. Wire sizes are per family (the transformer pytree is
    # the heavy payload); compression rescales the effective upload bytes
    # through `uplink_bytes_ratio`, so `need_up` — and with it how often
    # uploads complete inside a pass — moves with the ratio.
    models = {
        "mlp": ({"hidden": 64}, 300.0),
        "transformer": ({}, 600.0),          # default decoder stack
    }
    compression = {
        "off": dict(topk=0.0, int8=False),
        "topk25": dict(topk=0.25, int8=False),
        "int8": dict(topk=0.0, int8=True),
    }
    scheds = {
        "fedbuff": ("fedbuff", {"M": 2}),
        "async": ("async", {}),
    }
    days, windows = 2.0, 192
    cells = {}
    for mname, (mp, mb) in models.items():
        for cname, ckw in compression.items():
            for sname, (skind, skw) in scheds.items():
                fed, eng, res, t = _payload_run(_payload_exp(
                    preset="starlink40", ground="sparse1", days=days,
                    windows=windows, eval_every=windows,
                    adapter_kind=mname, adapter_params=mp, model_mb=mb,
                    scheduler=skind, sched_params=skw,
                    num_train=600, num_val=200, **ckw))
                b = fed.link_budget
                cells[f"{mname}/{cname}/{sname}"] = {
                    "model_mb": mb,
                    "bytes_ratio": uplink_bytes_ratio(
                        ckw["topk"], int8=ckw["int8"]),
                    "need_up": b.need_up, "need_dn": b.need_dn,
                    "global_updates": res.num_global_updates,
                    "aggregated_gradients": res.num_aggregated_gradients,
                    "idle_fraction": res.idle_connections
                    / max(res.total_connections, 1),
                    "final_accuracy": res.accuracy[-1],
                    "t_run_s": t,
                }
                c = cells[f"{mname}/{cname}/{sname}"]
                print(f"payloads {mname}/{cname}/{sname}: need_up "
                      f"{c['need_up']}, grads {c['aggregated_gradients']}, "
                      f"acc {c['final_accuracy']:.3f}", flush=True)
    need_up_reduced = all(
        cells[f"{m}/{c}/{s}"]["need_up"] < cells[f"{m}/off/{s}"]["need_up"]
        for m in models for c in ("topk25", "int8") for s in scheds)
    agg_shift = any(
        cells[f"{m}/{c}/{s}"]["aggregated_gradients"]
        != cells[f"{m}/off/{s}"]["aggregated_gradients"]
        for m in models for c in ("topk25", "int8") for s in scheds)
    out.update({
        "study_preset": "starlink40", "study_ground": "sparse1",
        "study_windows": windows,
        "study_cells": cells,
        "need_up_reduced": bool(need_up_reduced),
        "agg_gradients_shift": bool(agg_shift),
    })
    return out


# ---------------------------------------------------------------------------
# 11. incremental replan service: delta-vs-full parity gate + latency study


@section("replan",
         parity=lambda r: r["selection_identical"] and r["delta_steps"] >= 1)
def bench_replan(smoke: bool) -> dict:
    """Incremental replanning (`repro.fl.replan.ReplanService`): on each
    consecutive aggregation event the service reuses the cached rollout
    prefix over the overlapping horizon and simulates only the newly
    revealed window. Parity: every answered schedule must be bit-identical
    to a full `score_candidates` + `select_candidate` rescan of the
    service's own live pool from the caller's state, and at least one
    request must have taken the delta path. The study reports the warm
    delta answer latency against the full-rescan latency at the same
    shapes (the serving claim in docs/replanning.md), plus the deferred
    `maintain()` cost."""
    from repro.core.search import score_candidates, select_candidate
    from repro.fl.replan import ReplanService

    K = 16 if smoke else 1000         # starlink1000 scale
    R = 256 if smoke else 20000       # serving-scale candidate pool
    I0 = 8 if smoke else 24
    steps = 8
    s_max = 8
    rf = _fit_search_regressor(s_max=s_max)
    rng = np.random.default_rng(0)
    C = rng.random((I0 + steps, K)) < 0.15

    svc = ReplanService(rf, I0=I0, num_candidates=R, n_min=4, n_max=8,
                        s_max=s_max, seed=3,
                        min_pool=16 if smoke else 256)
    state = jax.tree.map(np.asarray, SS.bootstrap_state(K))
    ig = 0
    draw_rng = np.random.default_rng(7)

    identical = True
    t_delta, t_maintain, t_full = [], [], []
    for i in range(steps):
        Cw = C[i:i + I0]
        t0 = time.perf_counter()
        plan = svc.replan(i, Cw, state, ig, 1.0, rng=draw_rng)
        t_ans = time.perf_counter() - t0
        if svc.last_mode == "delta":
            t_delta.append(t_ans)
            t0 = time.perf_counter()
            svc.maintain()               # deferred advance, off the answer
            t_maintain.append(time.perf_counter() - t0)
        # the gate: full rescan of the live pool from the caller's state
        pool = svc.pool
        t0 = time.perf_counter()
        scores = score_candidates(pool, Cw, state, ig, rf, 1.0,
                                  s_max=s_max)
        w = select_candidate(pool, scores)
        t_full.append(time.perf_counter() - t0)
        identical = identical and bool(np.array_equal(plan, pool[w]))
        # realize the winning bit: the true state advances one window
        st, g, _ = SS.step(jax.tree.map(jnp.asarray, state),
                           jnp.int32(ig), jnp.asarray(C[i]),
                           jnp.asarray(bool(plan[0])), s_max=s_max,
                           collect="none")
        state = jax.tree.map(np.asarray, st)
        ig = int(g)

    # warm numbers: drop each path's first (compile-bearing) sample
    warm_delta_ms = (min(t_delta[1:] or t_delta) * 1e3
                     if t_delta else None)
    warm_full_ms = min(t_full[1:] or t_full) * 1e3
    out = {
        "K": K, "num_candidates": R, "I0": I0, "steps": steps,
        "delta_steps": len(t_delta),
        "full_steps": svc.stats["full"],
        "invalidated": dict(svc.stats["invalidated"]),
        "warm_delta_ms": warm_delta_ms,
        "warm_full_rescan_ms": warm_full_ms,
        "maintain_ms": (min(t_maintain[1:] or t_maintain) * 1e3
                        if t_maintain else None),
        "speedup_warm": (warm_full_ms / warm_delta_ms
                         if warm_delta_ms else None),
        "selection_identical": bool(identical),
    }
    print(f"replan: {out['delta_steps']}/{steps} delta, warm delta "
          f"{warm_delta_ms and round(warm_delta_ms, 1)}ms vs full rescan "
          f"{warm_full_ms:.1f}ms, maintain "
          f"{out['maintain_ms'] and round(out['maintain_ms'], 1)}ms, "
          f"selection_identical={bool(identical)}", flush=True)
    return out


# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes (CI harness-rot check)")
    ap.add_argument("--out", default=None,
                    help="output JSON path (default: repo-root "
                         "BENCH_hotpaths.json, or BENCH_hotpaths.smoke.json "
                         "with --smoke)")
    ap.add_argument("--sections", default=None,
                    help="comma-separated subset of registered sections to "
                         "run (e.g. --sections faults,isl); other sections' "
                         "entries are preserved from the existing report")
    args = ap.parse_args()

    compile_cache.enable()
    out_path = args.out or os.path.join(
        _ROOT, "BENCH_hotpaths.smoke.json" if args.smoke
        else "BENCH_hotpaths.json")

    selected = SECTIONS
    if args.sections:
        names = [n for n in args.sections.split(",") if n]
        unknown = [n for n in names if n not in SECTIONS]
        if unknown:
            raise SystemExit(f"unknown sections {unknown}; registered: "
                             f"{sorted(SECTIONS)}")
        selected = {n: SECTIONS[n] for n in names}

    t0 = time.time()
    print(f"# hot-path benchmark (smoke={args.smoke}, sections="
          f"{','.join(selected)}) on {jax.default_backend()}", flush=True)
    result = {}
    if args.sections and os.path.exists(out_path):
        # subset run: keep the other sections' entries from the existing
        # report so the file stays complete
        try:
            with open(out_path) as f:
                result = json.load(f)
        except (OSError, json.JSONDecodeError):
            result = {}
    result["meta"] = {
        "smoke": args.smoke,
        "date": time.strftime("%Y-%m-%d"),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
    }
    for name, (fn, _) in selected.items():
        result[name] = fn(args.smoke)
    result["meta"]["bench_wall_s"] = round(time.time() - t0, 2)

    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(f"# wrote {out_path} ({result['meta']['bench_wall_s']}s total)")

    # registered sections cannot rot by omission: every selected one must
    # have produced a report entry, and every parity verdict must hold
    missing = [n for n in selected
               if n not in result or result[n] is None]
    if missing:
        raise SystemExit(f"benchmark sections silently skipped: {missing}")
    violations = [n for n, (_, parity) in selected.items()
                  if parity is not None and not parity(result[n])]
    if violations:
        raise SystemExit(f"parity violation in {violations} — see JSON "
                         f"output")


if __name__ == "__main__":
    main()

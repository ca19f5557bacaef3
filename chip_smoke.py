"""Chip smoke test: the federation path on a TPU, at the paper's size.

    PYTHONPATH=src python chip_smoke.py              # phases 1-4, one chip
    PYTHONPATH=src python chip_smoke.py --chips 4    # the "sat" mesh only

(The script puts `src/` on the path itself, so `PYTHONPATH` is optional.)

1. device     — require a TPU; print its kind and the device count.
2. kernels    — each Pallas kernel at the shapes the FL path feeds it,
                against its `ref.py` oracle.
3. federation — FedBuff (M = 96, MLP payload) and FedSpace (eq.-13 search
                over 5,000 candidates, transformer payload) on flock191 for
                one simulated day, built with `Federation.from_experiment`
                as `repro.launch.fl_train` builds them. The FedBuff
                counters must equal those of the same run on the CPU.
4. replan     — a `ReplanService` on flock191: one full rescan, then delta
                replans, each equal to a full rescan of the live pool.
   mesh       — (`--chips 4` only) a starlink1000 FedBuff run and the
                eq.-13 search on the 4-chip "sat" mesh against one device.

Every phase is a function with size arguments, so tests run them on the
CPU at toy size; only `main()` requires the TPU. The seconds printed are
informational, not a benchmark. A failed phase raises, so the script exits
non-zero; the last line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.core import connectivity as CN  # noqa: E402
from repro.core import mesh as MM  # noqa: E402
from repro.core import staleness as SS  # noqa: E402
from repro.core.aggregation import aggregation_weights  # noqa: E402
from repro.core.search import (random_candidates, score_candidates,  # noqa: E402
                               select_candidate)
from repro.data.fmow import FmowSpec, SyntheticFmow  # noqa: E402
from repro.data.partition import iid_partition  # noqa: E402
from repro.data.pipeline import make_clients  # noqa: E402
from repro.fl.api import (AdapterConfig, ConstellationConfig,  # noqa: E402
                          DatasetConfig, FLExperiment, Federation,
                          PartitionConfig, SchedulerConfig)
from repro.fl.client import make_batched_client_update  # noqa: E402
from repro.fl.engine import EngineConfig, protocol_mismatches  # noqa: E402
from repro.fl.registry import ADAPTERS  # noqa: E402
from repro.fl.replan import ReplanService, calibrate_forest  # noqa: E402
from repro.kernels.agg.ops import aggregate_params_tree  # noqa: E402
from repro.kernels.agg.ref import weighted_aggregate_ref  # noqa: E402
from repro.kernels.flash_attention.ops import flash_attention_bshd  # noqa: E402
from repro.kernels.flash_attention.ref import attention_ref  # noqa: E402
from repro.kernels.rmsnorm.ops import rmsnorm  # noqa: E402
from repro.kernels.rmsnorm.ref import rmsnorm_ref  # noqa: E402

# Kernel-vs-oracle bounds on max |kernel - oracle|, for outputs of order
# 1. The kernels run at the default matmul precision, as the FL path calls
# them; the oracles at "highest". agg: f32 multiply-adds summed in another
# order. rmsnorm and flash: the f32 readings on a TPU v5e are about 1e-6,
# and the same computation on bf16-rounded inputs is off by about 1e-2;
# the bounds sit between, so a kernel that computes in bf16 fails (the
# kernel phase measures that bf16 error and requires it above the bound).
AGG_TOL = 1e-5
RMSNORM_TOL = 1e-4
FLASH_TOL = 1e-4

# FedBuff protocol counters of `fedbuff_experiment()` at its defaults,
# from the same phase run with JAX_PLATFORMS=cpu. They depend only on the
# connectivity and the buffer size, never on float results, so the chip
# must reproduce them exactly.
FEDBUFF_CPU_COUNTERS = {
    "global_updates": 12, "aggregated_gradients": 1204,
    "idle_connections": 152, "total_connections": 2105,
    "staleness_hist": [691, 377, 70, 33, 15, 15, 2, 1, 0],
    "windows_run": 96}

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class Clock:
    """Wall seconds of a block, and the seconds JAX spent tracing,
    lowering and compiling inside it. Informational only."""

    def _on_event(self, event, secs, **_):
        if event in _COMPILE_EVENTS:
            self.compile_s += secs

    def __enter__(self):
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.wall_s = time.perf_counter() - self._t0
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def seconds(self) -> dict:
        return {"wall_s": self.wall_s, "compile_s": self.compile_s}


def check(ok, message: str) -> None:
    """A phase's verdict: raises (and so fails the script) when not ok."""
    if not ok:
        raise RuntimeError(message)


def _report(name: str, out: dict) -> None:
    print(f"[{name}] " + json.dumps(out, default=str), flush=True)


# ---------------------------------------------------------------------------
# 1. device


def device_info() -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


# ---------------------------------------------------------------------------
# 2. kernels


def transformer_adapter(seed: int = 0):
    """The `transformer` adapter at its registered defaults, over a tiny
    dataset: its parameter tree and tensor shapes do not depend on the
    data size."""
    data = SyntheticFmow(FmowSpec(num_train=64, num_val=16))
    return ADAPTERS.build("transformer", data,
                          make_clients(iid_partition(64, 2, seed)))


def _max_err(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float32)
                                - np.asarray(b, np.float32))))


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def phase_kernels(*, Ms=(1, 96, 191), flat_n: int = 4_000_037,
                  flat_Ms=(96, 191), batch: int = 32,
                  interpret: bool = False, seed: int = 0) -> dict:
    """Each kernel through its ops entry point (`interpret=False`: the
    compiled kernel) at the default matmul precision, against its oracle
    at "highest": agg over the transformer adapter's parameter tree at
    every buffer size in `Ms`, and over one flat `flat_n` leaf (many
    blocks, a partial last one) at `flat_Ms`; rmsnorm at
    (batch * S, d_model); flash attention at (batch, S, H, hd) with K
    kv-heads, also at "highest". `bf16_err` is the oracle's own error on
    bf16-rounded inputs, which the bounds must stay under."""
    ad = transformer_adapter(seed)
    cfg, S = ad.cfg, ad.seq_len
    key = jax.random.PRNGKey(seed)
    highest = functools.partial(jax.default_matmul_precision, "highest")

    def agg_err(params, M):
        leaves = jax.tree.leaves(params)
        stack = jax.tree.map(
            lambda p, i: jax.random.normal(jax.random.fold_in(key, i),
                                           (M,) + p.shape),
            params, jax.tree.unflatten(jax.tree.structure(params),
                                       range(len(leaves))))
        w = aggregation_weights(jnp.arange(M) % 7, 0.5)
        got = aggregate_params_tree(params, stack, w, interpret=interpret)
        with highest():
            ref = jax.tree.map(
                lambda p, u: weighted_aggregate_ref(
                    p.reshape(-1), u.reshape(M, -1), w).reshape(p.shape),
                params, stack)
        return max(_max_err(a, b) for a, b in
                   zip(jax.tree.leaves(got), jax.tree.leaves(ref)))

    params = ad.init(key)
    flat = {"w": jax.random.normal(jax.random.fold_in(key, 99), (flat_n,))}
    out = {"agg_tree": {M: agg_err(params, M) for M in Ms},
           "agg_flat": {"n": flat_n,
                        **{M: agg_err(flat, M) for M in flat_Ms}}}

    d = cfg.d_model
    x = jax.random.normal(key, (batch * S, d))
    s = jax.random.normal(jax.random.fold_in(key, 1), (d,))
    got = rmsnorm(x, s, cfg.norm_eps, interpret=interpret)
    with highest():
        ref = rmsnorm_ref(x, s, cfg.norm_eps)
        ref_bf16 = rmsnorm_ref(_bf16(x), _bf16(s), cfg.norm_eps)
    out["rmsnorm"] = {"shape": [batch * S, d], "err": _max_err(got, ref),
                      "bf16_err": _max_err(ref_bf16, ref)}

    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = jax.random.normal(key, (batch, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 2), (batch, S, K, hd))
    v = jax.random.normal(jax.random.fold_in(key, 3), (batch, S, K, hd))

    def oracle(q, k, v):
        return jnp.moveaxis(attention_ref(*(jnp.moveaxis(t, 2, 1)
                                            for t in (q, k, v)),
                                          causal=True), 1, 2)

    def kernel(q, k, v):
        return flash_attention_bshd(q, k, v, causal=True, bq=S, bk=S,
                                    interpret=interpret)

    got, ref_default = kernel(q, k, v), oracle(q, k, v)
    with highest():
        got_highest, ref = kernel(q, k, v), oracle(q, k, v)
        ref_bf16 = oracle(_bf16(q), _bf16(k), _bf16(v))
    out["flash"] = {"shape": [batch, H, K, S, hd], "err": _max_err(got, ref),
                    "err_highest": _max_err(got_highest, ref),
                    "bf16_err": _max_err(ref_bf16, ref),
                    "oracle_default_err": _max_err(ref_default, ref)}

    agg = [*out["agg_tree"].values(),
           *(out["agg_flat"][M] for M in flat_Ms)]
    rn, fl = out["rmsnorm"], out["flash"]
    check(max(agg) <= AGG_TOL
          and rn["err"] <= RMSNORM_TOL < rn["bf16_err"]
          and max(fl["err"], fl["err_highest"]) <= FLASH_TOL < fl["bf16_err"],
          f"kernels against their oracles: {out}")
    return out


# ---------------------------------------------------------------------------
# 3. federation


def _experiment(name, *, adapter: AdapterConfig, scheduler: SchedulerConfig,
                preset: str, days: float, num_train: int, num_val: int,
                local_steps: int, seed: int) -> FLExperiment:
    """`repro.launch.fl_train.build_experiment`'s wiring (non-IID split,
    eval every 6 hours, connectivity tiled over the horizon) on a preset
    constellation and the benchmarks' dataset size, with no target-accuracy
    stop."""
    return FLExperiment(
        name=name,
        constellation=ConstellationConfig(preset=preset, days=days),
        dataset=DatasetConfig(num_train=num_train, num_val=num_val,
                              noise=2.2),
        partition=PartitionConfig(kind="noniid"),
        adapter=adapter,
        scheduler=scheduler,
        train=EngineConfig(local_steps=local_steps, client_lr=1.0,
                           eval_every=24, target_acc=None,
                           max_windows=int(days * 96),
                           repeat_connectivity=0),
        seed=seed)


def fedbuff_experiment(*, preset="flock191", days=1.0, num_train=36_000,
                       num_val=5_304, M=96, local_steps=16,
                       seed=0) -> FLExperiment:
    return _experiment(
        f"chip-smoke-fedbuff-M{M}",
        adapter=AdapterConfig(kind="mlp", params={"hidden": 48}),
        scheduler=SchedulerConfig(kind="fedbuff", params={"M": M}),
        preset=preset, days=days, num_train=num_train, num_val=num_val,
        local_steps=local_steps, seed=seed)


def fedspace_experiment(*, preset="flock191", days=1.0, num_train=36_000,
                        num_val=5_304, num_candidates=5000, local_steps=16,
                        setup=None, seed=0) -> FLExperiment:
    """FedSpace with the transformer payload. `setup` overrides phase-1
    knobs (`repro.fl.fedspace_setup.build_utility_regressor`)."""
    return _experiment(
        "chip-smoke-fedspace-transformer",
        adapter=AdapterConfig(kind="transformer"),
        scheduler=SchedulerConfig(
            kind="fedspace", params={"num_candidates": num_candidates},
            setup={"local_steps": local_steps, "client_lr": 1.0,
                   **(setup or {})}),
        preset=preset, days=days, num_train=num_train, num_val=num_val,
        local_steps=local_steps, seed=seed)


def client_update_hlo(fed: Federation) -> str:
    """Lowered text of the engine's jitted batched client update (same
    program `SimulationEngine.prepare` builds) for a 2-satellite event."""
    cfg = fed.experiment.train
    update_many = make_batched_client_update(
        fed.adapter, local_steps=cfg.local_steps, lr=cfg.client_lr)
    batches, rows = fed.adapter.client_batch_many(
        [0, 1], 0, cfg.batch_size, cfg.local_steps)
    check(rows, "no client batch to lower the update with")
    params = fed.adapter.init(jax.random.PRNGKey(0))
    m = jax.tree.leaves(batches)[0].shape[0]
    bases = jax.tree.map(lambda p: jnp.broadcast_to(p, (m,) + p.shape),
                         params)
    return update_many.lower(bases, batches).as_text()


def phase_federation(exp: FLExperiment) -> dict:
    """Build the world and run it; the integer counters, the final
    accuracy, and whether the client update holds a TPU kernel call."""
    with Clock() as build:
        fed = Federation.from_experiment(exp)
    with Clock() as run:
        res = fed.run()
    acc = res.accuracy[-1]
    check(res.num_global_updates >= 1, f"{exp.name}: no aggregation")
    check(np.isfinite(acc), f"{exp.name}: final accuracy {acc}")
    return {"counters": res.counters(), "final_acc": acc,
            "regressor": fed.scheduler_diag or None,
            "client_update_tpu_custom_call":
                "tpu_custom_call" in client_update_hlo(fed),
            "build": build.seconds(), "run": run.seconds()}


# ---------------------------------------------------------------------------
# 4. replan service


def phase_replan(*, preset="flock191", days=0.5, I0=24,
                 num_candidates=5000, steps=6, s_max=8, seed=0) -> dict:
    """Consecutive replans on a persistent service, realizing each
    answer's first action. Every answer must equal `score_candidates` +
    `select_candidate` on the service's live pool from the same state."""
    C = CN.connectivity_sets(CN.constellation_preset(preset), days=days)
    rf = calibrate_forest(C, s_max=s_max, seed=seed)
    svc = ReplanService(rf, I0=I0, num_candidates=num_candidates,
                        s_max=s_max, seed=seed, min_pool=64)
    state = jax.tree.map(np.asarray, SS.bootstrap_state(C.shape[1]))
    ig, status, modes, ms = 0, 1.0, [], []
    rng = np.random.default_rng(seed + 1)
    for i in range(steps):
        Cw = C[i:i + I0]
        t0 = time.perf_counter()
        plan = svc.replan(i, Cw, state, ig, status, rng=rng)
        ms.append((time.perf_counter() - t0) * 1e3)
        modes.append(svc.last_mode)
        pool = svc.pool
        scores = score_candidates(pool, Cw, state, ig, rf, status,
                                  s_max=s_max)
        full = pool[select_candidate(pool, scores)]
        check(np.array_equal(plan, full),
              f"replan window {i} ({svc.last_mode}) differs from a rescan")
        svc.maintain()
        st, g, _ = SS.step(jax.tree.map(jnp.asarray, state), jnp.int32(ig),
                           jnp.asarray(C[i]), jnp.asarray(bool(plan[0])),
                           s_max=s_max, collect="none")
        state, ig = jax.tree.map(np.asarray, st), int(g)
    check(modes[0] == "full" and "delta" in modes,
          f"no full-then-delta answer sequence: {modes}")
    return {"K": int(C.shape[1]), "modes": modes, "stats": svc.stats,
            "answer_ms": ms}


# ---------------------------------------------------------------------------
# mesh (four chips)


def phase_mesh(*, preset="starlink1000", days=1.0, num_train=36_000,
               num_val=5_304, M=96, I0=24, num_candidates=5000,
               seed=0) -> dict:
    """The "sat" mesh over every visible device against one device, in
    this process: a FedBuff run (counters and final `SatState`) and one
    eq.-13 search from a mid-run state (selected schedule)."""
    fed = Federation.from_experiment(fedbuff_experiment(
        preset=preset, days=days, num_train=num_train, num_val=num_val,
        M=M, seed=seed))
    mesh = MM.sim_mesh()
    engines, times = {}, {}
    for name, m in (("mesh", mesh), ("single", None)):
        with Clock() as clock:
            engines[name] = fed.engine(mesh=m)
            engines[name].run()
        times[name] = clock.seconds()
    diff = protocol_mismatches(engines["mesh"], engines["single"])
    check(not diff, f"the mesh run differs from one device in {diff}")

    C = fed.C
    rf = calibrate_forest(C[:4 * I0], seed=seed)
    state = SS.bootstrap_state(C.shape[1])
    a = (np.arange(I0) % 4 == 3).astype(np.int32)
    state, ig, _ = SS.simulate_window(jnp.asarray(C[:I0]), jnp.asarray(a),
                                      state, jnp.int32(0), collect="none")
    cands = random_candidates(np.random.default_rng(seed), I0, 2, 8,
                              num_candidates)
    Cw = C[I0:2 * I0]
    picks = {}
    for name, m in (("mesh", mesh), ("single", None)):
        with Clock() as clock:
            sc = score_candidates(cands, Cw, state, int(ig), rf, 1.0,
                                  mesh=m)
        picks[name] = (cands[select_candidate(cands, sc)], np.asarray(sc),
                       clock.seconds())
    check(np.array_equal(picks["mesh"][0], picks["single"][0]),
          "sharded search selected another schedule")
    return {"K": int(C.shape[1]), "devices": MM.mesh_size(mesh),
            "counters": engines["single"].result.counters(),
            "final_ig": engines["single"].ig,
            "state_identical": True, "schedule_identical": True,
            "scores_identical": bool(np.array_equal(picks["mesh"][1],
                                                    picks["single"][1])),
            "run_mesh": times["mesh"], "run_single": times["single"],
            "search_mesh": picks["mesh"][2],
            "search_single": picks["single"][2]}


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the 'sat' mesh phase, on 4 chips")
    args = ap.parse_args(argv)

    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"no TPU: JAX sees {dev['count']} {dev['platform']} "
              "device(s)", file=sys.stderr)
        return 1
    if dev["count"] < args.chips:
        print(f"--chips {args.chips} needs {args.chips} TPUs, found "
              f"{dev['count']}", file=sys.stderr)
        return 1
    _report("device", dev)
    compile_cache.enable()

    if args.chips == 4:
        with Clock() as clock:
            out = phase_mesh()
        _report("mesh", {**out, "phase": clock.seconds()})
    else:
        with Clock() as clock:
            out = phase_kernels()
        _report("kernels", {**out, "phase": clock.seconds()})

        out = phase_federation(fedbuff_experiment())
        _report("federation.fedbuff", out)
        check(out["counters"] == FEDBUFF_CPU_COUNTERS,
              f"FedBuff counters differ from the CPU run: "
              f"{out['counters']}")
        out = phase_federation(fedspace_experiment())
        _report("federation.fedspace", out)
        check(out["client_update_tpu_custom_call"],
              "the transformer client update holds no TPU kernel call")

        with Clock() as clock:
            out = phase_replan()
        _report("replan", {**out, "phase": clock.seconds()})

    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Federation driver: one `Federation` run through `SimulationEngine.run`,
warmed up for whole simulated days, then measured until the window's
seconds have passed.

Set-up builds the world (connectivity tiled by the engine's
`repeat_connectivity`), the data, the payload and the scheduler from the
configuration and the seed, and starts ONE engine run. Its first
`warmup_windows` windows are the warm-up: they compile (or load from the
persistent cache) every scan bucket, training bucket and aggregation size
of a day, and the first `check_events` aggregations are the steps the
reference follows. The same run then continues into the measured window;
a callback stops it once `--seconds` have passed.

Correctness, decided once the window has closed:
  * protocol: the integer counters, the final `SatState` and the global
    version against the plain Algorithm-1 reference over the same windows
    (exact);
  * payload: the worst leaf's gap between the norm of the program's
    parameter change and the reference's (float32 at `highest`), after
    the first aggregation (the first update as eq. 4 applies it) and
    after `check_events` aggregations. The reference draws its own
    weights from the seed and trains on the batches the program fed.
"""
from __future__ import annotations

import bisect
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import common
from bench.flops import Payload
from bench.reference import payload as RP
from bench.reference import protocol as RPROT
from bench.spans import PREFIX


# validation rows of one `accuracy` or `val_loss` pass: the adapter's
# default `max_n`
EVAL_ROWS = 2048


def experiment(cfg: dict, seed: int, windows: int):
    """The configuration as an `FLExperiment` over `windows` windows."""
    from repro.fl.api import (AdapterConfig, ConstellationConfig,
                              DatasetConfig, FLExperiment, PartitionConfig,
                              SchedulerConfig)
    from repro.fl.engine import EngineConfig
    w, d, e = cfg["world"], cfg["dataset"], cfg["engine"]
    return FLExperiment(
        name=cfg["name"],
        constellation=ConstellationConfig(preset=w["preset"],
                                          ground=w["ground"],
                                          days=w["days"]),
        dataset=DatasetConfig(num_train=d["num_train"],
                              num_val=d["num_val"], noise=d["noise"],
                              image_size=d["image_size"],
                              feature_dim=d["feature_dim"], seed=seed),
        partition=PartitionConfig(kind=d["partition"]),
        adapter=AdapterConfig(kind=cfg["payload"]["kind"],
                              params=dict(cfg["payload"]["params"])),
        scheduler=SchedulerConfig(kind=cfg["scheduler"]["kind"],
                                  params=dict(cfg["scheduler"]["params"])),
        train=EngineConfig(local_steps=e["local_steps"],
                           batch_size=e["batch_size"],
                           client_lr=e["client_lr"],
                           server_lr=e["server_lr"], alpha=e["alpha"],
                           eval_every=e["eval_every"], s_max=e["s_max"],
                           target_acc=None, max_windows=windows,
                           repeat_connectivity=0),
        seed=seed)


def host_tree(tree):
    return jax.tree.map(lambda x: np.asarray(x, np.float32), tree)


def leaf_norms(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): float(np.linalg.norm(
        np.asarray(v, np.float64))) for k, v in flat}


def worst_leaf_gap(prog: dict, ref: dict, keep) -> float:
    """max over kept leaves of |‖prog‖ - ‖ref‖| / max(‖ref‖, median ‖ref‖).
    A leaf missing from the program counts as a gap of 1."""
    med = float(np.median([ref[k] for k in keep]))
    return max(abs(prog.get(k, 0.0) - ref[k]) / max(ref[k], med, 1e-30)
               for k in keep)


class Recorder:
    """The callback and wrappers of one run: window clock, stop, spans,
    the first aggregations' parameters and the batches fed to them."""

    def __init__(self, spans, *, warmup: int, seconds: float,
                 check_events: int, tracer=None):
        self.S = spans
        self.warmup, self.seconds = warmup, seconds
        self.check_events = check_events
        self.tracer = tracer
        self.snapshots = []          # params after event 0..check_events
        self.batches = {}            # (client, round) -> (X, y) host
        self.events = 0
        self.window_events = []      # buffer size of each event in window
        self.trained_rows = 0        # client rows trained in the window
        self.train_buckets = []      # padded group size of each update
        self.eval_calls = 0          # accuracy / val_loss passes
        self.t0 = self.t1 = None
        self.fast_ok = None
        self.compile_times = []

    # -- wrappers ------------------------------------------------------
    def recording(self) -> bool:
        return self.events < self.check_events

    def on_batch_many(self, out, client_ids, round_rng, *_a, **_k):
        stacked, rows = out
        if self.t0 is not None:
            self.trained_rows += len(rows)
        if self.recording() and rows:
            X, y = (np.asarray(a) for a in stacked)
            for j, r in enumerate(rows):
                self.batches[(int(client_ids[r]), int(round_rng))] = \
                    (X[j], y[j])

    def on_batch(self, out, client_idx, round_rng, *_a, **_k):
        if out is None:
            return
        if self.t0 is not None:
            self.trained_rows += 1
        if self.recording():
            self.batches[(int(client_idx), int(round_rng))] = tuple(
                np.asarray(a) for a in out)

    def on_train(self, out, base, batches):
        if self.t0 is not None:
            self.train_buckets.append(
                int(jax.tree.leaves(batches)[0].shape[0]))

    def on_eval_pass(self, *_):
        if self.t0 is not None:
            self.eval_calls += 1

    def _on_compile(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_times.append(time.perf_counter())

    # -- callback events -------------------------------------------------
    def on_run_begin(self, engine):
        jax.monitoring.register_event_duration_secs_listener(
            self._on_compile)
        self.fast_ok = engine._fast_ok
        self.snapshots.append(host_tree(engine.params))
        self.S.wrap(engine, "_batched_update", "client.train",
                    after=self.on_train)

    def on_aggregate_end(self, engine, window, info):
        self.events += 1
        if self.events <= self.check_events:
            self.snapshots.append(host_tree(engine.params))
        if self.t0 is not None:
            self.window_events.append(int(info["n_aggregated"]))
        t = time.perf_counter()
        self.S.spans.append(("agg.end", t, t))

    def on_window_end(self, engine, window):
        if window < self.warmup and window % 8 == 7:
            common.log(f"warm-up window {window + 1}/{self.warmup}, "
                       f"{self.events} aggregations, "
                       f"{len(self.compile_times)} compiles")
        if window == self.warmup - 1:
            jax.block_until_ready((engine.params, engine.state))
            if self.tracer is not None:
                self.tracer.__enter__()
            self._ann = jax.profiler.TraceAnnotation(PREFIX + "window")
            self._ann.__enter__()
            self.t0 = time.perf_counter()
        elif self.t0 is not None:
            t = time.perf_counter()
            self.S.spans.append(("window.end", t, t))
            if t - self.t0 >= self.seconds:
                engine.request_stop()

    def close(self, engine):
        """End the window: wait for the device, stop the clock."""
        jax.block_until_ready((engine.params, engine.state))
        self.t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        self.S.spans.append(("window", self.t0, self.t1))
        jax.monitoring.unregister_event_duration_listener(self._on_compile)
        if self.tracer is not None:
            self.tracer.__exit__(None, None, None)


def reference_readings(rec: Recorder, C, cfg: dict, seed: int,
                       windows_run: int, dtype=jnp.float32) -> tuple:
    """The plain reference over the run: the protocol over `windows_run`
    windows, and the payload through the first `check_events`
    aggregations in `dtype`. Returns (protocol reference, [params after
    event 0..n] on host, missing batches)."""
    e = cfg["engine"]
    M = cfg["scheduler"]["params"]["M"]
    events = []

    def on_event(i, ks, base, stal):
        if len(events) < rec.check_events:
            events.append((i, ks, base, stal))

    proto = RPROT.run_fedbuff(np.asarray(C, bool), M, windows_run,
                              s_max=e["s_max"], on_event=on_event)
    P = Payload.from_config(cfg)
    hist = [RP.cast(RP.init(jax.random.PRNGKey(seed), P), dtype)]
    missing = 0
    for i, ks, base, stal in events:
        got = [rec.batches.get((int(k), int(i))) for k in ks]
        missing += sum(g is None for g in got)
        if any(g is None for g in got):
            break
        rows = []          # one client per call: one program per shape
        for (X, y), b in zip(got, base):
            u = RP.client_updates(
                jax.tree.map(lambda t: t[None], hist[int(b)]),
                jnp.asarray(X[None]).astype(dtype), jnp.asarray(y[None]),
                P=P, lr=float(e["client_lr"]))
            rows.append(jax.tree.map(lambda t: t[0], u))
        u = jax.tree.map(lambda *xs: jnp.stack(xs), *rows)
        w = RP.weights(stal, e["alpha"], dtype)
        hist.append(RP.aggregate(hist[-1], u, w, float(e["server_lr"])))
    return proto, [host_tree(h) for h in hist], missing


def program_protocol(result, engine) -> dict:
    """The program's protocol outcome: counters, global version, state."""
    return {"counters": result.counters(), "ig": engine.ig,
            "version": np.asarray(engine.version),
            "pending": np.asarray(engine.pending),
            "buffered": np.asarray(engine.buffered_base)}


def protocol_mismatches(proto, prog: dict) -> int:
    """Protocol quantities in which the program and the reference differ."""
    n = sum(prog["counters"][k] != v for k, v in proto.counters.items())
    n += int(prog["ig"] != proto.ig)
    return n + sum(int(not np.array_equal(prog[f], getattr(proto, f)))
                   for f in ("version", "pending", "buffered"))


def payload_gaps(snaps, ref_hist, n: int) -> tuple:
    """(first-update gap, gap after n events). Leaves whose reference first
    update is under a thousandth of the median leaf's move by round-off
    alone and are left out."""
    if len(snaps) <= n or len(ref_hist) <= n:
        return 1.0, 1.0

    def delta(h, j):
        return jax.tree.map(lambda a, b: a - b, h[j], h[0])

    r1 = leaf_norms(delta(ref_hist, 1))
    med = float(np.median(list(r1.values())))
    keep = [k for k, v in r1.items() if v >= 1e-3 * med]
    g1 = worst_leaf_gap(leaf_norms(delta(snaps, 1)), r1, keep)
    gn = worst_leaf_gap(leaf_norms(delta(snaps, n)),
                        leaf_norms(delta(ref_hist, n)), keep)
    return g1, gn


def derive_spans(spans) -> list:
    """The wrapped calls' spans, plus two spans the engine's steps fill
    between them, derived from the callbacks' instants: `agg` from the
    last training dispatch of an event to its `on_aggregate_end` (weights,
    the aggregation kernel, the checkpoint ring), and `engine.chunk` from
    a window's end to the next gather or event end (the window scan, the
    buffer read, the grouping, the downloads)."""
    work = sorted((s, e, n) for n, s, e in spans
                  if n in ("data.gather", "client.train", "eval"))
    marks = sorted((s, n) for n, s, _ in spans
                   if n in ("agg.end", "window.end"))
    out = [(n, s, e) for s, e, n in work]
    starts = [s for s, _, _ in work]
    prev = None
    for t, name in marks:
        if prev is not None:
            lo = bisect.bisect_left(starts, prev)
            hi = bisect.bisect_left(starts, t)
            inside = work[lo:hi]
            if name == "agg.end" and inside:
                out.append(("engine.chunk", prev, inside[0][0]))
                out.append(("agg", max(e for _, e, _ in inside), t))
            else:
                out.append(("engine.chunk", prev,
                            inside[0][0] if inside else t))
        prev = t
    return out


def run(ctx) -> dict:
    cfg, wl = ctx.config, ctx.workload
    from repro.fl.api import Federation
    warmup = int(wl["warmup_windows"])
    horizon = warmup + int(wl["max_window_windows"])
    S = ctx.spans
    fed = Federation.from_experiment(experiment(cfg, ctx.seed, horizon))
    common.log("world built")
    tracer = ctx.tracer() if ctx.trace else None
    rec = Recorder(S, warmup=warmup, seconds=ctx.seconds,
                   check_events=int(wl["check_events"]), tracer=tracer)
    ad = fed.adapter
    S.wrap(ad, "client_batch_many", "data.gather", after=rec.on_batch_many)
    S.wrap(ad, "client_batch", "data.gather", after=rec.on_batch)
    S.wrap(ad, "accuracy", "eval", after=rec.on_eval_pass)
    S.wrap(ad, "val_loss", "eval", after=rec.on_eval_pass)
    engine = fed.engine(callbacks=[rec])
    result = engine.run()
    rec.close(engine)
    common.log(f"window closed: {result.windows_run - warmup} windows")
    window_s = rec.t1 - rec.t0
    windows = result.windows_run - warmup
    setup_s = rec.t0 - ctx.t_start
    mem = common.memory_peak_bytes(ctx.chips)
    leaf_sizes = [int(x.size) for x in jax.tree.leaves(engine.params)]
    C, windows_run = fed.C, result.windows_run
    prog = program_protocol(result, engine)
    del fed, engine, result      # the reference runs on a freed chip
    common.log("reference")
    n_check = rec.check_events
    proto, ref_hist, missing = reference_readings(rec, C, cfg, ctx.seed,
                                                  windows_run)
    mism = protocol_mismatches(proto, prog) + missing
    g1, gn = payload_gaps(rec.snapshots, ref_hist, n_check)
    lim = wl["limits"]
    names = ("protocol_mismatches", "update1_gap", f"change{n_check}_gap")
    limits = (lim["protocol_mismatches"], lim["update1_gap"],
              lim["change_gap"])
    checks = list(zip(names, (mism, g1, gn), limits))
    correct = all(v <= lim_ for _, v, lim_ in checks)
    control = None
    if ctx.extra.get("control"):
        _, ctl_hist, _ = reference_readings(
            rec, C, cfg, ctx.seed, windows_run, dtype=jnp.bfloat16)
        control = list(zip(names[1:], payload_gaps(ctl_hist, ref_hist,
                                                   n_check), limits[1:]))
    compiles = sum(1 for t in rec.compile_times if rec.t0 <= t <= rec.t1)
    return {
        "correct": bool(correct), "attempted": windows, "failed": 0,
        "end_to_end": {"setup_s": setup_s,
                       "sim_windows_per_s": windows / window_s},
        "memory_peak_bytes": mem, "checks": checks, "control": control,
        "info": {"windows": windows, "window_s": window_s,
                 "events": len(rec.window_events), "fast_ok": rec.fast_ok,
                 "compiles_in_window": compiles},
        "record": {"window": (rec.t0, rec.t1), "window_s": window_s,
                   "spans": derive_spans(
                       [x for x in S.spans if rec.t0 <= x[1] <= rec.t1]),
                   "windows": windows, "events": rec.window_events,
                   "trained_rows": rec.trained_rows,
                   "train_buckets": rec.train_buckets,
                   "eval_rows": rec.eval_calls * min(
                       EVAL_ROWS, cfg["dataset"]["num_val"]),
                   "compiles": compiles, "leaf_sizes": leaf_sizes,
                   "payload": Payload.from_config(cfg),
                   "local_steps": cfg["engine"]["local_steps"],
                   "batch_size": cfg["engine"]["batch_size"]},
    }

"""Replan driver: one ground-station caller in a closed loop against a
persistent `ReplanService`.

Request i asks for the schedule of windows [i, i + I0) of the day's
connectivity, tiled; the caller then realizes the answer's first action
through the protocol (`staleness.step`) and lets the service run
`maintain()` before the next request, as a real caller's 15-minute gap
would. The training status T changes every `status_every` requests, to a
value drawn from the seed. Each answer is timed on the host clock from
the request to the returned schedule.

Set-up builds the connectivity, the forest (weights drawn from the seed
by the benchmark) and the service, then answers `warmup_requests`
requests of the same traffic, which compile or load every program the
window uses. The window then runs until `--seconds` have passed.

Correctness, decided once the window has closed: for a sample of the
window's answers drawn from the seed, with the full rescans and the
slowest answer in it, the plain reference scores the answer's whole
candidate pool from its own protocol state and the widest relative gap
by which the served schedule's score lies below the pool's best is
compared with its limit.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import common
from bench.reference import protocol as RPROT
from bench.reference import search as RS


def program_forest(forest: dict):
    """The benchmark's forest weights as the program's fitted regressor."""
    from repro.core.utility import RandomForestRegressor, _Node
    n_int = 2 ** forest["depth"] - 1
    trees = []
    for t in range(forest["feature"].shape[0]):
        nodes = []
        for n in range(forest["feature"].shape[1]):
            inner = n < n_int
            nodes.append(_Node(
                feature=int(forest["feature"][t, n]) if inner else -1,
                thresh=float(forest["thresh"][t, n]),
                left=2 * n + 1 if inner else -1,
                right=2 * n + 2 if inner else -1,
                value=float(forest["value"][t, n])))
        trees.append(nodes)
    rf = RandomForestRegressor(n_trees=len(trees),
                               max_depth=forest["depth"])
    rf.trees = trees
    return rf


class Caller:
    """The closed-loop caller and its record of the run."""

    def __init__(self, svc, C, *, I0: int, s_max: int, statuses,
                 status_every: int, rng, spans):
        from repro.core import staleness as SS
        self.SS = SS
        self.svc, self.C, self.I0, self.s_max = svc, C, I0, s_max
        self.statuses, self.status_every = statuses, status_every
        self.rng, self.S = rng, spans
        self.state = jax.tree.map(np.asarray,
                                  SS.bootstrap_state(C.shape[1]))
        self.ig = 0
        self.i = 0
        self.actions = []           # first bit of every answer
        self.answers = []           # (request, mode, ms) in the window
        self.kept = {}              # request -> (pool, plan, status)

    def window_rows(self, i):
        W = self.C.shape[0]
        return self.C[(i + np.arange(self.I0)) % W]

    def status(self, i) -> float:
        return float(self.statuses[(i // self.status_every)
                                   % len(self.statuses)])

    def request(self, keep=None):
        """One request, its realization and the service's maintenance.
        `keep(i, mode, ms)` says whether to keep the pool for the check."""
        i, svc = self.i, self.svc
        Cw = self.window_rows(i)
        status = self.status(i)
        with self.S.span("replan.answer"):
            t0 = time.perf_counter()
            plan = svc.replan(i, Cw, self.state, self.ig, status,
                              rng=self.rng)
            ms = (time.perf_counter() - t0) * 1e3
        mode = svc.last_mode
        if keep is not None:
            self.answers.append((i, mode, ms))
            if keep(i, mode, ms):
                self.kept[i] = (svc.pool, np.asarray(plan), status)
        self.actions.append(int(plan[0]))
        with self.S.span("caller.step"):
            st, g, _ = self.SS.step(
                jax.tree.map(jnp.asarray, self.state), jnp.int32(self.ig),
                jnp.asarray(self.C[i % self.C.shape[0]]),
                jnp.asarray(bool(plan[0])), s_max=self.s_max,
                collect="none")
            self.state, self.ig = jax.tree.map(np.asarray, st), int(g)
        with self.S.span("replan.maintain"):
            svc.maintain()
        self.i += 1


class Sample:
    """Which window answers the check keeps: a reservoir of full rescans
    and one of delta answers, drawn from the seed, and the slowest."""

    def __init__(self, rng, n_full: int, n_delta: int):
        self.rng = rng
        self.cap = {"full": n_full, "delta": n_delta}
        self.seen = {"full": 0, "delta": 0}
        self.held = {"full": [], "delta": []}
        self.slowest = (-1.0, None)

    def __call__(self, i, mode, ms) -> bool:
        keep = False
        self.seen[mode] += 1
        held, cap = self.held[mode], self.cap[mode]
        if len(held) < cap:
            held.append(i)
            keep = True
        else:
            j = int(self.rng.integers(0, self.seen[mode]))
            if j < cap:
                held[j] = i
                keep = True
        if ms > self.slowest[0]:
            self.slowest = (ms, i)
            keep = True
        return keep

    def chosen(self):
        out = set(self.held["full"]) | set(self.held["delta"])
        if self.slowest[1] is not None:
            out.add(self.slowest[1])
        return sorted(out)


def reference_gaps(caller: Caller, chosen, forest: dict, *, s_max: int,
                   dtype=np.float32, served=None) -> list:
    """For each chosen request, (request, gap): the reference's best score
    over the kept pool less its score of the served schedule, over the
    best. `served(pool, scores)` picks the schedule in the program's place
    (the control); by default the program's own answer is scored."""
    chosen = set(chosen)
    proto = RPROT.Protocol(caller.C.shape[1], s_max)
    out = []
    for i, bit in enumerate(caller.actions):
        if i in chosen:
            pool, plan, status = caller.kept[i]
            st = proto.state()
            ref = RS.scores(pool, caller.window_rows(i), st, st["ig"],
                            forest, status, s_max=s_max)
            if served is None:
                hit = np.flatnonzero((pool == plan[None, :]).all(1))
                got = float(ref[hit].max()) if hit.size else -np.inf
            else:
                low = RS.scores(pool, caller.window_rows(i), st, st["ig"],
                                forest, status, s_max=s_max, dtype=dtype)
                got = float(ref[served(pool, low)])
            best = float(ref.max())
            out.append((i, (best - got) / max(abs(best), 1e-30)))
        conn = caller.C[i % caller.C.shape[0]]
        proto.upload(conn)
        if bit and (proto.buffered >= 0).any():
            proto.aggregate()
        proto.download(conn)
    return out


def top_candidate(pool, scores) -> int:
    """The control's selection: the first best-scoring candidate."""
    return int(np.argmax(np.asarray(scores, np.float32)))


def setup(cfg: dict, wl: dict, seed: int, spans):
    """World, forest, service and caller of one run."""
    from repro.core import connectivity as CN
    from repro.fl.replan import ReplanService
    w, sch = cfg["world"], cfg["scheduler"]["params"]
    C = np.asarray(CN.connectivity_sets(
        CN.constellation_preset(w["preset"], ground=w["ground"]),
        days=w["days"]), bool)
    rng = np.random.default_rng(seed)
    forest = RS.make_forest(rng, n_trees=cfg["forest"]["n_trees"],
                            depth=cfg["forest"]["max_depth"],
                            s_max=sch["s_max"],
                            status_range=wl["status_range"])
    lo, hi = wl["status_range"]
    statuses = lo + (hi - lo) * rng.random(wl["status_values"])
    svc = ReplanService(program_forest(forest), I0=sch["I0"],
                        num_candidates=sch["num_candidates"],
                        s_max=sch["s_max"], seed=seed,
                        min_pool=wl["min_pool"])
    caller = Caller(svc, C, I0=sch["I0"], s_max=sch["s_max"],
                    statuses=statuses, status_every=wl["status_every"],
                    rng=np.random.default_rng(rng.integers(2 ** 63)),
                    spans=spans)
    return caller, forest


def warm_shapes(caller: Caller, min_pool: int) -> int:
    """Warm every program shape the window can meet, which the traffic's
    own warm-up may miss. A full rescan's programs take the pool's largest
    aggregation count n as a width (1 to I0 // 2, the cap `infer_n_range`
    puts on it); a delta answer runs its one-window step and the
    histogram, features and forest inference of the candidates that
    schedule the new window at power-of-two buckets of rows up to the pool
    size, and re-reduces the survivors at a bucket of at least `min_pool`
    rows by a width up to n. Everything goes through the service's own
    functions, at the dtypes its cache holds. Returns the shapes warmed."""
    from repro.core import staleness as SS
    from repro.core.search import (random_candidates, scan_candidates,
                                   step_candidates)
    from repro.core.utility import featurize_jnp
    svc = caller.svc
    I0, K, s_max, R = caller.I0, caller.C.shape[1], svc.s_max, \
        svc.num_candidates
    status = caller.status(0)
    widths = range(1, I0 // 2 + 1)
    rng = np.random.default_rng(0)
    state = jax.tree.map(np.asarray, SS.bootstrap_state(K))
    for n in widths:
        scan_candidates(random_candidates(rng, I0, n, n, R),
                        caller.window_rows(0), state, 0, svc.regressor,
                        status, s_max=s_max)
    conn = jnp.asarray(caller.C[0])
    buckets = [1 << k for k in range((R - 1).bit_length() + 1)]
    for b in buckets:
        rows = SS.SatState(*(jnp.zeros((b, K), jnp.int16),) * 3)
        marks, _, _ = step_candidates(rows, jnp.zeros(b, jnp.int16), conn,
                                      jnp.zeros(b, jnp.int32), None,
                                      s_max=s_max)
        hists = SS.hist_from_marks(marks, s_max=s_max, dtype=jnp.int16)
        jax.block_until_ready(svc.regressor.predict_device(
            featurize_jnp(hists, jnp.float32(status))))
        if b < min_pool:
            continue
        for n in widths:
            util = jnp.asarray(np.zeros((b, n), np.float32))
            mask = jnp.asarray(np.zeros((b, n), bool), jnp.float32)
            np.asarray((util * mask).sum(axis=1))
    return len(widths) + len(buckets)


def run(ctx) -> dict:
    cfg, wl, S = ctx.config, ctx.workload, ctx.spans
    s_max = cfg["scheduler"]["params"]["s_max"]
    caller, forest = setup(cfg, wl, ctx.seed, S)
    common.log("service built")
    for _ in range(int(wl["warmup_requests"])):
        caller.request()
    common.log(f"{warm_shapes(caller, wl['min_pool'])} shapes warmed")
    sample = Sample(np.random.default_rng([ctx.seed, 1]),
                    wl["check_full"], wl["check_delta"])
    compiles = []

    def on_compile(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(time.perf_counter())

    tracer = ctx.tracer() if ctx.trace else None
    if tracer is not None:
        tracer.__enter__()
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    t0 = time.perf_counter()
    with S.span("window"):
        while time.perf_counter() - t0 < ctx.seconds:
            caller.request(keep=sample)
    t1 = time.perf_counter()
    jax.monitoring.unregister_event_duration_listener(on_compile)
    if tracer is not None:
        tracer.__exit__(None, None, None)
    setup_s = t0 - ctx.t_start
    common.log(f"window closed: {len(caller.answers)} answers; reference")
    mem = common.memory_peak_bytes(ctx.chips)
    ms = [m for _, _, m in caller.answers]
    chosen = sample.chosen()
    gaps = reference_gaps(caller, chosen, forest, s_max=s_max)
    worst = max((g for _, g in gaps), default=float("inf"))
    lim = wl["limits"]["score_gap"]
    checks = [("score_gap", worst, lim)]
    control = None
    if ctx.extra.get("control"):
        low = reference_gaps(caller, chosen, forest, s_max=s_max,
                             dtype=jnp.bfloat16, served=top_candidate)
        control = [("score_gap", max(g for _, g in low), lim)]
    modes = [m for _, m, _ in caller.answers]
    return {
        "correct": bool(gaps) and worst <= lim,
        "attempted": len(ms), "failed": 0,
        "end_to_end": {"setup_s": setup_s,
                       "replan_p50_ms": common.quantile(ms, 0.5),
                       "replan_p95_ms": common.quantile(ms, 0.95)},
        "memory_peak_bytes": mem, "checks": checks, "control": control,
        "info": {"requests": len(ms), "full": modes.count("full"),
                 "delta": modes.count("delta"), "window_s": t1 - t0,
                 "compiles_in_window": len(compiles),
                 "stats": caller.svc.stats},
        "record": {"window": (t0, t1), "window_s": t1 - t0,
                   "spans": [x for x in S.spans if t0 <= x[1] <= t1],
                   "answers": caller.answers, "compiles": len(compiles)},
    }

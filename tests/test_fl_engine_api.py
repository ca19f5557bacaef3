"""Tests for the `repro.fl` experiment layer: SimulationEngine parity with
the legacy `run_simulation` loop (transcribed below verbatim from the
pre-engine implementation), registry round-trips, the declarative
`FLExperiment`/`Federation` builder, and callbacks."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.checkpoint import CheckpointStore
from repro.core import connectivity as CN
from repro.core import staleness as SS
from repro.core.aggregation import apply_aggregation
from repro.core.scheduler import Scheduler, make_scheduler
from repro.data.fmow import FmowSpec, SyntheticFmow
from repro.data.partition import iid_partition
from repro.data.pipeline import make_clients
from repro.fl.adapters import MlpFmowAdapter
from repro.fl.api import (AdapterConfig, ConstellationConfig, DatasetConfig,
                          FLExperiment, Federation, PartitionConfig,
                          SchedulerConfig)
from repro.fl.callbacks import (Callback, EarlyStopCallback,
                                JsonlMetricsCallback)
from repro.fl.client import make_client_update
from repro.fl.engine import (EngineConfig, SimulationEngine,
                             protocol_mismatches)
from repro.fl.registry import (Registry, SCHEDULERS, register_scheduler)
from repro.fl.simulation import run_simulation


@pytest.fixture(scope="module")
def tiny_world():
    spec = CN.ConstellationSpec(num_satellites=16)
    C = CN.connectivity_sets(spec, days=1.0)
    data = SyntheticFmow(FmowSpec(num_train=800, num_val=200))
    adapter = MlpFmowAdapter(data, make_clients(iid_partition(800, 16, 0)))
    return C, adapter


# ---------------------------------------------------------------------------
# engine parity vs the legacy loop


def _legacy_run_simulation(C, adapter, scheduler, *, local_steps=4,
                           client_lr=0.05, server_lr=1.0, alpha=0.5,
                           eval_every=8, target_acc=None, max_windows=None,
                           s_max=8, seed=0, stop_at_target=True):
    """The pre-engine `run_simulation` body (seed commit), kept here as the
    reference trajectory the engine must reproduce bit-for-bit."""
    from repro.fl.engine import SimResult
    I, K = C.shape
    if max_windows:
        I = min(I, max_windows)
    scheduler.reset()
    params = adapter.init(jax.random.PRNGKey(seed))
    client_update = make_client_update(adapter, local_steps=local_steps,
                                      lr=client_lr, trainable_mask=None)
    store = CheckpointStore(keep_in_memory=s_max + 26)
    store.put(0, params)
    ig = 0
    version = np.zeros(K, np.int64)
    pending = np.zeros(K, np.int64)
    buffered_base = np.full(K, -1, np.int64)
    res = SimResult(scheme=scheduler.name, target_acc=target_acc)
    res.staleness_hist = np.zeros(s_max + 1, np.int64)
    status = float(adapter.val_loss(params))
    for i in range(I):
        conn = np.flatnonzero(C[i])
        for k in conn:
            res.total_connections += 1
            if pending[k] >= 0:
                buffered_base[k] = pending[k]
                pending[k] = -1
            elif version[k] == ig:
                res.idle_connections += 1
        n_buf = int((buffered_base >= 0).sum())
        state = SS.SatState(jnp.asarray(version, jnp.int32),
                            jnp.asarray(pending, jnp.int32),
                            jnp.asarray(buffered_base, jnp.int32))
        a = scheduler.decide(i, n_in_buffer=n_buf, K=K, state=state, ig=ig,
                             connectivity=C, status=status)
        if a and n_buf > 0:
            ks = np.flatnonzero(buffered_base >= 0)
            stal = ig - buffered_base[ks]
            updates = [client_update(store.get(int(buffered_base[k])),
                                     int(k), round_rng=i) for k in ks]
            stack = jax.tree.map(lambda *xs: jnp.stack(xs), *updates)
            params = apply_aggregation(params, stack, jnp.asarray(stal),
                                       alpha=alpha, server_lr=server_lr)
            ig += 1
            store.put(ig, params)
            refs = [v for v in np.concatenate([pending, buffered_base])
                    if v >= 0]
            store.prune(min(refs) if refs else ig)
            res.num_global_updates += 1
            res.num_aggregated_gradients += len(ks)
            np.add.at(res.staleness_hist, np.clip(stal, 0, s_max), 1)
            buffered_base[:] = -1
        for k in conn:
            if version[k] < ig:
                version[k] = ig
                pending[k] = ig
        res.windows_run = i + 1
        if (i + 1) % eval_every == 0 or i == I - 1:
            acc = adapter.accuracy(params)
            status = float(adapter.val_loss(params))
            res.accuracy.append(acc)
            res.val_loss.append(status)
            res.eval_windows.append(i)
            if (target_acc is not None and acc >= target_acc
                    and res.time_to_target_days is None):
                res.time_to_target_days = res.days(i)
                if stop_at_target:
                    break
    return res


@pytest.mark.parametrize("scheme,kw", [("sync", {}), ("async", {}),
                                       ("fedbuff", {"M": 4})])
def test_engine_matches_legacy_trajectory(tiny_world, scheme, kw):
    C, adapter = tiny_world
    ref = _legacy_run_simulation(C, adapter, make_scheduler(scheme, **kw),
                                 eval_every=16, max_windows=64)
    new = run_simulation(C, adapter, make_scheduler(scheme, **kw),
                         eval_every=16, max_windows=64)
    # Integer protocol counters are exact. Floats get a tolerance: the
    # engine trains buffered satellites in one vmapped program, the legacy
    # loop one jitted call per satellite, and the two XLA programs may
    # round their reductions differently in the last ulp (jax 0.9 CPU
    # does). One eval sample of 200 is 0.005 accuracy.
    floats = ("final_acc", "best_acc")
    strip = lambda d: {k: v for k, v in d.items() if k not in floats}
    assert strip(new.summary()) == strip(ref.summary())
    np.testing.assert_allclose(new.accuracy, ref.accuracy, atol=0.005)
    np.testing.assert_allclose(new.val_loss, ref.val_loss, rtol=1e-5)
    assert new.eval_windows == ref.eval_windows
    assert new.windows_run == ref.windows_run


def test_engine_overridable_step(tiny_world):
    """Scenario variants subclass the engine and override one protocol
    step — here, a lossy downlink that never delivers to satellite 0."""
    C, adapter = tiny_world

    class LossyDownlink(SimulationEngine):
        def on_downloads(self, i, conn):
            super().on_downloads(i, np.asarray(conn) & (
                np.arange(self.K) != 0))

    eng = LossyDownlink(C, adapter, make_scheduler("async"),
                        EngineConfig(eval_every=16, max_windows=48))
    res = eng.run()
    assert res.num_global_updates > 0
    assert eng.version[0] == 0          # never downloaded a newer model


def test_protocol_mismatches_names_what_differs(tiny_world):
    """Two runs of one configuration match in every protocol quantity; a
    different buffer size shows up in the counters and the final state."""
    C, adapter = tiny_world

    def run(M):
        eng = SimulationEngine(C, adapter, make_scheduler("fedbuff", M=M),
                               EngineConfig(eval_every=16, max_windows=48))
        eng.run()
        return eng

    a = run(4)
    assert protocol_mismatches(a, run(4)) == []
    diff = protocol_mismatches(a, run(2))
    assert {"global_updates", "ig", "version"} <= set(diff)
    assert a.result.counters()["windows_run"] == 48


# ---------------------------------------------------------------------------
# registries


def test_registry_roundtrip_and_helpful_keyerror():
    reg = Registry("widget")

    @reg.register("spinny")
    class Spinny:
        def __init__(self, speed=1):
            self.speed = speed

    assert "spinny" in reg and reg.names() == ["spinny"]
    assert reg.build("spinny", speed=3).speed == 3
    with pytest.raises(KeyError) as ei:
        reg.get("spiny")
    assert "spinny" in str(ei.value) and "widget" in str(ei.value)


def test_make_scheduler_lists_registered_names():
    with pytest.raises(KeyError) as ei:
        make_scheduler("does-not-exist")
    msg = str(ei.value)
    for name in ("sync", "async", "fedbuff", "fedspace", "periodic"):
        assert name in msg


def test_builtin_schedulers_registered_and_decide():
    assert {"sync", "async", "fedbuff", "fedspace",
            "periodic"} <= set(SCHEDULERS.names())
    sched = make_scheduler("fedbuff", M=3)
    assert sched.decide(0, n_in_buffer=3) and \
        not sched.decide(0, n_in_buffer=2)


def test_custom_scheduler_end_to_end(tiny_world):
    """Acceptance: a new scheduler plugs in via decorator + name only —
    no engine/scheduler-module edits."""
    C, adapter = tiny_world

    @register_scheduler("every3-test")
    class EveryThird(Scheduler):
        name = "every3-test"

        def decide(self, i, *, n_in_buffer, **_):
            return n_in_buffer > 0 and i % 3 == 2

    exp = FLExperiment(
        constellation=ConstellationConfig(num_satellites=16, days=1.0),
        dataset=DatasetConfig(num_train=800, num_val=200),
        scheduler=SchedulerConfig(kind="every3-test"),
        train=EngineConfig(eval_every=16, max_windows=48),
    )
    res = Federation.from_experiment(exp).run()
    assert res.scheme == "every3-test"
    assert res.num_global_updates > 0


# ---------------------------------------------------------------------------
# the declarative builder


def test_federation_wiring():
    exp = FLExperiment(
        constellation=ConstellationConfig(num_satellites=12, days=0.5),
        dataset=DatasetConfig(num_train=600, num_val=150),
        partition=PartitionConfig(kind="noniid"),
        adapter=AdapterConfig(kind="mlp", params={"hidden": 24}),
        scheduler=SchedulerConfig(kind="fedbuff", params={"M": 4}),
        train=EngineConfig(eval_every=16, max_windows=32),
        seed=3,
    )
    fed = Federation.from_experiment(exp)
    assert fed.spec.num_satellites == 12
    assert fed.C.shape[1] == 12
    assert len(fed.adapter.clients) == 12
    assert fed.adapter.hidden == 24
    assert fed.scheduler.name == "fedbuff"
    # all samples covered by the partition
    covered = np.sort(np.concatenate(
        [c.indices for c in fed.adapter.clients]))
    assert (covered == np.arange(600)).all()
    res = fed.run()
    assert res.windows_run == 32
    # same world, different policy — adapter/data shared, not rebuilt
    fed2 = fed.with_scheduler("async")
    assert fed2.adapter is fed.adapter
    assert fed2.run().scheme == "async"


def test_federation_auto_repeat_connectivity():
    exp = FLExperiment(
        constellation=ConstellationConfig(num_satellites=8, days=0.25),
        dataset=DatasetConfig(num_train=200, num_val=50),
        scheduler=SchedulerConfig(kind="async"),
        train=EngineConfig(eval_every=16, max_windows=60,
                           repeat_connectivity=0),
    )
    fed = Federation.from_experiment(exp)
    assert fed.C.shape[0] == 24                       # 0.25 days of windows
    eng = fed.engine()
    assert eng.num_windows == 60                      # C tiled to cover


# ---------------------------------------------------------------------------
# callbacks


def test_jsonl_and_early_stop_callbacks(tiny_world, tmp_path):
    C, adapter = tiny_world
    path = str(tmp_path / "metrics.jsonl")

    class NeverImproves(EarlyStopCallback):
        def on_eval(self, engine, window, metrics):
            super().on_eval(engine, window,
                            {**metrics, "accuracy": 0.0})

    eng = SimulationEngine(
        C, adapter, make_scheduler("async"),
        EngineConfig(eval_every=4, max_windows=96),
        callbacks=[JsonlMetricsCallback(path),
                   NeverImproves(patience=2)])
    res = eng.run()
    assert res.windows_run < 96                       # stopped early
    lines = [json.loads(l) for l in open(path)]
    events = [l["event"] for l in lines]
    assert events[0] == "run_begin" and events[-1] == "run_end"
    evals = [l for l in lines if l["event"] == "eval"]
    assert len(evals) == len(res.accuracy)
    assert evals[0]["accuracy"] == res.accuracy[0]


def test_aggregate_hook_sees_updates(tiny_world):
    C, adapter = tiny_world
    seen = []

    class Spy(Callback):
        def on_aggregate_end(self, engine, window, info):
            seen.append(info)

    res = SimulationEngine(C, adapter, make_scheduler("fedbuff", M=4),
                           EngineConfig(eval_every=16, max_windows=48),
                           callbacks=[Spy()]).run()
    assert len(seen) == res.num_global_updates
    assert sum(s["n_aggregated"] for s in seen) == \
        res.num_aggregated_gradients
    assert seen[-1]["ig"] == res.num_global_updates

"""Programs compiled inside aggregation events.

An event trains its n buffered satellites as one batch of rows at one
bucket B (the next power of two at or above n), each row on its own base
model, so an event compiles a fixed handful of programs the first time it
meets a bucket and nothing after that: however the buffer splits by base
version, and whatever the payload's parameter leaf count.
"""
import jax
import numpy as np
import pytest

from repro.core import connectivity as CN
from repro.core.scheduler import make_scheduler
from repro.data.fmow import FmowSpec, SyntheticFmow
from repro.data.partition import iid_partition
from repro.data.pipeline import make_clients, row_bucket
from repro.fl.adapters import MlpFmowAdapter, TransformerFmowAdapter
from repro.fl.engine import EngineConfig, SimulationEngine

# Programs a bucket costs when an event first meets it: the ring gather of
# the rows' bases, the vmapped training, the row assembly, the staleness
# weights and the reduction. The first event also compiles the protocol's
# aggregation step.
PER_BUCKET = 5
ONCE = 1

_ADAPTERS = {"mlp": (MlpFmowAdapter, {"hidden": 24}),
             "transformer": (TransformerFmowAdapter,
                             {"d_model": 16, "num_layers": 1, "num_heads": 2,
                              "num_kv_heads": 1, "d_ff": 24, "seq_len": 4})}


@pytest.fixture(scope="module")
def world():
    C = CN.connectivity_sets(CN.ConstellationSpec(num_satellites=24),
                             days=1.0)
    data = SyntheticFmow(FmowSpec(num_train=480, num_val=64))
    return C, data, make_clients(iid_partition(480, 24, 0))


def _event_compiles(world, kind):
    """(compiles inside `on_aggregate`, buckets met, the most base
    versions one event mixed, parameter leaves) over a cold FedBuff run."""
    C, data, clients = world
    cls, kw = _ADAPTERS[kind]
    adapter = cls(data, clients, **kw)
    eng = SimulationEngine(C, adapter, make_scheduler("fedbuff", M=5),
                           EngineConfig(eval_every=48, max_windows=48,
                                        local_steps=2, batch_size=4))
    inside, count, buckets, mixed = [False], [0], set(), [0]

    def listen(event, secs, **_):
        if inside[0] and event == "/jax/core/compile/backend_compile_duration":
            count[0] += 1

    aggregate = eng.on_aggregate

    def counted(i):
        buffered = np.asarray(eng.state.buffered)
        n = int((buffered >= 0).sum())
        buckets.add(row_bucket(n))
        mixed[0] = max(mixed[0], len(set(buffered[buffered >= 0])))
        inside[0] = True
        try:
            aggregate(i)
        finally:
            inside[0] = False

    eng.on_aggregate = counted       # an instance attribute: fast loop kept
    jax.clear_caches()
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        res = eng.run()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
        jax.config.update("jax_enable_compilation_cache", cache)
    assert eng._fast_ok and res.num_global_updates >= 4
    return count[0], buckets, mixed[0], len(jax.tree.leaves(eng.params))


def test_event_programs_bounded_per_bucket_and_leaf_free(world):
    """The MLP (4 leaves) and the transformer (more than 4) compile the
    same number of programs inside their events, within PER_BUCKET per
    bucket met, on runs whose events mix several base versions."""
    got = {kind: _event_compiles(world, kind) for kind in sorted(_ADAPTERS)}
    (n_mlp, b_mlp, mix_mlp, leaves_mlp), (n_tfm, b_tfm, mix_tfm,
                                          leaves_tfm) = \
        got["mlp"], got["transformer"]
    assert leaves_mlp == 4 and leaves_tfm > leaves_mlp
    assert b_mlp == b_tfm and len(b_mlp) >= 2          # same protocol
    assert min(mix_mlp, mix_tfm) >= 2     # events split by base version
    assert n_mlp == n_tfm, got
    assert n_mlp <= PER_BUCKET * len(b_mlp) + ONCE, got

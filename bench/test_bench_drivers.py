"""CPU rehearsal of both drivers at toy size: control flow, the wrappers,
the result line. Nothing here is a device number: on the CPU the trace
holds no chip, and every device metric stays out of the line."""
from __future__ import annotations

import pytest

from bench import common, run as R
from bench.conftest import FED, REPLAN, context, toy_federation, toy_replan
from bench.drivers import federation, replan

CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
DEVICE_METRICS = {"device_idle.fl", "device_idle.replan", "agg_roofline",
                  "flash_attention_roofline", "client_train.device_share"}


@pytest.fixture(scope="module")
def fed_run():
    wl, cfg = toy_federation()
    return federation.run(context(wl, cfg, seed=2_147_483_659))


def test_federation_rehearsal(fed_run):
    out = fed_run
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    e2e = out["end_to_end"]
    assert e2e["sim_windows_per_s"] > 0 and e2e["setup_s"] > 0
    rec = out["record"]
    assert rec["windows"] == out["attempted"]
    assert rec["trained_rows"] > 0 and rec["train_buckets"]
    names = {n for n, _, _ in rec["spans"]}
    assert {"data.gather", "client.train", "engine.chunk"} <= names


def test_wrappers_leave_the_fast_loop_on(fed_run):
    """The benchmark's callback and wrappers leave `_fast_ok` as a bare
    engine has it: the chunked fast loop still runs."""
    from repro.fl.api import Federation
    wl, cfg = toy_federation()
    engine = Federation.from_experiment(
        federation.experiment(cfg, 1, 8)).engine()
    engine.prepare()
    assert engine._fast_ok is True
    assert fed_run["info"]["fast_ok"] is engine._fast_ok


def test_replan_rehearsal():
    wl, cfg = toy_replan()
    out = replan.run(context(wl, cfg, seed=3_000_000_019, seconds=1.0))
    assert out["correct"], out["checks"]
    e2e = out["end_to_end"]
    assert 0 < e2e["replan_p50_ms"] <= e2e["replan_p95_ms"]
    modes = {m for _, m, _ in out["record"]["answers"]}
    assert modes <= {"full", "delta"} and out["attempted"] == len(
        out["record"]["answers"])


@pytest.mark.parametrize("cell,toy", [(FED, toy_federation),
                                      (REPLAN, toy_replan)])
def test_traced_line_has_no_device_metric_off_the_chip(cell, toy,
                                                      tmp_path):
    wl, cfg = toy()
    ctx = context(wl, cfg, seed=7, trace=True, trace_dir=tmp_path)
    line, checks = R.execute(ctx, CPU)
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    assert not DEVICE_METRICS & set(line["metrics"])
    assert line["device"]["busy_s"] == 0.0
    allowed = {m["name"] for m in common.metrics_for(cell, "per_layer")}
    assert set(line["metrics"]) <= allowed
    for m in line["metrics"].values():
        common.check_unit(m["unit"])
    assert [n for n, _, _ in checks]

"""The correctness check fails a broken timed path. Each test skips the
harness's look for a chip, drives the rest of a run at toy size with one
fault planted underneath, and sees `correct` come out false; and the
control, the plain reference in the program's place at the precision
below, comes out not correct where the program is."""
from __future__ import annotations

import functools

import jax
import numpy as np
import pytest

from bench.conftest import context, toy_federation, toy_replan
from bench.drivers import federation, replan


def _fed(**extra):
    wl, cfg = toy_federation()
    ctx = context(wl, cfg, seed=1_234_567_891)
    ctx.extra.update(extra)
    return federation.run(ctx)


def _rep(seed=2_222_222_227, **extra):
    wl, cfg = toy_replan()
    if extra.get("control"):
        # the control misses the best schedule on some answers only: check
        # every answer of the window
        wl.update(check_full=1000, check_delta=1000)
    ctx = context(wl, cfg, seed=seed, seconds=1.5)
    ctx.extra.update(extra)
    return replan.run(ctx)


def _fails(out):
    return not out["correct"] and any(v > lim for _, v, lim in
                                      out["checks"])


def test_federation_state_left_unchanged(monkeypatch):
    """The aggregation returns the global model as it was."""
    import repro.fl.engine as E
    monkeypatch.setattr(E, "aggregate_params_tree",
                        lambda params, stack, w, **_: params)
    assert _fails(_fed())


def test_federation_half_the_batch_left_out(monkeypatch):
    """Every local step's loss is the mean over half of its batch."""
    from repro.fl.adapters import TransformerFmowAdapter as T
    loss = T.loss

    def half(self, params, batch):
        X, y = batch
        n = max(1, X.shape[0] // 2)
        return loss(self, params, (X[:n], y[:n]))

    monkeypatch.setattr(T, "loss", half)
    assert _fails(_fed())


def test_federation_answer_altered(monkeypatch):
    """The new global model moves one leaf twice as far as eq. 4 says."""
    import repro.fl.engine as E
    agg = E.aggregate_params_tree

    def double_first(params, stack, w, **kw):
        new = agg(params, stack, w, **kw)
        leaves, tree = jax.tree.flatten(new)
        old = jax.tree.leaves(params)
        leaves[0] = old[0] + 2.0 * (leaves[0] - old[0])
        return jax.tree.unflatten(tree, leaves)

    monkeypatch.setattr(E, "aggregate_params_tree", double_first)
    assert _fails(_fed())


def test_federation_decision_altered(monkeypatch):
    """FedBuff skips its aggregation in every odd window."""
    import repro.core.scheduler as SC
    plan = SC.FedBuffScheduler.device_plan

    def late(self, i, **kw):
        fn, args, horizon = plan(self, i, **kw)
        return (lambda t, n, a: fn(t, n, a) & (t % 2 == 0)), args, horizon

    monkeypatch.setattr(SC.FedBuffScheduler, "device_plan", late)
    out = _fed()
    assert _fails(out)
    assert dict((n, v) for n, v, _ in out["checks"])[
        "protocol_mismatches"] > 0


def test_replan_answer_altered(monkeypatch):
    """Each served schedule has its last window's bit flipped."""
    from repro.fl.replan import ReplanService
    real = ReplanService.replan

    def flipped(self, *a, **kw):
        plan = real(self, *a, **kw).copy()
        plan[-1] = 1 - plan[-1]
        return plan

    monkeypatch.setattr(ReplanService, "replan", flipped)
    assert _fails(_rep())


def test_replan_half_the_pool_left_out(monkeypatch):
    """The winner is chosen from the first half of the pool only."""
    import repro.fl.replan as RP
    select = RP.select_candidate

    def half(cands, scores):
        n = max(1, len(scores) // 2)
        return select(cands[:n], np.asarray(scores)[:n])

    monkeypatch.setattr(RP, "select_candidate", half)
    assert _fails(_rep())


@pytest.mark.parametrize("run", [_fed, functools.partial(_rep, seed=9)],
                         ids=["federation", "replan"])
def test_control_is_not_correct(run):
    out = run(control=True)
    assert out["correct"], out["checks"]
    assert any(v > lim for _, v, lim in out["control"]), out["control"]

"""Toy-size cells for the benchmark's CPU tests: the real configurations
and traffic files, shrunk so that a driver runs end to end in seconds.
They rehearse control flow and the correctness checks only; no device
metric comes from them."""
from __future__ import annotations

import time

import pytest

from bench import common
from bench.run import Context
from bench.spans import Spans

FED = "flock191-fedbuff-tfm.m10"
REPLAN = "flock191-fedspace-tfm.replan"
# The federation cell is not in the benchmark yet (PERF.md, Open
# questions): its toy runs carry a limit of their own, between the
# program's readings here (float32 on the CPU, about 1e-6) and the
# bfloat16 control's (about 1e-2).
TOY_GAP = 1e-3


def toy_federation():
    cfg = common.load_config("flock191-fedbuff-tfm")
    wl = {"name": FED, "config": cfg["name"], "driver": "federation",
          "chips": 1, "check_events": 3,
          "limits": {"protocol_mismatches": 0, "update1_gap": TOY_GAP,
                     "change_gap": TOY_GAP}}
    cfg["world"].update(preset="starlink40", days=0.25)
    cfg["scheduler"]["params"]["M"] = 4
    cfg["dataset"].update(num_train=400, num_val=64)
    cfg["payload"]["params"].update(d_model=8, num_heads=2, num_kv_heads=1,
                                    d_ff=16, seq_len=4, num_layers=1)
    cfg["engine"].update(local_steps=2, batch_size=4)
    wl.update(warmup_windows=4, max_window_windows=8)
    return wl, cfg


def toy_replan():
    wl = common.load_workload(REPLAN)
    cfg = common.load_config(wl["config"])
    cfg["world"].update(preset="starlink40", days=0.25)
    cfg["scheduler"]["params"].update(I0=12, num_candidates=512)
    cfg["forest"].update(n_trees=8, max_depth=4)
    wl.update(warmup_requests=3, min_pool=8)
    return wl, cfg


def context(wl, cfg, *, seed=5, seconds=0.5, trace=False, trace_dir=None):
    ctx = Context(workload=wl, config=cfg, seed=seed, seconds=seconds,
                  trace=trace, chips=1, t_start=time.perf_counter(),
                  spans=Spans())
    if trace_dir is not None:
        ctx.trace_dir = str(trace_dir)
    return ctx


@pytest.fixture
def toy_fed():
    return toy_federation()


@pytest.fixture
def toy_rep():
    return toy_replan()

"""The program's spans as the per-layer readers see them: a traced CPU
rehearsal of the replan cell read through `bench.program_spans`, the
nesting and the idle-inside-spans arithmetic by hand, and a trace of
another run refused."""
from __future__ import annotations

import os

import pytest

from bench import common, program_spans as P, run as R, trace
from bench.conftest import REPLAN, context, toy_replan
from bench.program_spans import Span
from bench.readers import Run

CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
SPAN_READERS = ("replan.check_ms", "replan.delta_score_ms",
                "replan.delta_reduce_ms", "replan.delta_rows")
IDLE_READERS = ("replan.delta_idle_share", "replan.full_idle_ms")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A traced toy run of the replan cell, with the trace where the
    harness keeps it: (result line, the run as the readers get it)."""
    root = tmp_path_factory.mktemp("trace")
    mp = pytest.MonkeyPatch()
    mp.setattr(R, "TRACE_DIR", str(root))
    wl, cfg = toy_replan()
    ctx = context(wl, cfg, seed=2_147_483_659, seconds=1.5, trace=True,
                  trace_dir=root)
    line, _ = R.execute(ctx, CPU)
    tr = trace.load(trace.find_xplane(os.path.join(root, REPLAN)))
    yield line, Run(REPLAN, {}, tr, {})
    mp.undo()


def test_span_readers_read_the_program_spans(traced):
    line, run = traced
    for name in SPAN_READERS:
        assert line["metrics"][name]["value"] > 0, name
        assert common.load_metric(name).read(run) == \
            line["metrics"][name]["value"]
    rows = line["metrics"]["replan.delta_rows"]["value"]
    assert 1 <= rows <= 512


def test_idle_readers_need_a_device_plane(traced):
    line, run = traced
    assert run.trace.devices == []
    for name in IDLE_READERS:
        assert name not in line["metrics"]
        assert common.load_metric(name).read(run) is None


def test_spans_nest_into_their_request(traced):
    _, run = traced
    reqs = P.requests(run)
    assert reqs
    lo, hi = run.trace.window
    for r in reqs:
        assert lo <= r.start and r.end <= hi
        kids = {c.name for c in r.children}
        assert "replan.check" in kids and kids & {"replan.delta",
                                                   "replan.full"}
        for s in r.walk():
            if s.name.startswith("replan."):
                assert s.counts["window"] == r.counts["window"]
    deltas = P.delta_requests(run)
    assert deltas and all(0.0 < P.coverage(r) <= 1.0 for r in deltas)
    summary = P.summary(run)
    assert summary["spans"]["replan.request"]["count"] == len(reqs)
    assert summary["spans"]["replan.request"]["idle_s"] is None


def test_a_trace_of_another_window_is_not_read(traced):
    _, run = traced
    other = trace.Trace(window=(run.trace.window[0] + 1.0,
                                run.trace.window[1]),
                        ops={}, modules={})
    assert P.roots(Run(REPLAN, {}, other, {})) == []
    assert P.roots(Run(REPLAN, {}, None, {})) == []
    for name in SPAN_READERS + IDLE_READERS:
        assert common.load_metric(name).read(Run(REPLAN, {}, other,
                                                 {})) is None


def test_nesting_by_hand():
    a = Span("replan.request", 0.0, 10.0, {})
    b = Span("replan.check", 1.0, 2.0, {})
    c = Span("replan.delta", 2.0, 9.0, {})
    d = Span("replan.delta.extend", 2.0, 3.0, {})
    e = Span("replan.request", 11.0, 12.0, {})
    assert P.nest([e, d, c, b, a]) == [a, e]
    assert a.children == [b, c] and c.children == [d]
    assert [s.name for s in a.walk()] == ["replan.request", "replan.check",
                                          "replan.delta",
                                          "replan.delta.extend"]
    # leaves b (1 s) and d (1 s) of a 10 s request
    assert P.coverage(a) == pytest.approx(0.2)


def test_idle_inside_spans_by_hand():
    t = trace.Trace(window=(0.0, 20.0),
                    ops={0: [("a", 1.0, 3.0), ("b", 2.0, 4.0),
                             ("c", 8.0, 12.0), ("d", 15.0, 16.0)],
                         1: [("a", 0.0, 20.0)]},
                    modules={})
    spans = [Span("x", 0.0, 5.0, {}), Span("y", 3.0, 9.0, {}),
             Span("z", 10.0, 14.0, {}), Span("w", 17.0, 18.0, {})]
    # device 0 busy [1, 4], [8, 12], [15, 16]
    assert P.idle_gaps(t, spans, 0) == [[(0.0, 1.0), (4.0, 5.0)],
                                        [(4.0, 8.0)], [(12.0, 14.0)],
                                        [(17.0, 18.0)]]
    # device 1 is never idle: the mean over the two devices halves it
    assert P.idle_s(t, spans) == pytest.approx([1.0, 2.0, 1.0, 0.5])
    assert P.idle_s(trace.Trace((0.0, 1.0), {}, {}), spans) is None
    assert P.idle_s(t, []) == []

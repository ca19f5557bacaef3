"""Program spans (`repro.tracing`): outside a profiler session a span is
one shared context that records nothing; under `jax.profiler.trace` the
replan service's spans land in the trace, nested in the request they
serve, with the counts the service holds; and tracing changes no answer
and no counter of the service."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.core import staleness as SS
from repro.core.utility import RandomForestRegressor, featurize
from repro.fl.replan import ReplanService, _bucket

S_MAX = 8
I0 = 8
# (window, status): a cold full rescan, deltas, a status change (full)
# and deltas again; the caller runs `maintain` once, after the cold plan,
# so its frontier reaches the delta answers on the device, while the
# status change's frontier reaches them from the host
REQUESTS = [(i, 3.0) for i in range(5)] + [(i, 2.0) for i in range(5, 8)]
MAINTAIN_AFTER = 0


def _forest():
    rng = np.random.default_rng(0)
    hists = rng.integers(0, 20, (120, S_MAX + 1)).astype(np.float32)
    return RandomForestRegressor(n_trees=3, max_depth=3, seed=0).fit(
        featurize(hists, 1.0), hists.sum(1).astype(np.float32))


def _drive(svc):
    """Answer `REQUESTS` against one world, realizing each answer's first
    action; returns the plans and, per request, (mode, reason, pool
    rows after it, pool rows that schedule its last window)."""
    rng = np.random.default_rng(1)
    C = rng.random((len(REQUESTS) + I0, 12)) < 0.4
    state = jax.tree.map(np.asarray, SS.bootstrap_state(12))
    ig, plans, seen = 0, [], []
    for window, status in REQUESTS:
        plan = svc.replan(window, C[window:window + I0], state, ig, status,
                          rng=np.random.default_rng(100 + window))
        plans.append(plan)
        seen.append((svc.last_mode, svc.last_reason, len(svc.pool),
                     int(svc.pool[:, -1].sum())))
        st, g, _ = SS.step(jax.tree.map(jnp.asarray, state),
                           jnp.int32(ig), jnp.asarray(C[window]),
                           jnp.asarray(bool(plan[0])), s_max=S_MAX,
                           collect="none")
        state, ig = jax.tree.map(np.asarray, st), int(g)
        if window == MAINTAIN_AFTER:
            svc.maintain()
    return plans, seen


def _service():
    return ReplanService(_forest(), I0=I0, num_candidates=64, s_max=S_MAX,
                         seed=3, min_pool=4)


def _spans(log_dir):
    """(name, thread, start_ns, end_ns, stats) of every `repro.*` event
    on the trace's host planes."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for t, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(tracing.PREFIX):
                    out.append((e.name[len(tracing.PREFIX):], t,
                                e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    log_dir = tmp_path_factory.mktemp("trace")
    svc = _service()
    with jax.profiler.trace(str(log_dir)):
        plans, seen = _drive(svc)
    return svc, plans, seen, _spans(log_dir)


def test_span_is_the_shared_noop_without_a_session(monkeypatch):
    made = []

    class Recording(tracing.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a[0])
            super().__init__(*a, **kw)

    monkeypatch.setattr(tracing, "TraceAnnotation", Recording)
    assert tracing.span("x", rows=3) is tracing.OFF
    with tracing.span("x") as sp:
        sp.set_metadata(width=2)
    _drive(_service())
    assert made == []


def test_spans_nest_in_their_request(traced):
    svc, _, seen, spans = traced
    requests = [s for s in spans if s[0] == "replan.request"]
    assert [s[4]["window"] for s in requests] == [w for w, _ in REQUESTS]

    def request_of(span):
        _, t, a, b, _ = span
        held = [r for r in requests if r[1] == t and r[2] <= a
                and b <= r[3]]
        return held[0] if held else None

    for s in spans:
        if s[0] == "replan.request":
            continue
        req = request_of(s)
        if s[0] == "replan.maintain" and req is None:
            # run between requests: it names the request it prepares
            assert s[4]["window"] == MAINTAIN_AFTER + 1
            continue
        assert req is not None, s
        if s[0].startswith("replan."):
            assert s[4]["window"] == req[4]["window"], s

    outside = [s for s in spans if s[0] == "replan.maintain"
               and request_of(s) is None]
    assert len(outside) == 1
    for (mode, reason, rows, scheduled), req in zip(seen, requests):
        inner = [s for s in spans if s is not req and request_of(s) is req]
        names = {s[0] for s in inner}
        assert "replan.check" in names and "replan.select" in names
        check, = [s for s in inner if s[0] == "replan.check"]
        # the drift program ran exactly where no earlier reason decided
        assert check[4]["drift_checked"] == int(
            mode == "delta" or reason == "drift")
        if mode == "delta":
            assert {"replan.delta", "replan.delta.extend",
                    "replan.delta.score", "replan.delta.reduce"} <= names
            assert not names & {"replan.full", "search.scan"}
            delta, = [s for s in inner if s[0] == "replan.delta"]
            assert delta[4]["survivors"] == rows
            assert delta[4]["bucket"] == _bucket(rows) >= rows
            # the program runs at the full plan's pool of rows
            score, = [s for s in inner if s[0] == "replan.delta.score"]
            assert score[4]["rows"] == svc.num_candidates >= rows
            assert score[4]["scheduled"] == scheduled <= rows
            assert score[4]["forest_dense"] == 1
            reduce_, = [s for s in inner if s[0] == "replan.delta.reduce"]
            assert reduce_[4]["rows"] == score[4]["rows"]
            assert reduce_[4]["width"] == I0
        else:
            assert {"replan.full", "replan.full.draw", "search.scan",
                    "search.chunk", "search.fetch"} <= names
            assert not names & {"replan.delta", "replan.delta.extend"}
            full, = [s for s in inner if s[0] == "replan.full"]
            assert full[4]["reason"] == reason
            scan, = [s for s in inner if s[0] == "search.scan"]
            assert scan[4] == {"rows": 64, "chunks": 1, "forest_dense": 1}
    assert any(s[0] == "replan.delta.score" for s in spans)
    assert [m for m, _, _, _ in seen].count("full") == 2
    assert seen[0][1] == "cold" and seen[5][1] == "status"
    assert svc.last_reason == seen[-1][1]


def test_tracing_changes_no_answer(traced):
    svc, plans, seen, _ = traced
    plain = _service()
    plans2, seen2 = _drive(plain)
    assert seen2 == seen
    assert all(np.array_equal(a, b) for a, b in zip(plans, plans2))
    assert plain.stats == svc.stats

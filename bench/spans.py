"""Host spans of one run, recorded from the benchmark's own wrappers.

A span is written twice: into the profiler's trace through
`jax.profiler.TraceAnnotation` (so a traced run puts it on the same clock
as the device's operations), and into this recorder's own list on the
host clock (so the readers can sum it without a trace). Wrappers replace
a method on an object the benchmark built (the adapter, the scheduler,
the replan service, an engine attribute set after `prepare`): they never
subclass the engine, so which engine loop runs is unchanged.
"""
from __future__ import annotations

import contextlib
import functools
import time

import jax

PREFIX = "bench."


class Spans:
    """Spans as (name, start_s, end_s) on `time.perf_counter`; an instant
    is a span of no length."""

    def __init__(self):
        self.spans = []

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(PREFIX + name):
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))

    def wrap(self, obj, method: str, name: str, after=None):
        """Replace `obj.method` by the same call inside span `name`;
        `after(result, *args, **kwargs)` sees each call's result."""
        fn = getattr(obj, method)

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with self.span(name):
                out = fn(*args, **kwargs)
            if after is not None:
                after(out, *args, **kwargs)
            return out

        setattr(obj, method, wrapped)

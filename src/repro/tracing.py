"""Named spans of the program on the profiler's timeline.

`span(name, **counts)` marks a stretch of host work as `repro.<name>`.
While a JAX profiler session captures (`jax.profiler.trace(dir)` or the
profiler server), the span is a `jax.profiler.TraceAnnotation`: the
profiler records it on the host plane of the trace, on the same clock as
the device's operations, and each keyword count becomes a stat of the
event. Outside a session it is one shared context that does nothing, so
a span costs one `is_enabled()` check.

Spans nest by lexical scope on the host thread; a count is a host value
the code already holds, never a read of a device value. Under
asynchronous dispatch a span that only enqueues device work measures the
dispatch: the wait for the device lands in the span that first blocks.
Where a count is known only inside the span, the context's
`set_metadata(**counts)` adds it. `docs/replanning.md` lists the spans.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

__all__ = ["PREFIX", "span"]

PREFIX = "repro."


class _Off:
    """The span outside a profiler session: records nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **counts):
        pass


OFF = _Off()


def span(name: str, **counts):
    """A context naming the enclosed host work `repro.<name>`, with
    `counts` as its stats, while the profiler captures; else `OFF`."""
    if TraceAnnotation.is_enabled():
        return TraceAnnotation(PREFIX + name, **counts)
    return OFF

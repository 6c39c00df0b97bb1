"""Named spans inside the serving engine, for ``jax.profiler`` traces.

Every span is a ``jax.profiler.TraceAnnotation`` named ``kf.<name>``: the
profiler writes it on the host's timeline, on the same clock as the
device's ops, so an idle gap on the device can be put down to the host
phase that was running. With no profiler running a span records nothing
and costs about a microsecond to enter and leave. Stats ride along as
profiler stats (ints and strings); ``docs/architecture.md`` lists the
spans.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

PREFIX = "kf."


def span(name: str, **stats) -> TraceAnnotation:
    """A span ``kf.<name>``; stats known only at its end go in through
    ``set_metadata`` on the object the ``with`` statement binds."""
    return TraceAnnotation(PREFIX + name, **stats)


def enabled() -> bool:
    """True while a profiler records: compute a stat that costs more than
    a few integer reads only then."""
    return TraceAnnotation.is_enabled()

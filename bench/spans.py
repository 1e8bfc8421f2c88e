"""The program's own host spans, read against the device's idle time.

The serving path opens ``serve.*`` spans (``jax.profiler.TraceAnnotation``
in ``repro.launch.serve_sim``) that land on the host plane of the same
trace as the device's ops. A span is matched by its name up to the first
``#``, so metadata a profiler writes into the name cannot break the
match. A trace without the span (a program that opens none) gives no
reading.
"""
from __future__ import annotations

from bench import trace


def named(tr: trace.Trace, name: str) -> list[trace.Event]:
    """Host events called ``name`` (up to the first ``#``)."""
    return [e for e in tr.host if e.name.split("#", 1)[0] == name]


def starting_in_window(tr: trace.Trace, events) -> list[trace.Event]:
    lo, hi = tr.window
    return [e for e in events if lo <= e.start < hi]


def idle_inside(tr: trace.Trace, name: str) -> float | None:
    """Percent of the window in which no op ran on a chip while the host
    was inside a ``name`` span, averaged over the chips; None when the
    trace holds no such span."""
    spans = named(tr, name)
    if not spans or not tr.devices:
        return None
    inside = trace.clip(trace.union((e.start, e.end) for e in spans), *tr.window)
    covered = 0.0
    for dev in tr.devices:
        idle = trace.subtract([tr.window], trace.busy(tr, dev))
        covered += trace.length(idle) - trace.length(trace.subtract(idle, inside))
    lo, hi = tr.window
    return 100.0 * covered / len(tr.devices) / (hi - lo)

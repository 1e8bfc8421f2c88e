"""Halo exchange time not hidden behind compute, per step (ms).

On each chip: the union of the ``collective-permute`` events (the
synchronous op, or the ``-start``/``-done`` pair of the asynchronous
one) in the window, less the union of every other op that did work
there (loops and calls that only hold others excluded); its length,
averaged over the chips, over the steps the traced window advanced.
"""
from __future__ import annotations

from bench import trace


def read(run) -> float | None:
    if run.trace is None or not run.window_steps:
        return None
    total, seen = 0.0, False
    for dev in run.trace.devices:
        work = trace.work_events(run.trace, dev)
        coll = [e for e in work if trace.opcode(e.name).startswith("collective-permute")]
        if coll:
            seen = True
        other = [e for e in work if not trace.opcode(e.name).startswith("collective-permute")]
        exposed = trace.subtract(
            trace.clip(trace.union((e.start, e.end) for e in coll), *run.trace.window),
            trace.union((e.start, e.end) for e in other),
        )
        total += trace.length(exposed)
    if not seen:
        return None
    return total * 1e-6 / len(run.trace.devices) / run.window_steps

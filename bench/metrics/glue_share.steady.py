"""Share of a steady cell's device time spent around its kernels
(``--trace 1``): the solver's glue.

100 * (device time of the window's work ops that are not Pallas
launches) / (device time of all its work ops): work ops are the events
of each chip's ``XLA Ops`` line that do not only hold others (no
``while`` / ``conditional`` / ``call``), clipped to the measured window;
launches are the events whose HLO text carries
``custom_call_target="tpu_custom_call"``. In the acoustic shot this is
the Dirichlet pad, the source injection and the receiver slice, plus
whatever the aux plumbing costs (a re-stack of the leapfrog's operands
would land here).
"""
from __future__ import annotations

from bench import trace

TARGET = 'custom_call_target="tpu_custom_call"'


def read(run) -> float | None:
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    glue = total = 0.0
    for dev in run.trace.devices:
        for e in trace.work_events(run.trace, dev):
            t = min(e.end, hi) - max(e.start, lo)
            total += t
            if TARGET not in e.name:
                glue += t
    if total <= 0.0:
        return None
    return 100.0 * glue / total

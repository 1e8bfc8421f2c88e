"""Device idle share of a steady cell's window (``--trace 1``).

100 * (1 - busy / window): busy is the union of the intervals in which
an op (not a loop or call that only holds others) ran on a chip, inside
the measured window, averaged over the chips used.
"""
from __future__ import annotations

from bench import trace


def read(run) -> float | None:
    return None if run.trace is None else trace.idle_share(run.trace)

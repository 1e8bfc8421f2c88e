"""Device idle share of a serving cell's window (``--trace 1``).

The same reduction as ``device_idle.steady``; kept apart because the
serving cells report ``request_p95_ms``, which this share moves.
"""
from __future__ import annotations

from bench import trace


def read(run) -> float | None:
    return None if run.trace is None else trace.idle_share(run.trace)

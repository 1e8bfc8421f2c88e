"""Device idle while the server dispatches a batch (``--trace 1``).

The share of the window in which no op runs on the chip and the host is
inside a ``serve.dispatch`` span (``SimServer`` calling ``integrate``:
trace, lower, load from the compile cache, enqueue), in percent,
averaged over the chips. A subset of ``device_idle.serve``.
"""
from __future__ import annotations

from bench import spans


def read(run) -> float | None:
    return None if run.trace is None else spans.idle_inside(run.trace, "serve.dispatch")

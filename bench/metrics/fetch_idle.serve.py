"""Device idle while the server copies a batch back (``--trace 1``).

The share of the window in which no op runs on the chip and the host is
inside a ``serve.fetch`` span (``np.asarray`` of the batch's result:
the device-to-host copy and the layout transpose), in percent, averaged
over the chips. A subset of ``device_idle.serve``, disjoint from
``dispatch_idle.serve``.
"""
from __future__ import annotations

from bench import spans


def read(run) -> float | None:
    return None if run.trace is None else spans.idle_inside(run.trace, "serve.fetch")

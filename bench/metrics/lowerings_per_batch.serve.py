"""JAX lowerings per served batch in the window (``--trace 1``).

Host events named ``lower_sharding_computation`` (JAX lowering a
computation to XLA) that start inside a ``serve.dispatch`` span and in
the window, over the ``serve.batch`` spans that start in the window. A
server whose batched program is compiled once and reused reads 0.
"""
from __future__ import annotations

from bench import spans

LOWERING = "lower_sharding_computation"


def read(run) -> float | None:
    if run.trace is None:
        return None
    tr = run.trace
    batches = spans.starting_in_window(tr, spans.named(tr, "serve.batch"))
    if not batches:
        return None
    dispatch = spans.named(tr, "serve.dispatch")
    lowerings = [
        e for e in spans.starting_in_window(tr, spans.named(tr, LOWERING))
        if any(d.start <= e.start <= d.end for d in dispatch)
    ]
    return len(lowerings) / len(batches)

"""Pallas kernels' share of their HBM roofline over the traced window.

Launches: the events of the device's ``XLA Ops`` line whose HLO text
carries ``custom_call_target="tpu_custom_call"`` (a Mosaic kernel; the
program's kernels carry no names yet, so this is the matching rule).

For each launch, the bytes it must move are the logical arrays it reads
and writes, counted once each: its results in full, and of each operand
its leading (field and member) extents times the result's spatial
extent, i.e. without the halo padding, re-reads or tile staging. An
operand whose rank differs from the result's (a table of weights) counts
in full. The share is sum(bytes / peak HBM bandwidth) over the summed
device time of those launches, in percent, over every chip. Each launch
is charged only what it reads and writes, so the share cannot pass 100%
whatever regime or temporal depth implements it.
"""
from __future__ import annotations

import math

from bench import trace

TARGET = 'custom_call_target="tpu_custom_call"'


def launch_bytes(hlo_text: str, spatial_rank: int) -> int:
    """Logical bytes of one kernel launch, from its HLO text."""
    results, operands = trace.result_and_operands(hlo_text)
    out = sum(size * math.prod(dims) for size, dims in results)
    _, rdims = results[0]
    spatial = math.prod(rdims[len(rdims) - spatial_rank :])
    for size, dims in operands:
        if len(dims) != len(rdims):
            out += size * math.prod(dims)
        else:
            out += size * math.prod(dims[: len(dims) - spatial_rank]) * spatial
    return out


def read(run) -> float | None:
    if run.trace is None:
        return None
    ideal_s = busy_s = 0.0
    for dev in run.trace.devices:
        for e in trace.in_window(run.trace, run.trace.devices[dev]):
            if TARGET in e.name:
                ideal_s += launch_bytes(e.name, run.spatial_rank) / run.peaks[
                    "hbm_bytes_per_s"
                ]
                busy_s += e.dur * 1e-9
    if busy_s <= 0.0:
        return None
    return 100.0 * ideal_s / busy_s

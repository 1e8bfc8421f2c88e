"""The readers of the serving path's own spans (``dispatch_idle.serve``,
``fetch_idle.serve``, ``lowerings_per_batch.serve``), on traces worked
out by hand and on a short recorded ensemble2d trace from a TPU v5e.

Run by path (the repository's test run collects ``tests/`` only):

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_spans.py
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import trace  # noqa: E402

DATA = ROOT / "bench" / "testdata"
READERS = ("dispatch_idle.serve", "fetch_idle.serve", "lowerings_per_batch.serve")


def metric(name: str):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("s_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(name: str, tr):
    return metric(name).read(SimpleNamespace(trace=tr))


def op(name: str, start: float, end: float) -> trace.Event:
    return trace.Event(f"%{name} = f32[8,128]{{1,0}} fusion(f32[8,128] %p)", start, end)


def span(name: str, start: float, end: float) -> trace.Event:
    return trace.Event(name, start, end)


def two_batches(devices: int = 1) -> trace.Trace:
    """Window [0, 1000] ns; the chip busy on [100, 200] and [600, 700].

    Batch 1 [0, 500]: dispatch [10, 150] (idle 10-100: 90 ns), wait
    [150, 220], fetch [220, 400] (idle all 180 ns), validate [400, 450].
    Batch 2 [500, 1000]: dispatch [500, 650] (idle 500-600: 100 ns),
    wait [650, 700], fetch [700, 800] (idle 100 ns). The idle gap
    [200, 600] is split over wait, fetch, validate, nothing and the
    second dispatch. Lowerings: [20, 60] and [510, 520], [530, 540]
    inside a dispatch; [300, 320] inside the fetch, which is not one.
    """
    ops = [op("fusion", 100, 200), op("fusion.1", 600, 700)]
    host = [
        span(trace.WINDOW_SPAN, 0, 1000),
        span("serve.drain#requests=4#", 0, 1000),
        span("serve.batch#bucket=32x128/float32/n8,members=2#", 0, 500),
        span("serve.stack", 2, 8),
        span("serve.dispatch#attempt=0#", 10, 150),
        span("lower_sharding_computation", 20, 60),
        span("serve.device_wait", 150, 220),
        span("serve.fetch", 220, 400),
        span("lower_sharding_computation", 300, 320),
        span("serve.validate", 400, 450),
        span("serve.batch#bucket=32x128/float32/n8,members=2#", 500, 1000),
        span("serve.dispatch#attempt=0#", 500, 650),
        span("lower_sharding_computation", 510, 520),
        span("lower_sharding_computation#x=1#", 530, 540),
        span("serve.device_wait", 650, 700),
        span("serve.fetch", 700, 800),
    ]
    return trace.Trace(
        {f"/device:TPU:{i}": list(ops) for i in range(devices)},
        host, (0.0, 1000.0),
    )


def test_idle_split_across_spans_by_hand():
    tr = two_batches()
    assert trace.idle_share(tr) == pytest.approx(80.0)
    assert read("dispatch_idle.serve", tr) == pytest.approx(19.0)
    assert read("fetch_idle.serve", tr) == pytest.approx(28.0)


def test_idle_shares_average_over_chips():
    tr = two_batches(devices=2)
    # Chip 1 runs one more op under the first fetch, [250, 350]: 10 %
    # of its window less idle there; the mean over the chips drops 5.
    tr.devices["/device:TPU:1"].append(op("copy", 250, 350))
    assert read("dispatch_idle.serve", tr) == pytest.approx(19.0)
    assert read("fetch_idle.serve", tr) == pytest.approx(23.0)


def test_lowerings_count_inside_dispatch_only():
    # Three lowerings start inside a dispatch, one inside the fetch.
    assert read("lowerings_per_batch.serve", two_batches()) == pytest.approx(1.5)


def test_nested_batches_and_spans_outside_the_window():
    tr = two_batches()
    # A bisected half nests its own serve.batch (and dispatch) inside
    # the second batch; a batch and a lowering that start before the
    # window, and idle time outside it, count for nothing.
    tr.host += [
        span("serve.batch#members=1#", 810, 900),
        span("serve.dispatch#attempt=0#", 820, 860),
        span("lower_sharding_computation", 830, 840),
        span("serve.batch", -300, -10),
        span("serve.dispatch", -200, -100),
        span("lower_sharding_computation", -150, -140),
    ]
    assert read("lowerings_per_batch.serve", tr) == pytest.approx(4 / 3)
    # The nested dispatch adds [820, 860] of idle time: 4 %.
    assert read("dispatch_idle.serve", tr) == pytest.approx(23.0)
    assert read("fetch_idle.serve", tr) == pytest.approx(28.0)


def test_a_program_without_spans_reads_nothing():
    tr = two_batches()
    tr.host = [e for e in tr.host if not e.name.startswith("serve.")]
    for name in READERS:
        assert read(name, tr) is None
        assert read(name, None) is None
    recorded_steady = trace.from_json(
        (DATA / "cube_depth2_v5e.trace.json").read_text()
    )
    for name in READERS:
        assert read(name, recorded_steady) is None


# -- the recorded trace ------------------------------------------------------


def recorded():
    """A 1.63 s ensemble2d window traced on a TPU v5e: every device op,
    and of the host events the ``bench.*``, ``serve.*`` and
    ``lower_sharding_computation`` ones (the readers' inputs)."""
    return trace.from_json((DATA / "ensemble2d_v5e.trace.json").read_text())


def test_recorded_ensemble_window():
    # Read on the chip from the whole trace, before the host events
    # were cut to the readers' inputs.
    tr = recorded()
    assert tr.window_s == pytest.approx(1.632220277)
    assert trace.idle_share(tr) == pytest.approx(87.92595296253631)
    dispatch = read("dispatch_idle.serve", tr)
    fetch = read("fetch_idle.serve", tr)
    assert dispatch == pytest.approx(49.737694503595485)
    assert fetch == pytest.approx(21.73930302178203)
    assert dispatch + fetch <= trace.idle_share(tr)
    # 18 batches (6 rounds of 3), one lowering of the eager scan each.
    assert read("lowerings_per_batch.serve", tr) == 1.0
    batches = [e for e in tr.host if e.name.split("#")[0] == "serve.batch"]
    assert len(batches) == 18

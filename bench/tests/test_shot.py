"""The acoustic shot cell's check can fail: a tiny shot on the CPU.

- A sound run of the ``shot`` driver comes out correct, its counters
  matching the steps the window issued.
- The control (the plain reference in bfloat16) reads above each limit.
- A run with its timed path broken underneath comes out not correct:
  the source dropped, the receivers zeroed, the state left unchanged.

The driver runs as ``bench/run.py`` would run it, without its look for
a chip (Pallas in interpret mode). The tiny cell is built here: the
configuration's numerics on a (24, 32, 128) grid with a 4-point layer.
Run by path:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_shot.py
"""
from __future__ import annotations

import argparse
import copy
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import control, harness, run  # noqa: E402

CELL = "acoustic-o8.shot512"
SEED = 3_000_000_021
GRID = [24, 32, 128]


def tiny_cell(seed: int = SEED, seconds: float = 0.5):
    import jax

    spec = run.load_json(ROOT / "BENCHMARK.json")
    args = argparse.Namespace(
        workload=CELL, seed=seed, seconds=seconds, trace=0
    )
    cell = run.Cell(spec, args, jax.devices(), harness.CompileLog())
    cell.t_start = time.perf_counter()
    cell.config = copy.deepcopy(cell.config)
    cell.traffic = copy.deepcopy(cell.traffic)
    num = cell.config["numerics"]
    cell.config["grid"] = GRID
    num["absorbing"]["layer"] = 4
    num["source"]["point"] = [4, 16, 64]
    cell.devices = jax.devices()[:1]
    return cell


def drive(cell):
    return cell.driver().run(cell, harness)


def test_sound_run_is_correct():
    out = drive(tiny_cell())
    assert out.attempted > 0
    assert harness.verdict(out), out.checks
    assert out.checks["counter_mismatch"][0] == 0, out.info


def test_control_fails_the_limits():
    cell = tiny_cell()
    for seed in (11, 12, 13):
        gaps = control.control_gaps(cell, seed)
        assert min(gaps) > cell.limits["max_rel_gap"], (seed, gaps)
        tgaps = cell.driver().control_trace_gaps(cell, seed)
        assert min(tgaps) > cell.limits["trace_rel_gap"], (seed, tgaps)


# -- faults planted under the timed path --------------------------------------


def _source_dropped(monkeypatch):
    from repro.physics import acoustic

    monkeypatch.setattr(
        acoustic.AcousticProblem, "ricker", lambda self, t, dt: 0.0 * t
    )


def _receivers_zeroed(monkeypatch):
    from repro.physics import acoustic

    real = acoustic.AcousticSolver._advance

    def broken(self, *args, **kw):
        u, um, t, traces = real(self, *args, **kw)
        return u, um, t, 0.0 * traces

    monkeypatch.setattr(acoustic.AcousticSolver, "_advance", broken)


def _state_unchanged(monkeypatch):
    from repro.physics import acoustic

    def frozen(self, carry, a, b, gain):
        u, um, t = carry
        return (u, um, t + 1), self.problem.receivers(u)

    monkeypatch.setattr(acoustic.AcousticSolver, "_step", frozen)


FAULTS = {
    "source_dropped": _source_dropped,
    "receivers_zeroed": _receivers_zeroed,
    "state_unchanged": _state_unchanged,
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_fault_is_not_correct(monkeypatch, fault):
    import jax

    FAULTS[fault](monkeypatch)
    jax.clear_caches()
    try:
        out = drive(tiny_cell())
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not harness.verdict(out), (fault, out.checks)

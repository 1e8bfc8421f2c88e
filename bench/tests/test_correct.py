"""The correctness check can fail: each cell, at a tiny size on the CPU.

- A sound run of each cell's driver comes out correct.
- The control (the plain reference in bfloat16, the precision below the
  configuration's float32) reads above each cell's limit.
- A run with its timed path broken underneath comes out not correct, for
  every fault the cell can have: a step that returns its state
  unchanged; half of a serving batch left unserved; an answer altered
  where it is produced; the exchange between chips left out.

The drivers run as ``bench/run.py`` would run them, without its look for
a chip (Pallas in interpret mode, four virtual CPU devices). Run by path:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ).strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import control, harness, rehearse  # noqa: E402

CELLS = (
    "mhd-256.steady",
    "diffusion-o6.cube512",
    "diffusion-o6.ensemble2d",
    # The sharded mix (bench/traffic/shard4.json) under the cube512
    # limit: its four-chip cell is not in BENCHMARK.json yet, but the
    # steady driver's sharded path is kept tested.
    "sharded",
)
SEED = 3_000_000_019


def tiny_cell(name: str, seed: int = SEED):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) CPU devices")
    if name == "sharded":
        _, cell = rehearse._cell(
            "diffusion-o6.cube512", seed, 0.5, False, jax.devices(), tiny=True
        )
        cell.traffic = json.loads(
            (ROOT / "bench" / "traffic" / "shard4.json").read_text()
        )
        cell.traffic["grid"] = [64, 16, 128]
        cell.devices = jax.devices()[:4]
        return cell
    _, cell = rehearse._cell(name, seed, 0.5, False, jax.devices(), tiny=True)
    return cell


def drive(cell):
    return cell.driver().run(cell, harness)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out = drive(tiny_cell(name))
    assert out.attempted > 0
    assert harness.verdict(out), out.checks


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_limit(name):
    cell = tiny_cell(name)
    limit = cell.limits["max_rel_gap"]
    for seed in (11, 12, 13):
        gaps = control.control_gaps(cell, seed)
        assert min(gaps) > limit, (seed, gaps, limit)


# -- faults planted under the timed path --------------------------------------


def _unchanged_mhd(monkeypatch):
    from repro.physics import mhd

    monkeypatch.setattr(mhd.MHDSolver, "step", lambda self, f, dt: f)


def _unchanged_integrate(monkeypatch):
    from repro.core import fusion

    monkeypatch.setattr(fusion, "integrate", lambda op, f, n: f)


def _serve_fault(kind):
    def plant(monkeypatch):
        from repro.launch import serve_sim

        real = serve_sim.SimServer._run_batch

        def broken(self, key, reqs, strategy):
            out, dt = real(self, key, reqs, strategy)
            out = np.array(out)
            if kind == "half":
                # The second half of the batch is handed back unserved.
                for m in range(len(reqs) // 2, len(reqs)):
                    out[m] = np.asarray(reqs[m].f0)
            else:
                # One answer altered where it is produced.
                out[0] = out[0] * np.float32(1.001)
            return out, dt

        monkeypatch.setattr(serve_sim.SimServer, "_run_batch", broken)

    return plant


def _no_exchange(monkeypatch):
    from repro.core import boundary, fusion

    def local_wrap(f, radii, mesh_axes, *, spatial_axes):
        return boundary.pad(f, list(radii), "periodic", spatial_axes=spatial_axes)

    monkeypatch.setattr(fusion, "exchange_halos_nd", local_wrap)


FAULTS = [
    ("mhd-256.steady", "state_unchanged", _unchanged_mhd),
    ("diffusion-o6.cube512", "state_unchanged", _unchanged_integrate),
    ("diffusion-o6.ensemble2d", "half_batch_unserved", _serve_fault("half")),
    ("diffusion-o6.ensemble2d", "answer_altered", _serve_fault("alter")),
    ("sharded", "exchange_left_out", _no_exchange),
]


@pytest.mark.parametrize(
    "name,fault,plant", FAULTS, ids=[f"{n}-{f}" for n, f, _ in FAULTS]
)
def test_fault_is_not_correct(monkeypatch, name, fault, plant):
    import jax

    plant(monkeypatch)
    jax.clear_caches()  # no program compiled before the fault is reused
    try:
        out = drive(tiny_cell(name))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not harness.verdict(out), (fault, out.checks)

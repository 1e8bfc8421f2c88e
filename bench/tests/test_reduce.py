"""The benchmark's reductions, on a recorded TPU trace and on small
traces whose numbers are worked out by hand; the peak table; the
command's refusal off the chip.

Run by path (the repository's test run collects ``tests/`` only):

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import peaks, trace  # noqa: E402

DATA = ROOT / "bench" / "testdata"
V5E = peaks.lookup("TPU v5 lite")


def metric(name: str):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_of(tr, **kw):
    base = dict(
        trace=tr, peaks=V5E, spatial_rank=3,
        window_steps=0, batches=[], max_batch=None, compile_s=None,
    )
    base.update(kw)
    return SimpleNamespace(**base)


# -- HLO text --------------------------------------------------------------

MHD_LAUNCH = (
    "%_lambda_.3 = f32[8,64,64,128]{3,2,1,0:T(8,128)S(1)} custom-call("
    "f32[8,70,70,134]{3,2,1,0:T(8,128)} %pad_maximum_fusion), "
    'custom_call_target="tpu_custom_call"'
)


def test_opcode_of_ops():
    assert trace.opcode(MHD_LAUNCH) == "custom-call"
    assert trace.opcode(
        "%while = (s32[]{:T(128)}, f32[1,8]{1,0}) while((s32[], f32[1,8]) "
        "%tuple.22), condition=%c, body=%b"
    ) == "while"
    assert trace.opcode(
        "%collective-permute-done = f32[1,6,512,512]{3,2,1,0} "
        "collective-permute-done(f32[1,6,512,512] %cp)"
    ) == "collective-permute-done"
    assert trace.opcode("not an op") == ""


def test_launch_bytes_count_logical_arrays_once():
    rl = metric("kernel_roofline")
    # 8 fields in, 8 out, over the 64x64x128 interior: no halo.
    assert rl.launch_bytes(MHD_LAUNCH, 3) == 16 * 64 * 64 * 128 * 4
    # A batched 2-D launch: batch x fields flattened to 3.
    batched = (
        "%closed_call.4 = f32[3,512,512]{2,1,0} custom-call("
        "f32[3,518,518]{2,1,0} %fusion.7), "
        'custom_call_target="tpu_custom_call"'
    )
    assert rl.launch_bytes(batched, 2) == 2 * 3 * 512 * 512 * 4


# -- intervals ---------------------------------------------------------------


def test_interval_algebra():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 6)]) == [
        (0, 2), (3, 5), (6, 10),
    ]
    assert trace.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    assert trace.length([(0, 3), (5, 8)]) == 6


def _ev(name, op, s, e):
    return trace.Event(f"%{name} = f32[1,16,128]{{2,1,0}} {op}(f32[1,28,140] %x)"
                       + (', custom_call_target="tpu_custom_call"' if op == "custom-call" else ""),
                       s, e)


def synthetic():
    """Two chips, a 100 ns window, steps of known work.

    chip 0: kernel [0, 30], fusion [30, 40], permute-done [50, 70]
            with a fusion [60, 65] under it, kernel [80, 100];
            a loop [0, 100] that holds them.
    chip 1: kernel [10, 40], permute-done [40, 60], kernel [60, 90].
    """
    d0 = [
        _ev("while", "while", 0, 100),
        _ev("k", "custom-call", 0, 30),
        _ev("f", "fusion", 30, 40),
        _ev("cp", "collective-permute-done", 50, 70),
        _ev("g", "fusion", 60, 65),
        _ev("k", "custom-call", 80, 100),
    ]
    d1 = [
        _ev("k", "custom-call", 10, 40),
        _ev("cp", "collective-permute-done", 40, 60),
        _ev("k", "custom-call", 60, 90),
    ]
    host = [trace.Event(trace.WINDOW_SPAN, 0, 100),
            trace.Event("bench.call", 40, 50)]
    return trace.Trace(
        {"/device:TPU:0": d0, "/device:TPU:1": d1}, host, (0.0, 100.0)
    )


def test_idle_share_by_hand():
    tr = synthetic()
    # chip 0 busy: [0,40] + [50,70] + [80,100] = 80 (the loop is no work);
    # chip 1 busy: [10,90] = 80. Idle 20 % on each.
    assert trace.busy_s(tr) == pytest.approx(80e-9)
    assert metric("device_idle.steady").read(run_of(tr)) == pytest.approx(20.0)
    assert metric("device_idle.serve").read(run_of(tr)) == pytest.approx(20.0)


def test_halo_exposed_by_hand():
    tr = synthetic()
    # chip 0: permute [50,70] less the fusion [60,65]: 15 ns exposed;
    # chip 1: permute [40,60], nothing under it: 20 ns. Mean 17.5 ns,
    # over 5 steps: 3.5 ns = 3.5e-6 ms a step.
    got = metric("halo_exposed_ms").read(run_of(tr, window_steps=5))
    assert got == pytest.approx(3.5e-6)
    assert metric("halo_exposed_ms").read(run_of(trace.Trace(
        {"/device:TPU:0": [_ev("k", "custom-call", 0, 10)]},
        [], (0.0, 10.0)), window_steps=1)) is None


def test_kernel_roofline_by_hand():
    tr = synthetic()
    # Four launches, each of 2 arrays x 16x128 f32 = 16384 B; their
    # device time is 30 + 20 on chip 0 and 30 + 30 on chip 1: 110 ns.
    each = 2 * 16 * 128 * 4
    want = 100.0 * 4 * each / V5E["hbm_bytes_per_s"] / 110e-9
    got = metric("kernel_roofline").read(run_of(tr, spatial_rank=2))
    assert got == pytest.approx(want)


def test_breakdown_names_gaps_by_host_span():
    bd = trace.breakdown(synthetic())
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    # chip 0's gaps: [40,50] under bench.call, [70,80] under nothing.
    assert bd["idle_gaps"][0] == ["bench.call", pytest.approx(10e-9)]
    assert bd["idle_gaps"][1] == ["none", pytest.approx(10e-9)]
    assert bd["device_ops"][0][0].startswith("k ")


# -- the recorded trace ------------------------------------------------------


def recorded():
    return trace.from_json((DATA / "cube_depth2_v5e.trace.json").read_text())


def test_recorded_trace_idle_share():
    # Worked out apart, on a 1-ns timeline of the 13087991 ns window:
    # 10688807 ns hold an op other than the while loop.
    tr = recorded()
    assert tr.window_s == pytest.approx(13087991e-9)
    assert trace.busy_s(tr) == pytest.approx(10688807e-9)
    assert metric("device_idle.steady").read(run_of(tr)) == pytest.approx(
        100.0 * (1 - 10688807 / 13087991)
    )


def test_recorded_trace_kernel_roofline():
    # 12 depth-2 launches over a 128x128x256 field (1 in, 1 out, f32),
    # 9484742 ns of launches in all.
    tr = recorded()
    want = 100.0 * 12 * (2 * 4 * 128 * 128 * 256) / 819e9 / 9484742e-9
    got = metric("kernel_roofline").read(run_of(tr))
    assert got == pytest.approx(want)
    assert 0.0 < got < 100.0
    assert metric("batched_kernel_roofline").read(run_of(tr)) == got


def test_batch_fill_counts_slots():
    m = metric("batch_fill")
    assert m.read(run_of(None, batches=[8, 2, 6] * 4, max_batch=8)) == (
        pytest.approx(100.0 * 16 / 24)
    )
    assert m.read(run_of(None)) is None


def test_no_trace_reads_nothing():
    for name in ("device_idle.steady", "device_idle.serve",
                 "kernel_roofline", "batched_kernel_roofline",
                 "halo_exposed_ms"):
        assert metric(name).read(run_of(None)) is None


# -- peaks and the command ---------------------------------------------------


def test_peak_table():
    assert V5E["hbm_bytes_per_s"] == 819e9
    assert V5E["bf16_flops_per_s"] == 197e12
    assert "Google Cloud" in V5E["source"]
    with pytest.raises(peaks.UnknownDevice):
        peaks.lookup("TPU v99 imaginary")
    with pytest.raises(peaks.UnknownDevice):
        peaks.lookup("cpu")


def _command(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mhd-256.steady",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def _no_result(out: str) -> bool:
    lines = out.strip().splitlines()
    if not lines:
        return True
    try:
        return "correct" not in json.loads(lines[-1])
    except ValueError:
        return True


def test_command_refuses_without_a_tpu():
    p = _command(ROOT)
    assert p.returncode != 0 and _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_command_fails_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0 and _no_result(p.stdout)

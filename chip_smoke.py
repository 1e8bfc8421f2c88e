#!/usr/bin/env python
"""Bring-up smoke test: the stencil engine's main path on a TPU chip.

Run from the root of a checkout:

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the sharded phase on four chips

Every phase runs in this one process, through the entry points a user
calls (``MHDSolver``, ``DiffusionProblem`` + ``integrate``, ``SimServer``,
``strategy="auto"``), at deployment sizes, and checks its result against
the compiler-managed ``hwc`` path or the ``vmap`` reference. Each Pallas
phase also checks that the compiled program holds a ``tpu_custom_call``
(the kernel went through Mosaic, not the interpreter or XLA). One line
is printed per phase; the times and peak bytes are bring-up readings,
not benchmark metrics. Any failed phase makes the exit code non-zero,
and only a clean run prints the final JSON line
``{"ok": true, "device": {...}}``. Without a TPU the script exits
non-zero before any work.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import tempfile
import time
import traceback
from importlib import metadata
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent

# MHD block: (8, 8, 128) needs ~50 MiB of scoped VMEM for the MHD φ;
# (2, 8, 128) fits the plain RHS kernel in 16 MiB and the depth-2
# RK-pair kernel in the emitter's 96 MiB limit.
MHD_BLOCK = (2, 8, 128)
MHD_N = 256
DIFF_N = 512
DIFF_STEPS = 8
SERVE_SHAPES = ((2048, 2048), (1024, 1024))
SERVE_REQUESTS = 16
SERVE_BATCH = 8
SERVE_STEPS = 8
AUTO_N = 256
SHARD_GLOBAL = (2048, 512, 512)
SHARD_STEPS = 4
# Relative tolerance of a Pallas result against hwc at the field's
# scale: 8 f32 steps of a 19-tap stencil round to ~1e-6 relative.
REL_TOL = 1e-4
# examples/mhd_simulation.py's cross-strategy tolerance, and a tighter
# check on the one-step increment, which the state's own scale hides.
MHD_ABS_TOL = 1e-4
MHD_INC_REL_TOL = 1e-3


class PhaseFailed(AssertionError):
    """A phase's check did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def compiled(fn, *args):
    """AOT-compile ``jax.jit(fn)`` for ``args``: (executable, seconds)."""
    t0 = time.perf_counter()
    exe = jax.jit(fn).lower(*args).compile()
    return exe, time.perf_counter() - t0


def assert_kernel(exe, what: str) -> None:
    check(
        "tpu_custom_call" in exe.as_text(),
        f"{what}: no tpu_custom_call in the compiled program — the "
        "Pallas kernel did not reach Mosaic",
    )


def timed_steps(exe, x, n: int) -> tuple:
    """Run ``exe`` ``n`` times chained; returns (result, per-call s)."""
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        x = jax.block_until_ready(exe(x))
        times.append(time.perf_counter() - t0)
    return x, times


def max_err(got, ref) -> tuple[float, float]:
    """(max abs error, field scale) of ``got`` against ``ref``."""
    err = float(jnp.max(jnp.abs(got - ref)))
    scale = float(jnp.max(jnp.abs(ref)))
    return err, scale


def finite(x) -> bool:
    return bool(jnp.all(jnp.isfinite(x)))


class Report:
    """Per-phase lines, compile seconds and failures of one run."""

    def __init__(self):
        self.failed: list[str] = []
        self.compile_s = 0.0

    def run(self, name: str, fn) -> None:
        t0 = time.perf_counter()
        try:
            detail = fn(self)
        except Exception as e:  # every phase reports; the exit code fails
            self.failed.append(name)
            print(f"[FAIL] {name}: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc(file=sys.stderr)
            return
        print(
            f"[ok]   {name}: {detail} | phase {time.perf_counter() - t0:.1f}s"
            f" | peak_bytes_in_use {peak_bytes()}",
            flush=True,
        )

    def compile(self, fn, *args):
        exe, secs = compiled(fn, *args)
        self.compile_s += secs
        return exe, secs


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def phase_mhd(rep: Report) -> str:
    from repro.physics.mhd import MHDSolver

    solver = MHDSolver((MHD_N,) * 3, strategy="swc", block=MHD_BLOCK)
    f0 = solver.init_fields(seed=0)
    check(f0.dtype == jnp.float32, f"state dtype {f0.dtype}")
    dt = float(solver.cfl_dt(f0))
    hwc = dataclasses.replace(solver, strategy="hwc")
    ref_exe, _ = rep.compile(lambda f: hwc.step(f, dt), f0)
    ref1 = ref_exe(f0)
    parts = [f"{MHD_N}^3 x8 f32 block={MHD_BLOCK} dt={dt:.3e}"]
    for label, s in (
        ("plain", solver),
        ("rk_pairs", dataclasses.replace(solver, fuse_rk_pairs=True)),
    ):
        exe, secs = rep.compile(lambda f, s=s: s.step(f, dt), f0)
        assert_kernel(exe, f"mhd {label}")
        got1 = exe(f0)
        err, scale = max_err(got1, ref1)
        check(
            err <= MHD_ABS_TOL * max(scale, 1.0),
            f"mhd {label}: one step vs hwc max abs err {err:.3e} > "
            f"{MHD_ABS_TOL} * max({scale:.3e}, 1)",
        )
        inc_err, inc_scale = max_err(got1 - f0, ref1 - f0)
        check(
            inc_err <= MHD_INC_REL_TOL * inc_scale,
            f"mhd {label}: step increment vs hwc max abs err "
            f"{inc_err:.3e} > {MHD_INC_REL_TOL} * {inc_scale:.3e}",
        )
        out, times = timed_steps(exe, f0, 3)
        check(finite(out), f"mhd {label}: non-finite state after 3 steps")
        parts.append(
            f"{label}: compile {secs:.1f}s, 1-step err {err:.2e} "
            f"(scale {scale:.2e}), increment err {inc_err:.2e} "
            f"(scale {inc_scale:.2e}), warm step "
            f"{statistics.median(times[1:]) * 1e3:.2f} ms"
        )
    return "; ".join(parts)


def phase_diffusion(rep: Report) -> str:
    from repro.core.fusion import integrate
    from repro.physics.diffusion import DiffusionProblem

    prob = DiffusionProblem((DIFF_N,) * 3, accuracy=6)
    f0 = prob.init_field(seed=0)
    ref_op = prob.step_op("hwc")
    ref_exe, _ = rep.compile(
        lambda f: integrate(ref_op, f, DIFF_STEPS), f0
    )
    ref = ref_exe(f0)
    parts = [f"{DIFF_N}^3 f32 order 6, {DIFF_STEPS} steps"]
    for strategy, depth in (
        ("swc", 1), ("swc", 2), ("swc_stream", 1), ("tc", 1),
    ):
        op = prob.step_op(strategy, fuse_steps=depth)
        exe, secs = rep.compile(
            lambda f, op=op: integrate(op, f, DIFF_STEPS), f0
        )
        assert_kernel(exe, f"diffusion {strategy} depth {depth}")
        out = exe(f0)
        err, scale = max_err(out, ref)
        check(
            finite(out) and err <= REL_TOL * scale,
            f"diffusion {strategy} depth {depth}: max abs err {err:.3e} "
            f"> {REL_TOL} * scale {scale:.3e}",
        )
        _, times = timed_steps(exe, f0, 3)
        parts.append(
            f"{strategy}@f{depth}: compile {secs:.1f}s, err {err:.2e} "
            f"(scale {scale:.2e}), warm {DIFF_STEPS} steps "
            f"{statistics.median(times[1:]) * 1e3:.2f} ms"
        )
    return "; ".join(parts)


def phase_serving(rep: Report) -> str:
    from repro.core.fusion import integrate
    from repro.launch import serve_sim
    from repro.physics.diffusion import DiffusionProblem
    from repro.tuning import lookup_fused_nd

    server = serve_sim.SimServer(
        strategy="swc", block="auto", max_batch=SERVE_BATCH
    )
    queue = serve_sim.demo_queue(
        list(SERVE_SHAPES), SERVE_STEPS, SERVE_REQUESTS
    )
    by_id = {r.req_id: r for r in queue.snapshot()}
    t0 = time.perf_counter()
    results = server.serve(queue)
    wall = time.perf_counter() - t0
    statuses = {
        rid: server.request_status.get(rid, "ok") for rid in by_id
    }
    check(
        not server.error_reports and set(results) == set(by_id),
        f"requests quarantined: {server.error_reports}",
    )
    not_ok = {rid: s for rid, s in statuses.items() if s != "ok"}
    check(not not_ok, f"requests not served cleanly: {not_ok}")
    parity_err = serve_sim._assert_parity(server, by_id, results)
    parts = [
        f"{len(results)} requests in {len(server.reports)} batches, "
        f"{wall:.1f}s wall (tuning and compile included), parity err "
        f"{parity_err:.2e}"
    ]
    for shape in SERVE_SHAPES:
        reqs = [r for r in by_id.values() if r.f0.shape[1:] == shape]
        fb = jnp.stack([r.f0 for r in reqs])
        prob = DiffusionProblem(shape, accuracy=server.accuracy)
        op = prob.step_op("swc")
        rec = lookup_fused_nd(fb, op.ops, 1, "swc")
        check(rec is not None, f"no tuning record for bucket {shape}")
        check(
            rec.source == "measured",
            f"bucket {shape}: tuning record source {rec.source!r}",
        )
        tuned = prob.step_op("swc", block=tuple(rec.block))
        exe, _ = rep.compile(
            lambda f, op=tuned: integrate(op, f, SERVE_STEPS), fb
        )
        assert_kernel(exe, f"serving bucket {shape}")
        batch_s = [rp.seconds for rp in server.reports if rp.key[0] == shape]
        parts.append(
            f"{shape[0]}x{shape[1]} b{len(reqs)}: block {tuple(rec.block)} "
            f"[{rec.source}], batch {min(batch_s) * 1e3:.2f} ms"
        )
    return "; ".join(parts)


def phase_auto(rep: Report) -> str:
    from repro.core.fusion import integrate
    from repro.physics.diffusion import DiffusionProblem
    from repro.tuning import lookup_fused_nd

    prob = DiffusionProblem((AUTO_N,) * 3, accuracy=6)
    f0 = prob.init_field(seed=1)
    op = prob.step_op("auto").resolved(f0)
    rec = lookup_fused_nd(f0, op.ops, 1, "auto")
    check(rec is not None, "strategy='auto' left no tuning record")
    table = ", ".join(
        f"{k}={v:.1f}us" for k, v in sorted(rec.timings_us.items())
    )
    print(f"       auto timings: {table}", flush=True)
    print(f"       auto failed: {rec.failed or 'none'}", flush=True)
    check(rec.source == "measured", f"auto record source {rec.source!r}")
    check(
        any(label != "hwc" for label in rec.timings_us),
        "strategy='auto' timed no Pallas candidate",
    )
    detail = (
        f"{AUTO_N}^3 chose {op.strategy} block {op.block} depth "
        f"{op.fuse_steps} [{rec.source}], {len(rec.timings_us)} timed, "
        f"{len(rec.failed)} failed"
    )
    if op.strategy != "hwc":
        exe, _ = rep.compile(lambda f: integrate(op, f, 2), f0)
        assert_kernel(exe, f"auto ({op.strategy})")
    return detail


def phase_four_chips(rep: Report) -> str:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.fusion import integrate
    from repro.physics.diffusion import DiffusionProblem

    devices = jax.devices()
    check(len(devices) == 4, f"need 4 devices, found {len(devices)}")
    prob = DiffusionProblem(SHARD_GLOBAL, accuracy=6)
    op = prob.step_op("swc", fuse_steps=2)
    f_host = prob.init_field(seed=2)

    one = jax.device_put(f_host, devices[0])
    ref_exe, _ = rep.compile(lambda f: integrate(op, f, SHARD_STEPS), one)
    ref = np.asarray(ref_exe(one))
    del one

    mesh = jax.make_mesh((4,), ("z",), devices=devices)
    spec = P(None, "z", None, None)
    sharded = jax.device_put(f_host, NamedSharding(mesh, spec))

    def local_steps(fl):
        for _ in range(SHARD_STEPS // 2):
            fl = op.apply_sharded(fl, ("z", None, None), overlap=True)
        return fl

    step = jax.shard_map(
        local_steps, mesh=mesh, in_specs=spec, out_specs=spec,
        check_vma=False,
    )
    exe, secs = rep.compile(step, sharded)
    assert_kernel(exe, "sharded swc depth 2")
    check(
        "collective-permute" in exe.as_text(),
        "no collective-permute halo exchange in the sharded program",
    )
    out = jax.block_until_ready(exe(sharded))
    homes = [s.device for s in out.addressable_shards]
    check(
        len(set(homes)) == 4
        and all(s.data.shape[1] == SHARD_GLOBAL[0] // 4
                for s in out.addressable_shards),
        f"output shards not spread over 4 devices: "
        f"{[(s.device, s.data.shape) for s in out.addressable_shards]}",
    )
    _, times = timed_steps(exe, sharded, 3)
    got = np.asarray(out)
    err = float(np.max(np.abs(got - ref)))
    scale = float(np.max(np.abs(ref)))
    check(
        np.isfinite(got).all() and err <= REL_TOL * scale,
        f"sharded vs one device: max abs err {err:.3e} > {REL_TOL} * "
        f"scale {scale:.3e}",
    )
    return (
        f"global {SHARD_GLOBAL} f32 order 6 swc@f2 on a z-mesh of 4, "
        f"{SHARD_STEPS} steps: compile {secs:.1f}s, err vs one device "
        f"{err:.2e} (scale {scale:.2e}), shards on "
        f"{sorted(d.id for d in homes)}, warm "
        f"{statistics.median(times[1:]) * 1e3:.2f} ms"
    )


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the phase sharded over four chips",
    )
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
            "nothing was run",
            file=sys.stderr,
        )
        return 2
    versions = {
        pkg: metadata.version(pkg) for pkg in ("jax", "jaxlib", "libtpu")
    }
    print(
        f"[ok]   device: {dev.device_kind}, {len(jax.devices())} "
        f"device(s), "
        + ", ".join(f"{k} {v}" for k, v in versions.items()),
        flush=True,
    )

    # The package lives in src/ of this checkout; the script is run
    # without installing it.
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import use_compile_cache

    print(f"       compile cache: {use_compile_cache()}", flush=True)
    rep = Report()
    with tempfile.TemporaryDirectory(prefix="repro-tune-") as tune_dir:
        # A fresh tuning cache: every run measures on the chip.
        os.environ["REPRO_TUNE_CACHE"] = tune_dir
        if args.four_chips:
            rep.run("four_chips", phase_four_chips)
        else:
            rep.run("mhd", phase_mhd)
            rep.run("diffusion", phase_diffusion)
            rep.run("serving", phase_serving)
            rep.run("auto", phase_auto)
    print(f"       compile seconds (AOT, all phases): {rep.compile_s:.1f}")
    if rep.failed:
        print(f"chip_smoke FAILED: {rep.failed}", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())

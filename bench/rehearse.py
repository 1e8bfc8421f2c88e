#!/usr/bin/env python3
"""Rehearse every cell of the benchmark without a chip.

    JAX_PLATFORMS=cpu python bench/rehearse.py [--part run|compile|all]

``run``: each cell's traffic driver end to end at a tiny size on the
CPU, Pallas kernels in interpret mode, the sharded cell on four virtual
CPU devices; prints the result object each would print. ``compile``:
each cell's program, and the reference run after its window, compiled
at full size for a described TPU v5e (``v5e:2x2``), with the compiled
program's ``memory_analysis()``. Neither times anything: a CPU run gives
no device number. ``bench/run.py`` itself keeps no CPU mode.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

# Tiny stand-ins for each cell's sizes, for the interpret-mode run.
TINY = {
    "mhd-256.steady": {"config": {"grid": [8, 16, 128]}},
    "diffusion-o6.cube512": {"traffic": {"grid": [16, 16, 128]}},
    "diffusion-o6.ensemble2d": {
        "traffic": {
            "clients": [
                {"count": 10, "grid": [32, 128]},
                {"count": 6, "grid": [16, 128]},
            ]
        }
    },
}


def _cell(name: str, seed: int, seconds: float, trace: bool, devices, tiny: bool):
    from bench import harness, run

    spec = run.load_json(ROOT / "BENCHMARK.json")
    args = argparse.Namespace(
        workload=name, seed=seed, seconds=seconds, trace=int(trace)
    )
    cell = run.Cell(spec, args, devices, harness.CompileLog())
    cell.t_start = time.perf_counter()
    if tiny:
        cell.config = copy.deepcopy(cell.config)
        cell.traffic = copy.deepcopy(cell.traffic)
        cell.config.update(TINY[name].get("config", {}))
        cell.traffic.update(TINY[name].get("traffic", {}))
    cell.devices = devices[: int(cell.workload["chips"])]
    return spec, cell


def rehearse_run(names, seed: int) -> None:
    import jax

    from bench import harness

    for name in names:
        _, cell = _cell(name, seed, 1.0, False, jax.devices(), tiny=True)
        out = cell.driver().run(cell, harness)
        print(json.dumps({
            "cell": name, "e2e": out.e2e, "checks": out.checks,
            "attempted": out.attempted, "failed": out.failed,
            "info": out.info,
        }, default=str), flush=True)


def rehearse_compile(names) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    import repro.kernels.ops as kops
    from repro.core.fusion import integrate

    kops._default_interpret = lambda: False  # compile Mosaic, not interpret
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def report(what, exe):
        ma = exe.memory_analysis()
        print(json.dumps({
            "compiled": what,
            "tpu_custom_call": "tpu_custom_call" in exe.as_text(),
            "collective_permute": "collective-permute" in exe.as_text(),
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
        }), flush=True)

    for name in names:
        _, cell = _cell(name, 0, 1.0, False, list(topo.devices), tiny=False)
        cfg, tr = cell.config, cell.traffic
        system, ref = cell.system(), cell.reference()
        if tr["driver"] == "closed_loop":
            steps = int(tr["steps_per_request"])
            server = system.server(cfg, tr["path"])
            counts = {}
            for g in tr["clients"]:
                counts[tuple(g["grid"])] = counts.get(tuple(g["grid"]), 0) + g["count"]
            for grid, n in counts.items():
                sizes = [server.max_batch] * (n // server.max_batch)
                if n % server.max_batch:
                    sizes.append(n % server.max_batch)
                for b in sorted(set(sizes)):
                    f = jax.ShapeDtypeStruct(
                        (b, cfg["fields"], *grid), jnp.float32, sharding=one
                    )
                    op = server._op_for((grid, "float32", steps), server.strategy)
                    exe = jax.jit(lambda x, op=op: integrate(op, x, steps)).lower(f).compile()
                    report(f"{name} serve batch {b} x {grid}", exe)
                f = jax.ShapeDtypeStruct((cfg["fields"], *grid), jnp.float32, sharding=one)
                exe = jax.jit(lambda x, g=grid: ref.advance(cfg, g, x, steps)).lower(f).compile()
                report(f"{name} reference {grid}", exe)
            continue
        grid = tuple(tr.get("grid") or cfg["grid"])
        steps = int(tr["steps_per_call"])
        shape = (cfg["fields"], *grid)
        sharding, _, mesh_axes, spec_p = cell.driver().layout(cell, grid)
        if mesh_axes is not None:
            fn = jax.shard_map(
                system.sharded_program(cfg, tr["path"], grid, steps, mesh_axes),
                mesh=sharding.mesh, in_specs=spec_p, out_specs=spec_p,
                check_vma=False,
            )
        else:
            fn = system.program(cfg, tr["path"], grid, steps)
        f = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)
        extra = jax.eval_shape(lambda x: ref.inputs(cfg, grid, x), f)
        scalar = one if mesh_axes is None else NamedSharding(sharding.mesh, P())
        extra = tuple(
            jax.ShapeDtypeStruct(e.shape, e.dtype, sharding=scalar) for e in extra
        )
        exe = jax.jit(fn).lower(f, *extra).compile()
        report(f"{name} program", exe)
        exe = jax.jit(
            lambda x, *e: ref.advance(cfg, grid, x, steps, *e),
            out_shardings=sharding,
        ).lower(f, *extra).compile()
        report(f"{name} reference", exe)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--part", choices=("run", "compile", "all"), default="all")
    ap.add_argument("--cells", nargs="*", default=None)
    ap.add_argument("--seed", type=int, default=3_000_000_017)
    args = ap.parse_args()
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        print("rehearse.py runs with JAX_PLATFORMS=cpu only", file=sys.stderr)
        return 2
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4"
        ).strip()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = args.cells or [w["name"] for w in spec["workloads"]]
    if args.part in ("run", "all"):
        rehearse_run(names, args.seed)
    if args.part in ("compile", "all"):
        rehearse_compile(names)
    return 0


if __name__ == "__main__":
    sys.exit(main())

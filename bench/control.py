#!/usr/bin/env python3
"""Readings that set a cell's correctness limit: the control.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...]

For each seed, builds the inputs the cell's window would hand the
program (the cell's driver says how: the state after one call, or a
fresh member of each grid), advances them with the plain reference in
float32 and again in
bfloat16, the precision below the configuration's, and prints the gap
``bench.harness.rel_gap`` between the two: the reading the control
gives, which the cell's limit must lie well below. Needs a TPU; the
benchmark's own runs never run it. ``bench/tests/test_correct.py`` runs
the same readings at a tiny size on the CPU.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def control_gaps(cell, seed: int) -> list[float]:
    """The bfloat16 reference's gap from the float32 one, per input."""
    import jax
    import jax.numpy as jnp

    from bench import harness

    ref = cell.reference()
    gaps = []
    for grid, steps, extra, f in cell.driver().control_inputs(cell, seed):
        def adv(x, *e, dtype, grid=grid, steps=steps):
            return ref.advance(cell.config, grid, x, steps, *e, dtype=dtype)

        want = jax.jit(lambda x, *e: adv(x, *e, dtype=jnp.float32))(f, *extra)
        low = jax.jit(lambda x, *e: adv(x, *e, dtype=jnp.bfloat16))(f, *extra)
        gaps.append(harness.rel_gap(low, want))
    return gaps


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench import harness, run

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control.py: JAX found no TPU", file=sys.stderr)
        return 2
    spec = run.load_json(ROOT / "BENCHMARK.json")
    ns = argparse.Namespace(workload=args.workload, seed=0, seconds=0, trace=0)
    cell = run.Cell(spec, ns, devices, harness.CompileLog())
    cell.devices = devices[: int(cell.workload["chips"])]
    for seed in args.seeds:
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "control_gaps": control_gaps(cell, seed),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run one cell of the stencil engine's benchmark on the chips of this host.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. ``BENCHMARK.json`` names the cell; the
cell names its configuration (``bench/configs/<config>.json``, with its
plain reference ``<config>.ref.py`` beside it) and its traffic mix
(``bench/traffic/<traffic>.json``), whose ``driver`` names the generator
in ``bench/drivers``; the configuration's ``system`` names the adapter in
``bench/systems`` that builds the program's entry points. A per-layer
metric is read by ``bench/metrics/<metric>.py``; the limits of the
correctness check are ``bench/limits/<cell>.json``.

With ``--trace 0`` the last line of standard output is one JSON object
holding the cell's end-to-end metrics; with ``--trace 1`` the window is
traced and the object holds the per-layer metrics. Every other line is
detail. The run fails, and prints no result, without a TPU whose kind is
in ``bench/peaks.json`` or with fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class Refused(Exception):
    """The run cannot measure this cell here; no result is printed."""


def load_module(path: Path, name: str):
    if not path.is_file():
        raise Refused(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise Refused(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    def __init__(self, spec: dict, args, devices, compile_log):
        cells = {w["name"]: w for w in spec["workloads"]}
        if args.workload not in cells:
            raise Refused(f"no workload {args.workload!r} in BENCHMARK.json")
        self.workload = cells[args.workload]
        self.config = load_json(BENCH / "configs" / f"{self.workload['config']}.json")
        self.traffic = load_json(BENCH / "traffic" / f"{self.workload['traffic']}.json")
        self.limits = {
            k: v["limit"]
            for k, v in load_json(BENCH / "limits" / f"{args.workload}.json").items()
        }
        self.seed, self.seconds, self.trace = args.seed, args.seconds, bool(args.trace)
        self.devices = devices
        self.compile_log = compile_log
        self.t_start = T_START

    def system(self):
        return load_module(
            BENCH / "systems" / f"{self.config['system']}.py", "bench_system"
        )

    def reference(self):
        return load_module(
            BENCH / "configs" / f"{self.config['name']}.ref.py", "bench_reference"
        )

    def driver(self):
        return load_module(
            BENCH / "drivers" / f"{self.traffic['driver']}.py", "bench_driver"
        )


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def layer_metrics(spec: dict, cell: Cell, out, peaks: dict) -> dict:
    run = SimpleNamespace(
        trace=out.trace, peaks=peaks,
        spatial_rank=out.spatial_rank, window_steps=out.window_steps,
        batches=out.batches, max_batch=out.max_batch,
        compile_s=out.compile_s,
    )
    metrics = {}
    for m in spec["per_layer"]:
        if not applies(m, cell.workload["name"]):
            continue
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py", "bench_metric")
        value = reader.read(run)
        if value is None:
            print(f"per-layer {m['name']}: nothing to read", file=sys.stderr)
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def measure(args, devices, peaks) -> dict:
    """Run the cell on ``devices``; returns the result object."""
    from bench import harness

    spec = load_json(ROOT / "BENCHMARK.json")
    compile_log = harness.CompileLog()
    cell = Cell(spec, args, devices, compile_log)
    chips = int(cell.workload["chips"])
    if len(devices) < chips:
        raise Refused(f"cell needs {chips} chips, JAX found {len(devices)}")
    cell.devices = devices[:chips]
    out = cell.driver().run(cell, harness)

    correct = harness.verdict(out)
    info = dict(out.info, setup_s=out.setup_s, compile_s=out.compile_s,
                memory_peak_bytes=out.memory_peak_bytes, **out.e2e)
    print("detail " + json.dumps(info, default=str), flush=True)

    dev = cell.devices[0]
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(cell.devices), "memory_peak_bytes": out.memory_peak_bytes,
    }
    result = {"correct": correct, "attempted": out.attempted, "failed": out.failed}
    if args.trace:
        from bench import trace as bench_trace

        result["metrics"] = layer_metrics(spec, cell, out, peaks)
        device["busy_s"] = bench_trace.busy_s(out.trace)
        device["window_s"] = out.trace.window_s
        result["device"] = device
        result["breakdown"] = bench_trace.breakdown(out.trace)
    else:
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
        values = dict(out.e2e, setup_s=out.setup_s)
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": units[m["name"]]}
            for m in spec["end_to_end"]
            if applies(m, cell.workload["name"])
        }
        result["device"] = device
    # A gap that is not finite (NaN output) is written as text, which
    # keeps the line strict JSON.
    checks = {
        k: {"value": v if math.isfinite(v) else str(v), "limit": lim}
        for k, (v, lim) in out.checks.items()
    }
    checks["failed_requests"] = {"value": out.failed, "limit": 0}
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    # The program lives in src/ of this checkout and is run uninstalled;
    # the benchmark's own modules import as ``bench.*``.
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import peaks as bench_peaks

    try:
        import jax

        devices = jax.devices()
        if devices[0].platform != "tpu":
            raise Refused(
                f"JAX found no TPU (platform {devices[0].platform!r})"
            )
        peaks = bench_peaks.lookup(devices[0].device_kind)
        from repro.compile_cache import use_compile_cache

        print(f"compile cache: {use_compile_cache()}", flush=True)
        result = measure(args, devices, peaks)
    except (Refused, bench_peaks.UnknownDevice) as e:
        print(f"bench/run.py: {e}; nothing was measured", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

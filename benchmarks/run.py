"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows; ``--json PATH`` also
archives the rows (plus device + git sha) for the CI perf trajectory.

Usage:
    PYTHONPATH=src python -m benchmarks.run            # quick (CPU-sized)
    PYTHONPATH=src python -m benchmarks.run --full     # paper-sized
    PYTHONPATH=src python -m benchmarks.run --only fig13
    PYTHONPATH=src python -m benchmarks.run --only fig06 --smoke \
        --json BENCH_fig06.json                        # CI artifact
"""
from __future__ import annotations

import argparse
import importlib
import inspect
import json
import subprocess

from benchmarks import util
from benchmarks.util import header
from repro.compile_cache import use_compile_cache

MODULES = (
    "fig06_bandwidth",
    "fig07_xcorr_library",
    "fig08_xcorr_tuned",
    "fig09_unroll",
    "fig10_diffusion_xla",
    "fig11_diffusion_fused",
    "fig13_mhd",
    "fig14_blocktune",
    "table3_energy",
)

JSON_SCHEMA = 1


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            check=True, timeout=10,
        ).stdout.strip()
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def _device() -> str:
    from repro.tuning.cache import current_backend

    return current_backend()


def write_json(path: str) -> None:
    """Archive the emitted rows. Row schema: name, us_per_call, derived,
    device, git_sha (the CI workflow uploads these as BENCH_*.json)."""
    device, sha = _device(), _git_sha()
    rows = [
        {**row, "device": device, "git_sha": sha} for row in util.ROWS
    ]
    payload = {
        "schema": JSON_SCHEMA,
        "device": device,
        "git_sha": sha,
        "smoke": util.smoke(),
        "rows": rows,
    }
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=1, sort_keys=True)
        fh.write("\n")
    print(f"wrote {len(rows)} row(s) to {path}")


def _parse_derived(derived: str) -> dict[str, str]:
    return dict(
        kv.split("=", 1) for kv in derived.split(";") if "=" in kv
    )


def summarize_rows(rows) -> dict:
    """Consolidate emitted rows into per-kernel GB/s + achieved-vs-
    roofline fraction. A row qualifies when its derived column carries a
    roofline bound (``tpu_bw_bound_s``/``tpu_roofline_s``): the fraction
    is bound/measured, and the achieved bandwidth is that fraction of
    the HBM roofline (``measured_GBps`` is used directly when a module
    already reports it)."""
    from repro.core.rooflinelib import TPU_V5E

    kernels = {}
    for row in rows:
        derived = _parse_derived(row.get("derived", ""))
        bound = derived.get("tpu_bw_bound_s") or derived.get(
            "tpu_roofline_s"
        )
        if bound is None:
            continue
        seconds = row["us_per_call"] / 1e6
        if seconds <= 0:
            continue
        fraction = float(bound) / seconds
        if "measured_GBps" in derived:
            gbps = float(derived["measured_GBps"])
        else:
            gbps = fraction * TPU_V5E.hbm_bw / 1e9
        entry = {
            "us_per_call": row["us_per_call"],
            "gbps": round(gbps, 3),
            "roofline_fraction": round(fraction, 6),
        }
        # tc rows additionally carry the MXU compute roof: report how
        # close the measured time sits to it, the compute-side analogue
        # of roofline_fraction (a tc kernel is compute-bound when its
        # mxu fraction exceeds its bandwidth fraction).
        if "tpu_mxu_bound_s" in derived:
            entry["mxu_roofline_fraction"] = round(
                float(derived["tpu_mxu_bound_s"]) / seconds, 6
            )
        # Cross-strategy "auto" rows report which caching regime the
        # tuning search picked for this shape — forward the decision so
        # the consolidated summary records it per kernel.
        for k in (
            "auto_strategy", "auto_depth", "tuned_block",
            "mxu_crossover_depth",
        ):
            if k in derived:
                entry[k] = derived[k]
        kernels[row["name"]] = entry
    return kernels


def write_summary(path: str = "BENCH_summary.json") -> None:
    """The consolidated perf-trajectory seed: ONE file at the repo root
    with every roofline-comparable kernel. Kernels from an existing
    summary of the same sha are merged in, so the CI job's sequential
    driver invocations (fig06, fig10 …, fig11 --fuse-steps 2)
    consolidate instead of overwriting each other."""
    sha = _git_sha()
    kernels = summarize_rows(util.ROWS)
    try:
        with open(path) as fh:
            prior = json.load(fh)
        if prior.get("git_sha") == sha and isinstance(
            prior.get("kernels"), dict
        ):
            kernels = {**prior["kernels"], **kernels}
    except (OSError, ValueError):
        pass
    payload = {
        "schema": JSON_SCHEMA,
        "device": _device(),
        "git_sha": sha,
        "smoke": util.smoke(),
        "kernels": kernels,
    }
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=1, sort_keys=True)
        fh.write("\n")
    print(f"wrote {len(kernels)} kernel summar(ies) to {path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-sized problems (hours on CPU)")
    ap.add_argument("--only", default=None,
                    help="substring filter on module names")
    ap.add_argument("--smoke", action="store_true",
                    help="single-iteration shrunk-size run (CI plumbing "
                         "check; timings not trustworthy)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as JSON (device + git sha "
                         "stamped) for artifact archiving")
    ap.add_argument("--dims", default=None, metavar="D[,D...]",
                    help="restrict dimensionality-sweep modules (fig10/"
                         "fig11) to these ranks, e.g. --dims 1,2 or "
                         "--dims 3 (default: all of 1,2,3)")
    ap.add_argument("--fuse-steps", type=int, default=1, metavar="S",
                    help="temporal-fusion depth for modules that sweep "
                         "it (fig11): S in-kernel time steps per launch "
                         "on halo-widened blocks, timings reported per "
                         "step (default 1)")
    ap.add_argument("--strategies", default=None, metavar="S[,S...]",
                    help="restrict/widen the caching-strategy sweep for "
                         "modules that take one (fig11), e.g. "
                         "--strategies swc_stream, --strategies tc "
                         "(MXU matmul lowering; rows gain "
                         "tpu_mxu_bound_s/mxu_crossover_depth), "
                         "--strategies auto (cross-strategy tuning "
                         "search; the chosen regime is reported per "
                         "shape), or --strategies hwc,swc,tc "
                         "(default: hwc,swc)")
    args = ap.parse_args()
    use_compile_cache()
    if args.fuse_steps < 1:
        ap.error("--fuse-steps must be >= 1")
    if args.smoke:
        util.set_smoke(True)
    dims = None
    if args.dims is not None:
        try:
            dims = tuple(sorted({int(d) for d in args.dims.split(",")}))
        except ValueError:
            dims = ()
        if not dims or any(d not in (1, 2, 3) for d in dims):
            ap.error("--dims entries must be in {1, 2, 3}")
    strategies = None
    if args.strategies is not None:
        strategies = tuple(
            s.strip() for s in args.strategies.split(",") if s.strip()
        )
        bad = [
            s for s in strategies
            if s not in ("hwc", "swc", "swc_stream", "tc", "auto")
        ]
        if not strategies or bad:
            ap.error(
                "--strategies entries must be in "
                "{hwc, swc, swc_stream, tc, auto}"
            )
    header()
    for name in MODULES:
        if args.only and args.only not in name:
            continue
        mod = importlib.import_module(f"benchmarks.{name}")
        params = inspect.signature(mod.run).parameters
        kwargs = {}
        if dims is not None and "dims" in params:
            kwargs["dims"] = dims  # others run normally (no rank sweep)
        if args.fuse_steps != 1 and "fuse_steps" in params:
            kwargs["fuse_steps"] = args.fuse_steps
        if strategies is not None and "strategies" in params:
            kwargs["strategies"] = strategies
        mod.run(full=args.full, **kwargs)
    if args.json:
        write_json(args.json)
        if args.smoke:
            # Seed the perf trajectory: consolidated per-kernel GB/s +
            # roofline fractions at the repo root, uploaded by CI.
            write_summary()


if __name__ == "__main__":
    main()

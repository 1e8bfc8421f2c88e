"""Audit driver: every lowerable plan of the registered shape set.

Two plan sources, both audited with the same obligations:

* the declarative audit-shape registry
  (:data:`repro.tuning.shapes.AUDIT_SHAPES` — mirrors the warm/bench
  registry plus auditor-only axes), expanded over its full strategy ×
  fuse × unroll × batch product;
* the cross-strategy tuner's own candidate space
  (:func:`repro.tuning.costmodel.enumerate_cross_strategy_nd` over
  each registry entry) — every non-hwc candidate the ``auto`` search
  could ever measure is lowered to its plan and audited, so a tuning
  winner can never be a plan the auditor has not proved.

Key obligations (sid injectivity, TuningKey uniqueness,
``plan_from_record`` round-trip) run over the union of both sets plus
the exhaustive sid axis product. The result is a JSON report
(``BENCH_audit.json``; schema in docs/analysis.md) and a process exit
code: nonzero iff any finding survived.
"""
from __future__ import annotations

import json
import subprocess
from typing import Any

import numpy as np

from repro.analysis.bounds import audit_plan
from repro.analysis.findings import Finding
from repro.analysis.keys import (
    audit_key_uniqueness,
    audit_record_roundtrip,
    audit_sid_injectivity,
)
from repro.analysis.vmem import check_vmem


# The audited candidate space is ranked with the peaks of the chip the
# kernels target, whatever host the audit runs on.
AUDIT_DEVICE_KIND = "TPU v5 lite"


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10, check=True,
        ).stdout.strip()
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def _device_kind() -> str:
    try:
        import jax

        return jax.devices()[0].device_kind
    except Exception:  # repolint: allow[broad-except] — stamp only
        return "unknown"


def _candidate_plans(entry: Any, domain: tuple[int, ...]):
    """Lower every non-hwc cross-strategy candidate for one registry
    entry to its StencilPlan. Yields (plan, ops); candidates the plan
    layer rejects are yielded as (ValueError, candidate) for the
    caller to report — the search must never rank a config that cannot
    lower."""
    from repro.kernels.plan import plan_stencil
    from repro.tuning.costmodel import enumerate_cross_strategy_nd

    ops = entry.operator_set()
    radii = ops.radius_per_axis()
    fuse_opts = (
        (1, 2)
        if entry.n_out == entry.n_f + entry.n_aux and not entry.n_aux
        else (1,)
    )
    cands = enumerate_cross_strategy_nd(
        domain, radii, entry.n_f, entry.n_out,
        np.dtype(entry.dtype).itemsize,
        fuse_steps_options=fuse_opts,
        stream_ok=not entry.n_aux,
        tc_ok=entry.dtype in ("float32", "bfloat16"),
        backend=AUDIT_DEVICE_KIND,
    )
    for c in cands:
        if c.strategy == "hwc":
            continue
        padded = tuple(
            n + 2 * r * c.fuse_steps for n, r in zip(domain, radii)
        )
        try:
            plan = plan_stencil(
                ops, (entry.n_f,) + padded, entry.n_out,
                strategy=c.strategy, block=c.block, dtype=entry.dtype,
                n_aux=entry.n_aux, fuse_steps=c.fuse_steps,
            )
        except ValueError as e:
            yield e, c
            continue
        yield plan, ops


def run_audit(
    *,
    full: bool = False,
    vmem_tol: float = 0.0,
    enumerate_candidates: bool = True,
) -> dict[str, Any]:
    """Run the complete audit; returns the JSON-serializable report."""
    from repro.tuning.shapes import AUDIT_SHAPES

    findings: list[Finding] = []
    audited: list[tuple[Any, Any]] = []  # (plan, ops)
    n_registry = 0
    n_candidates = 0
    for entry in AUDIT_SHAPES:
        domain = entry.full if full else entry.smoke
        for plan, ops in entry.plans(domain):
            res = audit_plan(plan, ops)
            findings.extend(res.findings)
            findings.extend(
                check_vmem(plan, res.measured_vmem, tol=vmem_tol)
            )
            audited.append((plan, ops))
            n_registry += 1
        if enumerate_candidates:
            # Candidate space over the smoke extents regardless of
            # --full: the point is coverage of the search space, and
            # the space only shrinks as extents grow past the budget.
            for plan, ops in _candidate_plans(entry, entry.smoke):
                if isinstance(plan, ValueError):
                    findings.append(Finding(
                        "bounds", f"{entry.name}:{ops.strategy}",
                        f"enumerated candidate does not lower: {plan}",
                    ))
                    continue
                res = audit_plan(plan, ops)
                findings.extend(res.findings)
                findings.extend(
                    check_vmem(plan, res.measured_vmem, tol=vmem_tol)
                )
                audited.append((plan, ops))
                n_candidates += 1

    sid_findings, n_combos = audit_sid_injectivity()
    findings.extend(sid_findings)
    findings.extend(audit_key_uniqueness([p for p, _ in audited]))
    seen_sids: set[tuple] = set()
    n_roundtrips = 0
    for plan, ops in audited:
        k = (plan.strategy_id, plan.interior, plan.block, plan.dtype)
        if k in seen_sids:
            continue
        seen_sids.add(k)
        findings.extend(audit_record_roundtrip(plan, ops))
        n_roundtrips += 1

    return {
        "schema": 1,
        "mode": "full" if full else "smoke",
        "device": _device_kind(),
        "git_sha": _git_sha(),
        "vmem_tol": vmem_tol,
        "counts": {
            "registry_plans": n_registry,
            "candidate_plans": n_candidates,
            "sid_combos": n_combos,
            "record_roundtrips": n_roundtrips,
            "findings": len(findings),
        },
        "findings": [f.to_json() for f in findings],
    }


def run_mutants() -> dict[str, Any]:
    """Run the mutation harness; report schema mirrors
    :func:`run_audit` with a ``mutants`` section instead of findings."""
    from repro.analysis.mutants import run_harness

    results = run_harness()
    undetected = sorted(
        name for name, r in results.items() if not r["detected"]
    )
    return {
        "schema": 1,
        "mode": "mutants",
        "device": _device_kind(),
        "git_sha": _git_sha(),
        "mutants": results,
        "undetected": undetected,
    }


def write_report(report: dict[str, Any], path: str) -> None:
    with open(path, "w") as fh:
        json.dump(report, fh, indent=2, sort_keys=True)
        fh.write("\n")

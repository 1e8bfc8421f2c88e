"""Tests for ``repro.analysis`` — the static plan auditor.

Four angles:

* the shipped shape registry audits clean (bounds + vmem + keys);
* the mutation harness detects every seeded defect class, so a clean
  audit is evidence and not vacuity;
* ``strategy_sid`` injectivity and the persisted-record round-trip,
  including a RAW-JSON regression for every post-PR-6 axis (fuse depth,
  stream flag, resolved strategy, unroll) and the legacy-record
  default (``unroll`` absent → 1);
* a property sweep: random valid plans are auditor-clean and
  round-trip through ``plan_from_record``.

Property tests use real ``hypothesis`` when installed and fall back to
the seeded sampler in ``tests/_minihypothesis.py`` otherwise (same
contract as ``test_kernel_properties.py``).
"""
import json

import pytest

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:  # bare interpreter: seeded fallback, not a skip
    from _minihypothesis import given, settings
    from _minihypothesis import strategies as st

from repro.analysis import (
    CLASSES,
    audit_plan,
    audit_record_roundtrip,
    audit_sid_injectivity,
    check_vmem,
    parse_sid,
    run_audit,
)
from repro.analysis.mutants import run_harness
from repro.core.stencil import derivative_operator_set
from repro.kernels.plan import plan_from_record, plan_stencil
from repro.tuning.cache import TuningRecord

OPS2 = derivative_operator_set(2, accuracy=2)  # radius 1


# --- the shipped registry audits clean -----------------------------------------


def test_registry_smoke_audit_is_finding_free():
    report = run_audit(full=False, vmem_tol=0.0, enumerate_candidates=False)
    assert report["findings"] == []
    assert report["counts"]["registry_plans"] >= 50
    assert report["counts"]["sid_combos"] >= 1000
    assert report["counts"]["record_roundtrips"] >= 50


# --- the auditor is not vacuous: every defect class is detectable --------------


def test_mutation_harness_detects_every_mutant():
    results = run_harness()
    assert results["__clean__"]["detected"], (
        "fixture plans must audit clean before mutation: "
        f"{results['__clean__']['classes']}"
    )
    missed = [
        name for name, r in results.items()
        if name != "__clean__" and not r["detected"]
    ]
    assert not missed, f"undetected mutants: {missed}"


def test_mutation_harness_covers_the_finding_classes():
    results = run_harness()
    detected = set()
    for name, r in results.items():
        if name != "__clean__" and r["detected"]:
            detected.update(set(r["classes"]) & set(r["expected"]))
    # every machine-checkable defect family has a live detector
    assert {"bounds", "uninit", "coverage", "phi", "vmem", "key"} <= detected
    assert detected <= set(CLASSES)


# --- key injectivity -----------------------------------------------------------


def test_sid_injectivity_exhaustive():
    findings, n_combos = audit_sid_injectivity()
    assert findings == []
    assert n_combos >= 1000  # the full axis product, not a sample


def test_parse_sid_roundtrips_marked_axes():
    for sid in (
        "swc", "swc:u2", "swc:f3", "swc:u4:b2", "tc:f2:b4:o8",
        "swc_stream:f2:a0:o4", "swc:b2:a1", "auto:f2",
    ):
        parsed = parse_sid(sid)
        assert parsed is not None, sid


# --- persisted-record round-trip (post-PR-6 axes, raw JSON) --------------------


def _roundtrip(plan, ops):
    assert audit_record_roundtrip(plan, ops) == []


def test_record_roundtrip_unroll():
    _roundtrip(plan_stencil(OPS2, (2, 10, 258), 2, strategy="swc", unroll=2), OPS2)


def test_record_roundtrip_stream():
    _roundtrip(plan_stencil(OPS2, (2, 66, 258), 2, strategy="swc_stream"), OPS2)


def test_record_roundtrip_temporal():
    _roundtrip(
        plan_stencil(OPS2, (2, 68, 260), 2, strategy="swc", fuse_steps=2), OPS2
    )


def test_record_roundtrip_batch_and_aux():
    _roundtrip(plan_stencil(OPS2, (4, 2, 10, 258), 2, strategy="swc"), OPS2)
    _roundtrip(plan_stencil(OPS2, (1, 10, 258), 2, n_aux=1), OPS2)


def test_record_roundtrip_accuracy_axis():
    ops6 = derivative_operator_set(2, accuracy=6)
    _roundtrip(plan_stencil(ops6, (2, 14, 262), 2, strategy="swc"), ops6)


def test_raw_json_record_rebuilds_unrolled_plan():
    """A persisted v2 record — as raw JSON, every post-PR-6 field — must
    rebuild the exact plan whose tuning decision it stores."""
    plan = plan_stencil(OPS2, (2, 10, 258), 2, strategy="swc", unroll=2)
    raw = json.dumps({
        "block": list(plan.block),
        "timings_us": {"8x128:u2": 12.5},
        "source": "measured",
        "schema": 2,
        "created": 1.0,
        "fuse_steps": 1,
        "stream": False,
        "strategy_resolved": "swc",
        "failed": {},
        "unroll": 2,
    })
    rec = TuningRecord.from_json(json.loads(raw))
    assert rec.unroll == 2
    back = plan_from_record(OPS2, (2, 8, 256), 2, rec)
    assert back == plan


def test_raw_json_legacy_record_defaults_unroll_1():
    """Pre-unroll records (no ``unroll`` key in the JSON) must parse as
    unroll=1, matching their unmarked tuning keys."""
    raw = json.dumps({
        "block": [8, 128],
        "timings_us": {},
        "source": "measured",
        "schema": 2,
        "fuse_steps": 2,
        "stream": True,
        "strategy_resolved": "swc_stream",
    })
    rec = TuningRecord.from_json(json.loads(raw))
    assert rec.unroll == 1
    back = plan_from_record(OPS2, (2, 64, 256), 2, rec)
    expect = plan_stencil(
        OPS2, (2, 68, 260), 2, strategy="swc_stream", fuse_steps=2,
        block=(8, 128),
    )
    assert back == expect


# --- vmem fidelity -------------------------------------------------------------


def test_vmem_shadow_measurement_matches_model():
    for plan in (
        plan_stencil(OPS2, (2, 10, 258), 2, strategy="swc", unroll=2),
        plan_stencil(OPS2, (1, 10, 258), 2, n_aux=1),
        plan_stencil(OPS2, (2, 66, 258), 2, strategy="swc_stream"),
    ):
        res = audit_plan(plan, OPS2)
        assert res.findings == []
        assert check_vmem(plan, res.measured_vmem) == []


@pytest.mark.parametrize(
    "strategy,fuse,rows",
    [("swc", 1, (1, 1, 1)), ("tc", 1, (2, 1)), ("swc", 2, (1, 2))],
)
def test_split_aux_audits_like_stacked_aux(strategy, fuse, rows):
    """A tuple ``aux`` (one operand per array, joined in VMEM) proves
    clean and stages what the stacked operand stages; rows that do not
    add up to the plan's are a finding."""
    n_aux = sum(rows)
    n_out = 1 + n_aux if fuse > 1 else 1
    pad = 2 * fuse
    plan = plan_stencil(
        OPS2, (1, 16 + pad, 256 + pad), n_out, strategy=strategy,
        n_aux=n_aux, fuse_steps=fuse,
    )
    stacked = audit_plan(plan, OPS2)
    split = audit_plan(plan, OPS2, aux_rows=rows)
    assert stacked.findings == [] and split.findings == []
    assert split.measured_vmem == stacked.measured_vmem
    assert audit_plan(plan, OPS2, aux_rows=rows[1:]).findings


def test_vmem_check_flags_mismatch():
    plan = plan_stencil(OPS2, (2, 10, 258), 2, strategy="swc")
    res = audit_plan(plan, OPS2)
    wrong = res.measured_vmem * 2
    findings = check_vmem(plan, wrong)
    assert findings and findings[0].cls == "vmem"


# --- property sweep: random valid plans audit clean ----------------------------


@settings(max_examples=12, deadline=None)
@given(
    strategy=st.sampled_from(("swc", "swc_stream", "tc")),
    accuracy=st.sampled_from((2, 4, 6)),
    interior_y=st.sampled_from((16, 32, 64)),
    fuse=st.sampled_from((1, 2)),
    unroll=st.sampled_from((1, 2)),
    batch=st.sampled_from((1, 2)),
)
def test_random_valid_plans_audit_clean(
    strategy, accuracy, interior_y, fuse, unroll, batch
):
    ops = derivative_operator_set(2, accuracy=accuracy)
    r = ops.radius
    if strategy != "swc" or fuse > 1:
        unroll = 1  # unroll composes only with depth-1 pipelined swc
    pad = 2 * r * fuse
    shape = (2, interior_y + pad, 256 + pad)
    if batch > 1:
        shape = (batch,) + shape
    plan = plan_stencil(
        ops, shape, 2, strategy=strategy, fuse_steps=fuse, unroll=unroll
    )
    res = audit_plan(plan, ops)
    assert res.findings == [], [f.detail for f in res.findings]
    assert check_vmem(plan, res.measured_vmem) == []
    assert audit_record_roundtrip(plan, ops) == []

"""VMEM-model fidelity: measured shadow working set vs the cost model.

The bounds audit measures the working set a plan's shadow run actually
staged — the shadow ref shapes (which are the emitter's own BlockSpec/
scratch shapes via ``lowering_windows``/``stream_extents``) plus the
carried intermediate extents OBSERVED at the synthetic-φ boundaries.
:func:`check_vmem` compares that against
``repro.kernels.plan.vmem_working_set`` (resolved through its
re-export in ``repro.tuning.costmodel``), which derives the same
quantity by independent arithmetic — the one formula whose answers
steer candidate enumeration, the 12 MiB budget filter and the default
tile of rank-3 ``swc`` plans. Divergence means the tuner and the
planner are budgeting for a different kernel than the one being
emitted — historically how the unroll and aux terms went missing.

Tolerance: the two derivations are exact mirrors, so the default
relative tolerance is 0 (byte equality). ``tol`` exists for callers
that deliberately loosen the contract (e.g. exploratory model edits);
``python -m repro.analysis`` exposes it as ``--vmem-tol``.
"""
from __future__ import annotations

import numpy as np

from repro.analysis.findings import Finding
from repro.kernels.plan import StencilPlan
from repro.tuning import costmodel


def model_vmem(plan: StencilPlan) -> int:
    """The cost model's working-set prediction for ``plan``, called
    with the plan's base (un-flattened) counts plus its batch extent —
    exercising the model's own batch scaling path. The model is
    resolved through the module at call time so the mutation harness's
    seeded model defects are what actually runs."""
    return costmodel.vmem_working_set(
        plan.block,
        plan.radii,
        plan.n_f,
        plan.n_out,
        np.dtype(plan.dtype).itemsize,
        plan.fuse_steps,
        plan.strategy == "swc_stream",
        batch=plan.batch,
        unroll=plan.unroll,
        n_aux=plan.n_aux,
        ghost=plan.ghost,
    )


def check_vmem(
    plan: StencilPlan, measured: int | None, *, tol: float = 0.0
) -> list[Finding]:
    """One finding (class ``vmem``) if ``measured`` and the model
    disagree beyond ``tol`` (relative); empty list otherwise."""
    if measured is None:
        return []  # bounds audit aborted; its findings already report
    model = model_vmem(plan)
    limit = tol * max(measured, model)
    if abs(measured - model) > limit:
        return [Finding(
            "vmem", plan.strategy_id,
            f"shadow run staged {measured} B, cost model predicts "
            f"{model} B (tol {tol:g})",
        )]
    return []

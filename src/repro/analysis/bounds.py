"""Interval-domain bounds proofs for every lowerable StencilPlan.

:func:`audit_plan` shadow-executes the plan's actual kernel body (the
very functions ``repro.kernels.emit`` hands to ``pl.pallas_call``)
against :class:`~repro.analysis.shadow.ShadowRef` operands shaped by
the emitter's own geometry hooks (``lowering_windows`` /
``stream_extents``), and proves:

* **placement** — per axis, the grid tiles the interior exactly and
  the extremal grid step's staged window lands exactly on the padded
  extent (affine index maps attain their extrema at grid corners, so
  corner arithmetic is a proof for the whole grid);
* **bounds** — every load in the body stays inside the staged window
  and every store inside the output tile (strict shadow slicing: any
  index numpy would silently clamp raises);
* **coverage** — the union of the body's store boxes covers the output
  tile exactly (catches unroll sub-tile gaps);
* **uninit** — scratch reads are covered by prior writes, across
  temporal-sweep shrinkage and the streaming kernel's carried halo
  planes (plane-provenance tracking: every working-set plane must hold
  exactly the global plane the chunk's input window calls for);
* **sweep geometry** — at each synthetic-φ call boundary, derivative
  blocks and aux carries are extent-aligned with the independently
  derived ``τ + 2r·(S-1-s)`` schedule;
* **zero ghosts** — a zero-ghost plan (``plan.ghost == "zero"``) is run
  over its whole grid in order, its copies landing at ``start()``: every
  copy box lies inside the unpadded operand, and every window element
  the body reads holds what the zero-padded operand holds there — the
  copied point inside the domain, a stored zero outside (element
  provenance across the two slots and the prefetch of the next step).

The shadow run also measures the VMEM working set actually staged
(ref shapes + the observed carried intermediate), which
``repro.analysis.vmem`` checks against the cost model.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Any

import numpy as np

from repro.analysis.findings import AuditError, Finding
from repro.analysis.shadow import (
    Box,
    ShadowArray,
    ShadowRef,
    ShimContext,
    ShimSem,
    make_synthetic_phis,
    shadow_shims,
    uncovered,
)
from repro.kernels.plan import StencilPlan, staged_window

# Window provenance of the zero-ghost audit: an element never written,
# and an element holding a stored zero. Others hold the flat index of
# the operand point copied there.
_UNSET, _ZERO = -1, -2


@dataclasses.dataclass
class PlanAudit:
    """Result of auditing one plan: findings plus the measured VMEM
    working set (bytes) the shadow run staged, for the fidelity check."""

    sid: str
    findings: list[Finding]
    measured_vmem: int | None


def _derived_exec_plan(plan: StencilPlan) -> StencilPlan:
    """The batch=1 plan a batched launch actually lowers — mirror of
    the ``dataclasses.replace`` in ``emit._fused_batched`` (member-
    major flattening scales every field count by B)."""
    if plan.batch == 1:
        return plan
    return dataclasses.replace(
        plan, batch=1, n_f=plan.batch * plan.n_f,
        n_out=plan.batch * plan.n_out, n_aux=plan.batch * plan.n_aux,
    )


def _sweep_exts(plan: StencilPlan) -> list[tuple[int, ...]]:
    """Independently derived per-sweep derivative extents: sweep ``s``
    of ``S`` sees ``τ + 2r·(S-1-s)`` per axis, except that a z-chunked
    body (``plan.z_chunk`` below the z tile) sees ``z_chunk`` planes on
    z in every sweep."""
    exts = [
        tuple(
            t + 2 * r * (plan.fuse_steps - 1 - s)
            for t, r in zip(plan.block, plan.radii)
        )
        for s in range(plan.fuse_steps)
    ]
    if plan.z_chunk < plan.block[0]:
        exts = [(plan.z_chunk,) + e[1:] for e in exts]
    return exts


def _audit_pipelined(
    plan: StencilPlan, ops: Any, findings: list[Finding],
    observed: list[tuple[int, ...]],
    aux_rows: tuple[int, ...] | None = None,
) -> int:
    """Shadow-run the pipelined/temporal/tc body once (it is grid-
    position independent; placement is proved arithmetically) and
    return the measured VMEM bytes. ``aux_rows`` splits the plan's aux
    rows over that many operands (default: one stacked operand)."""
    from repro.kernels import emit

    sid = plan.strategy_id
    windows = emit.lowering_windows(plan)
    window, out_tile = windows["window"], windows["out_tile"]
    aux_window = windows["aux_window"]
    steps = plan.block[:-1] + (plan.x_step,)
    padded = tuple(
        n + 2 * h for n, h in zip(plan.interior, plan.halo)
    )
    for a, (g, st) in enumerate(zip(plan.grid, steps)):
        if g * st != plan.interior[a]:
            findings.append(Finding(
                "coverage", sid,
                f"axis {a}: grid {g} x step {st} != interior "
                f"{plan.interior[a]}",
            ))
        if (g - 1) * st + window[a] != padded[a]:
            findings.append(Finding(
                "bounds", sid,
                f"axis {a}: extremal window [{(g - 1) * st}, "
                f"{(g - 1) * st + window[a]}) != padded extent "
                f"{padded[a]}",
            ))

    f_ref = ShadowRef(
        "f", (plan.n_f,) + window, plan.dtype, initialized=True
    )
    o_ref = ShadowRef("o", (plan.n_out,) + out_tile, plan.dtype)
    if aux_rows is None:
        aux_rows = (plan.n_aux,) if plan.n_aux else ()
    if sum(aux_rows) != plan.n_aux:
        raise AuditError(
            "bounds", f"aux operands of {aux_rows} rows for n_aux "
            f"{plan.n_aux}",
        )
    rest: list[ShadowRef] = [
        ShadowRef(
            f"aux{i}", (rows,) + aux_window, plan.dtype, initialized=True,
        )
        for i, rows in enumerate(aux_rows)
    ]
    chunked = plan.z_chunk < plan.block[0]
    phis = make_synthetic_phis(
        plan,
        _sweep_exts(plan) if plan.fuse_steps > 1 or chunked
        else [plan.block],
        observed_exts=observed,
    )
    scratch: list[ShadowRef] = []
    if windows["mid"] is not None:
        scratch.append(
            ShadowRef("mid", (plan.n_f,) + windows["mid"], plan.dtype)
        )
    z_chunk = plan.z_chunk if chunked else None
    tc = plan.strategy == "tc"
    ctx = ShimContext(program_ids=(0,) * plan.rank)
    try:
        with shadow_shims(ctx):
            # Kernel bodies and derivs lowerings resolved through the
            # module AT CALL TIME so the mutation harness's patched
            # defects are what actually runs.
            derivs_fn = (
                emit._block_derivs_tc if tc else emit._block_derivs
            )
            if plan.fuse_steps > 1:
                emit._kernel_temporal(
                    f_ref, *rest, o_ref, *scratch, ops=ops,
                    radii=plan.radii, tile=plan.block, phis=phis,
                    n_f=plan.n_f, n_aux_refs=len(rest),
                    derivs_fn=derivs_fn, z_chunk=z_chunk,
                )
            else:
                emit._kernel_pipelined(
                    f_ref, *rest, o_ref, ops=ops, radii=plan.radii,
                    tile=plan.block, phi=phis[0],
                    unroll=plan.unroll, n_aux_refs=len(rest),
                    derivs_fn=derivs_fn, z_chunk=z_chunk,
                )
    except AuditError as e:
        findings.append(Finding(e.cls, sid, e.detail))
    else:
        holes = uncovered(o_ref.full_box(), o_ref.writes)
        if holes:
            findings.append(Finding(
                "coverage", sid,
                f"output tile region {holes[0]} never stored",
            ))

    itemsize = np.dtype(plan.dtype).itemsize
    # The intermediate generation: the scratch a z-chunked body keeps
    # it in, else the extent the first sweep's φ returned.
    if scratch:
        mid = math.prod(scratch[0].shape)
    elif plan.fuse_steps > 1 and observed:
        mid = plan.n_f * math.prod(observed[0])
    else:
        mid = 0
    # Mosaic stages halo windows tile-aligned; the body reads only the
    # logical window the shadow refs hold.
    if plan.n_aux and plan.fuse_steps > 1:
        aux_window = staged_window(aux_window)
    aux_sz = (
        plan.n_aux * math.prod(aux_window) if plan.n_aux else 0
    )
    return itemsize * (
        2 * plan.n_f * math.prod(staged_window(window))
        + 2 * aux_sz
        + mid
        + plan.n_out * math.prod(out_tile)
    )


def _audit_zero_ghost(
    plan: StencilPlan, ops: Any, findings: list[Finding],
    observed: list[tuple[int, ...]],
    aux_rows: tuple[int, ...] | None = None,
) -> int:
    """Shadow-run a zero-ghost launch over its whole grid, in the order
    the chip runs it (z innermost), with element provenance on the
    double-buffered window, and return the measured VMEM bytes.

    Copies land when started (the next step's prefetch included) from
    an unpadded ``f_hbm``, so strict slicing proves every copy box lies
    inside the operand. At every read of the window, each element must
    hold what the zero-padded operand holds at its place for the step
    being computed: a wrong face copy shows as a misplaced point or a
    zero where data belongs ("bounds"), a missing fill as a never-
    written element ("uninit")."""
    from repro.kernels import emit

    sid = plan.strategy_id
    windows = emit.lowering_windows(plan)
    out_tile, margins = windows["out_tile"], windows["margins"]
    for a, (g, st) in enumerate(zip(plan.grid, plan.block)):
        if g * st != plan.interior[a]:
            findings.append(Finding(
                "coverage", sid,
                f"axis {a}: grid {g} x step {st} != interior "
                f"{plan.interior[a]}",
            ))
    f_shape = (plan.n_f,) + plan.interior
    f_hbm = ShadowRef("f_hbm", f_shape, plan.dtype, initialized=True)
    win = ShadowRef("win", (2, plan.n_f) + windows["window"], plan.dtype)
    held = np.full(win.shape, _UNSET, np.int64)
    where = [0, 0]  # (z, y) block of the step being computed

    def box_slices(box: Box) -> tuple[slice, ...]:
        return tuple(slice(lo, hi) for lo, hi in box)

    def on_write(box: Box, value: Any) -> None:
        ext = tuple(hi - lo for lo, hi in box)
        if isinstance(value, ShadowArray):
            if value.src is None or value.src[0] is not f_hbm:
                raise AuditError(
                    "bounds", f"window {box} filled from a value that is "
                    "no copy of the operand",
                )
            pts = np.meshgrid(
                *(np.arange(lo, hi) for lo, hi in value.src[1]),
                indexing="ij",
            )
            held[box_slices(box)] = np.ravel_multi_index(
                pts, f_shape
            ).reshape(ext)
            return
        zeros = np.asarray(value)
        if zeros.size != np.prod(ext) or np.any(zeros != 0):
            raise AuditError(
                "bounds", f"window {box} stored with a value other than "
                "zeros of its extent",
            )
        held[box_slices(box)] = _ZERO

    def on_read(box: Box) -> None:
        (s0, s1), (f0, f1), *planes = box
        z, y, x = (
            np.arange(lo, hi) + base
            for (lo, hi), base in zip(planes, (
                where[0] * plan.block[0] - margins[0],
                where[1] * plan.block[1] - margins[1],
                0,
            ))
        )
        pts = np.meshgrid(np.arange(f0, f1), z, y, x, indexing="ij")
        inside = np.ones(pts[0].shape, bool)
        for p, n in zip(pts[1:], plan.interior):
            inside &= (p >= 0) & (p < n)
        want = np.full(pts[0].shape, _ZERO, np.int64)
        want[inside] = np.ravel_multi_index(
            tuple(p[inside] for p in pts), f_shape
        )
        got = held[box_slices(box)].reshape(want.shape)
        bad = np.argwhere(got != want)
        if bad.size:
            at = tuple(int(v) for v in bad[0])
            raise AuditError(
                "uninit" if got[at] == _UNSET else "bounds",
                f"step (z {where[0]}, y {where[1]}): window slot {s0} "
                f"element {at} of read {box} holds {int(got[at])}, the "
                f"zero-padded operand has {int(want[at])} there",
            )

    win.write_hook = on_write
    win.read_hook = on_read
    if aux_rows is None:
        aux_rows = (plan.n_aux,) if plan.n_aux else ()
    aux = [
        ShadowRef(f"aux{i}", (rows,) + out_tile, plan.dtype, initialized=True)
        for i, rows in enumerate(aux_rows)
    ]
    chunked = plan.z_chunk < plan.block[0]
    phis = make_synthetic_phis(
        plan, _sweep_exts(plan) if chunked else [plan.block],
        observed_exts=observed,
    )
    n_z, n_y = plan.grid[0], plan.grid[1]
    try:
        for t in range(n_z * n_y):
            where[:] = t % n_z, t // n_z
            o_ref = ShadowRef("o", (plan.n_out,) + out_tile, plan.dtype)
            ctx = ShimContext(program_ids=(where[1], 0, where[0]))
            with shadow_shims(ctx):
                emit._kernel_zero_ghost(
                    f_hbm, *aux, o_ref, win, ShimSem(), ops=ops,
                    tile=plan.block, phi=phis[0], n_aux_refs=len(aux),
                    z_chunk=plan.z_chunk if chunked else None,
                    copies=emit.zero_ghost_copies(plan),
                    blocks=(n_z, n_y), margins=margins,
                )
            holes = uncovered(o_ref.full_box(), o_ref.writes)
            if holes:
                findings.append(Finding(
                    "coverage", sid,
                    f"step (z {where[0]}, y {where[1]}): output tile "
                    f"region {holes[0]} never stored",
                ))
    except AuditError as e:
        findings.append(Finding(e.cls, sid, e.detail))

    itemsize = np.dtype(plan.dtype).itemsize
    return itemsize * (
        math.prod(win.shape)
        + 2 * plan.n_aux * math.prod(out_tile)
        + plan.n_out * math.prod(out_tile)
    )


def _audit_stream(
    plan: StencilPlan, ops: Any, findings: list[Finding],
    observed: list[tuple[int, ...]],
) -> int:
    """Shadow-run the streaming kernel at every cross-grid corner with
    plane-provenance tracking, and return the measured VMEM bytes.

    The invariant proved at every chunk's compute read: working-set
    plane ``p`` holds global (padded) plane ``chunk·τ₀ + p`` — which
    is exactly what the carried-halo + fresh-plane choreography must
    establish. A wrong prologue width surfaces as an uninitialized
    plane (-1), a skewed carry or fresh-plane offset as a provenance
    mismatch (out-of-bounds in global coordinates).
    """
    from repro.kernels import emit

    sid = plan.strategy_id
    ext = emit.stream_extents(plan)
    ts, hs = plan.block[0], plan.halo[0]
    n_chunks = ext["n_chunks"]
    cross_grid = tuple(
        n // t for n, t in zip(plan.interior[1:], plan.block[1:])
    )
    corners = itertools.product(
        *[(0, g - 1) if g > 1 else (0,) for g in cross_grid]
    )
    for corner in corners:
        exp_halo = tuple(
            (c * t, c * t + w)
            for c, t, w in zip(corner, plan.block[1:], ext["cross"])
        )
        exp_tile = tuple(
            (c * t, (c + 1) * t)
            for c, t in zip(corner, plan.block[1:])
        )
        f_hbm = ShadowRef(
            "f_hbm", (plan.n_f,) + ext["padded"], plan.dtype,
            initialized=True,
        )
        o_hbm = ShadowRef(
            "o_hbm", (plan.n_out,) + plan.interior, plan.dtype
        )
        work = ShadowRef("work", (plan.n_f,) + ext["work"], plan.dtype)
        pf0 = ShadowRef("pf0", (plan.n_f,) + ext["prefetch"], plan.dtype)
        pf1 = ShadowRef("pf1", (plan.n_f,) + ext["prefetch"], plan.dtype)
        outbuf = ShadowRef(
            "outbuf", (plan.n_out,) + ext["outbuf"], plan.dtype
        )
        g_work = np.full(ext["work"][0], -1, np.int64)
        g_pf = {id(pf0): np.full(ts, -1, np.int64),
                id(pf1): np.full(ts, -1, np.int64)}
        chunk_now = [0]

        def check_cross(sbox: Box, expect, what: str) -> None:
            if tuple(sbox[2:]) != tuple(expect):
                raise AuditError(
                    "bounds",
                    f"{what}: cross-stream box {tuple(sbox[2:])} != "
                    f"grid-step window {tuple(expect)}",
                )

        def src_of(value: Any, what: str):
            if not isinstance(value, ShadowArray) or value.src is None:
                raise AuditError(
                    "bounds", f"{what} written from a non-copy value"
                )
            return value.src

        def pf_write(ref, box, value, exp_halo=exp_halo):
            sref, sbox = src_of(value, ref.name)
            if sref is not f_hbm:
                raise AuditError(
                    "bounds",
                    f"prefetch {ref.name} filled from {sref.name}, "
                    "expected f_hbm",
                )
            check_cross(sbox, exp_halo, f"prefetch {ref.name}")
            lo, hi = box[1]
            slo, shi = sbox[1]
            g_pf[id(ref)][lo:hi] = np.arange(slo, shi)

        def work_write(box, value, exp_halo=exp_halo):
            sref, sbox = src_of(value, "work")
            lo, hi = box[1]
            slo, shi = sbox[1]
            if sref is f_hbm:
                check_cross(sbox, exp_halo, "work<-f_hbm")
                g_work[lo:hi] = np.arange(slo, shi)
            elif sref is pf0 or sref is pf1:
                g_work[lo:hi] = g_pf[id(sref)][slo:shi]
            elif sref is work:
                g_work[lo:hi] = g_work[slo:shi].copy()
            else:
                raise AuditError(
                    "bounds", f"work filled from {sref.name}"
                )

        def work_read(box):
            if box != work.full_box():
                return  # partial read (carry source) — covered by the
                # uninit check; provenance is proved at compute reads
            c = chunk_now[0]
            expect = np.arange(c * ts, c * ts + ts + 2 * hs)
            if not np.array_equal(g_work, expect):
                bad = int(np.argmax(g_work != expect))
                raise AuditError(
                    "uninit" if g_work[bad] < 0 else "bounds",
                    f"chunk {c}: working-set plane {bad} holds global "
                    f"plane {int(g_work[bad])}, input window needs "
                    f"{int(expect[bad])}",
                )

        def out_write(box, value, exp_tile=exp_tile):
            sref, _ = src_of(value, "o_hbm")
            if sref is not outbuf:
                raise AuditError(
                    "bounds", f"o_hbm written from {sref.name}"
                )
            c = chunk_now[0]
            if box[1] != (c * ts, (c + 1) * ts):
                raise AuditError(
                    "bounds",
                    f"chunk {c}: output planes {box[1]} != "
                    f"({c * ts}, {(c + 1) * ts})",
                )
            check_cross(box, exp_tile, "o_hbm store")

        pf0.write_hook = lambda box, v: pf_write(pf0, box, v)
        pf1.write_hook = lambda box, v: pf_write(pf1, box, v)
        work.write_hook = work_write
        work.read_hook = work_read
        o_hbm.write_hook = out_write

        phis = make_synthetic_phis(
            plan, _sweep_exts(plan), observed_exts=observed
        )
        ctx = ShimContext(program_ids=corner)
        ctx.on_iter = lambda i: chunk_now.__setitem__(0, i)
        try:
            with shadow_shims(ctx):
                emit._kernel_stream(
                    f_hbm, o_hbm, work, pf0, pf1, outbuf,
                    ShimSem(), ShimSem(),  # inert DMA semaphores
                    ops=ops, radii=plan.radii, tile=plan.block,
                    phis=phis, n_chunks=n_chunks, cross=ext["cross"],
                )
        except AuditError as e:
            findings.append(Finding(e.cls, sid, e.detail))
            continue
        target = ((0, plan.n_out), (0, plan.interior[0])) + exp_tile
        holes = uncovered(target, o_hbm.writes)
        if holes:
            findings.append(Finding(
                "coverage", sid,
                f"streamed output region {holes[0]} never stored "
                f"(cross corner {corner})",
            ))

    itemsize = np.dtype(plan.dtype).itemsize
    mid = (
        plan.n_f * math.prod(observed[0])
        if plan.fuse_steps > 1 and observed else 0
    )
    return itemsize * (
        plan.n_f * math.prod(ext["work"])
        + 2 * plan.n_f * math.prod(ext["prefetch"])
        + mid
        + plan.n_out * math.prod(ext["outbuf"])
    )


def audit_plan(
    plan: StencilPlan, ops: Any, aux_rows: tuple[int, ...] | None = None
) -> PlanAudit:
    """Run the full bounds/coverage/uninit/geometry audit for one plan.

    Batched plans are audited through the batch=1 plan the launch
    actually lowers (member-major field scaling), reported under the
    ORIGINAL strategy id so findings name the user-facing plan.
    ``aux_rows`` audits an unbatched pipelined plan with its aux rows
    split over several operands, as a tuple ``aux`` launches it.
    """
    sid = plan.strategy_id
    exec_plan = _derived_exec_plan(plan)
    findings: list[Finding] = []
    observed: list[tuple[int, ...]] = []
    try:
        if plan.strategy == "swc_stream":
            measured = _audit_stream(exec_plan, ops, findings, observed)
        elif plan.ghost == "zero":
            measured = _audit_zero_ghost(
                exec_plan, ops, findings, observed, aux_rows
            )
        else:
            measured = _audit_pipelined(
                exec_plan, ops, findings, observed, aux_rows
            )
    except AuditError as e:  # geometry failures outside the body run
        findings.append(Finding(e.cls, sid, e.detail))
        measured = None
    findings = [
        dataclasses.replace(f, plan=sid) if f.plan != sid else f
        for f in findings
    ]
    return PlanAudit(sid=sid, findings=findings, measured_vmem=measured)

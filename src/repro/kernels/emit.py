"""Rank-generic Pallas emitters for :class:`~repro.kernels.plan.StencilPlan`.

This module subsumes the previously hand-written 1-D/3-D kernel bodies:
one pipelined software-managed-cache emitter serves ranks 1, 2 and 3,
and the explicit-streaming variant (paper Fig. 5b) is selected by a
plan attribute (``strategy="swc_stream"``, ranks 2 and 3, streaming the
slowest spatial axis) rather than living in a separate code path.

Strategies (paper Sec. 4.4, Figs. 4-5, on the TPU target):

* ``swc`` — the input tile plus halo, (τ…+2r…, τx+2rx) per field, is
  staged into VMEM by the Pallas pipeline with the slowest spatial axis
  iterating innermost at rank 3 (z-streaming with automatic
  double-buffered prefetch). Tap evaluation is fully unrolled with
  static offsets (stencil point-wise unrolling) and runs on the VPU as
  shifted-slice FMAs. ``plan.unroll > 1`` additionally computes several
  adjacent x sub-tiles per grid step from one staged window — the
  paper's element-wise unrolling, generalized to any rank.
  ``plan.fuse_steps > 1`` selects the temporal-fusion kernel instead:
  the staged halo widens to ``r·fuse_steps`` and the fused op is applied
  that many times on the VMEM-resident block (valid region shrinking by
  one radius per sweep), so intermediate time steps never round-trip
  through HBM.
* ``swc_stream`` — ranks 2 and 3: the cross-stream tile ((y, x) at rank
  3, (x,) at rank 2) is fixed per grid step and the kernel streams
  slowest-axis chunks (z at rank 3, y at rank 2) through an explicitly
  managed VMEM working buffer with async-DMA prefetch and carried halo
  planes (the TPU adaptation of the circular-buffer trick — see
  docs/architecture.md for the worked rank-2 lowering).
  ``plan.fuse_steps > 1`` composes: the carried halo widens to
  ``2·r·fuse_steps`` planes and each chunk runs the temporal sweeps on
  the streaming working set — the streaming variant of temporal
  blocking.
* ``tc`` — the matrix-unit regime: staging and grid are identical to
  pipelined ``swc``, but tap evaluation is lowered by
  :func:`_block_derivs_tc` instead of shifted-slice FMAs. Each
  multi-tap contraction group (see
  :func:`~repro.kernels.plan.tc_axis_groups`) becomes one
  ``jax.lax.dot_general`` of the staged window against a banded
  coefficient matrix of shape (τ_a+2r_a, τ_a) — with
  ``preferred_element_type=jnp.float32``, the form Mosaic places on
  the MXU with f32 accumulation (bf16 inputs run at double rate).
  Lone taps stay scalar slice-multiplies (a matmul per single tap
  would be all overhead). Temporal fusion reuses
  :func:`_temporal_sweeps` with the matmul derivs; the batch axis
  composes for free (members are extra rows of the contraction).

A ``swc`` plan with ``ghost="zero"`` (rank 3, depth 1, a tile spanning
the x row) takes its operand unpadded: :func:`_kernel_zero_ghost`
copies each window's part inside the domain into a double-buffered VMEM
slot itself and fills the zero ghost cells there, so the caller makes
no padded copy; the body is the pipelined one.

The HWC ("let the compiler manage residency") strategy lives in
``repro.kernels.ref`` as pure jnp.

Every emitter consumes the plan's tap tables verbatim: the (offset,
coefficient) sequences come from the generated Fornberg weights in
``repro.core.stencil`` (any even accuracy order — the order is a plan
axis, ``StencilPlan.accuracy``, joining the strategy id as ``:o{A}``),
so no kernel body hardwires a stencil order. See docs/stencils.md.

Every launch is named after its kernel body (``stencil_pipelined``,
``stencil_temporal``, ``stencil_tc``, ``stencil_stream``): the name
becomes the custom call's HLO instruction name, which is what a device
profile shows for the launch.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.stencil import OperatorSet
from repro.kernels.compat import element_window_spec
from repro.kernels.plan import (
    VMEM_LIMIT_BYTES,
    StencilPlan,
    staged_window,
    tc_axis_groups,
    zero_ghost_margins,
    zero_ghost_window,
)

_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _block_derivs(
    fblk: jnp.ndarray,
    ops: OperatorSet,
    radii: tuple[int, ...],
    tile: tuple[int, ...],
) -> dict[str, jnp.ndarray]:
    """Evaluate every operator over a VMEM-resident block of any rank.

    ``fblk``: (n_f, *(τ_a + 2r_a)). ``radii`` is where output point 0
    sits in ``fblk`` on each axis: the halo radius of a window that
    starts at its first ghost cell. Static slices per tap — unrolled at
    trace time (stencil point-wise unrolling)."""
    rank = len(tile)
    out: dict[str, jnp.ndarray] = {}
    for spec in ops.ops:
        acc = None
        for off, c in zip(spec.offsets, spec.coeffs):
            sl = (slice(None),) + tuple(
                slice(radii[a] + off[a], radii[a] + off[a] + tile[a])
                for a in range(rank)
            )
            term = jnp.asarray(c, dtype=fblk.dtype) * fblk[sl]
            acc = term if acc is None else acc + term
        out[spec.name] = acc
    return out


def _contract(window, band, axis: int):
    """One banded contraction of ``window`` along spatial ``axis``
    (``dot_general`` against the (ext+2r, ext) band, f32 accumulate,
    output dim moved back where the contracted axis was).

    This is the ONE data-dependent MXU op of the ``tc`` lowering, kept
    behind an indirection so the static auditor (``repro.analysis``)
    can thread its interval-domain shadow arrays through the kernel
    body: a window that implements ``shadow_contract`` dispatches there
    instead of running the matmul.
    """
    shadow = getattr(window, "shadow_contract", None)
    if shadow is not None:
        return shadow(band, axis)
    # f32 windows contract at full f32 precision, so tc matches the VPU
    # regimes to rounding; bf16 windows take the MXU's native pass.
    precision = (
        jax.lax.Precision.HIGHEST
        if window.dtype == jnp.float32
        else jax.lax.Precision.DEFAULT
    )
    term = jax.lax.dot_general(
        window,
        band,
        dimension_numbers=(((1 + axis,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32,
    )
    # dot_general appends the band's output dim last; put it back where
    # the contracted axis was.
    return jnp.moveaxis(term, -1, 1 + axis)


def _tc_band(
    taps: tuple[tuple[int, float], ...],
    out_extent: int,
    radius: int,
    dtype,
) -> jnp.ndarray:
    """Banded coefficient matrix for one tc contraction group.

    ``B[radius + j + i, i] = c`` for each tap ``(j, c)`` and output
    index ``i``: column ``i`` gathers the group's taps around the
    window position ``radius + i`` (output point ``i``'s center), so
    ``window @ B`` evaluates the whole 1-D contraction in one matmul.
    Shape (out_extent + 2·radius, out_extent). Built from 2-D iotas at
    trace time INSIDE the kernel — Pallas rejects large captured array
    constants, and the few compare/selects are noise next to the
    contraction itself. Temporal sweeps need one band per shrinking
    sub-tile extent.
    """
    shape = (out_extent + 2 * radius, out_extent)
    diag = jax.lax.broadcasted_iota(
        jnp.int32, shape, 0
    ) - jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    band = jnp.zeros(shape, jnp.float32)
    for j, c in taps:
        band = band + jnp.where(
            diag == radius + j, jnp.float32(c), jnp.float32(0)
        )
    return band.astype(dtype)


def _block_derivs_tc(
    fblk: jnp.ndarray,
    ops: OperatorSet,
    radii: tuple[int, ...],
    tile: tuple[int, ...],
) -> dict[str, jnp.ndarray]:
    """MXU variant of :func:`_block_derivs`: same (n_f, *(τ_a + 2r_a))
    window, same results, but every multi-tap contraction group runs as
    a banded-matrix ``dot_general`` with f32 accumulation.

    The band is materialized in the input dtype (so bf16 coefficients
    round exactly as the VPU path's), the contraction accumulates in
    float32 (``preferred_element_type``), and the operator result is
    cast back to the block dtype at the end — the
    "bf16-input-f32-accumulate" MXU contract.
    """
    rank = len(tile)
    out: dict[str, jnp.ndarray] = {}
    for spec in ops.ops:
        acc = None
        for (axis, rest), taps in sorted(
            tc_axis_groups(spec, rank).items()
        ):
            if len(taps) == 1:
                ((j, c),) = taps
                off = tuple(
                    j if a == axis else rest[a] for a in range(rank)
                )
                sl = (slice(None),) + tuple(
                    slice(radii[a] + off[a], radii[a] + off[a] + tile[a])
                    for a in range(rank)
                )
                term = (
                    jnp.asarray(c, dtype=fblk.dtype) * fblk[sl]
                ).astype(jnp.float32)
            else:
                sl = (slice(None),) + tuple(
                    slice(0, tile[a] + 2 * radii[a]) if a == axis
                    else slice(
                        radii[a] + rest[a],
                        radii[a] + rest[a] + tile[a],
                    )
                    for a in range(rank)
                )
                band = _tc_band(
                    tuple(sorted(taps)), tile[axis], radii[axis],
                    fblk.dtype,
                )
                term = _contract(fblk[sl], band, axis)
            acc = term if acc is None else acc + term
        out[spec.name] = acc.astype(fblk.dtype)
    return out


def _join_rows(blocks):
    """Aux blocks joined along the row axis, in VMEM — the one array φ
    sees whether the plan's aux rows arrived as one operand or several.
    Like :func:`_contract`, dispatches to the static auditor's shadow
    arrays (``shadow_join``) so they can run the same body."""
    if len(blocks) == 1:
        return blocks[0]
    shadow = getattr(blocks[0], "shadow_join", None)
    if shadow is not None:
        return shadow(blocks)
    return jnp.concatenate(blocks, axis=0)


def _zero_lanes(blk, lanes: int):
    """``blk`` with ``lanes`` zero lanes joined on each side of x, in
    registers: the x ghost cells of a zero-ghost window. ``lanes`` is a
    whole number of lane tiles, so the join moves no data. Like
    :func:`_join_rows`, dispatches to the static auditor's shadow
    arrays (``shadow_zero_lanes``)."""
    shadow = getattr(blk, "shadow_zero_lanes", None)
    if shadow is not None:
        return shadow(lanes)
    zeros = jnp.zeros(blk.shape[:-1] + (lanes,), blk.dtype)
    return jnp.concatenate([zeros, blk, zeros], axis=-1)


def _block_derivs_zero_lanes(fblk, ops, origin, tile):
    """:func:`_block_derivs` over a zero-ghost window: the staged rows
    joined with their zero x ghosts, every tap read at ``origin`` (where
    output point 0 sits, :func:`~repro.kernels.plan.zero_ghost_margins`)
    plus its offset."""
    return _block_derivs(_zero_lanes(fblk, origin[-1]), ops, origin, tile)


def _z_loop(n_planes: int, chunk: int, body) -> None:
    """Call ``body(z0)`` over chunks of ``chunk`` planes covering
    ``n_planes`` in one ``fori_loop``. When ``chunk`` does not divide
    ``n_planes`` the last chunk starts at ``n_planes - chunk`` and
    recomputes planes an earlier chunk wrote, with the same values."""
    last = n_planes - chunk

    def step(i, carry):
        body(jnp.minimum(i * chunk, last))
        return carry

    jax.lax.fori_loop(0, -(-n_planes // chunk), step, 0)


def _kernel_pipelined(
    f_ref, *rest, ops, radii, tile, phi, unroll, n_aux_refs,
    derivs_fn=_block_derivs, z_chunk=None,
):
    """Pipelined kernel, any rank. ``rest`` is (*aux_refs, o_ref): one
    ref per aux operand (``n_aux_refs`` of them, 0 for an aux-free
    plan), joined row-wise for φ. ``derivs_fn`` selects the
    tap-evaluation lowering (VPU shifted slices or MXU contractions).

    A ``z_chunk`` below the rank-3 z tile walks the staged block in
    chunks of that many output planes (``plan.z_chunk``), each reading
    its ``z_chunk + 2r`` window planes: the same taps in the same order
    per point, with a body Mosaic unrolls once per chunk, not once per
    tile."""
    aux_refs, o_ref = rest[:n_aux_refs], rest[n_aux_refs]
    if z_chunk is not None and z_chunk < tile[0]:
        sub = (z_chunk,) + tuple(tile[1:])

        def chunk(z0):
            derivs = derivs_fn(
                f_ref[:, pl.ds(z0, z_chunk + 2 * radii[0])],
                ops, radii, sub,
            )
            if aux_refs:
                val = phi(derivs, _join_rows(
                    [r[:, pl.ds(z0, z_chunk)] for r in aux_refs]
                ))
            else:
                val = phi(derivs)
            o_ref[:, pl.ds(z0, z_chunk)] = val

        _z_loop(tile[0], z_chunk, chunk)
        return
    fblk = f_ref[...]
    tx = tile[-1]
    rx = radii[-1]
    for e in range(unroll):  # static: unrolled at trace time
        sub = fblk if unroll == 1 else fblk[..., e * tx : e * tx + tx + 2 * rx]
        derivs = derivs_fn(sub, ops, radii, tile)
        if aux_refs:
            ablk = _join_rows([r[...] for r in aux_refs])
            a_sub = ablk if unroll == 1 else ablk[..., e * tx : (e + 1) * tx]
            val = phi(derivs, a_sub)
        else:
            val = phi(derivs)
        if unroll == 1:
            o_ref[...] = val
        else:
            o_ref[..., e * tx : (e + 1) * tx] = val


def zero_ghost_copies(
    plan: StencilPlan,
) -> tuple[tuple[tuple[int, int, int, int], ...], ...]:
    """The static copies of a zero-ghost launch, on z and on y: per
    axis, the cases ``(first, last, offset, extent)`` — the blocks
    ``first..last`` of that axis all copy ``extent`` planes (rows) of
    the operand into the window at ``offset``, the window's part inside
    the domain. Blocks at a face copy less; the window's rest is zeros.

    Shared by the emitter and the static auditor
    (``repro.analysis.bounds``), like :func:`lowering_windows`."""
    margins = zero_ghost_margins(plan.radii)
    cases = []
    for a in (0, 1):
        t, g, n = plan.block[a], margins[a], plan.interior[a]
        spans: dict[tuple[int, int], tuple[int, int]] = {}
        for b in range(n // t):
            lo, hi = max(0, b * t - g), min(n, b * t + t + g)
            shape = (lo - (b * t - g), hi - lo)
            spans[shape] = (spans.get(shape, (b,))[0], b)
        cases.append(tuple(
            (first, last, off, ext)
            for (off, ext), (first, last) in spans.items()
        ))
    return tuple(cases)


def _kernel_zero_ghost(
    f_hbm, *rest, ops, tile, phi, n_aux_refs, z_chunk, copies, blocks,
    margins,
):
    """Pipelined kernel with zero ghost cells staged in VMEM (rank 3).

    ``f_hbm`` is the UNPADDED operand, left in HBM; ``rest`` is
    (*aux_refs, o_ref, win, sem). Each grid step copies its window's
    part inside the domain (:func:`zero_ghost_copies`, one static copy
    per face case, picked with ``pl.when``) into one slot of the
    double-buffered ``win`` — the copy for the next grid step (z
    innermost) starts before this one computes — and stores zeros over
    the slot's rest: the ghost planes and rows. The x ghosts are zero
    lanes joined in registers (:func:`_block_derivs_zero_lanes`). The
    body is :func:`_kernel_pipelined`'s, reading every tap at the
    window's margins (:func:`~repro.kernels.plan.zero_ghost_margins`)
    plus its offset: the same taps in the same order as over a padded
    operand. ``blocks`` is the grid's (z, y) extent.
    """
    aux_refs, o_ref = rest[:n_aux_refs], rest[n_aux_refs]
    win, sem = rest[n_aux_refs + 1:]
    n_z, n_y = blocks
    j, i = pl.program_id(0), pl.program_id(2)
    t = j * n_z + i
    slot = t % 2
    wrap = i + 1 == n_z
    nxt = (jnp.where(wrap, 0, i + 1), jnp.where(wrap, j + 1, j))

    def each_copy(bz, by, fn):
        for zc in copies[0]:
            for yc in copies[1]:
                pl.when(
                    (bz >= zc[0]) & (bz <= zc[1])
                    & (by >= yc[0]) & (by <= yc[1])
                )(functools.partial(fn, zc, yc))

    def copy(bz, by, s, zc, yc):
        (_, _, oz, ez), (_, _, oy, ey) = zc, yc
        return pltpu.make_async_copy(
            f_hbm.at[
                :,
                pl.ds(bz * tile[0] - margins[0] + oz, ez),
                pl.ds(by * tile[1] - margins[1] + oy, ey),
            ],
            win.at[s, :, pl.ds(oz, ez), pl.ds(oy, ey)],
            sem.at[s],
        )

    def land(zc, yc):
        copy(i, j, slot, zc, yc).wait()
        (_, _, oz, ez), (_, _, oy, ey) = zc, yc
        n_f, wz, wy, wx = win.shape[1:]
        for lo, ext in ((0, oz), (oz + ez, wz - oz - ez)):
            if ext:
                win[slot, :, pl.ds(lo, ext)] = jnp.zeros(
                    (n_f, ext, wy, wx), win.dtype
                )
        for lo, ext in ((0, oy), (oy + ey, wy - oy - ey)):
            if ext:
                win[slot, :, pl.ds(oz, ez), pl.ds(lo, ext)] = jnp.zeros(
                    (n_f, ez, ext, wx), win.dtype
                )

    @pl.when(t == 0)
    def _():
        each_copy(0, 0, lambda zc, yc: copy(0, 0, 0, zc, yc).start())

    @pl.when(t + 1 < n_z * n_y)
    def _():
        each_copy(
            *nxt, lambda zc, yc: copy(*nxt, 1 - slot, zc, yc).start()
        )

    each_copy(i, j, land)
    _kernel_pipelined(
        win.at[slot], *aux_refs, o_ref, ops=ops, radii=margins,
        tile=tile, phi=phi, unroll=1, n_aux_refs=n_aux_refs,
        derivs_fn=_block_derivs_zero_lanes, z_chunk=z_chunk,
    )


def _kernel_tc(f_ref, *rest, ops, radii, tile, phi, n_aux_refs):
    """Depth-1 MXU kernel: the pipelined body with banded-contraction
    tap evaluation (named so tc launches are identifiable in traces)."""
    _kernel_pipelined(
        f_ref, *rest, ops=ops, radii=radii, tile=tile, phi=phi,
        unroll=1, n_aux_refs=n_aux_refs, derivs_fn=_block_derivs_tc,
    )


def _temporal_sweeps(
    cur: jnp.ndarray,
    ops: OperatorSet,
    radii: tuple[int, ...],
    tile: tuple[int, ...],
    phis,
    derivs_fn=_block_derivs,
) -> jnp.ndarray:
    """Apply ``len(phis)`` fused sweeps to one VMEM-resident window.

    ``cur``: (n_f, *(τ_a + 2·r_a·S)) — the tile staged with a halo of
    one radius per sweep. Sweep ``s`` evaluates the operators over the
    window shrunk to a ``r·(S-1-s)`` margin, so the final sweep lands
    exactly on (·, *τ). Intermediate field stacks never leave registers/
    VMEM. No aux carry (the streaming kernel rejects aux); the aux-aware
    variant lives in :func:`_kernel_temporal`. Returns the final tile.
    """
    n_f = cur.shape[0]
    n_steps = len(phis)
    for s, phi in enumerate(phis):  # static: unrolled at trace time
        margin = n_steps - 1 - s
        sub_tile = tuple(t + 2 * r * margin for t, r in zip(tile, radii))
        derivs = derivs_fn(cur, ops, radii, sub_tile)
        val = phi(derivs)
        if margin:
            cur = val[:n_f]
    return val


def _kernel_temporal(
    f_ref, *rest, ops, radii, tile, phis, n_f, n_aux_refs,
    derivs_fn=_block_derivs, z_chunk=None,
):
    """Temporal-fusion kernel, any rank: apply the fused op
    ``len(phis)`` times on one VMEM-resident block staged with a
    ``radii * fuse_steps`` halo. Each sweep's valid region shrinks by
    one radius per axis; intermediate field stacks (and carries) stay
    on-chip — only the final tile is written back to HBM.

    ``rest`` is (*aux_refs, o_ref), one ref per aux operand
    (``n_aux_refs``, 0 for an aux-free plan), joined row-wise into one
    carry. The staged aux window is ``tile + 2r(S-1)`` so every
    intermediate sweep sees a point-wise-aligned carry. The aux-free
    case delegates to :func:`_temporal_sweeps` (shared with the
    streaming kernel) so the sweep-shrinking arithmetic lives once.

    A ``z_chunk`` below the z tile (``plan.z_chunk``: depth 2, aux-free,
    rank 3) runs each sweep as a loop over chunks of that many planes:
    sweep 1 writes the ``tile + 2r`` intermediate generation to the VMEM
    scratch ref that then follows ``o_ref`` in ``rest``, and sweep 2
    reads it back — the same taps in the same order per point.
    """
    aux_refs, o_ref = rest[:n_aux_refs], rest[n_aux_refs]
    if z_chunk is not None and z_chunk < tile[0]:
        (mid_ref,) = rest[n_aux_refs + 1:]
        mid = tuple(t + 2 * r for t, r in zip(tile, radii))

        def sweep(src, dst, phi, ext, n_planes):
            def chunk(z0):
                val = phi(derivs_fn(
                    src[:, pl.ds(z0, z_chunk + 2 * radii[0])],
                    ops, radii, (z_chunk,) + tuple(ext),
                ))
                dst[:, pl.ds(z0, z_chunk)] = val[: dst.shape[0]]

            _z_loop(n_planes, z_chunk, chunk)

        first, last = phis
        sweep(f_ref, mid_ref, first, mid[1:], mid[0])
        sweep(mid_ref, o_ref, last, tile[1:], tile[0])
        return
    if not aux_refs:
        o_ref[...] = _temporal_sweeps(
            f_ref[...], ops, radii, tile, phis, derivs_fn=derivs_fn
        )
        return
    n_steps = len(phis)
    cur = f_ref[...]
    # The staged aux block may be tile-aligned past the r·(S-1)-widened
    # window (compat.element_window_spec): read the window only.
    window = (slice(None),) + tuple(
        slice(0, t + 2 * r * (n_steps - 1)) for t, r in zip(tile, radii)
    )
    cur_aux = _join_rows([r[window] for r in aux_refs])
    for s, phi in enumerate(phis):  # static: unrolled at trace time
        margin = n_steps - 1 - s  # sweeps remaining after this one
        sub_tile = tuple(
            t + 2 * r * margin for t, r in zip(tile, radii)
        )
        derivs = derivs_fn(cur, ops, radii, sub_tile)
        val = phi(derivs, cur_aux)
        if margin == 0:
            o_ref[...] = val
        else:
            cur = val[:n_f]
            n_aux = cur_aux.shape[0]
            cur_aux = val[n_f : n_f + n_aux][
                (slice(None),)
                + tuple(
                    slice(r, r + t + 2 * r * (margin - 1))
                    for t, r in zip(tile, radii)
                )
            ]


def _member_phi(phi, batch: int, n_f: int, n_aux: int):
    """Wrap a single-member φ for a member-major flattened ensemble.

    The batched lowering stacks B members along the leading field axis
    (rows ``m·n_f .. (m+1)·n_f`` belong to member ``m``), so every
    kernel body stays batch-oblivious: taps vectorize over the B·n_f
    rows, and only the point-wise φ needs to know member boundaries.
    The wrapper slices each member's derivative rows (and aux rows, if
    any), applies φ per member in a static Python loop (unrolled at
    trace time), and re-concatenates outputs member-major.
    """

    def wrapped(derivs, aux=None):
        outs = []
        for m in range(batch):  # static: unrolled at trace time
            d_m = {
                k: v[m * n_f : (m + 1) * n_f] for k, v in derivs.items()
            }
            if aux is None:
                outs.append(phi(d_m))
            else:
                outs.append(phi(d_m, aux[m * n_aux : (m + 1) * n_aux]))
        return jnp.concatenate(outs, axis=0)

    return wrapped


def _fused_batched(
    f_padded, ops, phis, plan: StencilPlan, *, aux, interpret
):
    """Lower a batched (ensemble) plan: one kernel walks all B members
    per block instead of B independent launches. ``aux`` is the tuple
    of aux operands, empty for an aux-free plan.

    Members are flattened member-major onto the field axis —
    (B, n_f, *sp) → (B·n_f, *sp) — so the staged input window (and its
    halo fetch) is shared by the whole ensemble: the per-launch-step
    pipeline/prologue cost is paid once per block, not once per member.
    Each φ is wrapped by :func:`_member_phi` and the plan is re-derived
    with ``batch=1`` and B-scaled field counts, so the pipelined,
    temporal and streaming kernel bodies all serve ensembles unchanged.
    Member-major rows stay aligned across temporal sweeps because
    depth > 1 requires per-member ``n_out == n_f`` (aux carries with
    batching are rejected at plan level). Returns
    (batch, n_out, *interior).
    """
    b = plan.batch
    if f_padded.shape[:2] != (b, plan.n_f):
        raise ValueError(
            f"batched operand must be (batch, n_f, *spatial) = "
            f"({b}, {plan.n_f}, ...), got shape {f_padded.shape}"
        )
    flat = f_padded.reshape((b * plan.n_f,) + f_padded.shape[2:])
    aux_flat = None
    if aux:
        # Member-major flattening needs each member's aux rows adjacent,
        # so several aux operands are stacked here, in HBM.
        aux = jnp.concatenate(aux, axis=1) if len(aux) > 1 else aux[0]
        if aux.shape[:2] != (b, plan.n_aux):
            raise ValueError(
                f"batched aux must be (batch, n_aux, *spatial) = "
                f"({b}, {plan.n_aux}, ...), got shape {aux.shape}"
            )
        aux_flat = aux.reshape((b * plan.n_aux,) + aux.shape[2:])
    wrapped = tuple(
        _member_phi(p, b, plan.n_f, plan.n_aux) for p in phis
    )
    derived = dataclasses.replace(
        plan, batch=1, n_f=b * plan.n_f, n_out=b * plan.n_out,
        n_aux=b * plan.n_aux,
    )
    out = fused_stencil_pallas(
        flat, ops, wrapped, derived, aux=aux_flat, interpret=interpret
    )
    return out.reshape((b, plan.n_out) + plan.interior)


def lowering_windows(plan: StencilPlan) -> dict[str, tuple[int, ...]]:
    """Static per-grid-step extents of the pipelined lowering — the ONE
    derivation shared by :func:`fused_stencil_pallas` (which turns them
    into BlockSpecs) and the static auditor ``repro.analysis`` (which
    instantiates shadow refs of exactly these shapes), so the audited
    geometry can never diverge from the emitted one.

    Returns spatial extents (no field axis): ``window`` — the staged
    input block (halo-widened, x spanning all ``unroll`` sub-tiles);
    ``out_tile`` — the output block; ``aux_window`` — the staged aux
    block (``None`` for aux-free plans): halo-free at depth 1, widened
    by ``r·(S-1)`` per axis at temporal depth ``S > 1``; ``mid`` — the
    VMEM scratch a z-chunked temporal kernel keeps its intermediate
    generation in (``tile + 2r``; ``None`` unless ``plan.z_chunk`` is
    below the z tile at depth > 1); ``margins`` — where output point 0
    sits in a zero-ghost window (``None`` for other plans).

    A zero-ghost plan's ``window`` is the slot its kernel stages
    (:func:`~repro.kernels.plan.zero_ghost_window`).
    """
    radii, tile = plan.radii, plan.block
    window = tuple(
        (plan.x_step if a == plan.rank - 1 else tile[a]) + 2 * h
        for a, h in enumerate(plan.halo)
    )
    margins = None
    if plan.ghost == "zero":
        window = zero_ghost_window(tile, radii)
        margins = zero_ghost_margins(radii)
    out_tile = tile[:-1] + (plan.x_step,)
    aux_window: tuple[int, ...] | None = None
    if plan.n_aux:
        if plan.fuse_steps == 1:
            aux_window = out_tile
        else:
            aux_window = tuple(
                t + 2 * r * (plan.fuse_steps - 1)
                for t, r in zip(tile, radii)
            )
    mid = None
    if plan.fuse_steps > 1 and plan.z_chunk < tile[0]:
        mid = tuple(
            t + 2 * r * (plan.fuse_steps - 1) for t, r in zip(tile, radii)
        )
    return {
        "window": window, "out_tile": out_tile, "aux_window": aux_window,
        "mid": mid, "margins": margins,
    }


def stream_extents(plan: StencilPlan) -> dict[str, tuple[int, ...] | int]:
    """Static scratch extents of the explicit-streaming lowering —
    shared by :func:`_fused_stream` (VMEM scratch allocation) and the
    auditor's shadow run, like :func:`lowering_windows` for the
    pipelined path. Spatial extents only (``work``/``prefetch``/
    ``outbuf``, the staged ``cross`` window and the ``padded`` operand
    the DMAs address), plus the stream chunk count ``n_chunks``.
    """
    tile, halo = plan.block, plan.halo
    # A DMA slice must be tile-aligned on the (sublane, lane) axes, so
    # the cross-stream window is staged aligned, and the operand is
    # padded on its high side until the last window fits (``padded``).
    cross = staged_window(
        tuple(t + 2 * h for t, h in zip(tile[1:], halo[1:]))
    )
    padded = (plan.interior[0] + 2 * halo[0],) + tuple(
        n - t + w for n, t, w in zip(plan.interior[1:], tile[1:], cross)
    )
    return {
        "work": (tile[0] + 2 * halo[0],) + cross,
        "prefetch": (tile[0],) + cross,
        "outbuf": tile,
        "cross": cross,
        "padded": padded,
        "n_chunks": plan.interior[0] // tile[0],
    }


def _grid_and_maps(plan: StencilPlan):
    """Grid extents and (input, tile-indexed) index maps per rank.

    The input map returns *element* offsets on the window (spatial)
    dims; the tile map returns block indices for halo-free operands
    (aux, output). At rank 3 the grid iterates (y, x, z) with z
    innermost so the pipeline's next-block prefetch walks the z-stream.
    """
    steps = plan.block[:-1] + (plan.x_step,)
    grid_n = plan.grid
    if plan.rank == 1:
        (sx,) = steps
        return (
            grid_n,
            lambda i: (0, i * sx),
            lambda i: (0, i),
        )
    if plan.rank == 2:
        sy, sx = steps
        return (
            grid_n,
            lambda i, j: (0, i * sy, j * sx),
            lambda i, j: (0, i, j),
        )
    sz, sy, sx = steps
    return (
        (grid_n[1], grid_n[2], grid_n[0]),
        lambda j, k, i: (0, i * sz, j * sy, k * sx),
        lambda j, k, i: (0, i, j, k),
    )


def fused_stencil_pallas(
    f_padded: jnp.ndarray,
    ops: OperatorSet,
    phi: Callable[..., jnp.ndarray],
    plan: StencilPlan,
    *,
    aux: jnp.ndarray | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Emit and invoke the fused φ(A·B) kernel described by ``plan``.

    ``f_padded``: (n_f, *(n_a + 2r_a·fuse_steps)) with radii from the
    plan. ``aux`` — extra point-wise inputs passed as phi's second
    argument, fusing point-wise follow-up work (e.g. the RK axpy) into
    the stencil kernel: (n_aux, *interior) at depth 1 (staged as
    halo-free center tiles), (n_aux, *(interior + 2r(S-1))) at temporal
    depth S > 1 (staged as overlapping windows so intermediate sweeps
    see an aligned carry). ``aux`` may also be a tuple of such arrays
    whose rows sum to ``plan.n_aux``: each is then its own operand with
    its own BlockSpec, and the blocks are joined row-wise only in VMEM,
    so arrays that live apart in HBM (a level that changes every step
    beside coefficient fields that never change) are never stacked
    there. φ sees the same (n_aux, ...) rows either way. ``phi`` may be
    a sequence of ``fuse_steps`` callables (one per fused sweep).
    Returns (n_out, *interior).

    A zero-ghost plan (``plan.ghost == "zero"``) takes ``f_padded``
    UNPADDED, (n_f, *interior): its kernel fills the zero ghost cells
    itself (:func:`_fused_zero_ghost`).

    When ``plan.batch > 1`` the operands grow a leading ensemble axis —
    ``f_padded`` (batch, n_f, *padded), ``aux`` (batch, n_aux, ...) —
    and one kernel walks all members per block (member-major field
    rows, shared halo window; see :func:`_fused_batched`). Returns
    (batch, n_out, *interior).
    """
    aux_ops = () if aux is None else (
        tuple(aux) if isinstance(aux, (tuple, list)) else (aux,)
    )
    row_axis = 1 if f_padded.ndim == plan.rank + 2 else 0
    aux_rows = sum(a.shape[row_axis] for a in aux_ops)
    if aux_rows != plan.n_aux:
        raise ValueError(
            f"aux operands carry {aux_rows} rows, plan.n_aux is "
            f"{plan.n_aux}"
        )
    phis = (
        tuple(phi)
        if isinstance(phi, (tuple, list))
        else (phi,) * plan.fuse_steps
    )
    if len(phis) != plan.fuse_steps:
        raise ValueError(
            f"got {len(phis)} phi callables for plan with "
            f"fuse_steps={plan.fuse_steps}"
        )
    if plan.batch > 1 or row_axis:
        return _fused_batched(
            f_padded, ops, phis, plan, aux=aux_ops, interpret=interpret
        )
    if plan.strategy == "swc_stream":
        return _fused_stream(
            f_padded, ops, phis, plan, interpret=interpret
        )
    if plan.ghost == "zero":
        return _fused_zero_ghost(
            f_padded, ops, phis[0], plan, aux=aux_ops, interpret=interpret
        )

    radii, tile = plan.radii, plan.block
    windows = lowering_windows(plan)
    window = windows["window"]
    out_tile = windows["out_tile"]
    grid, in_map, tile_map = _grid_and_maps(plan)
    in_specs = [
        element_window_spec(
            (plan.n_f,) + window,
            in_map,
            window_dims=tuple(range(1, plan.rank + 1)),
        )
    ]
    operands = [f_padded]
    for a in aux_ops:
        rows = (a.shape[0],) + windows["aux_window"]
        if plan.fuse_steps == 1:
            in_specs.append(pl.BlockSpec(rows, tile_map))
        else:
            in_specs.append(
                element_window_spec(
                    rows, in_map,
                    window_dims=tuple(range(1, plan.rank + 1)),
                )
            )
        operands.append(a)
    tc = plan.strategy == "tc"
    z_chunk = plan.z_chunk
    chunked = z_chunk < tile[0]
    scratch = []
    if plan.fuse_steps > 1:
        name = "stencil_temporal"
        kernel = functools.partial(
            _kernel_temporal, ops=ops, radii=radii, tile=tile,
            phis=phis, n_f=plan.n_f, n_aux_refs=len(aux_ops),
            derivs_fn=_block_derivs_tc if tc else _block_derivs,
            z_chunk=z_chunk if chunked else None,
        )
        if chunked:
            scratch = [pltpu.VMEM(
                (plan.n_f,) + windows["mid"], f_padded.dtype
            )]
    elif tc:
        name = "stencil_tc"
        kernel = functools.partial(
            _kernel_tc, ops=ops, radii=radii, tile=tile,
            phi=phis[0], n_aux_refs=len(aux_ops),
        )
    else:
        name = "stencil_pipelined"
        kernel = functools.partial(
            _kernel_pipelined, ops=ops, radii=radii, tile=tile,
            phi=phis[0], unroll=plan.unroll, n_aux_refs=len(aux_ops),
            z_chunk=z_chunk if chunked else None,
        )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((plan.n_out,) + out_tile, tile_map),
        out_shape=jax.ShapeDtypeStruct(
            (plan.n_out,) + plan.interior, f_padded.dtype
        ),
        scratch_shapes=scratch,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=name,
    )(*operands)


def _fused_zero_ghost(
    f, ops, phi, plan: StencilPlan, *, aux, interpret: bool = False
):
    """Lower a zero-ghost plan: ``f`` is the unpadded (n_f, *interior)
    operand, handed to :func:`_kernel_zero_ghost` whole and in HBM
    (``pl.ANY``) — one operand, copied window by window by the kernel.
    The aux operands and the output keep the pipelined path's halo-free
    BlockSpecs. The grid runs in order ("arbitrary"), since each step
    starts the copy for the next."""
    if tuple(f.shape) != (plan.n_f,) + plan.interior:
        raise ValueError(
            f"a zero-ghost plan takes the unpadded operand "
            f"{(plan.n_f,) + plan.interior}, got shape {f.shape}"
        )
    windows = lowering_windows(plan)
    out_tile = windows["out_tile"]
    grid, _, tile_map = _grid_and_maps(plan)
    z_chunk = plan.z_chunk
    kernel = functools.partial(
        _kernel_zero_ghost, ops=ops, tile=plan.block, phi=phi,
        n_aux_refs=len(aux),
        z_chunk=z_chunk if z_chunk < plan.block[0] else None,
        copies=zero_ghost_copies(plan), blocks=(grid[2], grid[0]),
        margins=windows["margins"],
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] + [
            pl.BlockSpec((a.shape[0],) + out_tile, tile_map) for a in aux
        ],
        out_specs=pl.BlockSpec((plan.n_out,) + out_tile, tile_map),
        out_shape=jax.ShapeDtypeStruct(
            (plan.n_out,) + plan.interior, f.dtype
        ),
        scratch_shapes=[
            pltpu.VMEM((2, plan.n_f) + windows["window"], f.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
            dimension_semantics=("arbitrary",) * 3,
        ),
        interpret=interpret,
        name="stencil_pipelined",
    )(f, *aux)


# ---------------------------------------------------------------------------
# Fig. 5b: explicit streaming along the slowest axis with carried halo
# planes + prefetch DMA (rank-2/3 plans; plan.strategy == "swc_stream").
# Temporal fusion composes: the carried halo widens to 2·r·fuse_steps
# planes and each chunk runs the fused sweeps on the working set.
# ---------------------------------------------------------------------------


def _kernel_stream(
    f_hbm, o_hbm, work, pf0, pf1, outbuf, sem_pf, sem_out, *,
    ops, radii, tile, phis, n_chunks, cross,
):
    """Grid step = one cross-stream tile; the kernel streams all chunks
    of the slowest axis (z at rank 3, y at rank 2) through VMEM.

    With ``h_a = r_a · S`` (one radius of halo per fused sweep,
    ``S = len(phis)``), the VMEM scratch is:

      ``work``  (n_f, τ₀+2h₀, *(τ_a+2h_a)) — the working set; the
                leading 2h₀ planes are the halo carried chunk to chunk
                (the circular-buffer trick, unrolled as a plane copy);
      ``pf0/1`` (n_f, τ₀, *(τ_a+2h_a)) — double-buffered prefetch of
                the τ₀ fresh planes for the next chunk;
      ``outbuf``(n_out, *τ) — staging for the output DMA.

    Each chunk applies the ``S`` fused sweeps of
    :func:`_temporal_sweeps` to the working set (valid region shrinking
    one radius per sweep on every axis, including the stream axis), so
    streaming and temporal fusion compose in one kernel.
    """
    rank = len(tile)
    halo = tuple(r * len(phis) for r in radii)
    ts, hs = tile[0], halo[0]
    cross_off = tuple(
        pl.program_id(i) * tile[1 + i] for i in range(rank - 1)
    )
    cross_halo = tuple(pl.ds(o, w) for o, w in zip(cross_off, cross))
    cross_tile = tuple(
        pl.ds(o, t) for o, t in zip(cross_off, tile[1:])
    )

    def fresh_copy(chunk, pf_ref, slot):
        """DMA the τ₀ fresh planes of ``chunk`` into a prefetch buffer."""
        return pltpu.make_async_copy(
            f_hbm.at[
                (slice(None), pl.ds(chunk * ts + 2 * hs, ts)) + cross_halo
            ],
            pf_ref,
            sem_pf.at[slot],
        )

    # Prologue: leading halo planes go straight into the working buffer;
    # chunk 0's fresh planes start streaming into prefetch slot 0.
    halo_cp = pltpu.make_async_copy(
        f_hbm.at[(slice(None), pl.ds(0, 2 * hs)) + cross_halo],
        work.at[:, pl.ds(0, 2 * hs)],
        sem_out,  # reuse; waited below before any compute
    )
    halo_cp.start()
    fresh_copy(0, pf0, 0).start()
    halo_cp.wait()

    def body(chunk, _):
        slot = jax.lax.rem(chunk, 2)

        # Kick off the NEXT chunk's fresh-plane DMA before computing this
        # one (the paper's "prefetch buffer updated in parallel with
        # computations").
        @pl.when(chunk + 1 < n_chunks)
        def _():
            @pl.when(slot == 0)
            def _():
                fresh_copy(chunk + 1, pf1, 1).start()

            @pl.when(slot == 1)
            def _():
                fresh_copy(chunk + 1, pf0, 0).start()

        # Land this chunk's fresh planes behind the carried halo.
        @pl.when(slot == 0)
        def _():
            fresh_copy(chunk, pf0, 0).wait()
            work[:, pl.ds(2 * hs, ts)] = pf0[...]

        @pl.when(slot == 1)
        def _():
            fresh_copy(chunk, pf1, 1).wait()
            work[:, pl.ds(2 * hs, ts)] = pf1[...]

        outbuf[...] = _temporal_sweeps(work[...], ops, radii, tile, phis)
        out_cp = pltpu.make_async_copy(
            outbuf,
            o_hbm.at[(slice(None), pl.ds(chunk * ts, ts)) + cross_tile],
            sem_out,
        )
        out_cp.start()

        # Carry the trailing halo: the last 2h₀ planes become the next
        # chunk's leading halo (VMEM-to-VMEM plane copy; see module
        # docstring on why TPU prefers this over the circular buffer).
        work[:, pl.ds(0, 2 * hs)] = work[:, pl.ds(ts, 2 * hs)]
        out_cp.wait()
        return 0

    jax.lax.fori_loop(0, n_chunks, body, 0)


def _fused_stream(
    f_padded, ops, phis, plan: StencilPlan, *, interpret: bool = False
):
    """Lower an ``swc_stream`` plan (rank 2 or 3, any fuse depth)."""
    tile = plan.block
    ext = stream_extents(plan)
    dtype = f_padded.dtype

    kernel = functools.partial(
        _kernel_stream, ops=ops, radii=plan.radii, tile=tile,
        phis=phis, n_chunks=ext["n_chunks"], cross=ext["cross"],
    )
    grow = [(0, 0)] + [
        (0, want - have)
        for want, have in zip(ext["padded"], f_padded.shape[1:])
    ]
    if any(hi for _, hi in grow):
        f_padded = jnp.pad(f_padded, grow)
    return pl.pallas_call(
        kernel,
        grid=tuple(n // t for n, t in zip(plan.interior[1:], tile[1:])),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(
            (plan.n_out,) + plan.interior, dtype
        ),
        scratch_shapes=[
            pltpu.VMEM((plan.n_f,) + ext["work"], dtype),
            pltpu.VMEM((plan.n_f,) + ext["prefetch"], dtype),
            pltpu.VMEM((plan.n_f,) + ext["prefetch"], dtype),
            pltpu.VMEM((plan.n_out,) + ext["outbuf"], dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="stencil_stream",
    )(f_padded)

"""Structural cost model + measurement protocol — the paper's Sec. 5.1
tuning search on TPU terms.

The paper tunes thread-block dimensions (τx, τy, τz) with a pruned
heuristic search: τx a multiple of the L2-line/word ratio, total threads
a multiple of warp size, invalid launches discarded, 3-iteration timing,
best picked. The TPU analogues (DESIGN.md §2):

* τx multiple of the 128-wide lane dimension (vector register width),
* the VMEM working set must fit the per-core VMEM budget (invalid
  "launches" = blocks that exceed VMEM → discarded *statically*),
* per-candidate timing = warm-up + median of k timed calls.

Additionally a *structural* cost model ranks candidates without hardware
— used on CPU-only containers and as a search-space pruner on real TPUs
(napkin math first, measurement second). The persistent layer on top of
this module lives in ``repro.tuning.cache`` / ``repro.tuning.session``.
"""
from __future__ import annotations

import dataclasses
import itertools
import logging
import math
import time
from typing import Callable, Iterable, Sequence

import jax
import numpy as np

from repro.core.trafficmodel import (
    peak_hbm_bw,
    peak_mxu_flops,
    peak_vpu_flops,
    stencil_batched_hbm_bytes_per_member_step,
    stencil_hbm_bytes_per_step,
    stencil_mxu_flops_per_step,
    stencil_redundant_compute_fraction,
    stencil_stream_hbm_bytes_per_step,
)
from repro.kernels.plan import (
    LANE,
    SUBLANE,
    TC_MAX_TILE,
    VMEM_LIMIT_BYTES,
    staged_window,
    tpu_tile_ok,
)

log = logging.getLogger("repro.tuning")

# VMEM the staged blocks of one candidate may take (bytes): an eighth
# of the scoped limit the emitter hands Mosaic, leaving the rest for
# φ's temporaries, which this model does not count.
VMEM_BUDGET = VMEM_LIMIT_BYTES // 8


@dataclasses.dataclass(frozen=True)
class Candidate:
    block: tuple[int, ...]  # rank-length tile, x last
    vmem_bytes: int
    halo_overhead: float  # redundant-fetch fraction vs perfect reuse
    score: float  # structural cost-model score (lower = better)
    fuse_steps: int = 1  # temporal fusion depth of this candidate
    # True for explicit-streaming (swc_stream) configurations: the
    # slowest axis is streamed with carried halo planes, so the traffic
    # and VMEM terms use the streaming model.
    stream: bool = False
    # Caching regime this candidate lowers through ("hwc" | "swc" |
    # "swc_stream" | "tc") — the cross-strategy "auto" search mixes
    # them in one ranked space, and the tuning record persists the
    # winner.
    strategy: str = "swc"


# Weight of redundant halo *compute* against saved HBM traffic in the
# temporal score. Stencils are bandwidth-bound on both paper targets
# (and on TPU: ~1 FLOP/byte stencil intensity vs ~100 machine balance),
# so recomputed halo points cost far less than re-fetched ones; the
# weight is the modeled compute-time share of a balanced fused kernel.
# Calibration: the paper's 3-D order-6 diffusion step (38 flops/point,
# 8 compulsory bytes/point, v5e peaks) gives
# (38/24.625e12)/(8/819e9) ≈ 0.158 — which is what
# :func:`temporal_compute_weight` reproduces from first principles for
# any tap count when the caller supplies ``flops_per_point``; this
# constant is the fixed fallback for hand-built operator sets that
# don't report one.
TEMPORAL_COMPUTE_WEIGHT = 0.15


def temporal_compute_weight(
    flops_per_point: float | None,
    n_f: int,
    n_out: int,
    itemsize: int,
    backend: str | None = None,
) -> float:
    """Per-order compute weight of the temporal score: the ratio of a
    point's VPU time (``flops_per_point / peak_vpu``) to its compulsory
    HBM time (``(n_f + n_out)·itemsize / peak_bw``) — the fraction of
    the bandwidth roof one redundantly recomputed point costs.

    This is how the operator's accuracy order reaches the strategy
    ranking: an order-2 set (few taps) weighs halo recompute lightly
    and fuses deep, an order-8 set (≈4× the taps) pays ≈4× more per
    recomputed point and the model backs off the depth. Falls back to
    :data:`TEMPORAL_COMPUTE_WEIGHT` when ``flops_per_point`` is None
    (hand-built taps with no operator metadata).
    """
    if flops_per_point is None:
        return TEMPORAL_COMPUTE_WEIGHT
    hbm_time = (n_f + n_out) * itemsize / peak_hbm_bw(backend)
    return (flops_per_point / peak_vpu_flops(backend)) / hbm_time


def vmem_working_set(
    block: Sequence[int],
    radii: Sequence[int],
    n_f: int,
    n_out: int,
    itemsize: int,
    fuse_steps: int = 1,
    stream: bool = False,
    *,
    batch: int = 1,
    unroll: int = 1,
    n_aux: int = 0,
) -> int:
    """VMEM footprint of one block, any rank. Temporal fusion widens
    the staged window to ``radii * fuse_steps`` and holds one
    intermediate field generation on-chip between sweeps.

    ``stream=True`` models the explicit-streaming kernel's scratch
    instead: the working buffer (tile + widened halo on every axis),
    two prefetch buffers (τ₀ fresh planes × the cross window), and the
    output staging tile — the shapes ``emit._fused_stream`` allocates.

    ``batch`` is the ensemble extent of a batched launch: the member-
    major lowering stages all B members' field rows in one window, so
    every field-count term scales by B — which is why the batched
    candidate enumeration picks smaller blocks at larger B.

    Halo windows count at the tile-aligned extents Mosaic stages
    (:func:`~repro.kernels.plan.staged_window`): the lane axis rounded
    up to 128 and the sublane axis to 8.

    ``unroll`` is the element-wise unroll factor of a pipelined plan:
    the staged window and output tile span all ``unroll`` x sub-tiles
    per grid step (``τx·unroll + 2r`` / ``τx·unroll``), so an unrolled
    block is NOT the footprint of its base block — before this term
    the model under-counted unrolled plans by nearly ``unroll``×.
    ``n_aux`` counts point-wise aux operands, staged (and, like every
    pipelined input, double-buffered) as a halo-free tile at depth 1
    and an ``r·(S-1)``-widened window at temporal depth S. Streaming
    plans reject both (plan validation), so the kwargs are ignored for
    ``stream=True``. The shapes here mirror
    ``emit.lowering_windows``/``emit.stream_extents`` — the fidelity
    contract ``repro.analysis.vmem`` checks per lowerable plan.
    """
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    block, radii = tuple(block), tuple(radii)
    n_f = n_f * batch
    n_out = n_out * batch
    n_aux = n_aux * batch
    last = len(block) - 1
    steps = tuple(
        t * unroll if a == last and not stream else t
        for a, t in enumerate(block)
    )
    halo_win = tuple(
        s + 2 * r * fuse_steps for s, r in zip(steps, radii)
    )
    carry_win = tuple(
        t + 2 * r * (fuse_steps - 1) for t, r in zip(block, radii)
    )
    mid = (n_f if fuse_steps > 1 else 0) * math.prod(carry_win)
    out = n_out * math.prod(steps)
    if stream:
        cross = staged_window(halo_win[1:])
        work = n_f * halo_win[0] * math.prod(cross)
        pf = n_f * block[0] * math.prod(cross)
        return (work + 2 * pf + mid + out) * itemsize
    # Halo windows are staged tile-aligned (plan.staged_window); a
    # depth-1 aux operand is a halo-free output-shaped tile.
    inp = n_f * math.prod(staged_window(halo_win))
    aux = n_aux * (
        math.prod(steps) if fuse_steps == 1
        else math.prod(staged_window(carry_win))
    )
    # Pallas double-buffers pipelined input blocks: 2x input (and aux).
    return (2 * inp + 2 * aux + mid + out) * itemsize


def halo_overhead(
    block: Sequence[int],
    radii: Sequence[int],
    fuse_steps: int = 1,
) -> float:
    """Redundant-fetch fraction of one staged block vs perfect reuse.

    Guard (tiny blocks × anisotropic radii, fused depths only): when a
    fused sweep's valid region — which shrinks by one radius per step —
    would hit zero/negative interior volume on some axis
    (``t <= 2·r·fuse_steps`` with ``fuse_steps > 1``), the
    configuration is all overhead, so the score is ``inf`` and
    enumeration excludes the candidate instead of ranking it on a
    misleading finite value. At depth 1 nothing shrinks, so small tiles
    keep their (finite, merely large) overhead.
    """
    fetched, useful = 1, 1
    for t, r in zip(block, radii):
        if fuse_steps > 1 and t <= 2 * r * fuse_steps:
            return math.inf
        fetched *= t + 2 * r * fuse_steps
        useful *= t
    return fetched / useful - 1.0


def enumerate_candidates_nd(
    domain: Sequence[int],
    radii: Sequence[int],
    n_f: int,
    n_out: int,
    itemsize: int = 4,
    *,
    vmem_budget: int = VMEM_BUDGET,
    axis_options: Sequence[Sequence[int]] | None = None,
    fuse_steps_options: Sequence[int] = (1,),
    stream_options: Sequence[bool] = (False,),
    tc_options: Sequence[bool] = (False,),
    tc_groups: Sequence[int] | None = None,
    backend: str | None = None,
    batch: int = 1,
    flops_per_point: float | None = None,
) -> list[Candidate]:
    """Generate, filter (divisibility + Mosaic tile layout + VMEM + the tiny-block guard),
    and rank (block, fuse_steps, stream) configurations for a
    rank-1/2/3 domain (the planner's search space — blocks are listed
    in axis order, x last). ``axis_options`` overrides the per-axis
    tile bases (same order); ``fuse_steps_options`` widens the sweep to
    temporal fusion depths, and ``stream_options`` to the explicit-
    streaming kernel (rank ≥ 2 only — the entry is skipped at rank 1),
    all scored jointly.

    The score is a roofline-flavored sum of the modeled per-step HBM
    traffic (via ``core.trafficmodel.stencil_hbm_bytes_per_step``, or
    its ``stencil_stream_hbm_bytes_per_step`` sibling for streaming
    candidates — the carried halo planes eliminate the stream-axis halo
    re-fetch, which is why a streaming candidate can out-score every
    pipelined block), normalized to the compulsory read+write of the
    interior, plus the weighted redundant-halo compute a fused depth
    re-evaluates, with mild penalties for lane-misaligned x tiles, very
    small stream-axis tiles (per-chunk/pipeline bubble), and — at rank
    1, where the grid-step count is the only parallel axis — short
    blocks that don't amortize the per-step pipeline overhead. Lower is
    better.

    ``batch > 1`` models a batched ensemble launch: the VMEM filter
    scales every field-count term by B (so larger ensembles admit only
    smaller blocks) and the traffic term switches to the per-member
    batched model, which amortizes the fixed per-launch overhead over
    B·fuse_steps — different B therefore rank (and admit) different
    blocks/depths, which is why ``batch`` joins the tuning key.

    ``tc_options`` adds matrix-unit (``tc``) candidates: same staging
    and traffic model as pipelined ``swc``, but scored on
    ``max(traffic_time, mxu_time)`` — a genuine two-resource roofline
    instead of the scalar :data:`TEMPORAL_COMPUTE_WEIGHT` hack, because
    the MXU work of a banded contraction grows with the tile extent and
    really can dominate. The MXU term normalizes the modeled FLOPs
    (``stencil_mxu_flops_per_step`` with ``tc_groups`` matmul groups
    per axis, peak rates looked up for ``backend``) against the same
    ideal-traffic denominator the traffic score uses, so the two sides
    of the ``max`` are in the same unit. tc candidates are skipped for
    8-byte dtypes (no f64 MXU path) and for tiles beyond
    ``TC_MAX_TILE`` on any axis (the contraction extent — and with it
    the per-point FLOPs — grows with the tile).

    ``flops_per_point`` is the operator set's VPU work per grid point
    (``OperatorSet.flops_per_point(n_f)`` — 2 FLOPs per tap per field):
    when given, the temporal redundancy weight is derived from it per
    order via :func:`temporal_compute_weight`, so ``strategy="auto"``
    re-ranks depths as the tap count grows with the accuracy order;
    when None the fixed :data:`TEMPORAL_COMPUTE_WEIGHT` applies.
    """
    domain = tuple(domain)
    compute_weight = temporal_compute_weight(
        flops_per_point, n_f, n_out, itemsize, backend
    )
    rank = len(domain)
    if axis_options is None:
        axis_options = axis_tile_options(domain)
    points = 1
    for n in domain:
        points *= n
    ideal_bytes = (n_f + n_out) * points * itemsize  # compulsory traffic
    out: list[Candidate] = []
    regimes: list[str] = []
    for stream in stream_options:
        if stream and rank < 2:
            continue  # streaming needs a cross-stream tile axis
        regimes.append("swc_stream" if stream else "swc")
    for tc in tc_options:
        # No MXU path for 8-byte dtypes (f32/bf16-input-f32-accumulate
        # only — mirrors StencilPlan validation).
        if tc and itemsize in (2, 4) and "tc" not in regimes:
            regimes.append("tc")
    for regime in regimes:
        stream = regime == "swc_stream"
        tc = regime == "tc"
        for fuse in fuse_steps_options:
            for raw in itertools.product(*axis_options):
                blk = []
                ok = True
                for n, t in zip(domain, raw):
                    if n % t and t != n:
                        ok = False
                        break
                    blk.append(min(t, n))
                if not ok:
                    continue
                blk = tuple(blk)
                if not tpu_tile_ok(blk, domain):
                    continue  # Mosaic rejects the tile's layout
                if tc and any(t > TC_MAX_TILE for t in blk):
                    continue  # contraction extent (→ FLOPs) unbounded
                if stream and fuse > 1 and (
                    domain[0] < 2 * radii[0] * fuse + blk[0]
                ):
                    # The fused stream walk needs the stream-axis extent
                    # to hold the carried halo (2·r·S planes) plus one
                    # chunk — the same bound StencilPlan validates.
                    continue
                ho = halo_overhead(blk, radii, fuse)
                if not math.isfinite(ho):
                    continue  # tile swallowed by its widened halo
                vm = vmem_working_set(
                    blk, radii, n_f, n_out, itemsize, fuse, stream,
                    batch=batch,
                )
                if vm > vmem_budget:
                    continue  # the "failed launch" discard
                if batch == 1:
                    traffic_fn = (
                        stencil_stream_hbm_bytes_per_step
                        if stream
                        else stencil_hbm_bytes_per_step
                    )
                    traffic = traffic_fn(
                        domain, blk, radii, n_f, n_out, itemsize, fuse
                    ) / ideal_bytes
                else:
                    traffic = stencil_batched_hbm_bytes_per_member_step(
                        domain, blk, radii, n_f, n_out, itemsize,
                        batch=batch, fuse_steps=fuse, stream=stream,
                    ) / ideal_bytes
                redundancy = stencil_redundant_compute_fraction(
                    blk, radii, fuse
                )
                align_pen = 0.0 if blk[-1] % LANE == 0 else 0.15
                bubble_pen = (
                    0.05
                    if (rank == 3 or stream) and rank > 1 and blk[0] < 4
                    else 0.0
                )
                step_pen = LANE / blk[-1] if rank == 1 else 0.0
                pens = 1.0 + align_pen + bubble_pen + step_pen
                if tc:
                    # Two-resource roofline: the launch takes the
                    # slower of its HBM walk and its MXU contractions.
                    # Halo recompute is already inside the FLOPs term
                    # (sub-windows include the shrinking margins), so
                    # no separate redundancy weight.
                    mxu = (
                        stencil_mxu_flops_per_step(
                            domain, blk, radii, n_f, fuse,
                            groups_per_axis=tc_groups,
                        )
                        / peak_mxu_flops(backend, itemsize)
                    ) / (ideal_bytes / peak_hbm_bw(backend))
                    score = max(traffic, mxu) * pens
                else:
                    score = (
                        traffic * pens
                        + compute_weight * redundancy
                    )
                out.append(
                    Candidate(
                        blk, vm, ho, score, fuse, stream,
                        strategy=regime,
                    )
                )
    # Tie-break equal modeled scores on the smaller VMEM working set
    # (e.g. a full-extent pipelined tile vs the streaming kernel, whose
    # carried planes make the same traffic with less residency).
    out.sort(key=lambda c: (c.score, c.vmem_bytes))
    return out


def hwc_candidate(
    domain: Sequence[int],
    fuse_steps: int = 1,
) -> Candidate:
    """The hardware-managed-caching baseline as a tuning candidate.

    ``hwc`` stages nothing itself — XLA owns on-chip residency — so it
    is modeled at the compulsory-traffic *floor*: one read of every
    input field plus one write of every output per step, normalized
    score exactly 1.0 with no VMEM footprint. A ``swc``/``swc_stream``
    candidate therefore only out-ranks it structurally when temporal
    fusion (or streaming) models *less* than the compulsory per-step
    traffic; on an eager resolution the measured XLA baseline competes
    on real time instead. The block is the per-rank default clamped to
    the domain — the hwc path ignores it, but the record round-trips a
    concrete value.
    """
    from repro.kernels.plan import DEFAULT_BLOCKS

    block = tuple(
        min(t, n) for t, n in zip(DEFAULT_BLOCKS[len(domain)], domain)
    )
    return Candidate(
        block=block, vmem_bytes=0, halo_overhead=0.0, score=1.0,
        fuse_steps=fuse_steps, stream=False, strategy="hwc",
    )


def enumerate_cross_strategy_nd(
    domain: Sequence[int],
    radii: Sequence[int],
    n_f: int,
    n_out: int,
    itemsize: int = 4,
    *,
    vmem_budget: int = VMEM_BUDGET,
    fuse_steps_options: Sequence[int] = (1,),
    stream_ok: bool = True,
    tc_ok: bool = True,
    tc_groups: Sequence[int] | None = None,
    backend: str | None = None,
    batch: int = 1,
    flops_per_point: float | None = None,
) -> list[Candidate]:
    """The ``strategy="auto"`` candidate space: every ``swc``, (rank
    ≥ 2, ``stream_ok``) ``swc_stream`` and (f32/bf16, ``tc_ok``) ``tc``
    configuration the joint ``(strategy, block, fuse_steps, stream)``
    enumeration admits, plus the ``hwc`` baseline as the modeled-
    traffic floor, ranked in ONE ordered list — the space in which
    ``strategy="auto"`` discovers the VPU/MXU crossover.

    The hwc entry is always present, so the cross-strategy search can
    never come back empty or VMEM-degenerate — a domain too small to
    block or stream profitably resolves to the compiler-managed path
    instead of a fallback record. Its depth is the smallest enumerated
    depth (1 unless a per-step φ sequence pins the search deeper).
    """
    cands = enumerate_candidates_nd(
        domain, radii, n_f, n_out, itemsize, vmem_budget=vmem_budget,
        fuse_steps_options=fuse_steps_options,
        stream_options=(False, True) if stream_ok else (False,),
        tc_options=(False, True) if tc_ok else (False,),
        tc_groups=tc_groups, backend=backend,
        batch=batch, flops_per_point=flops_per_point,
    )
    out = [hwc_candidate(domain, min(fuse_steps_options))] + cands
    out.sort(key=lambda c: (c.score, c.vmem_bytes))
    return out


def enumerate_candidates(
    domain: tuple[int, int, int],
    radii: tuple[int, int, int],
    n_f: int,
    n_out: int,
    itemsize: int = 4,
    *,
    vmem_budget: int = VMEM_BUDGET,
    tx_options: Sequence[int] = (128, 256, 512),
    ty_options: Sequence[int] = (4, 8, 16, 32),
    tz_options: Sequence[int] = (2, 4, 8, 16, 32),
) -> list[Candidate]:
    """Rank-3 enumeration (historical signature).

    .. deprecated::
        ``enumerate_candidates`` is deprecated; use
        :func:`enumerate_candidates_nd` (rank-generic, with
        ``axis_options`` in axis order, x last).
    """
    import warnings

    warnings.warn(
        "enumerate_candidates is deprecated; use enumerate_candidates_nd",
        DeprecationWarning,
        stacklevel=2,
    )
    return enumerate_candidates_nd(
        domain, radii, n_f, n_out, itemsize, vmem_budget=vmem_budget,
        axis_options=(tz_options, ty_options, tx_options),
    )


# Per-axis tile bases: x spans the 128-wide lane dimension; at rank 1 it
# is the only axis, so long blocks dominate. y/z use the paper's
# TPU-friendly sublane/streaming bases.
X_BASE_1D = (512, 1024, 2048, 4096, 8192)
X_BASE = (64, 128, 256, 512)
Y_BASE = (4, 8, 16, 32)
Z_BASE = (2, 4, 8, 16, 32)


def axis_tile_options(
    domain: Sequence[int],
) -> tuple[tuple[int, ...], ...]:
    """Per-axis tile options adapted to the actual extents, any rank:
    the TPU-friendly bases, each capped at the axis extent (so small
    research domains like 16³ still enumerate valid candidates), plus
    the full extent itself."""
    rank = len(domain)

    def opts(n: int, base: Sequence[int]) -> tuple[int, ...]:
        kept = [o for o in base if o <= n] + [n]
        return tuple(dict.fromkeys(kept))

    bases = {
        1: (X_BASE_1D,),
        2: (Y_BASE, X_BASE),
        3: (Z_BASE, Y_BASE, X_BASE),
    }[rank]
    return tuple(opts(n, b) for n, b in zip(domain, bases))


def domain_axis_options(
    domain: tuple[int, int, int],
    *,
    tx_base: Sequence[int] = X_BASE,
    ty_base: Sequence[int] = Y_BASE,
    tz_base: Sequence[int] = Z_BASE,
) -> tuple[tuple[int, ...], tuple[int, ...], tuple[int, ...]]:
    """Rank-3 per-axis options (historical signature).

    .. deprecated::
        ``domain_axis_options`` is deprecated; use
        :func:`axis_tile_options` (rank-generic).
    """
    import warnings

    warnings.warn(
        "domain_axis_options is deprecated; use axis_tile_options",
        DeprecationWarning,
        stacklevel=2,
    )
    nz, ny, nx = domain

    def opts(n: int, base: Sequence[int]) -> tuple[int, ...]:
        kept = [o for o in base if o <= n] + [n]
        return tuple(dict.fromkeys(kept))

    return opts(nz, tz_base), opts(ny, ty_base), opts(nx, tx_base)


@dataclasses.dataclass(frozen=True)
class Candidate1D:
    """Block-length candidate for the 1-D kernels (xcorr, depthwise
    conv): ``block`` elements per grid step along the streamed axis."""

    block: int
    vmem_bytes: int
    score: float


def enumerate_candidates_1d(
    n: int,
    halo: int,
    *,
    width: int = 1,
    itemsize: int = 4,
    vmem_budget: int = VMEM_BUDGET,
    options: Sequence[int] = (256, 512, 1024, 2048, 4096, 8192),
) -> list[Candidate1D]:
    """Rank 1-D block lengths: VMEM filter, then a structural score that
    trades halo refetch + per-step pipeline overhead (favoring long
    blocks) against tail-padding waste (favoring blocks near a divisor
    of ``n``). ``width`` is the per-element row width (channels for the
    depthwise conv)."""
    out: list[Candidate1D] = []
    for b in options:
        if b > max(n, LANE):
            continue
        vm = (2 * (b + halo) + b) * width * itemsize
        if vm > vmem_budget:
            continue
        waste = (-(-n // b) * b - n) / n
        score = (1.0 + halo / b) * (1.0 + waste) * (1.0 + LANE / b)
        out.append(Candidate1D(b, vm, score))
    out.sort(key=lambda c: c.score)
    return out


def time_candidate(
    fn: Callable[[], jax.Array],
    *,
    warmup: int = 2,
    iters: int = 5,
    validate: bool = True,
) -> float:
    """Median wall-clock seconds (paper: warm-up then median of timed
    iterations, block_until_ready for proper synchronization).

    ``validate`` checks the first warm-up output for NaN/inf and raises
    ``ValueError`` on corruption — a mis-lowered candidate that blows
    up numerically must be discarded as a failed launch (and recorded
    as a ``failed`` row by the session), not timed into a cache winner.
    """
    for i in range(warmup):
        out = jax.block_until_ready(fn())
        if validate and i == 0:
            _check_finite(out)
    ts = []
    for i in range(iters):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
        if validate and warmup == 0 and i == 0:
            _check_finite(out)
    return float(np.median(ts))


def _check_finite(out: object) -> None:
    """Raise ``ValueError`` if any floating leaf of ``out`` contains
    NaN/inf (the candidate-output validation gate of
    :func:`time_candidate`)."""
    for leaf in jax.tree_util.tree_leaves(out):
        arr = np.asarray(leaf)
        # bfloat16 (ml_dtypes) reports numpy kind "V", not "f" — catch
        # it by name so low-precision candidates are validated too.
        if arr.dtype.kind not in "fc" and "float" not in arr.dtype.name:
            continue
        try:
            finite = bool(np.isfinite(arr).all())
        except TypeError:  # exotic float dtypes (e.g. bfloat16)
            finite = bool(np.isfinite(arr.astype(np.float32)).all())
        if not finite:
            raise ValueError(
                "candidate produced non-finite output "
                f"(shape {arr.shape}, dtype {arr.dtype})"
            )


def autotune(
    make_fn: Callable[[tuple[int, int, int]], Callable[[], jax.Array]],
    candidates: Iterable[Candidate],
    *,
    top_k: int = 4,
    warmup: int = 2,
    iters: int = 5,
) -> tuple[Candidate, dict[tuple[int, int, int], float]]:
    """Measure the ``top_k`` structurally-ranked candidates and return the
    winner plus the full timing table (the paper's search, with the cost
    model as the pruner)."""
    timings: dict[tuple[int, int, int], float] = {}
    best: tuple[float, Candidate] | None = None
    for cand in list(candidates)[:top_k]:
        try:
            fn = make_fn(cand.block)
            t = time_candidate(fn, warmup=warmup, iters=iters)
        except Exception as e:
            # The paper's discarded launch: log which candidate died
            # and why, then keep ranking the rest.
            log.warning(
                "autotune candidate %s discarded: %s: %s",
                cand.block, type(e).__name__, e,
            )
            continue
        timings[cand.block] = t
        if best is None or t < best[0]:
            best = (t, cand)
    if best is None:
        raise RuntimeError("no candidate ran successfully")
    return best[1], timings

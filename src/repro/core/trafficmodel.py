"""Modeled per-chip HBM traffic of stencil launches — the *fused*
memory roofline term.

What one simulated time step moves through HBM under temporal fusion
(fuse_steps in-kernel time steps on halo-widened blocks), as a function
of (block, radii, depth), with a separate function for the explicit-
streaming kernel (whose carried halo planes eliminate the stream-axis
halo re-fetch). ``repro.tuning.costmodel`` scores its joint
(block, fuse_steps, stream) candidates through these exact functions,
so the autotuner's temporal/streaming terms and the reported traffic
model cannot diverge. The per-device peak rates (``DEVICE_PEAKS``) the
roofline divides by live here too.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence


# ---------------------------------------------------------------------------
# Stencil temporal-fusion traffic (the fused-kernel bandwidth lever).
# ---------------------------------------------------------------------------


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def stencil_hbm_bytes_per_step(
    domain: Sequence[int],
    block: Sequence[int],
    radii: Sequence[int],
    n_f: int,
    n_out: int,
    itemsize: int,
    fuse_steps: int = 1,
) -> float:
    """Modeled HBM bytes moved per simulated TIME step.

    One kernel launch stages, per block, the tile plus a halo widened to
    ``radii * fuse_steps`` (reads), writes the interior tile once, and
    advances ``fuse_steps`` steps — so the per-step traffic is the whole
    launch divided by the depth. Depth 1 reduces to the classic
    read-tile-plus-halo / write-tile model.
    """
    if fuse_steps < 1:
        raise ValueError(f"fuse_steps must be >= 1, got {fuse_steps}")
    n_blocks, read_per_block, points = 1, n_f, 1
    for n, t, r in zip(domain, block, radii):
        n_blocks *= _ceil_div(n, t)
        read_per_block *= t + 2 * r * fuse_steps
        points *= n
    read = n_blocks * read_per_block
    write = n_out * points
    return (read + write) * itemsize / fuse_steps


def stencil_stream_hbm_bytes_per_step(
    domain: Sequence[int],
    block: Sequence[int],
    radii: Sequence[int],
    n_f: int,
    n_out: int,
    itemsize: int,
    fuse_steps: int = 1,
) -> float:
    """Modeled HBM bytes per simulated TIME step for the explicit-
    streaming kernel (``swc_stream``, paper Fig. 5b), any fuse depth.

    The stream walks axis 0 (z at rank 3, y at rank 2) carrying
    ``2·r₀·fuse_steps`` halo planes in VMEM between chunks, so — unlike
    the pipelined model, which re-fetches the stream-axis halo for every
    block — each cross-stream tile column reads the full stream extent
    plus ONE leading/trailing halo: ``N₀ + 2·r₀·S`` planes of the
    ``Π(τ_a + 2·r_a·S)`` cross window. Cross-axis halos are still
    re-fetched per tile column. The interior is written once; a launch
    advances ``fuse_steps`` steps, so the total is divided by the depth.
    """
    if fuse_steps < 1:
        raise ValueError(f"fuse_steps must be >= 1, got {fuse_steps}")
    n_cols, read_per_col, points = 1, n_f, 1
    for a, (n, t, r) in enumerate(zip(domain, block, radii)):
        points *= n
        if a == 0:
            read_per_col *= n + 2 * r * fuse_steps
        else:
            n_cols *= _ceil_div(n, t)
            read_per_col *= t + 2 * r * fuse_steps
    read = n_cols * read_per_col
    write = n_out * points
    return (read + write) * itemsize / fuse_steps


# Fixed per-launch overhead charged by the batched per-member model:
# grid bookkeeping, kernel argument marshalling, and the pipeline's
# prologue/epilogue DMA ramp, expressed as equivalent HBM bytes. One
# batched launch walking B members amortizes this over the whole
# ensemble (and over the fuse depth), which is exactly the lever the
# batch axis pulls — B vmap'd launches would each pay it in full.
STENCIL_LAUNCH_OVERHEAD_BYTES = 64 * 1024


def stencil_batched_hbm_bytes_per_member_step(
    domain: Sequence[int],
    block: Sequence[int],
    radii: Sequence[int],
    n_f: int,
    n_out: int,
    itemsize: int,
    *,
    batch: int = 1,
    fuse_steps: int = 1,
    stream: bool = False,
    launch_overhead_bytes: float = STENCIL_LAUNCH_OVERHEAD_BYTES,
) -> float:
    """Modeled HBM bytes per ENSEMBLE MEMBER per simulated time step
    for a batched launch walking ``batch`` members per block.

    The field/halo traffic itself is per-member (every member's tile
    and halo must move regardless of batching — the per-member byte
    functions above already describe it), but the fixed per-launch
    overhead (:data:`STENCIL_LAUNCH_OVERHEAD_BYTES`) is paid once per
    launch and divides across all ``batch`` members and ``fuse_steps``
    in-kernel sweeps. Per-member bytes therefore strictly decrease in
    ``batch`` (for any positive overhead), which is the quantity the
    batched candidate enumeration ranks.
    """
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    bytes_fn = (
        stencil_stream_hbm_bytes_per_step
        if stream
        else stencil_hbm_bytes_per_step
    )
    member = bytes_fn(
        domain, block, radii, n_f, n_out, itemsize, fuse_steps
    )
    return member + launch_overhead_bytes / (batch * fuse_steps)


def stencil_redundant_compute_fraction(
    block: Sequence[int],
    radii: Sequence[int],
    fuse_steps: int = 1,
) -> float:
    """Extra stencil evaluations per useful output point under temporal
    fusion: sweep ``s`` of ``S`` covers the tile plus a
    ``radii * (S - 1 - s)`` margin (the valid region shrinks one radius
    per sweep), so fused blocks recompute halo points the unfused
    schedule would have read from HBM. Returns 0.0 at depth 1.
    """
    tile = 1
    for t in block:
        tile *= t
    total = 0
    for s in range(fuse_steps):
        vol = 1
        for t, r in zip(block, radii):
            vol *= t + 2 * r * (fuse_steps - 1 - s)
        total += vol
    return total / (fuse_steps * tile) - 1.0


# ---------------------------------------------------------------------------
# MXU compute model (the tc caching regime: stencils as banded matmuls).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """One device's peak rates, as the cost model reads them."""

    mxu_flops_f32: float  # matrix unit, f32 inputs, f32 accumulate
    hbm_bw: float  # HBM bytes/s
    # Vector unit (VPU) f32 FLOP/s: ~a quarter of the MXU f32 rate (8×128
    # lanes × 2 ALU slots vs the 128×128 systolic array) — the rate the
    # tap-by-tap swc/swc_stream/hwc regimes run their multiply-adds at.
    vpu_flops_f32: float


# Keyed by the exact ``device_kind`` JAX reports. MXU and HBM: Google
# Cloud TPU documentation per generation (bf16 peak halved for f32
# inputs; v5e: 197 TFLOP/s bf16, 819 GB/s). VPU: a quarter of the MXU
# f32 rate (estimate, not published). ``"cpu"`` is the interpret-mode
# host the tests run on: it models the TPU v5e the kernels are written
# for, so CPU runs rank candidates as that chip would.
_V5E = ChipPeaks(98.5e12, 819e9, 24.625e12)
DEVICE_PEAKS: dict[str, ChipPeaks] = {
    "TPU v4": ChipPeaks(137.5e12, 1228e9, 34.375e12),
    "TPU v5 lite": _V5E,
    "TPU v5": ChipPeaks(229.5e12, 2765e9, 57.375e12),
    "TPU v6 lite": ChipPeaks(459.5e12, 1640e9, 114.875e12),
    "cpu": _V5E,
}


def device_peaks(backend: str | None = None) -> ChipPeaks:
    """Peak rates of ``backend`` (a JAX ``device_kind``; None → the
    default device's). A device that is not in :data:`DEVICE_PEAKS` is
    an error, never a default.

    Raises:
        KeyError: for a device kind with no row.
    """
    if backend is None:
        import jax

        backend = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[backend]
    except KeyError:
        raise KeyError(
            f"no peak rates for device kind {backend!r}; add a row to "
            f"DEVICE_PEAKS (known: {sorted(DEVICE_PEAKS)})"
        ) from None


def peak_mxu_flops(
    backend: str | None = None, itemsize: int = 4
) -> float:
    """Peak MXU FLOP/s of ``backend`` for the given input itemsize: bf16
    inputs (itemsize 2) run at double the f32 rate — the f32-accumulate
    contract the tc emitter lowers with."""
    base = device_peaks(backend).mxu_flops_f32
    return base * (2.0 if itemsize == 2 else 1.0)


def peak_hbm_bw(backend: str | None = None) -> float:
    """Peak HBM bandwidth (bytes/s) of ``backend``."""
    return device_peaks(backend).hbm_bw


def peak_vpu_flops(backend: str | None = None) -> float:
    """Peak VPU FLOP/s of ``backend``. The element-wise rate is
    dtype-agnostic on the f32-wide VPU, so there is no itemsize
    scaling. The generalized-order cost model normalizes per-point
    stencil FLOPs against it to weigh temporal fusion's redundant halo
    compute."""
    return device_peaks(backend).vpu_flops_f32


def stencil_mxu_flops_per_step(
    domain: Sequence[int],
    block: Sequence[int],
    radii: Sequence[int],
    n_f: int,
    fuse_steps: int = 1,
    *,
    groups_per_axis: Sequence[int] | None = None,
) -> float:
    """Modeled MXU FLOPs per simulated TIME step of a ``tc`` plan.

    Each multi-tap contraction group on axis ``a`` (see
    :func:`~repro.kernels.plan.tc_groups_per_axis`) contracts the FULL
    staged window extent — the banded matrix is dense as far as the MXU
    is concerned, zeros included — so the per-point cost is
    ``2 · (τ_a + 2·r_a·(margin+1))`` FLOPs per group, growing with the
    tile, not the tap count. That tile dependence is exactly the
    VPU/MXU trade-off the cost model must see: big tiles amortize halo
    traffic but inflate matmul work. Temporal sweeps evaluate over the
    shrinking sub-windows (margin ``S-1-s``), and a launch advances
    ``fuse_steps`` steps, so the total divides by the depth.

    ``groups_per_axis`` defaults to one matmul group per axis (a star
    stencil like fused diffusion).
    """
    if fuse_steps < 1:
        raise ValueError(f"fuse_steps must be >= 1, got {fuse_steps}")
    rank = len(tuple(block))
    groups = (
        (1,) * rank
        if groups_per_axis is None
        else tuple(groups_per_axis)
    )
    n_blocks = 1
    for n, t in zip(domain, block):
        n_blocks *= _ceil_div(n, t)
    total = 0.0
    for s in range(fuse_steps):
        margin = fuse_steps - 1 - s
        sub = [
            t + 2 * r * margin for t, r in zip(block, radii)
        ]
        vol = 1
        for x in sub:
            vol *= x
        per_point = sum(
            2.0 * g * (sub[a] + 2 * radii[a])
            for a, g in enumerate(groups)
        )
        total += vol * per_point
    return n_blocks * n_f * total / fuse_steps


def stencil_traffic_reduction(
    domain: Sequence[int],
    radii: Sequence[int],
    n_f: int,
    n_out: int,
    itemsize: int,
    *,
    block_base: Sequence[int],
    block_fused: Sequence[int],
    fuse_steps: int,
    stream: bool = False,
) -> float:
    """Modeled per-step HBM-traffic reduction of a fused configuration
    over its depth-1 baseline (>1 means the fused plan moves less).
    ``stream=True`` models both sides with the explicit-streaming
    kernel's byte function instead of the pipelined one."""
    bytes_fn = (
        stencil_stream_hbm_bytes_per_step
        if stream
        else stencil_hbm_bytes_per_step
    )
    base = bytes_fn(domain, block_base, radii, n_f, n_out, itemsize, 1)
    fused = bytes_fn(
        domain, block_fused, radii, n_f, n_out, itemsize, fuse_steps
    )
    return base / fused

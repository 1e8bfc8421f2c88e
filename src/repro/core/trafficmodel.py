"""Modeled per-chip HBM traffic — the *fused* memory roofline term.

``compiled.cost_analysis()['bytes accessed']`` sums every HLO op's
operand+result bytes as if nothing were fused — on the CPU backend this
overstates real HBM traffic by 10-30× (every intermediate counted).
We report that number as the spec'd upper bound, AND this analytic model
of what a fused TPU execution actually moves:

train step (per chip):
  params     f32 master read + bf16 cast write/read + f32 write (update)
  adam       mu, nu: read + write (f32)
  grads      write + read (f32)
  acts       remat-saved activations: write (fwd) + read (bwd)
  logits     write + read of the sharded logits block (f32-equivalent)
  batch      token ids + label reads (negligible, included)

decode step (per chip):
  params     one bf16-equivalent read of ACTIVE params
  kv/state   full cache read + one-slot write

prefill:
  params     one bf16 read
  acts       write+read once per layer boundary (no backward)

Assumptions (documented in EXPERIMENTS.md §Roofline): parameters are
TP-sharded over `model` (÷M), FSDP additionally over `data`; activations
are DP-sharded (÷D on tokens) with hidden dims TP-sharded where the rules
shard them. Within ~2× of a real profile, which is what a roofline term
needs.

The second half of this module models *stencil* HBM traffic under
temporal fusion (fuse_steps in-kernel time steps on halo-widened
blocks): what one simulated time step moves through HBM as a function
of (block, radii, depth), with a separate function for the explicit-
streaming kernel (whose carried halo planes eliminate the stream-axis
halo re-fetch). ``repro.tuning.costmodel`` scores its joint
(block, fuse_steps, stream) candidates through these exact functions,
so the autotuner's temporal/streaming terms and the reported traffic
model cannot diverge.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

from repro.models.config import ModelConfig


def _act_bytes_per_token_layer(cfg: ModelConfig, model_ways: int) -> float:
    """Remat-saved bytes per token per layer (bf16), TP-sharded dims ÷M."""
    d = cfg.d_model
    m = model_ways
    if cfg.family == "ssm":
        dv = cfg.d_inner
        # in_proj out (2dv+2gn+h)/M, conv out, ssd y, out_proj in
        per = (2 * dv + 2 * cfg.ssm_n_groups * cfg.ssm_state) / m * 3 + d
    elif cfg.hybrid_pattern:
        w = cfg.lru_width or d
        per = (3 * w / m + d) * 2 / 3 + (  # rec blocks (2 of 3)
            (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.hd / m + d
            + 2 * cfg.d_ff / m
        ) / 3
    else:
        ff = cfg.d_ff_expert * cfg.top_k if cfg.family == "moe" else cfg.d_ff
        per = (
            (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.hd / m  # qkv
            + cfg.n_heads * cfg.hd / m  # attn out
            + 2 * ff / m  # gate/up
            + 2 * d  # residual stream saves
        )
    return per * 2.0  # bf16


def modeled_hbm_bytes(
    cfg: ModelConfig,
    kind: str,
    seq: int,
    global_batch: int,
    *,
    model_ways: int,
    dp_ways: int,
    fsdp: bool = False,
) -> float:
    """Per-chip HBM bytes for one step of ``kind``."""
    n_params = cfg.n_params()
    n_active = cfg.n_active_params()
    p_shard = n_params / model_ways / (dp_ways if fsdp else 1)
    eff_seq = cfg.max_target_len if cfg.is_encdec and kind != "prefill" else seq
    tokens_chip = global_batch * eff_seq / dp_ways / max(
        1, (model_ways if kind != "decode" else 1)
    )
    # sequence is model-sharded (SP) in train/prefill; decode has 1 token.
    if kind == "decode":
        tokens_chip = max(global_batch / dp_ways, 1.0)

    n_layers = cfg.n_layers + cfg.n_encoder_layers

    if kind == "train":
        param_traffic = p_shard * (4 + 4 + 2 + 2)  # f32 r/w + bf16 w/r
        opt_traffic = p_shard * 4 * 4  # mu, nu r+w
        grad_traffic = p_shard * 4 * 2
        act = tokens_chip * n_layers * _act_bytes_per_token_layer(
            cfg, model_ways
        ) * 2.0  # write fwd + read bwd
        logits = tokens_chip * (cfg.vocab / model_ways) * 2 * 3
        return param_traffic + opt_traffic + grad_traffic + act + logits
    if kind == "prefill":
        param_traffic = p_shard * 2  # bf16-equivalent read
        act = tokens_chip * n_layers * _act_bytes_per_token_layer(
            cfg, model_ways
        )
        return param_traffic + act
    # decode
    p_active_shard = n_active / model_ways
    param_traffic = p_active_shard * 2  # bf16 read per token
    cache = _decode_cache_bytes(cfg, seq, global_batch, dp_ways, model_ways)
    return param_traffic + cache


def _decode_cache_bytes(
    cfg: ModelConfig, seq: int, global_batch: int, dp_ways: int,
    model_ways: int,
) -> float:
    b_chip = max(global_batch / dp_ways, 1.0)
    if cfg.family == "ssm":
        dv = cfg.d_inner
        state = (
            cfg.ssm_n_heads * cfg.ssm_state * cfg.ssm_head_dim
            + (cfg.ssm_conv_kernel - 1)
            * (dv + 2 * cfg.ssm_n_groups * cfg.ssm_state)
        )
        shard = model_ways  # heads/channels sharded
        return b_chip * cfg.n_layers * state / shard * 4 * 2  # f32 r+w
    if cfg.hybrid_pattern:
        n_rec = cfg.n_layers - cfg.n_layers // cfg.hybrid_pattern
        n_att = cfg.n_layers // cfg.hybrid_pattern
        w = cfg.lru_width or cfg.d_model
        rec = b_chip * n_rec * (
            w + (cfg.ssm_conv_kernel - 1) * w
        ) / model_ways * 4 * 2
        win = min(cfg.local_window, seq)
        att = b_chip * n_att * win * cfg.n_kv_heads * cfg.hd * 2
        return rec + att
    cache_len = (
        min(cfg.sliding_window, seq) if cfg.sliding_window else seq
    )
    kv_shard = model_ways if cfg.n_kv_heads % model_ways == 0 else 1
    return (
        b_chip * cfg.n_layers * cache_len * 2  # k and v
        * cfg.n_kv_heads * cfg.hd / kv_shard * 2  # bf16
    )


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

"""Public wrappers for the Pallas kernels (inner bodies jit'd).

These handle shape padding (block divisibility), dtype plumbing, the
interpret-mode switch for CPU validation, strategy selection (``"swc"``
pipelined VPU, ``"swc_stream"`` explicit streaming, ``"tc"`` banded
matrix-unit contractions, plus the compiler-managed ``"hwc"`` baseline),
and ``"auto"`` block resolution through ``repro.tuning``, so callers
(fusion engine, physics, models) never touch BlockSpecs.

On the CPU backend ``interpret`` defaults to True; on TPU it defaults
to False; any other backend raises. Override explicitly for tests.

Block parameters accept ``"auto"``: the persistent tuning cache
(``repro.tuning``) is consulted, and on a miss with concrete operands
the paper's rank-then-measure protocol runs once and records the winner
(under tracing the structural cost-model winner is used instead).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.stencil import OperatorSet
from repro.kernels import ref as _ref
from repro.kernels.conv1d_depthwise import conv1d_depthwise_pallas
from repro.kernels.emit import fused_stencil_pallas
from repro.kernels.plan import StencilPlan, plan_stencil

# ops.py IS the sanctioned facade over the legacy kernels.
from repro.kernels.stencil1d import xcorr1d_pallas  # repolint: allow[legacy-kernel-import]


def _default_interpret() -> bool:
    """Interpret Pallas kernels on the CPU backend (where the tests
    run) and compile them with Mosaic on a TPU. Any other backend is an
    error: it must not quietly run the interpreter in place of the
    chip."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas stencil kernels run on 'tpu' (Mosaic) or 'cpu' "
            f"(interpret mode), not on backend {backend!r}"
        )
    return backend == "cpu"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


# The public xcorr1d is un-jitted (it resolves "auto" blocks eagerly);
# keep the hwc early-return compiled like it was when xcorr1d itself
# carried @jax.jit.
_xcorr1d_hwc_jit = jax.jit(_ref.xcorr1d)


def xcorr1d(
    f_padded: jnp.ndarray,
    g: jnp.ndarray,
    *,
    strategy: str = "baseline",
    block_size: int | str = 2048,
    unroll: int = 4,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """1-D cross-correlation over the valid region (paper Eq. 3).

    Accepts any n; pads the tail to a block multiple and slices back.
    ``strategy='hwc'`` dispatches to the pure-jnp/XLA-managed path.
    ``block_size="auto"`` resolves through the tuning subsystem.
    """
    if interpret is None:
        interpret = _default_interpret()
    if strategy == "hwc":
        return _xcorr1d_hwc_jit(f_padded, g)
    if block_size == "auto":
        from repro.tuning.session import auto_block_xcorr1d

        block_size = auto_block_xcorr1d(
            f_padded, g, strategy=strategy, unroll=unroll,
            interpret=interpret,
        )
    return _xcorr1d_jit(
        f_padded, g, strategy=strategy, block_size=block_size,
        unroll=unroll, interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("strategy", "block_size", "unroll", "interpret"),
)
def _xcorr1d_jit(
    f_padded: jnp.ndarray,
    g: jnp.ndarray,
    *,
    strategy: str,
    block_size: int,
    unroll: int,
    interpret: bool,
) -> jnp.ndarray:
    n_taps = g.shape[0]
    n = f_padded.shape[0] - (n_taps - 1)
    n_pad = _round_up(n, block_size)
    if n_pad != n:
        f_padded = jnp.concatenate(
            [f_padded, jnp.zeros((n_pad - n,), f_padded.dtype)]
        )
    out = xcorr1d_pallas(
        f_padded, g, strategy=strategy, block_size=block_size,
        unroll=unroll, interpret=interpret,
    )
    return out[:n]


def fused_stencil_nd(
    f_padded: jnp.ndarray,
    ops: OperatorSet,
    phi: Callable[..., jnp.ndarray],
    n_out: int,
    *,
    aux: jnp.ndarray | None = None,
    strategy: str = "swc",
    block: tuple[int, ...] | str | None = None,
    unroll: int = 1,
    fuse_steps: int = 1,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused φ(A·B) over a padded (n_f, *spatial) domain of rank 1-3
    (paper Eq. 9) — the thin dispatch over :class:`StencilPlan`.

    ``strategy``: 'hwc' (XLA-managed), 'swc' (Pallas pipelined blocks,
    any rank) or 'swc_stream' (Pallas explicit streaming of the slowest
    axis with carried halo planes + prefetch DMA, paper Fig. 5b —
    z-streaming at rank 3, y-streaming at rank 2). ``block`` is a
    rank-length tile (``None`` → per-rank default; longer tuples keep
    their trailing, x-last entries; non-divisible extents shrink the
    tile to the largest divisor) or ``"auto"``, which consults the
    persistent tuning cache (measuring on a miss when eager) — for
    every rank and strategy, through the same cache.

    ``fuse_steps`` is the temporal-fusion depth: ``f_padded`` must be
    padded by ``radius * fuse_steps`` (and ``aux``, if any, by
    ``radius * (fuse_steps - 1)``), the op is applied that many times
    inside one kernel, and ``phi`` may be a sequence of per-step
    callables. One call advances ``fuse_steps`` time steps. Depth > 1
    composes with both 'swc' (halo-widened pipelined blocks) and
    'swc_stream' (the carried halo widens to ``2·r·fuse_steps`` planes).

    A batched (ensemble) operand is detected by rank: ``f_padded`` of
    shape (batch, n_f, *spatial_padded) — i.e. ``ops.ndim + 2`` axes —
    lowers every strategy through one kernel that walks all members per
    block (member-major, shared halo window; 'hwc' uses the ``vmap``
    reference). ``aux`` then carries the same leading axis. Returns
    (batch, n_out, *interior).
    """
    if interpret is None:
        interpret = _default_interpret()
    batched = f_padded.ndim == ops.ndim + 2
    if strategy == "hwc":
        if batched:
            if fuse_steps == 1:
                return _ref.fused_stencil_batched(
                    f_padded, ops, phi, aux=aux
                )
            return _ref.fused_stencil_steps_batched(
                f_padded, ops, phi, fuse_steps, aux=aux
            )
        if fuse_steps == 1:
            return _ref.fused_stencil(f_padded, ops, phi, aux=aux)
        return _ref.fused_stencil_steps(
            f_padded, ops, phi, fuse_steps, aux=aux
        )
    if block == "auto":
        from repro.tuning.session import auto_block_nd

        block = auto_block_nd(
            f_padded, ops, phi, n_out, aux=aux, strategy=strategy,
            unroll=unroll, fuse_steps=fuse_steps, interpret=interpret,
        )
    plan = plan_for_nd(
        ops, f_padded.shape, n_out,
        aux_shape=None if aux is None else aux.shape,
        strategy=strategy, block=block, dtype=str(f_padded.dtype),
        unroll=unroll, fuse_steps=fuse_steps,
    )
    return fused_stencil_pallas(
        f_padded, ops, phi, plan, aux=aux, interpret=interpret
    )


def plan_for_nd(
    ops: OperatorSet,
    padded_shape: tuple[int, ...],
    n_out: int,
    *,
    aux_shape: tuple[int, ...] | None = None,
    strategy: str = "swc",
    block: tuple[int, ...] | None = None,
    dtype: str = "float32",
    unroll: int = 1,
    fuse_steps: int = 1,
) -> StencilPlan | None:
    """The :class:`StencilPlan` a :func:`fused_stencil_nd` call with
    these arguments lowers through — the ONE construction shared by the
    dispatch above and the static auditor (``repro.analysis``), so the
    audited plan can never diverge from the launched one. ``None`` for
    ``strategy="hwc"`` (no Pallas plan); ``block`` must be concrete
    (resolve ``"auto"`` through the tuning session first)."""
    if strategy == "hwc":
        return None
    if isinstance(block, str):
        raise ValueError(
            f"plan_for_nd needs a concrete block, got {block!r} — "
            "resolve 'auto' via repro.tuning first"
        )
    n_aux = 0
    if aux_shape is not None:
        batched = len(padded_shape) == ops.ndim + 2
        n_aux = aux_shape[1] if batched else aux_shape[0]
    return plan_stencil(
        ops, padded_shape, n_out, strategy=strategy, block=block,
        dtype=dtype, n_aux=n_aux, unroll=unroll,
        fuse_steps=fuse_steps,
    )


def fused_stencil3d(
    f_padded: jnp.ndarray,
    ops: OperatorSet,
    phi: Callable[..., jnp.ndarray],
    n_out: int,
    *,
    aux: jnp.ndarray | None = None,
    strategy: str = "swc",
    block: tuple[int, int, int] | str = (8, 8, 128),
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Historical rank-3 entry point.

    .. deprecated::
        ``fused_stencil3d`` is deprecated; use :func:`fused_stencil_nd`
        (rank-generic, same keyword surface plus ``unroll`` and
        ``fuse_steps``).
    """
    import warnings

    warnings.warn(
        "fused_stencil3d is deprecated; use fused_stencil_nd",
        DeprecationWarning,
        stacklevel=2,
    )
    return fused_stencil_nd(
        f_padded, ops, phi, n_out, aux=aux, strategy=strategy,
        block=block, interpret=interpret,
    )


def conv1d_depthwise(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    activation: str = "none",
    block_seq: int | str | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused depthwise causal conv1d (+ SiLU) — mamba2 frontend stencil.

    ``block_seq=None`` (model call sites) uses 512 unless auto-tuning is
    globally enabled (``repro.tuning.enable_auto()`` — the train/serve
    drivers' ``--auto-tune``), in which case it resolves like ``"auto"``:
    persistent cache first, measured tune on an eager miss.
    """
    if interpret is None:
        interpret = _default_interpret()
    if block_seq is None:
        from repro.tuning.session import AUTO_ENABLED

        block_seq = "auto" if AUTO_ENABLED else 512
    if block_seq == "auto":
        from repro.tuning.session import auto_block_conv1d

        block_seq = auto_block_conv1d(
            x, w, activation=activation, interpret=interpret
        )
    return _conv1d_depthwise_jit(
        x, w, activation=activation, block_seq=block_seq,
        interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("activation", "block_seq", "interpret")
)
def _conv1d_depthwise_jit(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    activation: str,
    block_seq: int,
    interpret: bool,
) -> jnp.ndarray:
    b, s, c = x.shape
    block_seq = min(block_seq, _round_up(s, 128))
    s_pad = _round_up(s, block_seq)
    if s_pad != s:
        x = jnp.pad(x, ((0, 0), (0, s_pad - s), (0, 0)))
    out = conv1d_depthwise_pallas(
        x, w, activation=activation, block_seq=block_seq,
        interpret=interpret,
    )
    return out[:, :s, :]

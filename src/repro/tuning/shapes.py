"""Registered benchmark shapes for cache pre-warming.

``python -m repro.tuning warm`` drives every entry through the real
``block="auto"`` code paths (eager, so measurement runs), which both
populates the persistent cache for the benchmark suite and exercises the
exact key derivation the hot paths use — warm once, every later
``FusedStencilOp``/kernel call cache-hits.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class WarmEntry:
    name: str
    run: Callable[[bool], None]  # run(full): eager auto-tuned call(s)


def _warm_diffusion3d(full: bool) -> None:
    from repro.physics.diffusion import DiffusionProblem

    shape = (256, 256, 256) if full else (32, 32, 64)
    for acc in (2, 6):
        p = DiffusionProblem(shape, accuracy=acc)
        f0 = p.init_field()
        op = p.step_op("swc", block="auto")
        op(f0)


def _warm_diffusion_lowdim(full: bool) -> None:
    """Rank-1/2 fused plans (the engine's new dimensionalities)."""
    from repro.physics.diffusion import DiffusionProblem

    shapes = [
        ((1 << 22,) if full else (1 << 14,)),
        ((2048, 2048) if full else (64, 128)),
    ]
    for shape in shapes:
        for acc in (2, 6):
            p = DiffusionProblem(shape, accuracy=acc)
            op = p.step_op("swc", block="auto")
            op(p.init_field())


def _warm_diffusion_stream(full: bool) -> None:
    """Explicit-streaming plans at both ranks (y-stream at rank 2,
    z-stream at rank 3), including a fused depth-2 streaming plan —
    the stream axis and depth are part of the cache key."""
    from repro.physics.diffusion import DiffusionProblem

    shapes = [
        ((2048, 2048) if full else (64, 128)),
        ((128, 128, 128) if full else (16, 16, 64)),
    ]
    for shape in shapes:
        p = DiffusionProblem(shape, accuracy=6)
        f0 = p.init_field()
        p.step_op("swc_stream", block="auto")(f0)
        p.step_op("swc_stream", block="auto", fuse_steps=2)(f0)


def _warm_diffusion_tc(full: bool) -> None:
    """MXU (``tc``) plans at rank 2 and 3, plus one 4-member batched
    ensemble shape — the ``tc`` marker and the ``:b{B}`` batch extent
    are both part of the cache key (``tc:b4`` never replays a ``swc``
    winner), so each needs its own warmed record."""
    from repro.physics.diffusion import DiffusionProblem

    shapes = [
        ((2048, 2048) if full else (64, 128)),
        ((128, 128, 128) if full else (16, 16, 64)),
    ]
    for shape in shapes:
        p = DiffusionProblem(shape, accuracy=6)
        f0 = p.init_field()
        p.step_op("tc", block="auto")(f0)
    p2 = DiffusionProblem(shapes[0], accuracy=6)
    stack = jnp.stack([p2.init_field(seed=s) for s in range(4)])
    p2.step_op("tc", block="auto")(stack)


def _warm_diffusion_auto(full: bool) -> None:
    """Cross-strategy ``strategy="auto"`` records (one ``auto:sauto``
    key per shape holding the resolved strategy/block/depth/stream), so
    jitted ``"auto"`` call sites replay the measured cross-strategy
    winner instead of the structural one."""
    from repro.physics.diffusion import DiffusionProblem

    shapes = [
        ((2048, 2048) if full else (64, 128)),
        ((128, 128, 128) if full else (16, 16, 64)),
    ]
    for shape in shapes:
        p = DiffusionProblem(shape, accuracy=6)
        f0 = p.init_field()
        p.step_op("auto", fuse_steps="auto").resolved(f0)


def _warm_mhd(full: bool) -> None:
    from repro.physics.mhd import MHDSolver

    n = 64 if full else 16
    solver = MHDSolver((n, n, n), strategy="swc", block="auto")
    f0 = solver.init_fields()
    solver.rhs(f0)


def _warm_mhd_stream(full: bool) -> None:
    from repro.physics.mhd import MHDSolver

    n = 64 if full else 16
    solver = MHDSolver((n, n, n), strategy="swc_stream", block="auto")
    f0 = solver.init_fields()
    solver.rhs(f0)


def _warm_xcorr1d(full: bool) -> None:
    from repro.kernels import ops as kops

    n = 1 << (22 if full else 16)
    rng = np.random.default_rng(0)
    for radius in (1, 32):
        f = jnp.asarray(
            rng.standard_normal(n + 2 * radius), jnp.float32
        )
        g = jnp.asarray(rng.standard_normal(2 * radius + 1), jnp.float32)
        kops.xcorr1d(f, g, strategy="baseline", block_size="auto")


# ---------------------------------------------------------------------------
# Static-audit shapes (repro.analysis)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AuditShape:
    """One declarative entry of the static-audit registry: the auditor
    builds every valid plan in the cross product of the listed axes
    (strategies × fuse × unroll × batch) over this problem identity and
    proves bounds/coverage/VMEM/key obligations for each — no kernels
    run. ``smoke`` mirrors the warm registry's smoke extents;
    ``full`` its benchmark extents (``python -m repro.analysis
    --full``). ``accuracy=0`` selects the hand-built cross-correlation
    tap set instead of generated central differences. ``ghost`` lists
    the boundary offers planned (``None``: a padded operand; ``"zero"``:
    zero ghost cells the kernel may stage itself, planned for the
    ``swc`` depth-1 plans only)."""

    name: str
    ndim: int
    accuracy: int
    smoke: tuple[int, ...]
    full: tuple[int, ...]
    n_f: int = 1
    n_out: int = 1
    n_aux: int = 0
    dtype: str = "float32"
    strategies: tuple[str, ...] = ("swc",)
    fuse: tuple[int, ...] = (1,)
    unroll: tuple[int, ...] = (1,)
    batch: tuple[int, ...] = (1,)
    ghost: tuple[str | None, ...] = (None,)

    def operator_set(self):
        import numpy as _np

        from repro.core.stencil import (
            derivative_operator_set,
            xcorr_operator_set,
        )

        if self.accuracy == 0:
            g = _np.arange(1, 2 * 32 + 2, dtype=_np.float64)
            return xcorr_operator_set(g, self.ndim)
        return derivative_operator_set(self.ndim, self.accuracy)

    def plans(self, domain: tuple[int, ...]):
        """Yield every valid (plan, ops) over this entry's axis
        product at the given interior extents (invalid combinations —
        the same constraints ``StencilPlan`` enforces — are skipped,
        not errors)."""
        import itertools

        from repro.kernels.plan import plan_stencil

        ops = self.operator_set()
        radii = ops.radius_per_axis()
        for s, f, u, b, g in itertools.product(
            self.strategies, self.fuse, self.unroll, self.batch, self.ghost
        ):
            if g is not None and (s, f, u, b) != ("swc", 1, 1, 1):
                continue
            if s == "swc_stream" and (
                self.ndim == 1 or self.n_aux or u != 1
            ):
                continue
            if s == "tc" and (
                u != 1 or self.dtype not in ("float32", "bfloat16")
            ):
                continue
            if u != 1 and f != 1:
                continue
            if f > 1 and self.n_out != self.n_f + self.n_aux:
                continue
            if b > 1 and self.n_aux and f > 1:
                continue
            padded = tuple(
                n + 2 * r * f for n, r in zip(domain, radii)
            )
            lead = (b,) if b > 1 else ()
            yield plan_stencil(
                ops, lead + (self.n_f,) + padded, self.n_out,
                strategy=s, dtype=self.dtype, n_aux=self.n_aux,
                unroll=u, fuse_steps=f, ghost=g,
            ), ops


# Mirrors the warm registry above (same figures, same smoke/full
# extents) plus the axes only the auditor sweeps today (unroll > 1,
# aux carries, batch 2). Every lowerable strategy appears at every
# rank it supports.
AUDIT_SHAPES: tuple[AuditShape, ...] = (
    AuditShape(
        "fig11/diffusion3d", 3, 2, (32, 32, 64), (256, 256, 256),
        strategies=("swc", "swc_stream", "tc"), fuse=(1, 2),
    ),
    AuditShape(
        "fig11/diffusion3d_o6", 3, 6, (32, 32, 64), (256, 256, 256),
        strategies=("swc", "swc_stream", "tc"), fuse=(1, 2),
    ),
    AuditShape(
        "fig11/diffusion1d", 1, 6, (1 << 14,), (1 << 22,),
        strategies=("swc", "tc"), fuse=(1, 2), unroll=(1, 2),
    ),
    AuditShape(
        "fig11/diffusion2d", 2, 6, (64, 128), (2048, 2048),
        strategies=("swc", "swc_stream", "tc"), fuse=(1, 2, 3),
        unroll=(1, 2), batch=(1, 2, 4),
    ),
    AuditShape(
        "fig13-14/mhd8f", 3, 6, (16, 16, 64), (64, 64, 64),
        n_f=8, n_out=8, strategies=("swc", "swc_stream", "tc"),
    ),
    AuditShape(
        "engine/rk-aux-carry", 2, 6, (64, 128), (2048, 2048),
        n_f=1, n_out=2, n_aux=1, strategies=("swc", "tc"),
        fuse=(1, 2),
    ),
    AuditShape(
        "fig07-09/xcorr1d-r32", 1, 0, (1 << 14,), (1 << 22,),
        strategies=("swc",), unroll=(1, 2),
    ),
    AuditShape(
        "fig11/diffusion2d_bf16", 2, 6, (64, 128), (2048, 2048),
        dtype="bfloat16", strategies=("swc", "tc"),
    ),
    AuditShape(
        "acoustic/shot-o8", 3, 8, (24, 32, 128), (512, 512, 512),
        n_aux=3, strategies=("swc", "tc"), ghost=(None, "zero"),
    ),
)


REGISTRY: tuple[WarmEntry, ...] = (
    WarmEntry("fig11/diffusion3d_swc", _warm_diffusion3d),
    WarmEntry("fig11/diffusion1d2d_swc", _warm_diffusion_lowdim),
    WarmEntry("fig11/diffusion_swc_stream", _warm_diffusion_stream),
    WarmEntry("fig11/diffusion_tc", _warm_diffusion_tc),
    WarmEntry("fig11/diffusion_auto", _warm_diffusion_auto),
    WarmEntry("fig13-14/mhd_swc", _warm_mhd),
    WarmEntry("fig13/mhd_swc_stream", _warm_mhd_stream),
    WarmEntry("fig07-09/xcorr1d", _warm_xcorr1d),
)

"""Diffusion equation ∂f/∂t = α∇²f as a linear stencil computation
(paper Sec. 3.2, Figs. 10-12).

Forward-Euler time integration folds into a SINGLE merged cross-
correlation kernel g = c^(1) + Δt·α·c^(2) (paper Eqs. 5-7): one stencil
application per step, any dimensionality, any even accuracy order.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fusion import FusedStencilOp
from repro.core.stencil import (
    OperatorSet,
    diffusion_kernel_1d,
    diffusion_kernel_nd,
)
from repro.kernels import ops as kops


@dataclasses.dataclass(frozen=True)
class DiffusionProblem:
    """Numerical setup following the paper's App. B (Table B2): periodic
    domain of extent 2π per axis, Δs_i = 2π/n_i."""

    shape: tuple[int, ...]  # grid points per axis (z, y, x ordering)
    accuracy: int = 6  # FD accuracy order (radius = accuracy // 2)
    alpha: float = 1.0
    safety: float = 0.2  # dt = safety · min(Δs)² / (2·d·α)

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def spacing(self) -> tuple[float, ...]:
        return tuple(2.0 * np.pi / n for n in self.shape)

    @property
    def dt(self) -> float:
        d = self.ndim
        h = min(self.spacing)
        return self.safety * h * h / (2.0 * d * self.alpha)

    @property
    def radius(self) -> int:
        return self.accuracy // 2

    def merged_stencil(self):
        """Paper Eq. 7: identity + Δt·α·∇² as one stencil."""
        return diffusion_kernel_nd(
            self.ndim, self.accuracy, self.dt, self.alpha, self.spacing
        )

    def step_op(
        self,
        strategy: str = "hwc",
        block: tuple[int, ...] | str | None = None,
        fuse_steps: int | str = 1,
    ) -> FusedStencilOp:
        """One forward-Euler step as a fused op. ``strategy="swc"``
        lowers through the rank-generic engine at any dimensionality
        (1-D/2-D/3-D), ``strategy="swc_stream"`` through the
        explicit-streaming kernel (2-D/3-D), and ``strategy="tc"``
        through the MXU matmul lowering (any rank; f32/bf16 fields);
        ``strategy="auto"`` lets the cross-strategy tuning search pick
        the caching regime itself (hwc vs swc vs swc_stream vs tc,
        jointly with block/depth/stream —
        ``block`` defaults to ``"auto"`` in that case). ``block`` is a
        rank-length tile, ``"auto"`` for the persistent tuning cache,
        or None for the planner's default tile. ``fuse_steps`` is the
        temporal-fusion depth (each op call then advances that many
        Euler steps in one kernel, streamed or pipelined); ``"auto"``
        resolves block and depth jointly from the traffic model.
        """
        spec = dataclasses.replace(self.merged_stencil(), name="step")  # type: ignore[arg-type]
        ops = OperatorSet((spec,))
        return FusedStencilOp(
            ops=ops,
            phi=lambda d: d["step"],
            n_out=1,
            boundary_mode="periodic",
            strategy=strategy,
            block=block,
            fuse_steps=fuse_steps,
        )

    def init_field(self, seed: int = 0, amplitude: float = 1e-5) -> jnp.ndarray:
        """Benchmark initialization (paper Table B2: random in
        (-1e-5, 1e-5] for benchmarks)."""
        rng = np.random.default_rng(seed)
        f = rng.uniform(-amplitude, amplitude, size=self.shape)
        return jnp.asarray(f[None], dtype=jnp.float32)  # (n_f=1, *shape)

    def fourier_mode(self, k: Sequence[int]) -> jnp.ndarray:
        """sin(k·x) eigenmode — decays analytically as exp(-α|k|²t)."""
        axes = [
            np.linspace(0.0, 2.0 * np.pi, n, endpoint=False)
            for n in self.shape
        ]
        grids = np.meshgrid(*axes, indexing="ij")
        phase = sum(ki * gi for ki, gi in zip(k, grids))
        return jnp.asarray(np.sin(phase)[None], dtype=jnp.float64)

    def analytic_decay(self, k: Sequence[int], t: float) -> float:
        return float(np.exp(-self.alpha * sum(ki * ki for ki in k) * t))


def step_1d_xcorr(
    f: jnp.ndarray,
    problem: DiffusionProblem,
    *,
    strategy: str = "hwc",
    block_size: int = 2048,
) -> jnp.ndarray:
    """1-D diffusion step via the cross-correlation kernel path (the
    paper's cuDNN/MIOpen-comparable formulation): pad periodically, then
    f' = g ⋆ f̂ with the merged kernel of Eq. 5."""
    g = jnp.asarray(
        diffusion_kernel_1d(
            problem.accuracy, problem.dt, problem.alpha, problem.spacing[0]
        ),
        f.dtype,
    )
    r = problem.radius
    fp = jnp.concatenate([f[-r:], f, f[:r]])
    return kops.xcorr1d(fp, g, strategy=strategy, block_size=block_size)


def simulate(
    problem: DiffusionProblem,
    f0: jnp.ndarray,
    n_steps: int,
    *,
    strategy: str = "hwc",
    block: tuple[int, ...] | str | None = None,
    fuse_steps: int | str = 1,
) -> jnp.ndarray:
    """Run ``n_steps`` of forward-Euler diffusion with the fused engine.

    ``fuse_steps > 1`` advances that many steps per kernel launch
    (temporal fusion; a remainder is finished at shallower depth so the
    step count stays exact)."""
    from repro.core.fusion import integrate

    op = problem.step_op(strategy, block, fuse_steps)

    @jax.jit
    def run(f):
        return integrate(op, f, n_steps)

    return run(f0)

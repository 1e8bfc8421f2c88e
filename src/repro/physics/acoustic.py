"""3-D isotropic acoustic wave propagation: the seismic forward model.

The inner loop of reverse-time migration and full-waveform inversion
(Shan, Araya-Polo et al., arXiv:2404.04441, "acoustic isotropic
kernel": 8th order in space, 2nd order in time):

  u_tt + σ(x) u_t = v(x)² ∇²u + v² s(t) δ(x − x_s)

on a (z, y, x) grid of spacing ``h`` with z pointing down, a read-only
velocity model ``v``, an absorbing layer of ``L`` points on the four
sides and the bottom, a free surface on top, a point source fired every
step and a plane of receivers sampled every step.

Time stepping — leapfrog, central in time. With d = σ·dt/2,

  (u⁺ − 2u + u⁻)/dt² + σ (u⁺ − u⁻)/(2 dt) = v² ∇²₈u
  ⇒ u⁺ = 2b·u − (2b − 1)·u⁻ + a·∇²₈u,   a = v²dt²/(1 + d),  b = 1/(1 + d)

(``(1 − d)/(1 + d) = 2b − 1``). ``a`` and ``b`` are computed once per
run. One step is one fused stencil launch: ``f = u`` (one field, so the
Laplacian is taken of ``u`` alone) and ``aux = (u⁻, a, b)``, three
arrays handed to the kernel as three operands, so the level that
changes every step and the two fields that never change are not
stacked in HBM on any step.

Time step — ``dt = cfl·h/v_max``. The leapfrog is stable while
``v·dt/h · sqrt(Σ_axes λ) ≤ 2``, with λ the largest magnitude of the
1-D second-difference symbol times h², reached at k·h = π: for the
8th-order weights c₀ = −205/72, c₁ = 8/5, c₂ = −1/5, c₃ = 8/315,
c₄ = −1/560, λ = |c₀ + 2(−c₁ + c₂ − c₃ + c₄)| = 6.5016. In 3-D the
limit is ``v_max·dt/h ≤ 2/sqrt(3·6.5016) = 0.4529``; ``cfl = 0.4``
keeps 12 % below it. The damping term only lowers the amplification.

Damping — ``σ = σ_max (dist/L)²``, ``dist`` the Euclidean distance
from a point to the undamped box (so ``(dist/L)²`` is the sum of the
per-axis ``(depth into the layer/L)²``), with
``σ_max = 3·v_max·ln(1/R)/(2·L·h)`` for a target reflection ``R``. The
top face (z = 0) is a free surface: no layer. Ghost cells are zero
Dirichlet on all six faces (``boundary_mode="dirichlet"``).

Source — a Ricker wavelet of peak frequency ``f0`` and delay ``1/f0``,
fired again every ``source_period`` steps, at one grid point: each step
adds ``a[x_s]·A·w(t_n)/h³`` to ``u⁺`` there. Receivers — the plane
``z = receiver_depth``, every ``receiver_stride``-th point in y and x
between the side layers; ``u⁺`` is recorded there every step.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core

from repro.core.fusion import FusedStencilOp
from repro.core.stencil import OperatorSet, derivative_operator_set


@dataclasses.dataclass(frozen=True)
class AcousticProblem:
    """Numerics of one shot: grid, layer, time step rule, source and
    receiver geometry. Velocities are m/s, lengths m, times s."""

    shape: tuple[int, int, int]  # (z, y, x), z down from the surface
    spacing: float = 10.0  # h
    accuracy: int = 8
    layer: int = 40  # L, absorbing points on the sides and bottom
    reflection: float = 1e-3  # R, target reflection of the layer
    cfl: float = 0.4
    peak_frequency: float = 10.0  # f0 of the Ricker wavelet
    source: tuple[int, int, int] = (4, 256, 256)
    source_amplitude: float = 1e-2  # A
    source_period: int = 232  # steps between two firings
    receiver_depth: int = 2
    receiver_stride: int = 2

    def dt(self, v_max: float) -> float:
        return self.cfl * self.spacing / v_max

    def sigma_max(self, v_max: float) -> float:
        return (
            3.0 * v_max * math.log(1.0 / self.reflection)
            / (2.0 * self.layer * self.spacing)
        )

    @property
    def receiver_slices(self) -> tuple[slice, slice]:
        """(y, x) slices of the receiver plane: stride points between
        the side layers."""
        L, s = self.layer, self.receiver_stride
        return tuple(slice(L, n - L, s) for n in self.shape[1:])

    def receivers(self, u: jnp.ndarray) -> jnp.ndarray:
        """The receiver plane of a (1, z, y, x) level, (ny, nx): one
        strided slice (numpy-style strided indexing would lower to a
        gather of every point)."""
        (y, x), z = self.receiver_slices, self.receiver_depth
        return jax.lax.slice(
            u, (0, z, y.start, x.start), (1, z + 1, y.stop, x.stop),
            (1, 1, y.step, x.step),
        )[0, 0]

    @property
    def n_receivers(self) -> int:
        return math.prod(len(range(n)[sl]) for n, sl in zip(
            self.shape[1:], self.receiver_slices
        ))

    def operator_set(self) -> OperatorSet:
        """The operators φ reads: the value and the three second
        derivatives of the 8th-order set (its first derivatives and
        mixed partials would only be traced and dropped)."""
        full = derivative_operator_set(
            3, self.accuracy, self.spacing, include_mixed=False
        )
        return OperatorSet(tuple(
            s for s in full.ops if s.name in ("val", "dzz", "dyy", "dxx")
        ))

    def ricker(self, t: jnp.ndarray, dt: float) -> jnp.ndarray:
        """The source's wavelet at step ``t`` (int32)."""
        f0 = self.peak_frequency
        tau = (t % self.source_period).astype(jnp.float32) * dt - 1.0 / f0
        arg = (math.pi * f0 * tau) ** 2
        return (1.0 - 2.0 * arg) * jnp.exp(-arg)

    def coefficients(self, velocity: jnp.ndarray, dt: float, sigma_max: float):
        """(a, b) as (1, z, y, x) fields from the velocity model."""
        L = self.layer
        depth = 0.0
        for axis, n in enumerate(self.shape):
            i = jax.lax.broadcasted_iota(jnp.float32, self.shape, axis)
            into = jnp.maximum(i - (n - 1 - L), 0.0)  # bottom / high side
            if axis > 0:  # the top (z = 0) is a free surface
                into = jnp.maximum(into, L - i)
            depth = depth + (into / L) ** 2
        d = sigma_max * depth * (dt / 2.0)
        b = 1.0 / (1.0 + d)
        a = velocity * velocity * (dt * dt) * b
        return a[None], b[None]


def _phi(d, aux):
    """u⁺ = 2b·u − (2b − 1)·u⁻ + a·∇²u, aux rows (u⁻, a, b)."""
    um, a, b = aux[0:1], aux[1:2], aux[2:3]
    lap = d["dzz"] + d["dyy"] + d["dxx"]
    return 2.0 * b * d["val"] - (2.0 * b - 1.0) * um + a * lap


def _count_primitives(jaxpr, names: set[str]) -> dict[str, int]:
    """Equations of each primitive in ``names``, sub-jaxprs included."""
    counts = dict.fromkeys(names, 0)
    stack = [jaxpr]
    while stack:
        jp = stack.pop()
        for eqn in jp.eqns:
            if eqn.primitive.name in counts:
                counts[eqn.primitive.name] += 1
            for p in eqn.params.values():
                for q in p if isinstance(p, (tuple, list)) else (p,):
                    if isinstance(q, jex_core.ClosedJaxpr):
                        stack.append(q.jaxpr)
                    elif isinstance(q, jex_core.Jaxpr):
                        stack.append(q)
    return counts


class AcousticSolver:
    """One shot of :class:`AcousticProblem` over a velocity model.

    ``run(u, um, t, n_steps)`` advances the levels ``u`` (step t) and
    ``um`` (step t − 1), each a (1, z, y, x) field stack, by
    ``n_steps`` steps in one jitted ``lax.scan`` and returns the new
    ``(u, um, t)`` and the ``(n_steps, ny, nx)`` receiver traces. Each
    step is one kernel launch, the injection and the receiver slice; the
    Pallas ``swc`` launch fills the zero ghost cells in VMEM where its
    plan can (``StencilPlan.ghost``), and other launches pad u first.
    The scan is unrolled: a rolled loop whose carry
    swaps two levels makes XLA copy a level into the loop's buffers on
    every step, where unrolled each level is handed on as the buffer it
    is. A shot of thousands of steps is advanced in calls of a few.

    ``counts`` adds up, over every ``run``, the steps advanced and what
    those steps executed: kernel launches, source injections, receiver
    samples written and ghost-cell pads, counted in the traced step
    program itself (a step that lost its launch or its injection counts
    0 of them).
    """

    def __init__(
        self,
        problem: AcousticProblem,
        velocity: jnp.ndarray,
        *,
        strategy: str = "swc",
        block: tuple[int, int, int] | None = None,
    ):
        if tuple(velocity.shape) != tuple(problem.shape):
            raise ValueError(
                f"velocity of shape {velocity.shape} for a "
                f"{problem.shape} grid"
            )
        self.problem = problem
        v_max = float(jnp.max(velocity))
        self.dt = problem.dt(v_max)
        self.a, self.b = jax.jit(
            problem.coefficients, static_argnums=(1, 2)
        )(velocity, self.dt, problem.sigma_max(v_max))
        zs, ys, xs = problem.source
        self.source_gain = (
            self.a[0, zs, ys, xs] * problem.source_amplitude
            / problem.spacing**3
        )
        self.op = FusedStencilOp(
            problem.operator_set(), _phi, n_out=1,
            boundary_mode="dirichlet", strategy=strategy, block=block,
            fuse_steps=1,
        )
        self._run = jax.jit(self._advance, static_argnames="n_steps")
        self.counts = dict.fromkeys(
            ("steps", "launches", "injections", "receiver_samples", "pads"),
            0,
        )

    def _step(self, carry, a, b, gain):
        u, um, t = carry
        p = self.problem
        up = self.op(u, aux=(um, a, b))
        up = up.at[(0,) + p.source].add(gain * p.ricker(t, self.dt))
        return (up, u, t + 1), p.receivers(up)

    def _advance(self, u, um, t, a, b, gain, *, n_steps: int):
        (u, um, t), traces = jax.lax.scan(
            lambda c, _: self._step(c, a, b, gain),
            (u, um, t), None, length=n_steps, unroll=True,
        )
        return u, um, t, traces

    @functools.cached_property
    def per_step(self) -> dict[str, int]:
        """What one traced step executes: kernel launches
        (``pallas_call``), injections (``scatter-add``), receiver
        samples (the size of its trace row) and ghost-cell pads
        (``pad``)."""
        lvl = jax.ShapeDtypeStruct((1,) + self.problem.shape, jnp.float32)
        t = jax.ShapeDtypeStruct((), jnp.int32)
        closed, (_, trace) = jax.make_jaxpr(
            lambda u, um, t: self._step(
                (u, um, t), self.a, self.b, self.source_gain
            ),
            return_shape=True,
        )(lvl, lvl, t)
        c = _count_primitives(
            closed.jaxpr, {"pallas_call", "scatter-add", "pad"}
        )
        return {
            "launches": c["pallas_call"],
            "injections": c["scatter-add"],
            "receiver_samples": math.prod(trace.shape),
            "pads": c["pad"],
        }

    def run(self, u, um, t, n_steps: int):
        """Advance ``n_steps`` steps; returns (u, um, t, traces)."""
        out = self._run(
            u, um, t, self.a, self.b, self.source_gain, n_steps=n_steps
        )
        self.counts["steps"] += n_steps
        for k, v in self.per_step.items():
            self.counts[k] += n_steps * v
        return out

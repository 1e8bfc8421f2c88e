"""Plain reference for the order-8 acoustic (seismic) configuration.

3-D isotropic acoustic wave equation u_tt + σ u_t = v² ∇²u + v² s(t)
δ(x − x_s) on a (z, y, x) grid of spacing h (z down), leapfrog in time:

  u⁺ = 2b·u − (2b − 1)·u⁻ + a·∇²u,  a = v²dt²/(1 + d),  b = 1/(1 + d),
  d = σ·dt/2,  dt = cfl·h/max(v),

∇² the 8th-order central Laplacian over zero ghost cells on all six
faces; σ = σ_max Σ_axes (depth into the layer/L)² in a layer of L points
on the four sides and the bottom (none under the free surface at z = 0),
σ_max = 3·max(v)·ln(1/R)/(2·L·h); after each update the source adds
a[x_s]·A·w(t_n)/h³ at its point, w a Ricker wavelet of peak frequency
f0 and delay 1/f0 fired every ``period_steps`` steps; u⁺ is then
recorded on the receiver plane. Written from those equations in plain
jax.numpy, float32 unless told otherwise: one zero-padded copy per step
and a slice of it per tap, no kernels and nothing imported from the
program.

A state ``f`` stacks the two levels, (2, *grid) = (u at step t,
u at step t − 1). ``inputs`` gives the velocity model drawn from a seed
and the step number t0 at which the shot starts.
"""
from __future__ import annotations

import math
from fractions import Fraction

import jax
import jax.numpy as jnp


def second_derivative_coeffs(order: int) -> list[float]:
    """Central second-derivative weights over offsets -r..r, r = order/2,
    from the Taylor conditions sum_k c_k k^m = 2 [m == 2], m < 2r + 1."""
    r = order // 2
    offsets = list(range(-r, r + 1))
    n = len(offsets)
    a = [[Fraction(k) ** m for k in offsets] for m in range(n)]
    b = [Fraction(2 if m == 2 else 0) for m in range(n)]
    for col in range(n):
        piv = next(i for i in range(col, n) if a[i][col] != 0)
        a[col], a[piv] = a[piv], a[col]
        b[col], b[piv] = b[piv], b[col]
        for i in range(n):
            if i != col and a[i][col] != 0:
                q = a[i][col] / a[col][col]
                a[i] = [x - q * y for x, y in zip(a[i], a[col])]
                b[i] = b[i] - q * b[col]
    return [float(b[i] / a[i][i]) for i in range(n)]


def _key(seed: int):
    seed = int(seed) % (1 << 64)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def velocity_model(config: dict, grid, seed: int) -> jnp.ndarray:
    """Layered model: ``layers`` layers from ``top`` to ``bottom`` m/s
    with depth, their interfaces dipping along y and x with slopes drawn
    from the seed, times a point-wise factor 1 ± ``perturbation``."""
    vm = config["numerics"]["velocity"]
    nz, ny, nx = grid
    k_dip, k_pert = jax.random.split(jax.random.fold_in(_key(seed), 1))
    slope = jax.random.uniform(
        k_dip, (2,), jnp.float32, -vm["max_slope"], vm["max_slope"]
    )
    z, y, x = (
        jax.lax.broadcasted_iota(jnp.float32, tuple(grid), a) for a in range(3)
    )
    along = z - slope[0] * (y - ny / 2) - slope[1] * (x - nx / 2)
    n = vm["layers"]
    layer = jnp.clip(jnp.floor(along * n / nz), 0, n - 1)
    v = vm["top"] + (vm["bottom"] - vm["top"]) * layer / (n - 1)
    noise = jax.random.uniform(k_pert, tuple(grid), jnp.float32, -1.0, 1.0)
    return v * (1.0 + vm["perturbation"] * noise)


def inputs(config: dict, grid, f, seed: int = 0) -> tuple:
    """(velocity model drawn from ``seed``, t0 = 0): a shot from rest."""
    return velocity_model(config, grid, seed), jnp.int32(0)


def _setup(config: dict, grid, velocity, dtype):
    num = config["numerics"]
    h, L = num["spacing"], num["absorbing"]["layer"]
    v_max = jnp.max(velocity)
    dt = num["cfl"] * h / v_max
    sigma_max = (
        3.0 * v_max * math.log(1.0 / num["absorbing"]["reflection"])
        / (2.0 * L * h)
    )
    depth = jnp.zeros(tuple(grid), jnp.float32)
    for axis, n in enumerate(grid):
        i = jnp.arange(n, dtype=jnp.float32)
        high = jnp.where(i >= n - L, i - (n - L - 1), 0.0)
        low = jnp.where(i < L, L - i, 0.0) if axis > 0 else 0.0 * i
        prof = ((high + low) / L) ** 2
        shape = [1, 1, 1]
        shape[axis] = n
        depth = depth + prof.reshape(shape)
    d = sigma_max * depth * dt / 2.0
    a = velocity * velocity * dt * dt / (1.0 + d)
    b = 1.0 / (1.0 + d)
    return dt, a.astype(dtype), b.astype(dtype)


def _ricker(config: dict, t, dt):
    src = config["numerics"]["source"]
    f0 = src["peak_frequency"]
    tau = (t % src["period_steps"]).astype(jnp.float32) * dt - 1.0 / f0
    arg = (jnp.pi * f0 * tau) ** 2
    return (1.0 - 2.0 * arg) * jnp.exp(-arg)


def advance_traces(
    config: dict, grid, f, steps: int, velocity, t0, *, dtype=jnp.float32
):
    """``steps`` leapfrog steps of the levels ``f`` (2, *grid) from step
    ``t0``, computed in ``dtype``. Returns (levels (2, *grid) f32,
    traces (steps, ny, nx) f32)."""
    num = config["numerics"]
    h = num["spacing"]
    c2 = second_derivative_coeffs(num["order"])
    r = len(c2) // 2
    dt, a, b = _setup(config, grid, velocity, dtype)
    src = num["source"]
    zs, ys, xs = src["point"]
    gain = a[zs, ys, xs].astype(jnp.float32) * src["amplitude"] / h**3
    L, rec = num["absorbing"]["layer"], num["receivers"]
    ry = slice(L, grid[1] - L, rec["stride"])
    rx = slice(L, grid[2] - L, rec["stride"])

    def step(carry, _):
        u, um, t = carry
        up_ = jnp.pad(u, r)
        lap = jnp.zeros_like(u)
        for axis, n in enumerate(grid):
            for k, c in enumerate(c2):
                idx = tuple(
                    slice(k, k + m) if ax == axis else slice(r, r + m)
                    for ax, m in enumerate(grid)
                )
                lap = lap + jnp.asarray(c / (h * h), dtype) * up_[idx]
        up = 2 * b * u - (2 * b - 1) * um + a * lap
        up = up.at[zs, ys, xs].add((gain * _ricker(config, t, dt)).astype(dtype))
        return (up, u, t + 1), up[rec["depth"], ry, rx].astype(jnp.float32)

    carry = (f[0].astype(dtype), f[1].astype(dtype), jnp.asarray(t0, jnp.int32))
    (u, um, _), traces = jax.lax.scan(step, carry, None, length=steps)
    return jnp.stack([u, um]).astype(jnp.float32), traces


def advance(
    config: dict, grid, f, steps: int, velocity, t0, *, dtype=jnp.float32
):
    """The levels after ``steps`` steps, (2, *grid) f32."""
    return advance_traces(
        config, grid, f, steps, velocity, t0, dtype=dtype
    )[0]

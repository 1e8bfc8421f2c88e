"""Plain reference for the order-6 diffusion configuration.

Heat equation df/dt = alpha * laplacian(f), explicit Euler, periodic,
central differences of the configured order on a grid of extent 2*pi per
axis, dt = safety * min(h)^2 / (2 * d * alpha) (paper App. B, Table B2).
Written from those equations in plain jax.numpy: one wrapped copy per
step and a slice of it per tap, no kernels and nothing imported from the
program.

``f`` is a field stack ``(n_f, *spatial)``; the spatial axes are the
last ``len(grid)`` axes. Works on a sharded global array too: under jit
XLA moves the wrapped planes between chips itself.
"""
from __future__ import annotations

from fractions import Fraction

import jax
import jax.numpy as jnp
import numpy as np


def second_derivative_coeffs(order: int) -> list[float]:
    """Central second-derivative weights over offsets -r..r, r = order/2,
    from the Taylor conditions sum_k c_k k^m = 2 [m == 2], m < 2r + 1."""
    r = order // 2
    offsets = list(range(-r, r + 1))
    n = len(offsets)
    # Exact rational Gauss-Jordan on the Vandermonde system.
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


def dt(config: dict, grid) -> float:
    num = config["numerics"]
    h = min(2.0 * np.pi / n for n in grid)
    return num["dt_safety"] * h * h / (2.0 * len(grid) * num["alpha"])


def inputs(config: dict, grid, f) -> tuple:
    """Step inputs beyond the state: none, dt is fixed by the grid."""
    return ()


def advance(config: dict, grid, f, steps: int, *, dtype=jnp.float32):
    """``steps`` Euler steps of ``f`` computed in ``dtype``; returns f32."""
    num = config["numerics"]
    c2 = second_derivative_coeffs(num["order"])
    r = len(c2) // 2
    step_dt = dt(config, grid)
    nd = len(grid)
    scales = [
        step_dt * num["alpha"] / (2.0 * np.pi / n) ** 2 for n in grid
    ]

    def step(g, _):
        # One wrapped copy a step; every tap is a static slice of it, so
        # XLA reads the copy once per step instead of once per tap.
        lead = g.ndim - nd
        gp = jnp.pad(g, [(0, 0)] * lead + [(r, r)] * nd, mode="wrap")
        out = g
        for a in range(nd):
            for k, c in enumerate(c2):
                # gp[p + k] = g[p + k - r], the tap at offset k - r.
                idx = [slice(None)] * lead + [
                    slice(k, k + n) if b == a else slice(r, r + n)
                    for b, n in enumerate(grid)
                ]
                out = out + (c * scales[a]) * gp[tuple(idx)]
        return out, None

    g = f.astype(dtype)
    g, _ = jax.lax.scan(step, g, None, length=steps)
    return g.astype(jnp.float32)

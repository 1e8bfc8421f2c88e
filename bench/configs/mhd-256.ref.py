"""Plain reference for the 8-field compressible MHD configuration.

The paper's practical case (arXiv:2406.08923, Sec. 3.3 and App. A):
fields (lnrho, ux, uy, uz, s, ax, ay, az) on a periodic box of extent
2*pi per axis, central differences of the configured order, Williamson's
2N-storage RK3, ideal-gas closure. Written from the equations, in plain
jax.numpy, with nothing imported from the program:

  dlnrho/dt = -u.grad(lnrho) - div(u)
  du/dt     = -(u.grad)u - cs2 grad(s/cp + lnrho) + (j x B)/rho
              + nu [lap(u) + grad(div u)/3 + 2 S.grad(lnrho)]
              + zeta grad(div u)
  ds/dt     = -u.grad(s) + [H - C + div(K grad T) + eta mu0 j^2
              + 2 rho nu S:S + zeta rho (div u)^2] / (rho T)
  dA/dt     = u x B + eta lap(A)

with B = curl A, j = (grad(div A) - lap A) / mu0, S the traceless rate
of strain, cs2 = cs0^2 exp(gamma s/cp + (gamma-1)(lnrho - lnrho0)),
T = cs2 / ((gamma-1) cp) and div(K grad T) = K T (lap lnT + |grad lnT|^2).

Arrays are (8, z, y, x): x is the last axis. The right-hand side is
taken in slabs of z-planes so that its temporaries fit beside the
program's saved states on one chip.
"""
from __future__ import annotations

from fractions import Fraction
from math import factorial

import jax
import jax.numpy as jnp
import numpy as np

LNRHO, UX, SS, AX = 0, 1, 4, 5
RK3_ALPHA = (0.0, -5.0 / 9.0, -153.0 / 128.0)
RK3_BETA = (1.0 / 3.0, 15.0 / 16.0, 8.0 / 15.0)
SLAB = 32


def central_coeffs(deriv: int, order: int) -> list[float]:
    """Central weights over offsets -r..r (r = order/2) from the Taylor
    conditions sum_k c_k k^m = deriv! [m == deriv], m < 2r + 1."""
    r = order // 2
    offsets = list(range(-r, r + 1))
    n = len(offsets)
    a = [[Fraction(k) ** m for k in offsets] for m in range(n)]
    b = [Fraction(factorial(deriv) if m == deriv else 0) for m in range(n)]
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


def _roll_diff(g, coeffs, axis: int, h: float, deriv: int):
    r = len(coeffs) // 2
    out = 0.0
    for k, c in enumerate(coeffs):
        if c:
            out = out + (c / h**deriv) * jnp.roll(g, r - k, axis=axis)
    return out


def _slice_diff_z(g, coeffs, h: float, deriv: int, planes: int):
    """Derivative along z (axis 1) of a slab padded by r planes a side."""
    out = 0.0
    for k, c in enumerate(coeffs):
        if c:
            out = out + (c / h**deriv) * g[:, k : k + planes]
    return out


def _rhs_slab(g, p: dict, order: int, spacing, planes: int):
    """Time derivatives of the ``planes`` inner z-planes of slab ``g``."""
    r = order // 2
    hz, hy, hx = spacing
    c1, c2 = central_coeffs(1, order), central_coeffs(2, order)
    crop = lambda a: a[:, r : r + planes]  # noqa: E731
    # Spatial components: 0 = x (axis 3), 1 = y (axis 2), 2 = z (axis 1).
    dx = _roll_diff(g, c1, 3, hx, 1)
    dy = _roll_diff(g, c1, 2, hy, 1)
    d1 = [crop(dx), crop(dy), _slice_diff_z(g, c1, hz, 1, planes)]
    d2 = [[None] * 3 for _ in range(3)]
    d2[0][0] = crop(_roll_diff(g, c2, 3, hx, 2))
    d2[1][1] = crop(_roll_diff(g, c2, 2, hy, 2))
    d2[2][2] = _slice_diff_z(g, c2, hz, 2, planes)
    d2[0][1] = d2[1][0] = crop(_roll_diff(dy, c1, 3, hx, 1))
    d2[0][2] = d2[2][0] = _slice_diff_z(dx, c1, hz, 1, planes)
    d2[1][2] = d2[2][1] = _slice_diff_z(dy, c1, hz, 1, planes)
    v = crop(g)

    gam, cp = p["gamma"], p["cp"]
    nu, zeta, eta, mu0 = p["nu"], p["zeta"], p["eta"], p["mu0"]
    lnrho, s = v[LNRHO], v[SS]
    u = [v[UX + c] for c in range(3)]
    grad = lambda i: [d1[c][i] for c in range(3)]  # noqa: E731
    lap = lambda i: d2[0][0][i] + d2[1][1][i] + d2[2][2][i]  # noqa: E731

    def graddiv(base):
        return [sum(d2[c][e][base + e] for e in range(3)) for c in range(3)]

    def cross(a, b):
        return [
            a[1] * b[2] - a[2] * b[1],
            a[2] * b[0] - a[0] * b[2],
            a[0] * b[1] - a[1] * b[0],
        ]

    def dot(a, b):
        return a[0] * b[0] + a[1] * b[1] + a[2] * b[2]

    g_lnrho, g_s = grad(LNRHO), grad(SS)
    div_u = sum(d1[c][UX + c] for c in range(3))
    # B = curl A
    b = [
        d1[1][AX + 2] - d1[2][AX + 1],
        d1[2][AX + 0] - d1[0][AX + 2],
        d1[0][AX + 1] - d1[1][AX + 0],
    ]
    lap_a = [lap(AX + c) for c in range(3)]
    j = [(gd - la) / mu0 for gd, la in zip(graddiv(AX), lap_a)]
    # Traceless rate of strain; du[c][e] = d u_c / d x_e.
    du = [[d1[e][UX + c] for e in range(3)] for c in range(3)]
    strain = [
        [
            0.5 * (du[c][e] + du[e][c]) - (div_u / 3.0 if c == e else 0.0)
            for e in range(3)
        ]
        for c in range(3)
    ]
    s_cp_lnrho = gam * s / cp + (gam - 1.0) * (lnrho - p["lnrho0"])
    cs2 = p["cs0"] ** 2 * jnp.exp(s_cp_lnrho)
    rho = jnp.exp(lnrho)
    temp = cs2 / ((gam - 1.0) * cp)

    dlnrho = -dot(u, g_lnrho) - div_u

    gd_u = graddiv(UX)
    jxb = cross(j, b)
    du_dt = []
    for c in range(3):
        adv = dot(u, grad(UX + c))
        press = cs2 * (g_s[c] / cp + g_lnrho[c])
        visc = nu * (
            lap(UX + c)
            + gd_u[c] / 3.0
            + 2.0 * dot(strain[c], g_lnrho)
        ) + zeta * gd_u[c]
        du_dt.append(-adv - press + jxb[c] / rho + visc)

    g_lnt = [(gam / cp) * g_s[c] + (gam - 1.0) * g_lnrho[c] for c in range(3)]
    lap_lnt = (gam / cp) * lap(SS) + (gam - 1.0) * lap(LNRHO)
    heating = (
        (p["heat"] - p["cool"])
        + p["kappa"] * temp * (lap_lnt + dot(g_lnt, g_lnt))
        + eta * mu0 * dot(j, j)
        + 2.0 * nu * rho * sum(
            strain[c][e] ** 2 for c in range(3) for e in range(3)
        )
        + zeta * rho * div_u**2
    )
    ds = -dot(u, g_s) + heating / (rho * temp)

    uxb = cross(u, b)
    da = [uxb[c] + eta * lap_a[c] for c in range(3)]
    return jnp.stack([dlnrho, *du_dt, ds, *da])


def _spacing(grid):
    return tuple(2.0 * np.pi / n for n in grid)


def rhs(config: dict, grid, f):
    """All eight time derivatives of ``f`` (8, z, y, x), slab by slab."""
    num = config["numerics"]
    order, p = num["order"], num["params"]
    r = order // 2
    nz = f.shape[1]
    planes = SLAB if nz % SLAB == 0 else nz
    fz = jnp.concatenate([f[:, nz - r :], f, f[:, :r]], axis=1)
    starts = jnp.arange(0, nz, planes)

    def one(z0):
        g = jax.lax.dynamic_slice_in_dim(fz, z0, planes + 2 * r, axis=1)
        return _rhs_slab(g, p, order, _spacing(grid), planes)

    out = jax.lax.map(one, starts)  # (slabs, 8, planes, y, x)
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape(f.shape)


def inputs(config: dict, grid, f) -> tuple:
    """The step's dt, by the configuration's CFL rule on state ``f``."""
    num = config["numerics"]
    p, rule = num["params"], num["dt_rule"]
    h = min(_spacing(grid))
    umax = jnp.sqrt(jnp.max(jnp.sum(f[UX : UX + 3] ** 2, axis=0)))
    cs2 = p["cs0"] ** 2 * jnp.exp(
        p["gamma"] * f[SS] / p["cp"]
        + (p["gamma"] - 1.0) * (f[LNRHO] - p["lnrho0"])
    )
    v = umax + jnp.sqrt(jnp.max(cs2))
    dt_adv = rule["cdt"] * h / jnp.maximum(v, 1e-30)
    diff = max(p["nu"], p["eta"], p["kappa"] / p["cp"])
    dt_diff = rule["cdtv"] * h * h / diff
    return (jnp.minimum(dt_adv, dt_diff).astype(jnp.float32),)


def advance(config: dict, grid, f, steps: int, dt, *, dtype=jnp.float32):
    """``steps`` RK3 steps of ``f`` computed in ``dtype``; returns f32."""
    g = f.astype(dtype)
    dt = jnp.asarray(dt, dtype)
    for _ in range(steps):
        w = jnp.zeros_like(g)
        for a, b in zip(RK3_ALPHA, RK3_BETA):
            w = a * w + dt * rhs(config, grid, g)
            g = g + b * w
    return g.astype(jnp.float32)

"""Decaying MHD turbulence on a 32³ periodic box — the paper's production
use case (Sec. 3.3) end to end: CFL-stepped RK3 integration with the
fused stencil engine, kinetic/magnetic energy diagnostics, and a
cross-check between caching strategies mid-run.

Run:  PYTHONPATH=src python examples/mhd_simulation.py          (~2 min)
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.physics.mhd import (
    AX, AZ, LNRHO, MHDParams, MHDSolver, SS, UX, UZ,
)


def energies(f):
    rho = jnp.exp(f[LNRHO])
    u2 = jnp.sum(f[UX : UZ + 1] ** 2, axis=0)
    e_kin = float(jnp.mean(0.5 * rho * u2))
    # B = ∇×A via spectral curl would be overkill for a diagnostic; use
    # simple central differences at 2nd order on the periodic box.
    a = f[AX : AZ + 1]
    def d(q, ax):
        return (jnp.roll(q, -1, ax) - jnp.roll(q, 1, ax)) * (16 / (4 * np.pi))
    bx = d(a[2], 1) - d(a[1], 0)
    by = d(a[0], 0) - d(a[2], 2)
    bz = d(a[1], 2) - d(a[0], 1)
    e_mag = float(jnp.mean(0.5 * (bx**2 + by**2 + bz**2)))
    return e_kin, e_mag


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=32)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--amplitude", type=float, default=0.05)
    args = ap.parse_args()
    use_compile_cache()

    solver = MHDSolver(
        (args.n,) * 3,
        params=MHDParams(nu=2e-2, eta=2e-2, kappa=2e-3),
        strategy="hwc",
    )
    f = solver.init_smooth(seed=3, amplitude=args.amplitude,
                           dtype=jnp.float32)
    step = jax.jit(lambda f, dt: solver.step(f, dt))

    print(f"MHD {args.n}^3, nu=eta=2e-2, RK3 + 6th-order FD")
    print(f"{'step':>5} {'t':>8} {'dt':>9} {'E_kin':>12} {'E_mag':>12} "
          f"{'max|u|':>9}")
    t_sim, t0 = 0.0, time.time()
    for i in range(args.steps):
        dt = float(solver.cfl_dt(f))
        f = step(f, dt)
        t_sim += dt
        if i % 8 == 0 or i == args.steps - 1:
            ek, em = energies(f)
            umax = float(jnp.abs(f[UX : UZ + 1]).max())
            print(f"{i:5d} {t_sim:8.3f} {dt:9.5f} {ek:12.4e} {em:12.4e} "
                  f"{umax:9.4f}", flush=True)
        assert np.isfinite(float(f.max())), "simulation blew up"
    wall = time.time() - t0
    ups = args.steps * args.n**3 / wall
    print(f"\n{args.steps} steps in {wall:.1f}s "
          f"({ups/1e6:.2f} Mupdates/s on CPU)")

    # Strategy cross-check mid-state (the paper's verification protocol),
    # with the SWC block resolved by the persistent autotuner: a cache
    # hit replays the recorded winner, a miss runs the paper's
    # rank-then-measure search once and persists it.
    from repro.tuning import format_block, lookup_fused_nd

    swc = MHDSolver((args.n,) * 3, params=solver.params, strategy="swc",
                    block="auto")
    err = float(jnp.abs(solver.rhs(f) - swc.rhs(f)).max())
    scale = float(jnp.abs(solver.rhs(f)).max())
    rec = lookup_fused_nd(f, swc.operator_set, f.shape[0], "swc")
    if rec is not None:
        print(f"auto-tuned SWC block: {format_block(rec.block)} "
              f"[{rec.source}]")
    print(f"HWC vs SWC on evolved state: max abs diff {err:.2e} "
          f"(field scale {scale:.2e})")
    assert err <= 1e-4 * max(scale, 1.0)
    print("mhd_simulation OK")


if __name__ == "__main__":
    main()

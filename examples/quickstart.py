"""Quickstart: the stencil engine in ~a minute on CPU.

1. The paper's fused stencil engine (φ(A·B)) on a 3-D multiphysics RHS,
   HWC vs SWC strategies agreeing bitwise-ish.
2. The diffusion equation solved with ONE merged cross-correlation kernel
   (paper Eq. 5-7), validated against the exact discrete eigenvalue.

Everything they touch lives in ``repro.{core,kernels,physics,tuning}``
(the StencilPlan pipeline — see docs/architecture.md).

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax.numpy as jnp
import numpy as np


def stencil_demo():
    print("=== 1. fused multiphysics stencil (paper Sec. 4.4) ===")
    from repro.physics.mhd import MHDSolver

    solver_hwc = MHDSolver((16, 16, 16), strategy="hwc")
    # strategy="auto": the persistent autotuner (repro.tuning) picks the
    # whole caching regime — hwc (XLA-managed) vs swc (Pallas VMEM
    # blocks) vs swc_stream — jointly with the block, measured on first
    # use and cached under ~/.cache/repro-tune (or $REPRO_TUNE_CACHE).
    # Run `python -m repro.tuning show` to see the recorded tables.
    solver_auto = MHDSolver((16, 16, 16), strategy="auto")
    f = solver_hwc.init_smooth(seed=0, amplitude=1e-2, dtype=jnp.float32)
    r1 = solver_hwc.rhs(f)
    r2 = solver_auto.rhs(f)
    err = float(jnp.abs(r1 - r2).max())
    rop = solver_auto.rhs_op().resolved(f)  # warm cache hit
    print(f"  8-field MHD RHS, 10 operators, 127 taps fused in one kernel")
    print(f"  strategy='auto' resolved to {rop.strategy!r} "
          f"(block={rop.block}, depth={rop.fuse_steps})")
    print(f"  XLA-managed (HWC) vs auto-tuned strategy max diff: {err:.2e}")
    dt = float(solver_hwc.cfl_dt(f))
    f1 = solver_hwc.step(f, dt)
    print(f"  one RK3 step (dt={dt:.3f}): max|Δf| = "
          f"{float(jnp.abs(f1 - f).max()):.3e}\n")


def diffusion_demo():
    print("=== 2. diffusion as one merged kernel (paper Eq. 5-7) ===")
    from repro.physics.diffusion import DiffusionProblem, simulate

    p = DiffusionProblem((16, 16, 32), accuracy=6)
    k = (1, 2, 1)
    f0 = p.fourier_mode(k)
    out = simulate(p, f0, 50)
    decay = float(jnp.linalg.norm(out) / jnp.linalg.norm(f0))
    spec = p.merged_stencil()
    lam = sum(
        c * np.cos(sum(ki * oi * hi for ki, oi, hi in zip(k, o, p.spacing)))
        for o, c in zip(spec.offsets, spec.coeffs)
    )
    print(f"  mode {k}: measured decay {decay:.6f}, "
          f"exact eigenvalue^50 {lam**50:.6f}\n")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    stencil_demo()
    diffusion_demo()
    print("quickstart OK")

"""Per-kernel allclose sweeps (shapes × dtypes) against the pure-jnp
oracles in kernels/ref.py (assignment requirement c).

The hypothesis property tests live in ``test_kernel_properties.py``,
which skips itself via ``pytest.importorskip`` when ``hypothesis`` (a
test extra) is absent — this module collects and runs on a bare
interpreter.
"""
import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.stencil import derivative_operator_set
from repro.kernels import ops, ref
# Kernel tests exercise the legacy 1-D entry point directly by design.
from repro.kernels.stencil1d import xcorr1d_pallas  # repolint: allow[legacy-kernel-import]

RNG = np.random.default_rng(42)


def _phi_test(d):
    lap = d["dxx"] + d["dyy"] + d["dzz"]
    o0 = d["val"][0] + 0.1 * lap[0] + d["dx"][1] * d["dy"][0]
    o1 = jnp.tanh(d["val"][1]) + d["dxy"][0] + d["dz"][1] * d["dxz"][0]
    return jnp.stack([o0, o1])


# --- 1-D cross-correlation sweeps ---------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
@pytest.mark.parametrize("radius", [0, 1, 5, 32, 200])
@pytest.mark.parametrize(
    "strategy,unroll",
    [("baseline", 1), ("pointwise", 4), ("pointwise", 7), ("elementwise", 4)],
)
def test_xcorr1d_sweep(dtype, radius, strategy, unroll):
    n = 2048
    f = jnp.asarray(RNG.standard_normal(n + 2 * radius), dtype)
    g = jnp.asarray(RNG.standard_normal(2 * radius + 1), dtype)
    out = xcorr1d_pallas(
        f, g, strategy=strategy, block_size=512, unroll=unroll,
        interpret=True,
    )
    expect = ref.xcorr1d(f, g)
    tol = 1e-4 if dtype == jnp.float32 else 1e-10
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expect), rtol=tol, atol=tol * 10
    )


def test_xcorr1d_nondivisible_n():
    f = jnp.asarray(RNG.standard_normal(1000 + 6), jnp.float32)
    g = jnp.asarray(RNG.standard_normal(7), jnp.float32)
    out = ops.xcorr1d(f, g, strategy="baseline", block_size=256,
                      interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.xcorr1d(f, g)), rtol=1e-4, atol=1e-4
    )


# --- fused 3-D kernel sweeps ---------------------------------------------------


@pytest.mark.parametrize("strategy", ["swc", "swc_stream"])
@pytest.mark.parametrize("accuracy", [2, 4, 6])
@pytest.mark.parametrize("block", [(4, 4, 8), (8, 8, 16), (2, 8, 16)])
def test_fused3d_sweep(strategy, accuracy, block):
    opset = derivative_operator_set(3, accuracy, spacing=0.2)
    r = opset.radius
    n_f, nz, ny, nx = 3, 8, 8, 16
    f = jnp.asarray(
        RNG.standard_normal((n_f, nz + 2 * r, ny + 2 * r, nx + 2 * r)),
        jnp.float32,
    )
    out = ops.fused_stencil_nd(
        f, opset, _phi_test, 2, block=block, strategy=strategy,
        interpret=True,
    )
    expect = ref.fused_stencil(f, opset, _phi_test)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expect), rtol=2e-3, atol=2e-3
    )


def test_fused3d_aux_inputs():
    opset = derivative_operator_set(3, 4, spacing=0.3)
    r = opset.radius
    f = jnp.asarray(RNG.standard_normal((2, 8 + 2 * r, 8 + 2 * r, 16 + 2 * r)),
                    jnp.float32)
    aux = jnp.asarray(RNG.standard_normal((2, 8, 8, 16)), jnp.float32)

    def phi(d, a):
        return d["val"] * 0.5 + a * d["dxx"]

    out = ops.fused_stencil_nd(
        f, opset, phi, 2, aux=aux, block=(4, 4, 8), strategy="swc",
        interpret=True,
    )
    expect = ref.fused_stencil(f, opset, phi, aux=aux)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expect), rtol=1e-4, atol=1e-4
    )

"""Hypothesis property tests on the stencil-engine invariants
(assignment requirement c): linearity, shift equivariance, fusion
equivalence, causality.

These RUN everywhere: with ``hypothesis`` installed (the ``test``/
``dev`` extras — what CI installs) they get the real coverage-guided
search; on a bare interpreter they fall back to the deterministic
seeded sampler in ``tests/_minihypothesis.py`` instead of being
skipped, so the invariants are always exercised.
"""
import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:  # bare interpreter: seeded fallback, not a skip
    from _minihypothesis import given, settings
    from _minihypothesis import strategies as st

from repro.core.stencil import derivative_operator_set
from repro.kernels import ref


def _phi_test(d):
    lap = d["dxx"] + d["dyy"] + d["dzz"]
    o0 = d["val"][0] + 0.1 * lap[0] + d["dx"][1] * d["dy"][0]
    o1 = jnp.tanh(d["val"][1]) + d["dxy"][0] + d["dz"][1] * d["dxz"][0]
    return jnp.stack([o0, o1])


@settings(max_examples=25, deadline=None)
@given(
    r=st.integers(0, 8),
    n=st.integers(16, 128),
    seed=st.integers(0, 2**31 - 1),
)
def test_xcorr_linearity(r, n, seed):
    """ζ is linear: ζ(αf + βh) = αζ(f) + βζ(h) (paper Sec. 2.4)."""
    rng = np.random.default_rng(seed)
    f = rng.standard_normal(n + 2 * r)
    h = rng.standard_normal(n + 2 * r)
    g = rng.standard_normal(2 * r + 1)
    a, b = rng.standard_normal(2)
    lhs = ref.xcorr1d_numpy(a * f + b * h, g)
    rhs = a * ref.xcorr1d_numpy(f, g) + b * ref.xcorr1d_numpy(h, g)
    np.testing.assert_allclose(lhs, rhs, rtol=1e-9, atol=1e-9)


@settings(max_examples=25, deadline=None)
@given(
    r=st.integers(1, 6),
    shift=st.integers(1, 5),
    seed=st.integers(0, 2**31 - 1),
)
def test_xcorr_shift_equivariance(r, shift, seed):
    """Stencils commute with translation on a periodic domain."""
    rng = np.random.default_rng(seed)
    n = 64
    f = rng.standard_normal(n)
    g = rng.standard_normal(2 * r + 1)

    def apply(fv):
        fp = np.concatenate([fv[-r:], fv, fv[:r]])
        return ref.xcorr1d_numpy(fp, g)

    np.testing.assert_allclose(
        apply(np.roll(f, shift)), np.roll(apply(f), shift),
        rtol=1e-9, atol=1e-9,
    )


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), accuracy=st.sampled_from([2, 4, 6]))
def test_fusion_equals_unfused(seed, accuracy):
    """φ(A·B) fused == evaluating each operator separately then φ."""
    rng = np.random.default_rng(seed)
    opset = derivative_operator_set(3, accuracy, spacing=0.5)
    r = opset.radius
    f = jnp.asarray(
        rng.standard_normal((2, 6 + 2 * r, 6 + 2 * r, 8 + 2 * r)),
        jnp.float64,
    )
    fused = ref.fused_stencil(f, opset, _phi_test)
    # unfused: evaluate each operator separately on a singleton-radius
    # view of the padded array (same interior geometry)
    R = opset.radius_per_axis()
    derivs = {}
    for spec in opset.ops:
        rr = spec.radius_per_axis() or (0, 0, 0)
        view = f[
            :,
            R[0] - rr[0] : f.shape[1] - (R[0] - rr[0]),
            R[1] - rr[1] : f.shape[2] - (R[1] - rr[1]),
            R[2] - rr[2] : f.shape[3] - (R[2] - rr[2]),
        ]
        derivs[spec.name] = ref.apply_operator_set(
            view, type(opset)((spec,))
        )[spec.name]
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(_phi_test(derivs)),
        rtol=1e-12, atol=1e-12,
    )

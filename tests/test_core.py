"""Core stencil-math, autotune, optimizer, and roofline-parser tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import rooflinelib as rl
from repro.tuning import (
    enumerate_candidates_nd,
    halo_overhead,
    vmem_working_set,
)
from repro.core.stencil import (
    OperatorSet,
    axis_stencil,
    central_difference_coeffs,
    derivative_operator_set,
    diffusion_kernel_1d,
    fornberg_weights,
    laplacian_stencil,
    mixed_partial_stencil,
)


def test_fornberg_matches_known_coefficients():
    # 2nd-order first derivative: [-1/2, 0, 1/2]
    np.testing.assert_allclose(
        central_difference_coeffs(1, 2), [-0.5, 0.0, 0.5], atol=1e-12
    )
    # 6th-order second derivative (the paper's MHD stencil)
    np.testing.assert_allclose(
        central_difference_coeffs(2, 6),
        [1 / 90, -3 / 20, 3 / 2, -49 / 18, 3 / 2, -3 / 20, 1 / 90],
        atol=1e-12,
    )
    # weights reproduce exact derivatives of polynomials
    w = fornberg_weights(0.0, np.arange(-3, 4), 2)[:, 2]
    x = np.arange(-3, 4, dtype=float)
    for p in range(6):
        d2 = np.dot(w, x**p)
        expect = p * (p - 1) * 0.0 ** (p - 2) if p >= 2 else 0.0
        np.testing.assert_allclose(d2, expect, atol=1e-8)


def test_derivative_set_matches_paper_configuration():
    """accuracy=6, 3-D: 10 operators, pruned n_k = 127 (paper Sec. 4.4)."""
    ops = derivative_operator_set(3, 6)
    assert ops.n_s == 10
    assert ops.n_k == 127
    assert ops.radius_per_axis() == (3, 3, 3)
    A, cols = ops.matrix()
    assert A.shape == (10, 127)
    # every column (tap) used by at least one operator
    assert (np.abs(A).sum(axis=0) > 0).all()


def test_mixed_partial_on_polynomial():
    """d²(x·y)/dxdy == 1 exactly for any accuracy order."""
    for acc in (2, 4, 6):
        spec = mixed_partial_stencil(2, 0, 1, acc, (1.0, 1.0))
        val = sum(
            c * (o[0] * o[1]) for o, c in zip(spec.offsets, spec.coeffs)
        )
        np.testing.assert_allclose(val, 1.0, atol=1e-10)


def test_laplacian_stencil_sums_axes():
    lap = laplacian_stencil(3, 6, 1.0)
    c2 = central_difference_coeffs(2, 6)
    # center tap = 3 × center coefficient
    center = dict(zip(lap.offsets, lap.coeffs))[(0, 0, 0)]
    np.testing.assert_allclose(center, 3 * c2[3], atol=1e-12)


def test_diffusion_kernel_merges_identity():
    g = diffusion_kernel_1d(6, dt=0.1, alpha=2.0)
    c2 = central_difference_coeffs(2, 6)
    np.testing.assert_allclose(g[3], 1.0 + 0.2 * c2[3], atol=1e-12)


def test_operator_set_rejects_duplicate_names():
    s = axis_stencil(1, 0, 1, 2, name="dx")
    with pytest.raises(ValueError):
        OperatorSet((s, s))


# --- autotune -------------------------------------------------------------------


def test_vmem_filter_discards_oversized_blocks():
    budget = 4 * 1024 * 1024
    cands = enumerate_candidates_nd(
        (256, 256, 256), (3, 3, 3), n_f=8, n_out=8, itemsize=4,
        vmem_budget=budget,
    )
    assert cands, "some candidate must fit"
    assert all(c.vmem_bytes <= budget for c in cands)
    roomier = enumerate_candidates_nd(
        (256, 256, 256), (3, 3, 3), n_f=8, n_out=8, itemsize=4,
        vmem_budget=4 * budget,
    )
    assert len(roomier) > len(cands), "the filter discarded nothing"
    # candidate accounting agrees with the working-set formula
    assert all(
        c.vmem_bytes == vmem_working_set(c.block, (3, 3, 3), 8, 8, 4)
        for c in cands
    )


def test_halo_overhead_monotone_in_block_size():
    small = halo_overhead((4, 4, 32), (3, 3, 3))
    big = halo_overhead((16, 16, 128), (3, 3, 3))
    assert big < small  # bigger blocks amortize the halo


def test_candidates_ranked_by_score():
    cands = enumerate_candidates_nd(
        (64, 64, 128), (3, 3, 3), n_f=8, n_out=8, itemsize=4
    )
    scores = [c.score for c in cands]
    assert scores == sorted(scores)


# --- roofline / HLO parsing ------------------------------------------------------


def test_collective_parser_on_synthetic_hlo():
    hlo = """
  %ag = f32[64,128]{1,0} all-gather(%x), replica_groups=[16,4]<=[64], dimensions={0}
  %ar = bf16[1024]{0} all-reduce(%y), replica_groups={{0,1,2,3}}, to_apply=%add
  %rs = f32[32]{0} reduce-scatter(%z), replica_groups=[8,8]<=[64]
  %cp = f32[16,16]{1,0} collective-permute(%w), source_target_pairs={{0,1}}
"""
    stats = rl.parse_collectives(hlo)
    assert stats.counts["all-gather"] == 1
    assert stats.result_bytes["all-gather"] == 64 * 128 * 4
    # group size 4 → wire = bytes × 3/4
    assert stats.wire_bytes["all-gather"] == int(64 * 128 * 4 * 3 / 4)
    assert stats.counts["all-reduce"] == 1
    assert stats.wire_bytes["all-reduce"] == int(2 * 1024 * 2 * 3 / 4)
    assert stats.counts["reduce-scatter"] == 1
    assert stats.wire_bytes["reduce-scatter"] == 32 * 4 * 7
    assert stats.counts["collective-permute"] == 1
    assert stats.wire_bytes["collective-permute"] == 16 * 16 * 4


def test_roofline_terms_and_dominance():
    r = rl.Roofline(
        flops=1e13, hbm_bytes=1e10, collective_result_bytes=0,
        collective_wire_bytes=1e9, chips=256, hw=rl.TPU_V5E,
    )
    assert r.compute_s == pytest.approx(1e13 / 197e12)
    assert r.memory_s == pytest.approx(1e10 / 819e9)
    assert r.collective_s == pytest.approx(1e9 / 50e9)
    assert r.dominant == "compute"
    assert 0 < r.roofline_fraction(0.5e13) <= 1.0


def test_machine_balance_matches_brief():
    assert rl.TPU_V5E.machine_balance(2) == pytest.approx(197e12 / 819e9)


# --- optimizer -------------------------------------------------------------------


def test_adamw_decreases_quadratic():
    from repro.optim import AdamWConfig, adamw_init, adamw_update

    cfg = AdamWConfig(lr_peak=0.1, warmup_steps=1, total_steps=100,
                      weight_decay=0.0)
    params = {"w": jnp.asarray([2.0, -3.0])}
    state = adamw_init(params)

    def loss(p):
        return jnp.sum(p["w"] ** 2)

    for _ in range(60):
        g = jax.grad(loss)(params)
        params, state, _ = adamw_update(cfg, g, state, params)
    assert float(loss(params)) < 0.1


def test_adamw_skips_decay_on_norms():
    from repro.optim.adamw import _decays

    class K:
        def __init__(self, key):
            self.key = key

    assert _decays((K("blocks"), K("wq")))
    assert not _decays((K("blocks"), K("ln1")))
    assert not _decays((K("blocks"), K("A_log")))


def test_grad_clip():
    from repro.optim import clip_by_global_norm

    g = {"a": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(20.0)
    total = float(jnp.sqrt(jnp.sum(clipped["a"] ** 2)))
    assert total == pytest.approx(1.0, rel=1e-5)

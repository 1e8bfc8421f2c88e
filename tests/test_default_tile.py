"""The derived default tile of rank-3 ``swc`` plans and the z-chunked
kernel body that lowers it.

``plan_stencil(..., block=None)`` derives a rank-3, unbatched ``swc``
tile from the plan's shape (``plan.default_block``): the candidate with
the fewest staged bytes per output point that fits the VMEM budget and
whose loop body stays within ``BODY_VREGS``. Every other plan keeps the
fixed per-rank default, and every explicit block is planned as given.
The emitter walks a large tile in z chunks (``StencilPlan.z_chunk``);
the chunked body computes the same taps in the same order per point,
so its output equals the (8, 8, 128) tile's bit for bit.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.bounds import audit_plan
from repro.analysis.vmem import check_vmem
from repro.kernels import ref
from repro.kernels.emit import fused_stencil_pallas
from repro.kernels.plan import (
    BODY_VREGS,
    VMEM_BUDGET,
    plan_stencil,
    plane_vregs,
    tpu_tile_ok,
    vmem_working_set,
)
from repro.physics.acoustic import AcousticProblem, _phi as acoustic_phi
from repro.physics.diffusion import DiffusionProblem

LEGACY = (8, 8, 128)


def _diffusion_ops(interior):
    return DiffusionProblem(interior, accuracy=6).step_op("swc").ops


def _case(name):
    """(ops, padded operand shape, n_out, plan kwargs, staged bytes per
    output point at the fixed (8, 8, 128) tile or None)."""
    if name == "shot512":
        # The acoustic shot: radius 4, depth 1, u⁻, a, b as three
        # one-row aux operands; 8 staged elements of u, 3 of aux and 1
        # of output per output point at (8, 8, 128).
        ops = AcousticProblem((512,) * 3).operator_set()
        return ops, (1, 520, 520, 520), 1, {"n_aux": 3}, 48.0
    interior, legacy = {
        # depth 2, radius 3: (20, 24, 256) staged for 8·8·128 outputs.
        "cube512": ((512, 512, 512), 64.0),
        # shard4's overlap split: the interior's z tile clamps to 5.
        "shard4_interior": ((500, 512, 512), 85.6),
        "shard4_border": ((6, 512, 512), None),
        "cube16": ((16, 16, 16), None),
        "awkward": ((12, 40, 136), None),
    }[name]
    padded = (1,) + tuple(n + 12 for n in interior)
    return _diffusion_ops(interior), padded, 1, {"fuse_steps": 2}, legacy


CASES = [
    "cube512", "shard4_interior", "shard4_border", "shot512", "cube16",
    "awkward",
]


@pytest.mark.parametrize("name", CASES)
def test_default_tile_divides_fits_and_stages_less(name):
    ops, padded, n_out, kw, legacy_bytes = _case(name)
    plan = plan_stencil(ops, padded, n_out, **kw)
    old = plan_stencil(ops, padded, n_out, block=LEGACY, **kw)
    assert all(n % t == 0 for n, t in zip(plan.interior, plan.block))
    assert tpu_tile_ok(plan.block, plan.interior)
    vmem = vmem_working_set(
        plan.block, plan.radii, plan.n_f, plan.n_out, 4,
        plan.fuse_steps, n_aux=plan.n_aux,
    )
    assert vmem <= VMEM_BUDGET
    assert (
        plan.z_chunk * plane_vregs(plan.block, plan.radii, plan.fuse_steps)
        <= BODY_VREGS
    )
    assert plan.block[0] % plan.z_chunk == 0
    if legacy_bytes is not None:
        assert old.staged_per_output == pytest.approx(legacy_bytes)
    assert plan.staged_per_output < old.staged_per_output


@pytest.mark.parametrize("name", CASES)
def test_default_tile_audits_clean(name):
    """The auditor shadow-runs the chunked body of the derived plan:
    loads inside the staged window, the output tile covered, the
    scratch generation written before it is read, and the measured
    working set equal to the shared VMEM formula."""
    ops, padded, n_out, kw, _ = _case(name)
    plan = plan_stencil(ops, padded, n_out, **kw)
    rows = (1,) * plan.n_aux if plan.n_aux else None
    res = audit_plan(plan, ops, aux_rows=rows)
    assert res.findings == []
    assert check_vmem(plan, res.measured_vmem) == []


def test_big_default_tiles_loop_over_z_chunks():
    """The 512³ launches keep a large staged block and bound the body:
    several z chunks a tile, each within BODY_VREGS."""
    for name in ("cube512", "shard4_interior", "shot512"):
        ops, padded, n_out, kw, _ = _case(name)
        plan = plan_stencil(ops, padded, n_out, **kw)
        assert 1 <= plan.z_chunk < plan.block[0], name
        assert plan.block[1] * plan.block[2] >= 32 * 256, name


@pytest.mark.parametrize(
    "block, kw, expect",
    [
        # Explicit tiles are planned as given (clamped to divisors).
        (LEGACY, {"fuse_steps": 2}, (8, 8, 16)),
        ((2, 8, 128), {}, (2, 8, 16)),
        # Plans the rule leaves alone keep the fixed default.
        (None, {"strategy": "swc_stream"}, (8, 8, 16)),
        (None, {"strategy": "tc"}, (8, 8, 16)),
        (None, {"unroll": 2}, (8, 8, 8)),
        (None, {"batch": 2}, (8, 8, 16)),
    ],
)
def test_other_rank3_plans_keep_their_tiles(block, kw, expect):
    ops = _diffusion_ops((16, 16, 16))
    depth = kw.get("fuse_steps", 1)
    padded = (1,) + (16 + 6 * depth,) * 3
    plan = plan_stencil(ops, padded, 1, block=block, **kw)
    assert plan.block == expect
    # ... and their kernel body computes the whole tile at once.
    assert plan.z_chunk == plan.block[0]


def test_rank2_and_batched_rank2_keep_the_fixed_default():
    ops = DiffusionProblem((64, 256), accuracy=6).step_op("swc").ops
    assert plan_stencil(ops, (1, 70, 262), 1).block == (16, 128)
    batched = plan_stencil(ops, (8, 1, 70, 262), 1)
    assert batched.batch == 8 and batched.block == (16, 128)


def test_explicit_legacy_tile_lowers_unchunked():
    """The (8, 8, 128) tile the legacy wrappers pass, and MHD's
    (2, 8, 128), keep their whole-tile bodies at 512³."""
    ops = _diffusion_ops((512,) * 3)
    for block, depth in ((LEGACY, 1), (LEGACY, 2), ((2, 8, 128), 1)):
        padded = (1,) + (512 + 6 * depth,) * 3
        plan = plan_stencil(ops, padded, 1, block=block, fuse_steps=depth)
        assert plan.block == block and plan.z_chunk == block[0]


# --- interpret-mode parity -----------------------------------------------------

PARITY_DOMAINS = [(16, 32, 512), (12, 40, 136)]


def _data(shape, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


@pytest.mark.parametrize("interior", PARITY_DOMAINS)
def test_chunked_depth2_equals_legacy_tile_and_reference(interior):
    op = DiffusionProblem(interior, accuracy=6).step_op("swc")
    padded = (1,) + tuple(n + 12 for n in interior)
    f = _data(padded, 0)
    plan = plan_stencil(op.ops, padded, 1, fuse_steps=2)
    legacy = plan_stencil(op.ops, padded, 1, block=LEGACY, fuse_steps=2)
    assert plan.block != legacy.block
    assert 1 < plan.z_chunk < plan.block[0]
    out = fused_stencil_pallas(f, op.ops, op.phi, plan, interpret=True)
    base = fused_stencil_pallas(f, op.ops, op.phi, legacy, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(base))
    expect = np.asarray(ref.fused_stencil_steps(f, op.ops, op.phi, 2))
    np.testing.assert_allclose(
        np.asarray(out), expect, rtol=1e-4,
        atol=1e-4 * np.abs(expect).max(),
    )


@pytest.mark.parametrize("interior", PARITY_DOMAINS)
def test_chunked_depth1_tuple_aux_equals_legacy_tile_and_reference(
    interior,
):
    ops = AcousticProblem(interior).operator_set()
    padded = (1,) + tuple(n + 8 for n in interior)
    u = _data(padded, 1)
    aux = tuple(_data((1,) + interior, 2 + i) for i in range(3))
    plan = plan_stencil(ops, padded, 1, n_aux=3)
    legacy = plan_stencil(ops, padded, 1, n_aux=3, block=LEGACY)
    assert plan.block != legacy.block
    assert 1 < plan.z_chunk < plan.block[0]
    out = fused_stencil_pallas(
        u, ops, acoustic_phi, plan, aux=aux, interpret=True
    )
    base = fused_stencil_pallas(
        u, ops, acoustic_phi, legacy, aux=aux, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(base))
    expect = np.asarray(ref.fused_stencil(u, ops, acoustic_phi, aux=aux))
    np.testing.assert_allclose(
        np.asarray(out), expect, rtol=1e-4,
        atol=1e-4 * np.abs(expect).max(),
    )

"""Compile rehearsal: the main-path Pallas kernels, at the sizes
``chip_smoke.py`` runs, compiled by Mosaic for a described TPU v5e.

No chip is needed: ``get_topology_desc`` describes a v5e 2x2 slice and
each kernel is lowered and compiled for one of its chips, so what the
chip's compiler would refuse (unaligned block shapes, DMA slices, more
scoped VMEM than allowed) fails here. ``jax.default_backend()`` still
reports the CPU, so every kernel is built with ``interpret=False``
explicitly. Each test asserts the compiled program holds a
``tpu_custom_call``. Nothing runs: results and times come only from a
chip run.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chip_smoke import DIFF_N, MHD_BLOCK, MHD_N, SERVE_BATCH, SERVE_SHAPES
from repro.kernels.emit import fused_stencil_pallas
from repro.kernels.plan import plan_stencil
from repro.physics.diffusion import DiffusionProblem
from repro.physics.mhd import MHDSolver, mhd_rhs_phi

SERVE_SHAPE = SERVE_SHAPES[0]
LINE_N = 4 * 1024 * 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def x32():
    """The chip path runs with 64-bit types off; another test file in
    the same worker may have switched them on (Mosaic refuses the i64
    indices that leak into the kernels then)."""
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", prev)


def _diffusion(shape, accuracy):
    op = DiffusionProblem(shape, accuracy=accuracy).step_op("swc")
    return op.ops, op.phi


def _padded(interior, radii, depth, lead):
    return lead + tuple(n + 2 * r * depth for n, r in zip(interior, radii))


def _case(name):
    """(ops, phi, padded operand shape, n_out, plan kwargs) of one
    kernel family at chip_smoke.py's size."""
    if name == "mhd_rhs":
        solver = MHDSolver((MHD_N,) * 3, strategy="swc", block=MHD_BLOCK)
        ops = solver.operator_set
        shape = _padded(solver.shape, ops.radius_per_axis(), 1, (8,))
        return ops, mhd_rhs_phi(solver.params), shape, 8, {
            "block": MHD_BLOCK,
        }
    if name == "serve_batched_2d":
        ops, phi = _diffusion(SERVE_SHAPE, 2)
        shape = _padded(
            SERVE_SHAPE, ops.radius_per_axis(), 1, (SERVE_BATCH, 1)
        )
        return ops, phi, shape, 1, {}
    if name == "swc_1d":
        ops, phi = _diffusion((LINE_N,), 6)
        return ops, phi, _padded((LINE_N,), (3,), 1, (1,)), 1, {}
    strategy, depth = {
        "swc_3d": ("swc", 1),
        "swc_3d_depth2": ("swc", 2),
        "swc_stream_3d": ("swc_stream", 1),
        "tc_3d": ("tc", 1),
    }[name]
    ops, phi = _diffusion((DIFF_N,) * 3, 6)
    shape = _padded((DIFF_N,) * 3, ops.radius_per_axis(), depth, (1,))
    return ops, phi, shape, 1, {"strategy": strategy, "fuse_steps": depth}


@pytest.mark.parametrize("name", [
    "swc_3d", "swc_3d_depth2", "swc_stream_3d", "tc_3d", "mhd_rhs",
    "serve_batched_2d", "swc_1d",
])
def test_kernel_compiles_for_v5e(name, one_chip, x32):
    ops, phi, shape, n_out, kw = _case(name)
    plan = plan_stencil(ops, shape, n_out, **kw)
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda f: fused_stencil_pallas(f, ops, phi, plan, interpret=False)
    ).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text(), plan.strategy_id

"""Compile rehearsal: the main-path Pallas kernels, at the sizes
``chip_smoke.py`` runs, compiled by Mosaic for a described TPU v5e.

No chip is needed: ``get_topology_desc`` describes a v5e 2x2 slice and
each kernel is lowered and compiled for one of its chips, so what the
chip's compiler would refuse (unaligned block shapes, DMA slices, more
scoped VMEM than allowed) fails here. ``jax.default_backend()`` still
reports the CPU, so every kernel is built with ``interpret=False``
explicitly. Each test asserts the compiled program holds a
``tpu_custom_call``, named after its kernel body (the name a device
profile shows for the launch). Nothing runs: results and times come
only from a chip run.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chip_smoke import DIFF_N, MHD_BLOCK, MHD_N, SERVE_BATCH, SERVE_SHAPES
from repro.kernels.emit import fused_stencil_pallas
from repro.kernels.plan import plan_stencil
from repro.kernels.stencil1d import xcorr1d_pallas
from repro.physics.acoustic import AcousticProblem, _phi as acoustic_phi
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
    kernel family at chip_smoke.py's size; plan kwargs with ``n_aux``
    carry ``aux_rows``, the rows of each aux operand."""
    if name == "mhd_rhs":
        solver = MHDSolver((MHD_N,) * 3, strategy="swc", block=MHD_BLOCK)
        ops = solver.operator_set
        shape = _padded(solver.shape, ops.radius_per_axis(), 1, (8,))
        return ops, mhd_rhs_phi(solver.params), shape, 8, {
            "block": MHD_BLOCK,
        }
    if name in ("acoustic_o8", "acoustic_o8_zero_ghost"):
        # The benchmark's shot: 512³, radius 4 over a zero pad, with
        # u⁻, a and b as three operands; its launch fed the unpadded u
        # and staging the zero ghost cells itself, as the shot runs it.
        ops = AcousticProblem((DIFF_N,) * 3).operator_set()
        shape = _padded((DIFF_N,) * 3, ops.radius_per_axis(), 1, (1,))
        kw = {"n_aux": 3, "aux_rows": (1, 1, 1)}
        if name == "acoustic_o8_zero_ghost":
            kw["ghost"] = "zero"
        return ops, acoustic_phi, shape, 1, kw
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


@pytest.fixture(scope="module")
def compiled_text(one_chip):
    """``get(name)``: the HLO text of one case compiled for the
    described chip, compiled once for the module."""
    texts = {}

    def get(name):
        if name not in texts:
            ops, phi, shape, n_out, kw = _case(name)
            rows = kw.pop("aux_rows", ())
            plan = plan_stencil(ops, shape, n_out, **kw)
            if plan.ghost == "zero":  # the kernel takes u unpadded
                shape = (plan.n_f,) + plan.interior
            x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
            aux = [
                jax.ShapeDtypeStruct(
                    (r,) + plan.interior, jnp.float32, sharding=one_chip
                )
                for r in rows
            ]
            texts[name] = jax.jit(
                lambda f, *aux: fused_stencil_pallas(
                    f, ops, phi, plan, aux=aux or None, interpret=False
                )
            ).lower(x, *aux).compile().as_text()
        return texts[name]

    return get


# Each case and the kernel body its launch is named after.
KERNEL_NAMES = {
    "swc_3d": "stencil_pipelined",
    "swc_3d_depth2": "stencil_temporal",
    "swc_stream_3d": "stencil_stream",
    "tc_3d": "stencil_tc",
    "mhd_rhs": "stencil_pipelined",
    "serve_batched_2d": "stencil_pipelined",
    "swc_1d": "stencil_pipelined",
    "acoustic_o8": "stencil_pipelined",
    "acoustic_o8_zero_ghost": "stencil_pipelined",
}


@pytest.mark.parametrize("name", list(KERNEL_NAMES))
def test_kernel_compiles_for_v5e(name, compiled_text, x32):
    assert "tpu_custom_call" in compiled_text(name), name


@pytest.mark.parametrize("name", list(KERNEL_NAMES))
def test_kernel_launch_carries_its_body_name(name, compiled_text, x32):
    launches = [
        line for line in compiled_text(name).splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]
    assert launches, name
    for line in launches:
        m = re.match(r"\s*(?:ROOT )?%([A-Za-z_]+)(?:\.\d+)? = ", line)
        assert m and m.group(1) == KERNEL_NAMES[name], line[:120]


def test_xcorr_1d_launch_carries_its_name(one_chip, x32):
    """The paper's 1-D cross-correlation kernel: Mosaic refuses its
    unaligned 1-D loads on a v5e, so its name is read from the lowered
    module, where the compiled instruction takes it from."""
    f = jax.ShapeDtypeStruct((LINE_N + 6,), jnp.float32, sharding=one_chip)
    g = jax.ShapeDtypeStruct((7,), jnp.float32, sharding=one_chip)
    text = jax.jit(
        lambda f, g: xcorr1d_pallas(f, g, interpret=False)
    ).lower(f, g).as_text()
    assert re.findall(r'kernel_name = "(\w+)"', text) == ["stencil_1d"]

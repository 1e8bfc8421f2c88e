"""The acoustic (seismic) solver against its plain reference, and the
tuple-aux kernel operands it launches with.

Small sizes on the CPU (Pallas in interpret mode): a 24 × 32 × 128
shot with a 4-point layer, the benchmark configuration's numerics
otherwise, seeded velocity model and levels.
"""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import acoustic_reference as ref
from repro.core.fusion import FusedStencilOp
from repro.core.stencil import OperatorSet, derivative_operator_set
from repro.kernels.emit import fused_stencil_pallas
from repro.kernels.plan import plan_stencil
from repro.physics.acoustic import (
    AcousticProblem,
    AcousticSolver,
    _count_primitives,
    _phi as acoustic_phi,
)

ROOT = Path(__file__).resolve().parents[1]
GRID = (24, 32, 128)
SEED = 3_000_000_015
# Near the wavelet's peak (its delay is 1/f0 = 0.1 s, about 118 steps),
# where the source term is largest against the initial levels.
T0 = 112


def config() -> dict:
    """The benchmark's configuration, cut to the small grid."""
    cfg = json.loads((ROOT / "bench" / "configs" / "acoustic-o8.json").read_text())
    num = cfg["numerics"]
    num["absorbing"]["layer"] = 4
    num["source"]["point"] = [4, 16, 64]
    return cfg


def problem(cfg, grid=GRID) -> AcousticProblem:
    num = cfg["numerics"]
    src, rec = num["source"], num["receivers"]
    return AcousticProblem(
        grid, spacing=num["spacing"], accuracy=num["order"],
        layer=num["absorbing"]["layer"],
        reflection=num["absorbing"]["reflection"], cfl=num["cfl"],
        peak_frequency=src["peak_frequency"], source=tuple(src["point"]),
        source_amplitude=src["amplitude"],
        source_period=src["period_steps"], receiver_depth=rec["depth"],
        receiver_stride=rec["stride"],
    )


def levels(seed: int, grid=GRID):
    k1, k2 = jax.random.split(jax.random.key(seed))
    shape = (1,) + grid
    return tuple(
        jax.random.uniform(k, shape, jnp.float32, -1e-5, 1e-5) for k in (k1, k2)
    )


def rel_gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# -- the solver against the plain reference ----------------------------------


def test_benchmark_reference_is_this_reference():
    """The chip check advances the benchmark's copy of the reference."""
    bench = ROOT / "bench" / "configs" / "acoustic-o8.ref.py"
    assert bench.read_bytes() == Path(ref.__file__).read_bytes()


# Float32 against float32: the kernel sums each axis's 9 taps and then
# the axes, the reference tap by tap over all three, so each step
# rounds differently at about 1e-7 of the field; 16 leapfrog steps (an
# amplification of at most 1 per step) read 3e-7 on the levels and
# 8e-7 on the traces here. A bfloat16 run rounds at 4e-3 of the field
# on every step and reads 1.6e-2 and 4.1e-2.
LEVEL_TOL = 1e-5
TRACE_TOL = 1e-5


@pytest.fixture(scope="module")
def shot():
    cfg = config()
    v = ref.velocity_model(cfg, GRID, SEED)
    u, um = levels(SEED)
    f = jnp.concatenate([u, um])
    want, want_tr = ref.advance_traces(cfg, GRID, f, 16, v, T0)
    low, low_tr = ref.advance_traces(
        cfg, GRID, f, 16, v, T0, dtype=jnp.bfloat16
    )
    return cfg, v, (u, um), (want, want_tr), (low, low_tr)


@pytest.mark.parametrize("strategy", ["swc", "hwc"])
def test_solver_matches_reference(shot, strategy):
    cfg, v, (u, um), (want, want_tr), _ = shot
    sol = AcousticSolver(problem(cfg), v, strategy=strategy)
    t = jnp.int32(T0)
    u, um, t, tr1 = sol.run(u, um, t, 8)
    u, um, t, tr2 = sol.run(u, um, t, 8)
    assert int(t) == T0 + 16
    assert rel_gap(jnp.concatenate([u, um]), want) <= LEVEL_TOL
    assert rel_gap(jnp.concatenate([tr1, tr2]), want_tr) <= TRACE_TOL


def test_bfloat16_reference_fails_the_tolerances(shot):
    _, _, _, (want, want_tr), (low, low_tr) = shot
    assert rel_gap(low, want) > LEVEL_TOL
    assert rel_gap(low_tr, want_tr) > TRACE_TOL


def test_dropped_source_fails_the_tolerances(shot):
    cfg, v, (u, um), (want, want_tr), _ = shot
    quiet = config()
    quiet["numerics"]["source"]["amplitude"] = 0.0
    sol = AcousticSolver(problem(quiet), v, strategy="hwc")
    u, um, _, tr = sol.run(u, um, jnp.int32(T0), 16)
    assert rel_gap(jnp.concatenate([u, um]), want) > LEVEL_TOL
    assert rel_gap(tr, want_tr) > TRACE_TOL


def test_counters_match_steps_and_receivers(shot):
    cfg, v, (u, um), _, _ = shot
    p = problem(cfg)
    sol = AcousticSolver(p, v, strategy="swc")
    sol.run(u, um, jnp.int32(0), 3)
    sol.run(u, um, jnp.int32(3), 2)
    assert p.n_receivers == 12 * 60
    # The swc launch fills its zero ghost cells itself: no pad.
    assert sol.counts == {
        "steps": 5, "launches": 5, "injections": 5,
        "receiver_samples": 5 * p.n_receivers, "pads": 0,
    }
    # XLA's reference regime launches no kernel and pads u every step,
    # and the counters say so.
    hwc = AcousticSolver(p, v, strategy="hwc").per_step
    assert (hwc["launches"], hwc["pads"]) == (0, 1)


# -- the absorbing layer ------------------------------------------------------


def _energy(u_new, u_old, c2dt2, h):
    """The leapfrog's conserved energy for a homogeneous medium without
    damping: |u⁺ − u|² − (v dt)² <u⁺, ∇²u> over the grid (∇² with zero
    ghost cells, so symmetric)."""
    c = ref.second_derivative_coeffs(8)
    r = len(c) // 2
    g = u_old[0]
    gp = jnp.pad(g, r)
    lap = jnp.zeros_like(g)
    for axis, n in enumerate(g.shape):
        for k, w in enumerate(c):
            idx = tuple(
                slice(k, k + m) if a == axis else slice(r, r + m)
                for a, m in enumerate(g.shape)
            )
            lap = lap + (w / (h * h)) * gp[idx]
    d = u_new[0] - g
    return float(jnp.sum(d * d) - c2dt2 * jnp.sum(u_new[0] * lap))


def test_outgoing_pulse_loses_energy_in_the_layer_only():
    grid = (40, 40, 128)
    v = jnp.full(grid, 2000.0, jnp.float32)
    z, y, x = np.meshgrid(*(np.arange(n) for n in grid), indexing="ij")
    pulse = np.exp(-((z - 20) ** 2 + (y - 20) ** 2 + (x - 64) ** 2) / 4.0)
    u0 = jnp.asarray(pulse[None], jnp.float32)
    energies = {}
    for name, reflection in (("damped", 1e-3), ("undamped", 1.0)):
        p = AcousticProblem(
            grid, layer=4, reflection=reflection, source=(4, 20, 64),
            source_amplitude=0.0,
        )
        sol = AcousticSolver(p, v, strategy="hwc")
        c2dt2 = (2000.0 * sol.dt) ** 2
        u, um, t = u0, u0, jnp.int32(0)
        trail = []
        for _ in range(12):
            u, um, t, _ = sol.run(u, um, t, 10)
            trail.append(_energy(u, um, c2dt2, p.spacing))
        energies[name] = np.asarray(trail)
    damped, undamped = energies["damped"], energies["undamped"]
    # Without damping the leapfrog conserves its energy to rounding.
    np.testing.assert_allclose(undamped, undamped[0], rtol=1e-4)
    # While the pulse is inside the layer-free interior (its front moves
    # 0.4 points a step, 16 points from the layer) the layer takes
    # nothing; once it has crossed, most of the energy is gone.
    np.testing.assert_allclose(damped[:2], undamped[:2], rtol=1e-4)
    assert damped[-1] < 0.5 * undamped[-1], damped / undamped


# -- aux as one stacked array or as a tuple of arrays -------------------------


def _aux_phi(d, aux):
    lap = sum(v for k, v in d.items() if k in ("dxx", "dyy", "dzz"))
    return 2.0 * aux[2:3] * d["val"] - aux[0:1] + aux[1:2] * lap


@pytest.mark.parametrize(
    "lead,spatial", [((), (16, 128)), ((), (8, 16, 128)), ((2,), (16, 128))],
    ids=["rank2", "rank3", "rank2-batch2"],
)
@pytest.mark.parametrize("strategy", ["swc", "tc", "hwc"])
def test_tuple_aux_equals_stacked_aux_bitwise(strategy, lead, spatial):
    rank = len(spatial)
    full = derivative_operator_set(rank, 8, 0.5, include_mixed=False)
    ops = OperatorSet(tuple(
        s for s in full.ops if s.name in ("val", "dxx", "dyy", "dzz")
    ))
    op = FusedStencilOp(
        ops, _aux_phi, n_out=1, boundary_mode="dirichlet",
        strategy=strategy,
    )
    k = jax.random.split(jax.random.key(7), 2)
    f = jax.random.normal(k[0], lead + (1,) + spatial, jnp.float32)
    aux = jax.random.normal(k[1], lead + (3,) + spatial, jnp.float32)

    def rows(lo, hi):
        return jax.lax.slice_in_dim(aux, lo, hi, axis=len(lead))

    stacked = op(f, aux=aux)
    split = op(f, aux=(rows(0, 1), rows(1, 2), rows(2, 3)))
    two = op(f, aux=(rows(0, 2), rows(2, 3)))
    np.testing.assert_array_equal(np.asarray(split), np.asarray(stacked))
    np.testing.assert_array_equal(np.asarray(two), np.asarray(stacked))


# -- zero ghost cells staged in the kernel ------------------------------------


def _mixed_phi(d, aux):
    """A φ over every operator of a set with mixed partials, so the
    window's corners (a z face beside a y face) are read."""
    return sum(d.values()) * aux[1:2] + aux[0:1] - aux[2:3]


# (interior, tile, operators): grids of at least 3 blocks in z and y, so
# the low-face, interior and high-face copies of both axes all run.
ZERO_GHOST_CASES = {
    # the shot's operators, one z chunk a tile
    "acoustic-o8": ((24, 24, 128), (8, 8, 128), "acoustic"),
    # mixed partials read the corners
    "mixed-o4": ((24, 24, 256), (8, 8, 256), "mixed"),
    # a body that walks its tile in z chunks (plan.z_chunk 4 of 8)
    "chunked-o8": ((24, 96, 384), (8, 32, 384), "acoustic"),
    # a z tile below the radius: two blocks copy at the low z face
    "thin-z-o8": ((12, 24, 128), (2, 8, 128), "acoustic"),
}


@pytest.mark.parametrize("case", list(ZERO_GHOST_CASES))
def test_zero_ghost_launch_equals_pad_and_launch(case):
    interior, tile, kind = ZERO_GHOST_CASES[case]
    if kind == "acoustic":
        ops, phi = AcousticProblem(interior).operator_set(), acoustic_phi
    else:
        ops, phi = derivative_operator_set(3, 4, 0.5), _mixed_phi
    radii = ops.radius_per_axis()
    padded = (1,) + tuple(n + 2 * r for n, r in zip(interior, radii))
    zero = plan_stencil(ops, padded, 1, n_aux=3, block=tile, ghost="zero")
    plain = plan_stencil(ops, padded, 1, n_aux=3, block=tile)
    assert (zero.ghost, plain.ghost) == ("zero", None)
    assert min(zero.grid[:2]) >= 3
    if case == "chunked-o8":
        assert zero.z_chunk < zero.block[0]
    k = jax.random.split(jax.random.key(11), 4)
    u = jax.random.normal(k[0], (1,) + interior, jnp.float32)
    aux = tuple(
        jax.random.normal(kk, (1,) + interior, jnp.float32) for kk in k[1:]
    )
    got = fused_stencil_pallas(u, ops, phi, zero, aux=aux, interpret=True)
    fp = jnp.pad(u, [(0, 0)] + [(r, r) for r in radii])
    want = fused_stencil_pallas(fp, ops, phi, plain, aux=aux, interpret=True)
    assert rel_gap(got, want) <= 1e-6


@pytest.mark.parametrize("block", [(8, 8, 128), (4, 16, 128)])
def test_solver_zero_ghost_tiles_match_reference(shot, block):
    """The solver on tiles of several blocks in z and y, each staging
    its zero ghosts in the kernel, against the plain reference."""
    cfg, v, (u, um), (want, want_tr), _ = shot
    sol = AcousticSolver(problem(cfg), v, strategy="swc", block=block)
    assert sol.op.lowering_plan((1,) + GRID, n_aux=3).ghost == "zero"
    assert sol.per_step["pads"] == 0
    u, um, t, tr = sol.run(u, um, jnp.int32(T0), 16)
    assert int(t) == T0 + 16
    assert rel_gap(jnp.concatenate([u, um]), want) <= LEVEL_TOL
    assert rel_gap(tr, want_tr) <= TRACE_TOL


def _shot_ops():
    return AcousticProblem(GRID).operator_set()


def _self_map(d):
    return d["val"] + 0.1 * (d["dzz"] + d["dyy"] + d["dxx"])


# Ops the zero-ghost staging must leave on the pad path: (op kwargs,
# leading axes of u, whether u⁻, a, b ride along as aux).
PADDED_ROUTES = {
    "periodic": ({"boundary_mode": "periodic"}, (), True),
    "neumann": ({"boundary_mode": "neumann"}, (), True),
    "batched": ({}, (2,), True),
    "depth-2": ({"boundary_mode": "periodic", "fuse_steps": 2}, (), False),
    "tc": ({"strategy": "tc"}, (), True),
    "narrow-tile": ({"block": (8, 8, 64)}, (), True),
    "boundary-weights": ({"boundary_weights": True}, (), True),
}


def _route(kw, lead, with_aux):
    """(plan, pad primitives, the kernel's first operand's shape) of one
    call of an op over the shot's operators on GRID."""
    kw = {"boundary_mode": "dirichlet", "strategy": "swc", **kw}
    op = FusedStencilOp(
        _shot_ops(), _aux_phi if with_aux else _self_map, n_out=1, **kw
    )
    f = jnp.zeros(lead + (1,) + GRID, jnp.float32)
    aux = (f, f, f) if with_aux else None
    plan = op.lowering_plan(f.shape, n_aux=3 if with_aux else 0)
    jaxpr = jax.make_jaxpr(lambda f: op(f, aux=aux))(f).jaxpr
    (launch,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    pads = _count_primitives(jaxpr, {"pad"})["pad"]
    return plan, pads, launch.invars[0].aval.shape[-3:]


def test_zero_ghost_routes_the_shot():
    """The shot's op stages its zero ghosts: no pad in its step, u fed
    unpadded, a plan keyed apart from the padded plan of the same tile,
    and at 512³ a tile spanning x."""
    plan, pads, fed = _route({}, (), True)
    assert plan.ghost == "zero" and plan.strategy_id.endswith(":gz")
    assert (pads, fed) == (0, GRID)
    padded = dataclasses.replace(plan, ghost=None)
    assert padded.strategy_id != plan.strategy_id
    assert padded.tuning_key() != plan.tuning_key()
    sol = AcousticSolver(problem(config()), jnp.full(GRID, 2000.0))
    assert (sol.per_step["pads"], sol.per_step["launches"]) == (0, 1)
    big = sol.op.lowering_plan((1, 512, 512, 512), n_aux=3)
    assert big.ghost == "zero" and big.block[-1] == 512


@pytest.mark.parametrize("route", list(PADDED_ROUTES))
def test_other_plans_keep_their_pad(route):
    """Every other op pads u as before and feeds the kernel the padded
    copy: a zero Dirichlet op through one ``pad``, periodic and
    neumann through their wrap and edge fills."""
    kw, lead, with_aux = PADDED_ROUTES[route]
    plan, pads, fed = _route(kw, lead, with_aux)
    depth = kw.get("fuse_steps", 1)
    assert plan.ghost is None
    assert fed == tuple(n + 8 * depth for n in GRID)
    dirichlet = kw.get("boundary_mode", "dirichlet") == "dirichlet"
    assert pads == (1 if dirichlet else 0)


@pytest.mark.parametrize(
    "kw",
    [
        {"strategy": "swc_stream"},
        {"strategy": "tc"},
        {"fuse_steps": 2},
        {"batch": 2},
        {"unroll": 2, "block": (8, 8, 64)},
        {"block": (8, 8, 64)},
        {"block": (8, 4, 128)},
    ],
    ids=["swc_stream", "tc", "depth-2", "batch-2", "unroll-2",
         "narrow-x", "y-below-sublane"],
)
def test_planner_offers_zero_ghosts_only_where_they_fit(kw):
    ops = _shot_ops()
    depth = kw.get("fuse_steps", 1)
    padded = (1,) + tuple(n + 8 * depth for n in GRID)
    plan = plan_stencil(ops, padded, 1, ghost="zero", **kw)
    assert plan.ghost is None
    assert plan == plan_stencil(ops, padded, 1, **kw)

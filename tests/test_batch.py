"""Ensemble batch axis: the batched lowering must be f64-identical to
``vmap`` of the single-member path across B × rank × strategy ×
fuse_steps (ISSUE acceptance sweep), the per-member traffic model must
reward batching, the ``:b{B}`` key component must separate cache
records per batch extent, and plan validation must reject the
unsupported batched-aux-temporal combination."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_enable_x64", True)

from repro.core.stencil import derivative_operator_set  # noqa: E402
from repro.core.trafficmodel import (  # noqa: E402
    stencil_batched_hbm_bytes_per_member_step,
)
from repro.kernels import ops as kops  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.plan import plan_stencil, strategy_sid  # noqa: E402

DOMAINS = {1: (64,), 2: (12, 24), 3: (8, 10, 16)}
BLOCKS = {1: (32,), 2: (6, 12), 3: (3, 5, 8)}


def _problem(
    rank: int, batch: int, n_f: int = 2, fuse_steps: int = 1, seed: int = 0
):
    """Self-map problem (n_out == n_f), operand padded for
    ``fuse_steps`` fused sweeps (halo width r·S)."""
    opset = derivative_operator_set(rank, 2, spacing=0.4)
    names = ["dxx", "dyy", "dzz"][:rank]

    def phi(d):
        lap = sum(d[k] for k in names)
        return jnp.stack([d["val"][0] + 0.05 * lap[0],
                          d["val"][1] - 0.02 * lap[1]])

    rng = np.random.default_rng(seed)
    f = jnp.asarray(
        rng.standard_normal((batch, n_f) + DOMAINS[rank]), jnp.float64
    )
    h = opset.radius * fuse_steps
    pad = ((0, 0), (0, 0)) + ((h, h),) * rank
    fp = jnp.pad(f, pad, mode="wrap")
    return opset, phi, fp


# Streaming needs a non-lane axis, so swc_stream starts at rank 2.
SWEEP = [
    (batch, rank, strategy, fuse_steps)
    for batch in (1, 4, 8)
    for rank in (1, 2, 3)
    for strategy in ("swc", "swc_stream")
    for fuse_steps in (1, 2)
    if not (strategy == "swc_stream" and rank == 1)
]


@pytest.mark.parametrize("batch,rank,strategy,fuse_steps", SWEEP)
def test_batched_matches_vmap_of_single_member(
    batch, rank, strategy, fuse_steps
):
    opset, phi, fp = _problem(rank, batch, fuse_steps=fuse_steps)
    out = kops.fused_stencil_nd(
        fp, opset, phi, 2, strategy=strategy, block=BLOCKS[rank],
        fuse_steps=fuse_steps, interpret=True,
    )
    expect = jax.vmap(
        lambda f: kops.fused_stencil_nd(
            f, opset, phi, 2, strategy="hwc", fuse_steps=fuse_steps,
        )
    )(fp)
    assert out.shape == (batch, 2) + DOMAINS[rank]
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expect), rtol=0, atol=1e-12
    )


def test_batched_ref_oracle_is_vmap():
    opset, phi, fp = _problem(2, 4)
    got = ref.fused_stencil_batched(fp, opset, phi)
    expect = jax.vmap(lambda f: ref.fused_stencil(f, opset, phi))(fp)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))
    got_s = ref.fused_stencil_steps_batched(fp, opset, phi, 3)
    expect_s = jax.vmap(
        lambda f: ref.fused_stencil_steps(f, opset, phi, 3)
    )(fp)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(expect_s))


def test_batched_aux_depth1_matches_vmap():
    opset = derivative_operator_set(2, 2, spacing=0.4)

    def phi(d, aux):
        return jnp.stack([d["val"][0] + 0.05 * (d["dxx"] + d["dyy"])[0]
                          + aux[0]])

    rng = np.random.default_rng(3)
    f = jnp.asarray(rng.standard_normal((4, 1, 12, 24)), jnp.float64)
    aux = jnp.asarray(rng.standard_normal((4, 1, 12, 24)), jnp.float64)
    r = opset.radius
    fp = jnp.pad(f, ((0, 0), (0, 0), (r, r), (r, r)), mode="wrap")
    out = kops.fused_stencil_nd(
        fp, opset, phi, 1, aux=aux, strategy="swc", block=(6, 12),
        interpret=True,
    )
    expect = jax.vmap(
        lambda fm, am: kops.fused_stencil_nd(
            fm, opset, phi, 1, aux=am, strategy="hwc"
        )
    )(fp, aux)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expect), rtol=0, atol=1e-12
    )


# --- traffic model --------------------------------------------------------------


def test_per_member_bytes_strictly_decrease_with_batch():
    """The batching argument (ISSUE motivation): launch overhead
    amortizes across members, so modeled HBM bytes per member strictly
    decrease from B=1 to B=8 — for a benchmarked (fig11-sized) shape,
    plain and streamed, fused and unfused."""
    for stream in (False, True):
        for fuse_steps in (1, 2):
            per_member = [
                stencil_batched_hbm_bytes_per_member_step(
                    (256, 512), (8, 128), (1, 1), 1, 1, 4,
                    batch=b, fuse_steps=fuse_steps, stream=stream,
                )
                for b in (1, 2, 4, 8)
            ]
            assert all(
                a > b for a, b in zip(per_member, per_member[1:])
            ), (stream, fuse_steps, per_member)


def test_batched_bytes_reduce_to_unbatched_plus_overhead():
    from repro.core.trafficmodel import (
        STENCIL_LAUNCH_OVERHEAD_BYTES,
        stencil_hbm_bytes_per_step,
    )

    base = stencil_hbm_bytes_per_step((64, 64), (8, 32), (1, 1), 2, 2, 4)
    b1 = stencil_batched_hbm_bytes_per_member_step(
        (64, 64), (8, 32), (1, 1), 2, 2, 4, batch=1
    )
    assert b1 == base + STENCIL_LAUNCH_OVERHEAD_BYTES


# --- keys and validation --------------------------------------------------------


def test_batch_joins_strategy_id_and_tuning_key():
    assert strategy_sid("swc", 2, batch=4) == "swc:b4"
    assert strategy_sid("swc", 2) == "swc"  # B=1 keys exactly as before
    assert strategy_sid("swc_stream", 3, fuse_steps=2, batch=8) == (
        "swc_stream:sz:f2:b8"
    )
    opset = derivative_operator_set(2, 2, spacing=0.4)
    plans = {
        b: plan_stencil(
            opset, (b, 2, 14, 26), 2, strategy="swc", block=(6, 12),
            dtype="float64", batch=b,
        )
        for b in (1, 4, 8)
    }
    keys = {b: p.tuning_key().cache_id for b, p in plans.items()}
    assert len(set(keys.values())) == 3  # one record per batch extent
    assert ":b4" in keys[4] and ":b8" in keys[8]
    assert ":b" not in keys[1]


def test_plan_infers_batch_from_operand_rank():
    opset = derivative_operator_set(2, 2, spacing=0.4)
    plan = plan_stencil(
        opset, (4, 2, 14, 26), 2, strategy="swc", block=(6, 12),
        dtype="float64",
    )
    assert plan.batch == 4 and plan.interior == (12, 24)
    with pytest.raises(ValueError):
        plan_stencil(
            opset, (4, 2, 14, 26), 2, strategy="swc", block=(6, 12),
            dtype="float64", batch=2,  # disagrees with the leading axis
        )


def test_plan_rejects_batched_aux_temporal():
    opset = derivative_operator_set(2, 2, spacing=0.4)
    with pytest.raises(ValueError, match="aux"):
        plan_stencil(
            opset, (4, 1, 14, 26), 1, strategy="swc", block=(6, 12),
            dtype="float64", n_aux=1, fuse_steps=2,
        )


def test_candidate_enumeration_depends_on_batch():
    """The batched VMEM working set scales with B, so a budget that
    admits large blocks at B=1 must prune them at B=8 — candidate
    selection genuinely depends on the batch extent."""
    from repro.tuning import enumerate_candidates_nd, vmem_working_set

    domain, radii = (64, 128), (1, 1)
    budget = 2 * 1024 * 1024
    c1 = enumerate_candidates_nd(
        domain, radii, n_f=4, n_out=4, itemsize=4, vmem_budget=budget
    )
    c8 = enumerate_candidates_nd(
        domain, radii, n_f=4, n_out=4, itemsize=4, vmem_budget=budget,
        batch=8,
    )
    assert c1 and c8
    blocks1 = {c.block for c in c1 if c.block is not None}
    blocks8 = {c.block for c in c8 if c.block is not None}
    assert blocks8 < blocks1  # batch-scaled VMEM prunes the big blocks
    assert all(
        c.vmem_bytes == vmem_working_set(
            c.block, radii, 4, 4, 4, batch=8
        )
        for c in c8 if c.block is not None
    )

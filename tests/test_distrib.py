"""Distribution tests on 8 fake CPU devices: halo exchange vs
single-device, sharding rules, grad sync utilities, checkpoint
elasticity, and the fault-tolerance supervisor."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core.fusion import FusedStencilOp  # noqa: E402
from repro.core.stencil import derivative_operator_set  # noqa: E402
from repro.distrib import sharding as shlib  # noqa: E402
from repro.distrib.grad_sync import (  # noqa: E402
    accumulate_grads,
    compressed_psum_tree,
    hierarchical_psum,
)
from repro.launch.mesh import make_mesh  # noqa: E402


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def test_sharded_stencil_matches_single_device():
    ops = derivative_operator_set(3, 6, spacing=0.3)

    def phi(d):
        return jnp.stack([
            d["val"][0] + 0.1 * (d["dxx"] + d["dyy"] + d["dzz"])[0],
            d["dx"][1] * d["dy"][0] + d["dxy"][1],
        ])

    op = FusedStencilOp(ops, phi, 2, strategy="hwc")
    rng = np.random.default_rng(0)
    f = jnp.asarray(rng.standard_normal((2, 8, 16, 32)), jnp.float32)
    expect = op(f)

    mesh = make_mesh((2, 4), ("data", "model"))
    fn = _shard_map(
        lambda fl: op.apply_sharded(fl, (None, "data", "model")),
        mesh,
        P(None, None, "data", "model"),
        P(None, None, "data", "model"),
    )
    out = jax.jit(fn)(f)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expect), rtol=1e-5, atol=1e-5
    )


def test_sharded_overlap_matches_non_overlapped():
    """overlap=True (interior_first compute/communication overlap
    decomposition) must be a pure scheduling change: numerics match the
    plain exchange-then-apply path and the single-device reference."""
    ops = derivative_operator_set(3, 6, spacing=0.3)

    def phi(d):
        return jnp.stack([
            d["val"][0] + 0.1 * (d["dxx"] + d["dyy"] + d["dzz"])[0],
            d["dx"][1] * d["dy"][0] + d["dxy"][1],
        ])

    op = FusedStencilOp(ops, phi, 2, strategy="hwc")
    rng = np.random.default_rng(5)
    f = jnp.asarray(rng.standard_normal((2, 8, 16, 32)), jnp.float32)
    expect = op(f)

    mesh = make_mesh((2, 4), ("data", "model"))
    axes = (None, "data", "model")

    def run(overlap):
        fn = _shard_map(
            lambda fl: op.apply_sharded(fl, axes, overlap=overlap),
            mesh,
            P(None, None, "data", "model"),
            P(None, None, "data", "model"),
        )
        return jax.jit(fn)(f)

    plain, overlapped = run(False), run(True)
    np.testing.assert_allclose(
        np.asarray(overlapped), np.asarray(plain), rtol=1e-6, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(overlapped), np.asarray(expect), rtol=1e-5, atol=1e-5
    )


def test_sharded_temporal_fusion_matches_single_device():
    """fuse_steps=2 over the mesh: ONE widened halo exchange
    (radius·depth planes per axis) buys two time steps, and numerics
    match two single-device applications."""
    ops = derivative_operator_set(3, 6, spacing=0.3)

    def phi(d):
        return jnp.stack([
            d["val"][0] + 0.1 * (d["dxx"] + d["dyy"] + d["dzz"])[0],
            d["val"][1] + 0.05 * d["dx"][1] * d["dy"][0],
        ])

    rng = np.random.default_rng(9)
    f = jnp.asarray(rng.standard_normal((2, 8, 16, 32)), jnp.float32)
    single = FusedStencilOp(ops, phi, 2, strategy="hwc")
    expect = single(single(f))  # two sequential steps

    fused = FusedStencilOp(ops, phi, 2, strategy="hwc", fuse_steps=2)
    mesh = make_mesh((2, 4), ("data", "model"))
    fn = _shard_map(
        lambda fl: fused.apply_sharded(fl, (None, "data", "model")),
        mesh,
        P(None, None, "data", "model"),
        P(None, None, "data", "model"),
    )
    out = jax.jit(fn)(f)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expect), rtol=1e-4, atol=1e-4
    )


def test_sharded_overlap_depth2_matches_non_overlapped(monkeypatch):
    """overlap=True no longer falls back at depth > 1: the interior-
    first decomposition widens to radius·fuse_steps (with the aux carry
    exchanged at radius·(fuse_steps-1)) and matches the plain
    exchange-then-apply path up to float reassociation."""
    ops = derivative_operator_set(3, 6, spacing=0.3)

    def mk_phi(c):
        def phi(d, a):
            f_new = d["val"] + c * d["dxx"] + 0.1 * a * d["dyy"][:1]
            w_new = 0.5 * a + c * d["val"][:1]
            return jnp.concatenate([f_new, w_new])

        return phi

    # Local sharded extents (32/2, 64/4) = (16, 16) must EXCEED
    # 2·radius·fuse_steps = 12, else the decomposition (correctly)
    # falls back to the plain path and the test is vacuous; the spy
    # below asserts the overlap path really engaged.
    rng = np.random.default_rng(13)
    f = jnp.asarray(rng.standard_normal((2, 8, 32, 64)), jnp.float32)
    aux = jnp.asarray(rng.standard_normal((1, 8, 32, 64)), jnp.float32)
    op = FusedStencilOp(
        ops, (mk_phi(0.3), mk_phi(0.7)), 3, strategy="hwc", fuse_steps=2
    )
    expect = op(f, aux)

    engaged = []
    orig = FusedStencilOp._apply_sharded_overlap

    def spy(self, *args, **kwargs):
        out = orig(self, *args, **kwargs)
        engaged.append(out is not None)
        return out

    monkeypatch.setattr(FusedStencilOp, "_apply_sharded_overlap", spy)

    mesh = make_mesh((2, 4), ("data", "model"))
    axes = (None, "data", "model")

    def run(overlap):
        fn = _shard_map(
            lambda fl, al: op.apply_sharded(fl, axes, al, overlap=overlap),
            mesh,
            (P(None, None, "data", "model"), P(None, None, "data", "model")),
            P(None, None, "data", "model"),
        )
        return jax.jit(fn)(f, aux)

    plain, overlapped = run(False), run(True)
    assert engaged and all(engaged), "overlap decomposition fell back"
    # scheduling change only: plain-path parity up to f32 reassociation
    np.testing.assert_allclose(
        np.asarray(overlapped), np.asarray(plain), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(overlapped), np.asarray(expect), rtol=1e-4, atol=1e-3
    )


def test_apply_sharded_rejects_mismatched_mesh_axes():
    """A mesh_axes list that doesn't cover every spatial dim is a clear
    ValueError up front (not a confusing zip truncation downstream)."""
    ops = derivative_operator_set(3, 2, spacing=0.3)
    op = FusedStencilOp(ops, lambda d: d["val"], 2, strategy="hwc")
    f = jnp.zeros((2, 8, 8, 8), jnp.float32)
    with pytest.raises(ValueError, match="mesh_axes has 2 entries"):
        op.apply_sharded(f, ("data", "model"))
    with pytest.raises(ValueError, match="spatial dim"):
        op.apply_sharded(f, (None, None, "data", "model"))


def test_param_spec_rules():
    mesh = make_mesh((2, 4), ("data", "model"))
    # TP on attention projections
    spec = shlib.param_spec("blocks/wq", (4, 64, 128), mesh)
    assert spec == P(None, None, "model")
    # kv heads too small to shard → replicated (trailing Nones stripped)
    spec = shlib.param_spec("blocks/wk", (4, 64, 2), mesh)
    assert spec == P()
    # MoE: experts ≥ mesh → EP
    spec = shlib.param_spec("blocks/moe/w_gate", (2, 8, 16, 32), mesh)
    assert spec == P(None, "model")
    # MoE: experts < mesh → expert-TP fallback on d_ff
    spec = shlib.param_spec("blocks/moe/w_gate", (2, 2, 16, 32), mesh)
    assert spec == P(None, None, None, "model")
    # FSDP shards the biggest free dim over data
    spec = shlib.param_spec("blocks/wq", (4, 64, 128), mesh, fsdp=True)
    assert spec == P(None, "data", "model")


def test_compressed_psum_tree():
    mesh = make_mesh((8,), ("data",))
    g = {"w": jnp.asarray(np.random.default_rng(0).standard_normal((8, 64)),
                          jnp.float32)}

    def fn(gl):
        synced, resid = compressed_psum_tree(gl, "data")
        return synced, resid

    out, resid = jax.jit(
        _shard_map(fn, mesh, P("data", None), (P(None), P("data", None)))
    )(g["w"])
    expect = np.asarray(g["w"]).reshape(8, 1, 64).sum(0)
    # bf16 wire: ~1e-2 relative accuracy per element
    np.testing.assert_allclose(
        np.asarray(out)[0], expect[0], rtol=5e-2, atol=5e-2
    )
    # error feedback captures the residual
    assert float(jnp.abs(resid).max()) > 0.0


def test_hierarchical_psum_matches_flat():
    mesh = make_mesh((2, 4), ("pod", "data"))
    x = jnp.asarray(
        np.random.default_rng(1).standard_normal((8, 16)), jnp.float32
    )

    def hier(xl):
        return hierarchical_psum(xl, "data", "pod")

    def flat(xl):
        return jax.lax.psum(xl, ("pod", "data"))

    a = jax.jit(_shard_map(hier, mesh, P(("pod", "data"), None), P(None)))(x)
    b = jax.jit(_shard_map(flat, mesh, P(("pod", "data"), None), P(None)))(x)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)


def test_accumulate_grads_matches_full_batch():
    def loss_fn(p, batch):
        pred = batch["x"] @ p["w"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    rng = np.random.default_rng(2)
    p = {"w": jnp.asarray(rng.standard_normal((8, 4)), jnp.float32)}
    x = jnp.asarray(rng.standard_normal((4, 16, 8)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((4, 16, 4)), jnp.float32)
    loss_acc, g_acc = accumulate_grads(loss_fn, p, {"x": x, "y": y})
    (loss_full, _), g_full = jax.value_and_grad(loss_fn, has_aux=True)(
        p, {"x": x.reshape(64, 8), "y": y.reshape(64, 4)}
    )
    np.testing.assert_allclose(float(loss_acc), float(loss_full), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(g_acc["w"]), np.asarray(g_full["w"]), rtol=1e-5, atol=1e-6
    )


def test_checkpoint_roundtrip_and_elastic_reshard(tmp_path):
    from repro.checkpoint import CheckpointManager

    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {
        "a": jnp.arange(32, dtype=jnp.float32).reshape(4, 8),
        "nested": {"b": jnp.ones((16,), jnp.int32)},
    }
    mgr.save(10, tree, blocking=True)
    mgr.save(20, tree, blocking=True)
    mgr.save(30, tree, blocking=True)
    assert mgr.all_steps() == [20, 30]  # keep=2 retention

    # restore onto a DIFFERENT sharding layout (elastic path)
    mesh = make_mesh((4, 2), ("data", "model"))
    shardings = {
        "a": NamedSharding(mesh, P("data", "model")),
        "nested": {"b": NamedSharding(mesh, P(None))},
    }
    restored, step = mgr.restore(tree, shardings=shardings)
    assert step == 30
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]))
    assert restored["a"].sharding.spec == P("data", "model")


def test_supervisor_recovers_from_failure(tmp_path):
    from repro.checkpoint import CheckpointManager
    from repro.ft import Supervisor

    mgr = CheckpointManager(str(tmp_path), keep=3)
    sup = Supervisor(mgr, ckpt_every=5)
    trace = []

    def step_fn(state, step):
        trace.append(step)
        return {"x": state["x"] + 1}

    def restore(state, step):
        if step is None:
            return {"x": jnp.zeros(())}, 0
        restored, got = mgr.restore(state, step)
        return restored, got

    state, report = sup.run(
        {"x": jnp.zeros(())}, step_fn, 20,
        failure_at=12, restore_fn=restore,
    )
    assert report["restarts"] == 1
    assert float(state["x"]) == 20  # exact replay: 10 (ckpt) + 10 more
    # steps 10 and 11 replayed after restore from step 10
    assert trace.count(10) == 2 and trace.count(11) == 2


def test_straggler_monitor():
    from repro.ft import StragglerMonitor

    mon = StragglerMonitor(factor=1.5, window=10)
    flagged = []
    for i in range(10):
        flagged.append(mon.record(i, 0.1))
    assert not any(flagged)
    assert mon.record(10, 0.3)  # 3× median


def test_data_pipeline_determinism_and_sharding():
    from repro.data import BatchIterator, MarkovLMDataset

    ds = MarkovLMDataset(vocab=64, seq_len=16, branching=4, seed=7)
    # Host shards partition the global batch exactly.
    full = BatchIterator(ds, 8, host_index=0, host_count=1).next_local()
    h0 = BatchIterator(ds, 8, host_index=0, host_count=2).next_local()
    h1 = BatchIterator(ds, 8, host_index=1, host_count=2).next_local()
    np.testing.assert_array_equal(
        np.concatenate([h0["tokens"], h1["tokens"]]), full["tokens"]
    )
    # Replays are bit-identical (the ft recovery contract).
    again = BatchIterator(ds, 8, host_index=0, host_count=2).next_local()
    np.testing.assert_array_equal(h0["tokens"], again["tokens"])
    # Markov property: every transition comes from the chain's table.
    table = ds._table()
    tok = full["tokens"]
    for row in tok:
        for t in range(len(row) - 1):
            assert row[t + 1] in table[row[t]]

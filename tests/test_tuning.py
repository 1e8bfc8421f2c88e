"""Tuning subsystem tests: cache round-trip persistence, key stability
across processes, schema-bump invalidation, VMEM fallback, and the
``block="auto"`` acceptance criterion — identical numerics to an
explicit block, with a second process reusing the persisted record
without re-measurement."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fusion import FusedStencilOp
from repro.core.stencil import derivative_operator_set
from repro.tuning import (
    SCHEMA_VERSION,
    TuningCache,
    TuningKey,
    TuningRecord,
    TuningSession,
    fused_nd_candidates,
)
from repro.tuning.session import auto_block_nd

SRC = str(Path(__file__).resolve().parent.parent / "src")

KEY = TuningKey(
    kernel="fused_stencil3d", strategy="swc", domain=(8, 8, 16),
    radii=(1, 1, 1), n_f=2, n_out=1, dtype="float32", backend="cpu",
)


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path))
    return tmp_path


def _subprocess_env(cache_dir) -> dict:
    env = dict(os.environ)
    env["REPRO_TUNE_CACHE"] = str(cache_dir)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    return env


# --- cache ---------------------------------------------------------------------


def test_cache_roundtrip_persistence(cache_dir):
    rec = TuningRecord(
        block=(4, 8, 16), timings_us={"4x8x16": 12.5, "8x8x16": 17.0},
        source="measured",
    )
    TuningCache().put(KEY, rec)
    # A fresh cache object re-reads from disk (new-process simulation).
    got = TuningCache().get(KEY)
    assert got is not None
    assert got.block == (4, 8, 16)  # tuple restored from JSON list
    assert got.timings_us == rec.timings_us
    assert got.source == "measured"
    assert got.schema == SCHEMA_VERSION
    assert got.created > 0


def test_cache_key_stable_across_processes(cache_dir):
    code = (
        "from repro.tuning import TuningKey\n"
        "print(TuningKey(kernel='fused_stencil3d', strategy='swc',"
        " domain=(8, 8, 16), radii=(1, 1, 1), n_f=2, n_out=1,"
        " dtype='float32', backend='cpu').cache_id)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=_subprocess_env(cache_dir), check=True,
    )
    assert out.stdout.strip() == KEY.cache_id


def test_schema_bump_invalidates_records(cache_dir):
    TuningCache().put(
        KEY, TuningRecord(block=(4, 8, 16), timings_us={}, source="model")
    )
    path = cache_dir / "cache.json"
    raw = json.loads(path.read_text())
    for rec in raw["records"].values():
        rec["schema"] = SCHEMA_VERSION - 1  # pretend an older build wrote it
    path.write_text(json.dumps(raw))
    assert TuningCache().get(KEY) is None


def test_cache_put_merges_with_disk(cache_dir):
    """Two cache objects (concurrent-process stand-ins) don't clobber
    each other's records."""
    a, b = TuningCache(), TuningCache()
    key2 = TuningKey(
        kernel="xcorr1d", strategy="baseline:u1", domain=(1024,),
        radii=(2,), n_f=1, n_out=1, dtype="float32", backend="cpu",
    )
    a.put(KEY, TuningRecord(block=(8, 8, 16), timings_us={}, source="model"))
    b.put(key2, TuningRecord(block=2048, timings_us={}, source="model"))
    fresh = TuningCache()
    assert fresh.get(KEY) is not None
    assert fresh.get(key2) is not None
    assert fresh.get(key2).block == 2048  # int block round-trips as int


# --- session -------------------------------------------------------------------


def test_session_cache_hit_skips_measurement(cache_dir):
    cands = fused_nd_candidates((8, 8, 16), (1, 1, 1), 2, 1, 4)
    calls = []

    def measure(cand):
        calls.append(cand.block)
        return 1.0 if cand.block != cands[0].block else 0.5

    sess = TuningSession(top_k=2)
    rec1 = sess.tune(KEY, cands, measure)
    assert rec1.source == "measured" and len(calls) == 2
    rec2 = sess.tune(KEY, cands, measure)
    assert len(calls) == 2  # fast path: no new measurements
    assert rec2.block == rec1.block


def test_session_upgrades_model_record_when_measurable(cache_dir):
    """A cost-model record (persisted under jit tracing) is re-tuned —
    not returned from the fast path — once a caller can measure."""
    cands = fused_nd_candidates((8, 8, 16), (1, 1, 1), 2, 1, 4)
    sess = TuningSession(top_k=2)
    traced = sess.tune(KEY, cands, measure=None)
    assert traced.source == "model"

    calls = []

    def measure(cand):
        calls.append(cand.block)
        return 1.0

    upgraded = sess.tune(KEY, cands, measure)
    assert upgraded.source == "measured" and len(calls) == 2
    # ...and the measured record now IS the fast path.
    again = sess.tune(KEY, cands, measure)
    assert len(calls) == 2 and again.source == "measured"


def test_session_all_discarded_falls_back_to_model(cache_dir):
    cands = fused_nd_candidates((8, 8, 16), (1, 1, 1), 2, 1, 4)

    def measure(cand):
        raise RuntimeError("launch failed")  # paper: discarded launches

    rec = TuningSession().tune(KEY, cands, measure)
    assert rec.source == "model"
    assert rec.block == cands[0].block


def _tiny_problem():
    opset = derivative_operator_set(3, 2, spacing=0.3)

    def phi(d):
        lap = d["dxx"] + d["dyy"] + d["dzz"]
        return jnp.stack([d["val"][0] + 0.1 * lap[0]])

    rng = np.random.default_rng(7)
    f = jnp.asarray(rng.standard_normal((2, 8, 8, 16)), jnp.float32)
    return opset, phi, f


def test_auto_block_vmem_fallback(cache_dir):
    """No candidate fits a (tiny) VMEM budget: auto degrades to the
    smallest-footprint block without measuring, and the kernel still
    runs with it."""
    from repro.kernels import ops as kops
    from repro.tuning import session as sess_mod

    opset, phi, f = _tiny_problem()
    r = opset.radius
    fp = jnp.pad(f, ((0, 0),) + ((r, r),) * 3, mode="wrap")
    before = sess_mod.MEASURE_COUNT
    block = auto_block_nd(fp, opset, phi, 1, strategy="swc",
                          interpret=True, vmem_budget=64)
    assert sess_mod.MEASURE_COUNT == before  # no launches attempted
    # _tiny_problem builds an accuracy-2 opset: the non-default order
    # joins the strategy id as :o2.
    rec = TuningCache().get(
        TuningKey("fused_stencil3d", "swc:o2", (8, 8, 16), (r,) * 3, 2, 1,
                  "float32", sess_mod.current_backend())
    )
    assert rec is not None and rec.source == "fallback"
    out = kops.fused_stencil_nd(
        fp, opset, phi, 1, strategy="swc", block=block, interpret=True
    )
    assert out.shape == (1, 8, 8, 16)


# --- block="auto" end to end (acceptance criterion) ---------------------------


def test_auto_matches_explicit_and_persists_across_processes(cache_dir):
    opset, phi, f = _tiny_problem()
    auto_op = FusedStencilOp(opset, phi, 1, strategy="swc", block="auto")
    explicit = FusedStencilOp(opset, phi, 1, strategy="swc",
                              block=(4, 4, 16))
    out_auto = auto_op(f)
    out_exp = explicit(f)
    np.testing.assert_array_equal(
        np.asarray(out_auto), np.asarray(out_exp)
    )

    records = TuningCache().items()
    assert len(records) == 1
    rec = next(iter(records.values()))
    assert rec.source == "measured" and rec.timings_us

    # Second process: same auto op must replay the persisted record with
    # ZERO measurements, and produce the same numerics.
    code = f"""
import numpy as np
import jax.numpy as jnp
from repro.core.fusion import FusedStencilOp
from repro.core.stencil import derivative_operator_set
from repro.tuning import session as sess_mod

opset = derivative_operator_set(3, 2, spacing=0.3)
def phi(d):
    lap = d["dxx"] + d["dyy"] + d["dzz"]
    return jnp.stack([d["val"][0] + 0.1 * lap[0]])
rng = np.random.default_rng(7)
f = jnp.asarray(rng.standard_normal((2, 8, 8, 16)), jnp.float32)
out = FusedStencilOp(opset, phi, 1, strategy="swc", block="auto")(f)
assert sess_mod.MEASURE_COUNT == 0, sess_mod.MEASURE_COUNT
expect = np.asarray(
    FusedStencilOp(opset, phi, 1, strategy="swc", block=(4, 4, 16))(f)
)
np.testing.assert_array_equal(np.asarray(out), expect)
print("REUSED_OK")
"""
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=_subprocess_env(cache_dir),
    )
    assert out.returncode == 0, out.stderr
    assert "REUSED_OK" in out.stdout


def test_xcorr1d_auto_matches_explicit(cache_dir):
    from repro.kernels import ops as kops
    from repro.kernels import ref

    rng = np.random.default_rng(3)
    f = jnp.asarray(rng.standard_normal(4096 + 4), jnp.float32)
    g = jnp.asarray(rng.standard_normal(5), jnp.float32)
    out = kops.xcorr1d(f, g, strategy="baseline", block_size="auto",
                       interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.xcorr1d(f, g)),
        rtol=1e-4, atol=1e-4,
    )
    assert any(
        k.startswith("xcorr1d|") for k in TuningCache().items()
    )


# --- corruption quarantine (ISSUE 8) --------------------------------------------


def _seed_cache(cache_dir):
    TuningCache().put(
        KEY, TuningRecord(block=(4, 8, 16), timings_us={}, source="measured")
    )
    return cache_dir / "cache.json"


def test_truncated_cache_is_quarantined_and_rebuilt(cache_dir):
    """A cache.json cut short mid-write (crashed writer) is renamed
    aside — not silently shadowed — and the next put starts clean."""
    path = _seed_cache(cache_dir)
    data = path.read_bytes()
    path.write_bytes(data[: len(data) // 2])

    fresh = TuningCache()
    assert fresh.get(KEY) is None  # corrupt view is empty, not wrong
    assert (cache_dir / "cache.json.corrupt").exists()
    assert (cache_dir / "cache.json.corrupt").read_bytes() == data[: len(data) // 2]

    fresh.put(KEY, TuningRecord(block=(8, 8, 16), timings_us={}, source="measured"))
    assert TuningCache().get(KEY).block == (8, 8, 16)


def test_garbage_cache_is_quarantined(cache_dir):
    path = _seed_cache(cache_dir)
    path.write_text("{garbage: definitely, not json\x00")
    assert TuningCache().get(KEY) is None
    assert (cache_dir / "cache.json.corrupt").exists()
    assert not path.exists()  # moved aside, not copied


def test_valid_json_wrong_layout_is_quarantined(cache_dir):
    path = _seed_cache(cache_dir)
    path.write_text('["not", "a", "cache", "document"]')
    assert TuningCache().get(KEY) is None
    assert (cache_dir / "cache.json.corrupt").exists()


def test_repeated_corruption_numbers_the_corpses(cache_dir):
    for n in range(3):
        path = _seed_cache(cache_dir)
        path.write_text("not json at all")
        assert TuningCache().get(KEY) is None
    names = sorted(p.name for p in cache_dir.glob("cache.json.corrupt*"))
    assert names == [
        "cache.json.corrupt", "cache.json.corrupt.1", "cache.json.corrupt.2",
    ]


def test_missing_cache_is_cold_start_not_corruption(cache_dir):
    assert TuningCache().get(KEY) is None
    assert list(cache_dir.glob("cache.json.corrupt*")) == []


_STRESS_WORKER = """
import sys
from repro.tuning import TuningCache, TuningKey, TuningRecord

worker = int(sys.argv[1])
cache = TuningCache()
for i in range(8):
    key = TuningKey(
        kernel="stress", strategy=f"w{worker}", domain=(i,),
        radii=(1,), n_f=1, n_out=1, dtype="float32", backend="cpu",
    )
    cache.put(key, TuningRecord(block=2 ** (i + 4), timings_us={}, source="measured"))
"""


def test_multiprocess_put_loses_no_record(cache_dir):
    """N processes hammering put() concurrently: the advisory lock +
    read-merge-write must preserve every record from every worker."""
    n_workers = 4
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _STRESS_WORKER, str(w)],
            env=_subprocess_env(cache_dir),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        for w in range(n_workers)
    ]
    for p in procs:
        _, err = p.communicate(timeout=120)
        assert p.returncode == 0, err.decode()
    items = TuningCache().items()
    stress = {k for k in items if k.startswith("stress|")}
    assert len(stress) == n_workers * 8, sorted(stress)
    for w in range(n_workers):
        for i in range(8):
            key_id = f"stress|w{w}|{i}|1|1|1|float32|cpu"
            assert key_id in items, key_id
            assert items[key_id].block == 2 ** (i + 4)


# --- failed-candidate rows (ISSUE 8) --------------------------------------------


def test_failed_candidates_recorded_and_skipped_on_retune(cache_dir):
    """A candidate whose measurement raises becomes a ``failed`` row of
    the persisted record; a later (forced) re-tune never re-launches
    it."""
    cands = fused_nd_candidates((8, 8, 16), (1, 1, 1), 2, 1, 4)
    bad = {cands[0].block}
    calls = []

    def measure(cand):
        calls.append(cand.block)
        if cand.block in bad:
            raise RuntimeError("RESOURCE_EXHAUSTED: VMEM")
        return 1.0

    sess = TuningSession(top_k=2)
    rec = sess.tune(KEY, cands, measure)
    assert rec.source == "measured"
    assert rec.block != cands[0].block
    assert len(rec.failed) == 1
    assert "RESOURCE_EXHAUSTED" in next(iter(rec.failed.values()))

    # Persisted: a fresh session sees the failed row.
    assert len(TuningCache().get(KEY).failed) == 1

    calls.clear()
    rec2 = TuningSession(top_k=2).tune(KEY, cands, measure, force=True)
    assert cands[0].block not in calls  # known-bad skipped
    assert len(calls) == 2  # top-k drawn from the healthy pool
    assert rec2.failed == rec.failed  # carried forward


def test_all_failed_still_resolves_and_marks_every_row(cache_dir):
    cands = fused_nd_candidates((8, 8, 16), (1, 1, 1), 2, 1, 4)

    def measure(cand):
        raise RuntimeError("launch failed")

    sess = TuningSession(top_k=2)
    rec = sess.tune(KEY, cands, measure)
    assert rec.source == "model"
    assert len(rec.failed) == 2  # every attempted candidate marked


def test_failed_rows_roundtrip_and_old_records_parse(cache_dir):
    rec = TuningRecord(
        block=(4, 8, 16), timings_us={"4x8x16": 9.0}, source="measured",
        failed={"8x8x16": "InjectedCompileFailure: boom"},
    )
    TuningCache().put(KEY, rec)
    got = TuningCache().get(KEY)
    assert got.failed == {"8x8x16": "InjectedCompileFailure: boom"}
    # Pre-ISSUE-8 records (no "failed" key) parse with no failures.
    d = rec.to_json()
    del d["failed"]
    assert TuningRecord.from_json(d).failed == {}


def test_injected_candidate_fault_lands_in_failed_rows(cache_dir):
    """The module-level active injector (the chaos seam) turns a
    targeted candidate fault into a failed row, and tuning still
    resolves a winner."""
    from repro.ft.faults import FaultInjector, FaultSpec
    from repro.ft import faults as ftfaults

    cands = fused_nd_candidates((8, 8, 16), (1, 1, 1), 2, 1, 4)
    inj = FaultInjector([
        FaultSpec("tune.candidate", "compile", label="*", times=1),
    ])
    with ftfaults.active(inj):
        rec = TuningSession(top_k=2).tune(KEY, cands, lambda c: 1.0)
    assert rec.source == "measured"
    assert len(rec.failed) == 1
    assert "InjectedCompileFailure" in next(iter(rec.failed.values()))
    assert inj.fired[0][0] == "tune.candidate"

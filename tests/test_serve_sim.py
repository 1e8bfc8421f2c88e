"""Serving-loop tests: shape-bucketed batching (a mixed-shape queue
drains into plan-compatible buckets, FIFO head-of-line), per-bucket
tuning-cache behavior (first batch of a bucket tunes, later batches and
later servers replay the persisted ``:b{B}`` record), and
``StragglerMonitor`` engagement on an injected slow batch."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.ft.supervisor import StragglerMonitor
from repro.launch.serve_sim import (
    RequestQueue,
    SimRequest,
    SimServer,
    demo_queue,
)


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path))
    return tmp_path


def _req(rid, shape, n_steps=4, dtype=jnp.float32):
    f0 = jnp.zeros((1,) + shape, dtype) + 1e-5 * (rid + 1)
    return SimRequest(rid, f0, n_steps)


# --- queue bucketing ------------------------------------------------------------


def test_mixed_queue_drains_into_correct_buckets():
    """Interleaved shapes/steps separate into plan-compatible batches;
    the oldest waiting request always leads the next batch."""
    queue = RequestQueue()
    for rid in range(9):
        shape = (16, 32) if rid % 2 == 0 else (12, 24)
        queue.push(_req(rid, shape, n_steps=4 if rid < 6 else 8))
    batches = []
    while queue:
        key, reqs = queue.next_bucket(lambda r: r.bucket_key, max_batch=4)
        assert all(r.bucket_key == key for r in reqs)
        batches.append((key, [r.req_id for r in reqs]))
    # (16,32)+4steps: rids 0,2,4; (12,24)+4steps: 1,3,5;
    # (16,32)+8steps: 6,8; (12,24)+8steps: 7 — head-of-line order.
    assert [ids for _, ids in batches] == [
        [0, 2, 4], [1, 3, 5], [6, 8], [7]
    ]
    assert batches[0][0] == ((16, 32), "float32", 4)
    assert batches[2][0] == ((16, 32), "float32", 8)
    assert len({key for key, _ in batches}) == 4


def test_next_bucket_respects_max_batch_and_fifo():
    queue = RequestQueue([_req(i, (8, 16)) for i in range(5)])
    _, first = queue.next_bucket(lambda r: r.bucket_key, max_batch=4)
    assert [r.req_id for r in first] == [0, 1, 2, 3]
    _, rest = queue.next_bucket(lambda r: r.bucket_key, max_batch=4)
    assert [r.req_id for r in rest] == [4]
    assert not queue


def test_server_routes_every_request_to_its_bucket_result():
    """End to end on two interleaved shapes: every request id comes
    back with its own shape, and the server builds exactly one op per
    bucket."""
    queue = demo_queue([(16, 32), (12, 24)], n_steps=4, requests=10)
    expect_shape = {
        r.req_id: (1,) + r.bucket_key[0] for r in queue.snapshot()
    }
    server = SimServer(strategy="swc", max_batch=4)
    results = server.serve(queue)
    assert sorted(results) == list(range(10))
    for rid, out in results.items():
        assert out.shape == expect_shape[rid]
    assert server.op_builds == 2
    assert {rep.key[0] for rep in server.reports} == {(16, 32), (12, 24)}


# --- tuning-cache sharing -------------------------------------------------------


def test_per_bucket_tuning_cache_hits(cache_dir):
    """block="auto": the first full-size batch of each bucket measures
    and persists a ``:b{B}``-keyed record; every later batch of that
    bucket — including in a FRESH server (new process stand-in) —
    replays it with zero new measurements."""
    from repro.tuning import TuningCache
    from repro.tuning import session as sess_mod

    # 2 buckets x 2 full batches of B=2 each.
    queue = demo_queue([(16, 32), (12, 24)], n_steps=2, requests=8)
    server = SimServer(strategy="swc", block="auto", max_batch=2)
    server.serve(queue)
    measured = sess_mod.MEASURE_COUNT
    assert measured > 0  # the cold cache really was tuned
    keys = set(TuningCache().items())
    # The demo problems build accuracy-2 opsets, so the order
    # suffix follows the batch extent in the id.
    assert any(":b2:o2|16x32|" in k for k in keys), keys
    assert any(":b2:o2|12x24|" in k for k in keys), keys

    fresh = SimServer(strategy="swc", block="auto", max_batch=2)
    fresh.serve(demo_queue([(16, 32), (12, 24)], n_steps=2, requests=8))
    assert sess_mod.MEASURE_COUNT == measured  # pure cache replay
    assert set(TuningCache().items()) == keys


# --- straggler engagement -------------------------------------------------------


def test_straggler_monitor_flags_injected_slow_batch():
    """A deliberately slowed batch (contended-member stand-in) trips
    the trailing-median monitor once enough history exists, and the
    flag lands in the server's batch report."""
    slow_index = 6

    def inject(index, reqs):
        if index == slow_index:
            time.sleep(0.4)

    server = SimServer(
        strategy="swc",
        max_batch=2,
        straggler=StragglerMonitor(factor=1.5, window=20),
        batch_hook=inject,
    )
    queue = demo_queue([(16, 32)], n_steps=2, requests=14)  # 7 batches
    results = server.serve(queue)
    assert len(results) == 14
    flags = [rep.straggler for rep in server.reports]
    assert flags[slow_index], server.reports
    assert not any(flags[:slow_index])
    assert server.straggler.flagged[0][0] == slow_index


def test_fast_batches_do_not_flag():
    server = SimServer(strategy="swc", max_batch=2)
    server.serve(demo_queue([(16, 32)], n_steps=2, requests=12))
    assert not any(rep.straggler for rep in server.reports)
    assert server.straggler.flagged == []


# --- batched numerics through the server ----------------------------------------


def test_server_matches_per_member_serving():
    """Batched serving returns the same fields as serving each request
    alone (B=1 path) — bucketing is a throughput decision, not a
    numerics decision."""
    queue = demo_queue([(12, 24)], n_steps=4, requests=4, seed=7)
    singles = {r.req_id: r for r in queue.snapshot()}
    batched = SimServer(strategy="swc", max_batch=4).serve(queue)
    solo_server = SimServer(strategy="swc", max_batch=1)
    for rid, req in singles.items():
        solo = solo_server.serve(RequestQueue([req]))[rid]
        np.testing.assert_allclose(
            batched[rid], solo, rtol=0, atol=1e-6
        )


# --- the CLI's clean-serve contract ---------------------------------------------


@pytest.mark.parametrize("fails", [1, 5])
def test_smoke_cli_fails_on_unclean_request(
    fails, cache_dir, tmp_path, monkeypatch
):
    """Outside --chaos, ``serve_sim --smoke`` exits with an error if any
    request was only served after a retry (``fails=1``) or after the
    bucket fell down the ladder to another regime (``fails=5``): a
    fallback must never pass for a kernel that works."""
    from repro.launch import serve_sim

    run_batch = SimServer._run_batch
    left = {"n": fails}

    def flaky(self, key, reqs, strategy):
        if strategy == "swc" and left["n"] > 0:
            left["n"] -= 1
            raise RuntimeError("injected swc launch failure")
        return run_batch(self, key, reqs, strategy)

    monkeypatch.setattr(SimServer, "_run_batch", flaky)
    monkeypatch.setattr(serve_sim, "use_compile_cache", lambda: None)
    monkeypatch.setattr(
        serve_sim.RetryPolicy, "backoff", lambda self, attempt: 0.0
    )
    monkeypatch.setattr("sys.argv", [
        "serve_sim", "--smoke", "--requests", "2", "--steps", "2",
        "--json", str(tmp_path / "serve.json"),
    ])
    with pytest.raises(AssertionError, match="not served cleanly"):
        serve_sim.main()
    left["n"] = 0
    serve_sim.main()  # the same run with no failure passes

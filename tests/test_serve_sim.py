"""Serving-loop tests: shape-bucketed batching (a mixed-shape queue
drains into plan-compatible buckets, FIFO head-of-line), per-bucket
tuning-cache behavior (first batch of a bucket tunes, later batches and
later servers replay the persisted ``:b{B}`` record),
``StragglerMonitor`` engagement on an injected slow batch, and the
server's profiler spans."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.fusion import integrate
from repro.ft.faults import FaultInjector, FaultSpec
from repro.ft.supervisor import StragglerMonitor
from repro.launch import serve_sim
from repro.launch.serve_sim import (
    RequestQueue,
    RetryPolicy,
    SimRequest,
    SimServer,
    _vmap_reference,
    demo_queue,
)


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path))
    return tmp_path


class _FakeClock:
    """A clock that moves ``tick`` seconds at every read, and by hand."""

    def __init__(self, tick: float = 0.01):
        self.now, self.tick = 0.0, tick

    def __call__(self) -> float:
        self.now += self.tick
        return self.now

    def advance(self, seconds: float) -> None:
        self.now += seconds


@pytest.fixture
def clock(monkeypatch):
    fake = _FakeClock()
    monkeypatch.setattr(serve_sim, "_clock", fake)
    return fake


def _req(rid, shape, n_steps=4, dtype=jnp.float32):
    f0 = jnp.zeros((1,) + shape, dtype) + 1e-5 * (rid + 1)
    return SimRequest(rid, f0, n_steps)


def _served_stacks(reports, reqs):
    """{bucket key: [(req ids, member stack)]} of the batches that
    ``reports`` record for ``reqs``, members in batch order."""
    by_id = {r.req_id: r for r in reqs}
    stacks = {}
    for rep in reports:
        ids = list(rep.statuses)
        fb = jnp.stack([by_id[rid].f0 for rid in ids])
        stacks.setdefault(rep.key, []).append((ids, fb))
    return stacks


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


def test_auto_block_record_is_measured_before_the_program_traces(
    cache_dir, monkeypatch
):
    """block="auto" under the jitted batch program: the eager
    ``serve.warm`` call measures and persists the bucket's ``:b{B}``
    record before the program of that (bucket, B) traces, so the trace
    replays a measured record and measures nothing itself; a fresh
    server replays it with no new measurement and returns what the
    eager ``integrate`` returns."""
    from repro.tuning import TuningCache
    from repro.tuning import session as sess_mod

    traced = []

    def spy(op, fb, n):
        before = sess_mod.MEASURE_COUNT
        records = {
            k: rec.source for k, rec in TuningCache().items().items()
            if f":b{fb.shape[0]}:" in k
            and f"|{'x'.join(map(str, fb.shape[2:]))}|" in k
        }
        out = integrate(op, fb, n)
        traced.append((fb.shape, records, sess_mod.MEASURE_COUNT - before))
        return out

    monkeypatch.setattr(serve_sim, "integrate", spy)

    def queue():
        return demo_queue([(16, 32), (12, 24)], n_steps=2, requests=8)

    server = SimServer(strategy="swc", block="auto", max_batch=2)
    server.serve(queue())
    measured = sess_mod.MEASURE_COUNT
    assert measured > 0
    assert server.exe_builds == len(traced) == 2
    for shape, records, new in traced:
        assert records and set(records.values()) == {"measured"}, (shape, records)
        assert new == 0, shape

    fresh = SimServer(strategy="swc", block="auto", max_batch=2)
    q = queue()
    reqs = q.snapshot()
    results = fresh.serve(q)
    assert sess_mod.MEASURE_COUNT == measured
    assert fresh.exe_builds == 2
    for key, stacks in _served_stacks(fresh.reports, reqs).items():
        op = fresh._op_for(key, "swc")
        for ids, fb in stacks:
            eager = np.asarray(integrate(op, fb, key[2]))
            for member, rid in enumerate(ids):
                np.testing.assert_array_equal(results[rid], eager[member])


# --- the jitted batch program -------------------------------------------------


def test_batch_program_compiles_once_per_bucket_and_batch_size():
    """Rounds of a two-bucket queue at fixed batch sizes (3 and 2 per
    bucket): one program trace per distinct (bucket, B, strategy) in
    the first round, none after, and every batch's result is the eager
    ``integrate`` of the same stack, bit for bit."""
    server = SimServer(strategy="swc", max_batch=3)
    for rnd in range(3):
        queue = demo_queue([(16, 32), (12, 24)], n_steps=3, requests=10, seed=rnd)
        reqs = queue.snapshot()
        first = len(server.reports)
        results = server.serve(queue)
        assert [rep.batch for rep in server.reports[first:]] == [3, 3, 2, 2]
        assert server.exe_builds == 4  # 2 buckets x B in {3, 2}, swc
        assert server.op_builds == 2
        for key, stacks in _served_stacks(server.reports[first:], reqs).items():
            op = server._op_for(key, "swc")
            for ids, fb in stacks:
                eager = np.asarray(integrate(op, fb, key[2]))
                for member, rid in enumerate(ids):
                    np.testing.assert_array_equal(results[rid], eager[member])


def test_degraded_rung_adds_one_program():
    """A fault planted on the first rung degrades the bucket to the next
    one, which traces exactly one program of its own; later batches of
    the bucket reuse it."""
    server = SimServer(
        strategy="swc", max_batch=2, retry=RetryPolicy(backoff_s=0.0)
    )
    server.serve(demo_queue([(16, 32)], n_steps=2, requests=4))
    assert server.exe_builds == 1
    server.faults = FaultInjector([
        FaultSpec("serve.batch", "compile", strategy="swc",
                  times=server.retry.max_retries + 1),
    ])
    queue = demo_queue([(16, 32)], n_steps=2, requests=6, seed=1)
    reqs = queue.snapshot()
    results = server.serve(queue)
    assert server.exe_builds == 2
    assert [rep.strategy for rep in server.reports[-3:]] == ["hwc"] * 3
    assert {server.request_status[r.req_id] for r in reqs} == {"degraded"}
    expect = np.asarray(_vmap_reference(server, reqs))
    got = np.stack([results[r.req_id] for r in reqs])
    np.testing.assert_allclose(got, expect, rtol=0, atol=1e-6)


def test_failed_trace_is_not_cached(monkeypatch):
    """A batch program whose trace raises is not kept: the retry traces
    a fresh one, which then serves every later batch of the bucket with
    no further trace (a jit whose trace failed traces on every call)."""
    fail = {"n": 0}

    def flaky(op, fb, n):
        if fail["n"]:
            fail["n"] -= 1
            raise RuntimeError("injected lowering failure")
        return integrate(op, fb, n)

    monkeypatch.setattr(serve_sim, "integrate", flaky)
    server = SimServer(
        strategy="swc", max_batch=2, retry=RetryPolicy(backoff_s=0.0)
    )

    def serve(requests):
        first = len(server.reports)
        queue = demo_queue([(16, 32)], n_steps=2, requests=requests)
        return server.serve(queue), server.reports[first:]

    serve(2)
    assert server.exe_builds == 1  # B=2
    fail["n"] = 1
    results, reports = serve(3)  # B=2 cached; B=1's first trace raises
    assert sorted(results) == [0, 1, 2]
    assert [(rep.batch, rep.retries) for rep in reports] == [(2, 0), (1, 1)]
    assert reports[1].statuses == {2: "retried"}
    assert server.exe_builds == 3  # the failed trace and its retry
    serve(3)
    assert server.exe_builds == 4  # the fresh program traces B=2 once
    serve(3)
    assert server.exe_builds == 4


# --- straggler engagement -------------------------------------------------------


def test_straggler_monitor_flags_injected_slow_batch(clock):
    """A deliberately slowed batch (contended-member stand-in) trips
    the trailing-median monitor once enough history exists, and the
    flag lands in the server's batch report."""
    slow_index = 6

    def inject(index, reqs):
        if index == slow_index:
            clock.advance(0.4)

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


def test_fast_batches_do_not_flag(clock):
    server = SimServer(strategy="swc", max_batch=2)
    server.serve(demo_queue([(16, 32)], n_steps=2, requests=12))
    assert not any(rep.straggler for rep in server.reports)
    assert server.straggler.flagged == []
    # One timing per batch: the clock is read at its start and its end.
    for rep in server.reports:
        assert rep.seconds == pytest.approx(clock.tick)


# --- profiler spans -------------------------------------------------------------

# Each span and the span it nests in.
SPAN_PARENT = {
    "serve.drain": None,
    "serve.batch": "serve.drain",
    "serve.stack": "serve.batch",
    "serve.warm": "serve.batch",
    "serve.dispatch": "serve.batch",
    "serve.device_wait": "serve.batch",
    "serve.fetch": "serve.batch",
    "serve.validate": "serve.batch",
}


def _serve_profiled(server, queue, logdir):
    """Serve under a profiler session; returns (results, serve.* spans
    as (name, start, end, metadata))."""
    with jax.profiler.trace(str(logdir)):
        results = server.serve(queue)
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    spans = [
        (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(list(e.stats)))
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
        if e.name.startswith("serve.")
    ]
    return results, spans


def test_serve_spans_nest_and_count_batches(cache_dir, tmp_path):
    """Every span of the serving path is recorded on the profiler's
    host plane, nested as the server runs it, one ``serve.batch`` per
    batch report, carrying its bucket, members and request ids."""
    def queue():
        return demo_queue([(16, 32), (12, 24)], n_steps=2, requests=8)

    # block="auto": the first batch of each bucket runs the warm call,
    # which here replays the records a first server measured.
    SimServer(strategy="swc", block="auto", max_batch=2).serve(queue())
    server = SimServer(strategy="swc", block="auto", max_batch=2)
    ids = {r.req_id for r in queue().snapshot()}
    results, spans = _serve_profiled(server, queue(), tmp_path / "trace")
    assert set(results) == ids
    assert {name for name, *_ in spans} == set(SPAN_PARENT)
    for name, start, end, _ in spans:
        holders = [
            s for s in spans
            if s[1] <= start and end <= s[2] and s[:3] != (name, start, end)
        ]
        parent = min(holders, key=lambda s: s[2] - s[1])[0] if holders else None
        assert parent == SPAN_PARENT[name], (name, parent)
    batches = [meta for name, *_, meta in spans if name == "serve.batch"]
    assert len(batches) == len(server.reports) == 4
    assert [b["members"] for b in batches] == [
        rep.batch for rep in server.reports
    ] == [2] * 4
    assert {b["bucket"] for b in batches} == {
        "16x32/float32/n2", "12x24/float32/n2"
    }
    assert all(b["strategy"] == "swc" for b in batches)
    served = sorted(
        int(i) for b in batches for i in str(b["req_ids"]).strip("[]").split(",")
    )
    assert served == sorted(ids)
    drains = [meta for name, *_, meta in spans if name == "serve.drain"]
    assert [d["requests"] for d in drains] == [8]
    attempts = [meta["attempt"] for name, *_, meta in spans if name == "serve.dispatch"]
    assert attempts == [0] * 4


def test_profiler_session_leaves_results_bit_identical(tmp_path):
    """Spans only record: the same queue served with and without a
    profiler session gives the same bits."""
    def queue():
        return demo_queue([(16, 32), (12, 24)], n_steps=3, requests=6, seed=5)

    plain = SimServer(strategy="swc", max_batch=2).serve(queue())
    traced, spans = _serve_profiled(
        SimServer(strategy="swc", max_batch=2), queue(), tmp_path / "trace"
    )
    assert spans
    assert sorted(plain) == sorted(traced)
    for rid, out in plain.items():
        np.testing.assert_array_equal(out, traced[rid])


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

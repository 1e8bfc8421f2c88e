"""Ensemble simulation serving: the stencil-workload front door.

``repro.launch.serve`` serves language-model decode; THIS module serves
stencil simulations — thousands of concurrent scenarios (parameter
sweeps, Monte-Carlo ensembles, per-user simulations) funneled through
the batched fused-stencil engine:

* ``SimRequest`` / ``RequestQueue`` — FIFO request intake with
  shape-bucketed draining: requests sharing (spatial shape, dtype,
  n_steps) form one plan-compatible group, and the oldest request's
  bucket is served first (head-of-line FIFO, no starvation).
* ``SimServer`` — one batched ``FusedStencilOp`` per bucket, stacked
  to a (B, n_f, *spatial) operand so one kernel walks all B members
  per block (member-major grid, shared halo — the batch axis of
  ``StencilPlan``). Ops are cached per bucket and ``block="auto"``
  resolves through the persistent tuning cache, so the first batch of
  a bucket warms the ``:b{B}``-keyed record and every later batch
  replays it.
* **Failure domains** — one poisoned request must cost one request,
  never the queue. Every batch runs under a :class:`RetryPolicy`:
  transient failures retry with backoff; repeated failures degrade the
  bucket down the strategy ladder (``tc → swc_stream → swc → hwc``);
  a batch that fails even at the bottom rung is bisected until the
  poison request is isolated and quarantined (its members get an error
  report in ``SimServer.error_reports``, everyone else completes).
  Outputs are validated for NaN/inf before results are handed back,
  and every request carries a status (``ok | retried | degraded |
  quarantined``) in ``BatchReport``/``BENCH_serve.json``.
* ``StragglerMonitor`` hooks (``repro.ft.supervisor``) — per-batch
  wall times feed the trailing-median monitor; a slow batch is flagged
  (and counted in the serve report) exactly like a slow training step.
* ``repro.ft.faults`` — the seeded deterministic fault-injection layer
  (``SimServer(faults=...)``); ``--chaos`` drives the standard seeded
  fault plan through a live serve and asserts the recovery contract.

Run:  PYTHONPATH=src python -m repro.launch.serve_sim --smoke

``--smoke`` serves a small mixed-shape queue, asserts batched-vs-vmap
parity per request, and writes a ``BENCH_serve.json`` throughput
artifact (CI serve-smoke job). ``--smoke --chaos`` additionally injects
the seeded fault plan (poison request, transient compile failure, slow
batch, failing tuning candidate, corrupted ``cache.json``) and writes
``BENCH_serve_chaos.json`` (CI chaos-smoke job).
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import logging
import subprocess
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core.fusion import FusedStencilOp, integrate
from repro.ft import faults as ftfaults
from repro.ft.faults import FaultInjector
from repro.ft.supervisor import StragglerMonitor
from repro.physics.diffusion import DiffusionProblem

log = logging.getLogger("repro.serve")

# (spatial shape, dtype string, n_steps): requests sharing a key lower
# through ONE batched plan (same domain/dtype) for the SAME step count.
BucketKey = tuple[tuple[int, ...], str, int]

# Graceful-degradation order: most specialized caching regime first,
# the compiler-managed baseline (which always lowers) last. The paper's
# cross-platform finding — no single regime wins everywhere — is also
# why the robust fallback shape is a LADDER across regimes rather than
# a single retry: each rung trades peak throughput for generality.
DEGRADATION_LADDER = ("tc", "swc_stream", "swc", "hwc")

# Per-request status severity: a request that was ever quarantined
# stays quarantined; degraded beats retried beats ok.
_SEVERITY = {"ok": 0, "retried": 1, "degraded": 2, "quarantined": 3}


@dataclasses.dataclass(frozen=True)
class SimRequest:
    """One ensemble member: advance ``f0`` (n_f, *spatial) by
    ``n_steps`` diffusion steps."""

    req_id: int
    f0: jnp.ndarray
    n_steps: int

    @property
    def bucket_key(self) -> BucketKey:
        return (
            tuple(int(n) for n in self.f0.shape[1:]),
            str(self.f0.dtype),
            int(self.n_steps),
        )


class RequestQueue:
    """FIFO request queue with bucket-aware batch draining.

    Generic over the request type: the LM example
    (``examples/serve_batched.py``) pops one request at a time into
    freed decode slots; ensemble serving drains plan-compatible batches
    with :meth:`next_bucket`. Backed by a ``collections.deque`` so the
    hot single-request pop is O(1), not ``list.pop(0)``'s O(n).
    """

    def __init__(self, items=()):
        self._items = collections.deque(items)

    def push(self, item) -> None:
        self._items.append(item)

    def pop(self):
        """Oldest request, or None when empty (LM slot refill)."""
        return self._items.popleft() if self._items else None

    def snapshot(self) -> list:
        """Copy of the queued items in FIFO order — the public,
        non-draining view (callers must not reach into the internal
        deque)."""
        return list(self._items)

    def __len__(self) -> int:
        return len(self._items)

    def __bool__(self) -> bool:
        return bool(self._items)

    def next_bucket(self, bucket_of: Callable, max_batch: int):
        """Drain up to ``max_batch`` requests sharing the OLDEST
        request's bucket key (head-of-line FIFO: the oldest waiting
        request is always served in the next batch). Returns
        ``(key, requests)`` or None when empty."""
        if not self._items:
            return None
        key = bucket_of(self._items[0])
        taken, kept = [], []
        for item in self._items:
            if len(taken) < max_batch and bucket_of(item) == key:
                taken.append(item)
            else:
                kept.append(item)
        self._items = collections.deque(kept)
        return key, taken


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Per-batch failure policy: how a failing batch is retried,
    degraded, and finally bisected.

    1. **Retry** the batch up to ``max_retries`` times at the current
       strategy, sleeping ``backoff_s · 2^(attempt-1)`` between tries
       (a transient compile hiccup or allocator race heals here).
    2. **Degrade** the bucket one rung down ``ladder`` when retries are
       exhausted (a strategy-specific failure — e.g. a tc dtype error
       or a VMEM-oversized streaming candidate — heals here); the rung
       sticks for later batches of the bucket until a quarantine
       re-attributes the fault to a request.
    3. **Bisect** the batch when even the bottom rung fails: halves are
       re-served independently, so a single poison request is isolated
       in O(log B) sub-batches and quarantined while every healthy
       member completes.
    """

    max_retries: int = 2
    backoff_s: float = 0.05
    ladder: tuple[str, ...] = DEGRADATION_LADDER

    def backoff(self, attempt: int) -> float:
        return self.backoff_s * (2 ** max(0, attempt - 1))

    def degrade(self, strategy: str) -> str | None:
        """Next rung down the ladder, or None at the bottom.
        ``"auto"`` — a meta-strategy that may have resolved to any
        regime — re-enters at the always-lowerable ``swc`` rung."""
        if strategy == "auto":
            return "swc"
        if strategy not in self.ladder:
            return None
        i = self.ladder.index(strategy)
        return self.ladder[i + 1] if i + 1 < len(self.ladder) else None


@dataclasses.dataclass
class BatchReport:
    """One executed batch: bucket, members, the timing the straggler
    monitor saw, and the failure-domain outcome (strategy actually
    used, retries consumed, per-request status)."""

    index: int
    key: BucketKey
    batch: int
    seconds: float
    straggler: bool
    strategy: str = ""
    retries: int = 0
    statuses: dict[int, str] = dataclasses.field(default_factory=dict)


class SimServer:
    """Shape-bucketed batch server over the batched fused engine.

    One ``FusedStencilOp`` per (bucket, strategy) — built lazily,
    cached for the server's lifetime (``op_builds`` counts cache
    misses); requests are stacked member-major to (B, n_f, *spatial)
    and integrated in one batched call per bucket.

    Failure domains: every batch executes inside a try/except driven
    by ``retry`` (:class:`RetryPolicy` — retry with backoff, then the
    strategy degradation ladder, then bisection + quarantine), outputs
    are NaN/inf-validated before being handed back
    (``validate_output``), and per-request outcomes accumulate in
    ``request_status`` (``ok | retried | degraded | quarantined``) and
    ``error_reports`` (quarantined requests only). A quarantine costs
    exactly the poisoned request: everyone else in its batch completes.

    ``batch_hook(index, requests)`` runs inside the timed region — the
    legacy fault-injection seam kept for straggler tests; structured
    injection goes through ``faults`` (a
    :class:`repro.ft.faults.FaultInjector`), whose batch faults fire
    inside the same timed try block.
    """

    def __init__(
        self,
        *,
        strategy: str = "swc",
        block=None,
        accuracy: int = 2,
        alpha: float = 1.0,
        max_batch: int = 8,
        straggler: StragglerMonitor | None = None,
        batch_hook: Callable[[int, list], None] | None = None,
        retry: RetryPolicy | None = None,
        faults: FaultInjector | None = None,
        validate_output: bool = True,
    ):
        self.strategy = strategy
        self.block = block
        self.accuracy = accuracy
        self.alpha = alpha
        self.max_batch = max_batch
        self.straggler = straggler or StragglerMonitor()
        self.batch_hook = batch_hook
        self.retry = retry or RetryPolicy()
        self.faults = faults
        self.validate_output = validate_output
        self.reports: list[BatchReport] = []
        self.op_builds = 0
        self.request_status: dict[int, str] = {}
        self.error_reports: dict[int, dict] = {}
        self._ops: dict[tuple, FusedStencilOp] = {}
        self._warmed: set = set()
        # Current degradation rung per bucket (absent = configured
        # strategy). Written when a batch only completes after
        # degrading; cleared when a quarantine re-attributes the
        # failure to a poison request rather than the strategy.
        self._strategy_for: dict[tuple, str] = {}

    def _op_for(self, key: BucketKey, strategy: str) -> FusedStencilOp:
        shape, dtype, _ = key
        op_key = (shape, dtype, strategy)  # n_steps lives in integrate
        if op_key not in self._ops:
            problem = DiffusionProblem(
                shape, accuracy=self.accuracy, alpha=self.alpha
            )
            # hwc ignores the block (XLA manages the cache); don't drag
            # the bottom rung through a pointless tuning resolution.
            block = None if strategy == "hwc" else self.block
            self._ops[op_key] = problem.step_op(strategy, block)
            self.op_builds += 1
        return self._ops[op_key]

    def serve(self, queue: RequestQueue) -> dict[int, np.ndarray]:
        """Drain the queue; returns {req_id: final (n_f, *spatial)}
        for every request that completed (quarantined requests are
        reported in ``error_reports`` instead)."""
        results: dict[int, np.ndarray] = {}
        while queue:
            key, reqs = queue.next_bucket(
                lambda r: r.bucket_key, self.max_batch
            )
            self._serve_batch(key, reqs, results)
        return results

    # -- failure-domain core ------------------------------------------------

    def _serve_batch(
        self, key: BucketKey, reqs: list, results: dict
    ) -> None:
        """Serve one plan-compatible batch through the retry →
        degrade → bisect → quarantine ladder."""
        bucket = (key[0], key[1])
        strategy = self._strategy_for.get(bucket, self.strategy)
        retries = 0
        while True:
            try:
                out, dt = self._run_batch(key, reqs, strategy)
                break
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                last_err = e
                log.warning(
                    "batch of %d over %s failed under %s: %s: %s",
                    len(reqs), bucket, strategy, type(e).__name__, e,
                )
                if retries < self.retry.max_retries:
                    retries += 1
                    pause = self.retry.backoff(retries)
                    if pause:
                        time.sleep(pause)
                    continue
                nxt = self._next_viable(strategy, key)
                if nxt is not None:
                    log.warning(
                        "degrading bucket %s: %s -> %s", bucket,
                        strategy, nxt,
                    )
                    strategy = nxt
                    self._strategy_for[bucket] = nxt
                    retries = 0
                    continue
                if len(reqs) > 1:
                    # Ladder exhausted: a member is poisoning the
                    # batch. Bisect to isolate it — healthy halves
                    # complete, the poison ends up in a singleton.
                    mid = len(reqs) // 2
                    log.warning(
                        "bisecting failing batch of %d over %s",
                        len(reqs), bucket,
                    )
                    self._serve_batch(key, reqs[:mid], results)
                    self._serve_batch(key, reqs[mid:], results)
                    return
                self._quarantine(key, reqs[0], last_err, strategy)
                # The fault was request-attributable: later batches of
                # this bucket restart at the configured strategy.
                self._strategy_for.pop(bucket, None)
                self.reports.append(BatchReport(
                    index=len(self.reports), key=key, batch=1,
                    seconds=0.0, straggler=False, strategy=strategy,
                    retries=retries,
                    statuses={reqs[0].req_id: "quarantined"},
                ))
                return

        # Success: validate member outputs, then hand results back.
        base = "ok"
        if strategy != self.strategy:
            base = "degraded"
        elif retries:
            base = "retried"
        bad = (
            self._nonfinite_members(out) if self.validate_output else ()
        )
        statuses: dict[int, str] = {}
        for member, req in enumerate(reqs):
            if member in bad:
                self._quarantine(
                    key, req,
                    ValueError("non-finite output (NaN/inf)"),
                    strategy,
                )
                statuses[req.req_id] = "quarantined"
            else:
                results[req.req_id] = np.asarray(out[member])
                statuses[req.req_id] = base
                self._mark(req.req_id, base)
        index = len(self.reports)
        flagged = self.straggler.record(index, dt)
        self.reports.append(BatchReport(
            index=index, key=key, batch=len(reqs), seconds=dt,
            straggler=flagged, strategy=strategy, retries=retries,
            statuses=statuses,
        ))

    def _run_batch(self, key: BucketKey, reqs: list, strategy: str):
        """One batched integrate under ``strategy``: warm the tuning
        cache if needed, fire injected batch faults inside the timed
        region, and return ``(output array, seconds)``."""
        op = self._op_for(key, strategy)
        fb = jnp.stack([r.f0 for r in reqs])  # (B, n_f, *spatial)
        warm_key = (key[0], key[1], len(reqs), strategy)
        if (
            (self.block == "auto" or strategy == "auto")
            and strategy != "hwc"
            and warm_key not in self._warmed
        ):
            # Eager warm call OUTSIDE lax control flow: a cache miss
            # runs the rank-then-measure search and persists the
            # measured :b{B} record; under integrate's scan tracing
            # it could only have written a cost-model record.
            jax.block_until_ready(op(fb))
            self._warmed.add(warm_key)
        index = len(self.reports)
        req_ids = [r.req_id for r in reqs]
        t0 = time.perf_counter()
        if self.batch_hook is not None:
            self.batch_hook(index, reqs)
        if self.faults is not None:
            self.faults.on_batch(index, req_ids, strategy)
        out = jax.block_until_ready(integrate(op, fb, key[2]))
        dt = time.perf_counter() - t0
        out = np.asarray(out)
        if self.faults is not None:
            out = self.faults.corrupt_output(req_ids, out)
        return out, dt

    def _next_viable(self, strategy: str, key: BucketKey) -> str | None:
        """First rung below ``strategy`` whose op actually builds for
        this bucket (e.g. ``swc_stream`` needs rank ≥ 2, ``tc`` needs
        f32/bf16 — invalid rungs are skipped, not crashed into)."""
        nxt = self.retry.degrade(strategy)
        while nxt is not None:
            try:
                self._op_for(key, nxt)
                return nxt
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                log.warning(
                    "ladder rung %s not viable for %s: %s",
                    nxt, key[0], e,
                )
                nxt = self.retry.degrade(nxt)
        return None

    @staticmethod
    def _nonfinite_members(out: np.ndarray) -> set[int]:
        """Member indices of a (B, ...) stack carrying NaN/inf — the
        output-validation gate before results are handed back."""
        bad: set[int] = set()
        for member in range(out.shape[0]):
            arr = out[member]
            try:
                finite = bool(np.isfinite(arr).all())
            except TypeError:  # exotic float dtypes (e.g. bfloat16)
                finite = bool(np.isfinite(arr.astype(np.float32)).all())
            if not finite:
                bad.add(member)
        return bad

    def _mark(self, req_id: int, status: str) -> None:
        cur = self.request_status.get(req_id, "ok")
        if _SEVERITY[status] >= _SEVERITY[cur]:
            self.request_status[req_id] = status

    def _quarantine(
        self, key: BucketKey, req, err: BaseException, strategy: str
    ) -> None:
        """Fail exactly one request: record its error report and mark
        it quarantined. Its batchmates are unaffected."""
        self._mark(req.req_id, "quarantined")
        self.error_reports[req.req_id] = {
            "req_id": req.req_id,
            "bucket": "x".join(map(str, key[0]))
            + f"/{key[1]}/n{key[2]}",
            "strategy": strategy,
            "error": f"{type(err).__name__}: {err}",
        }
        log.error(
            "quarantined request %d (%s under %s): %s: %s",
            req.req_id, key[0], strategy, type(err).__name__, err,
        )


# ---------------------------------------------------------------------------
# CLI: smoke queue, parity check, chaos plan, BENCH_serve*.json artifact.
# ---------------------------------------------------------------------------


def demo_queue(
    shapes, n_steps: int, requests: int, seed: int = 0
) -> RequestQueue:
    """Mixed-shape request stream: round-robin over ``shapes`` so every
    bucket interleaves with the others in FIFO order."""
    rng = np.random.default_rng(seed)
    queue = RequestQueue()
    for rid in range(requests):
        shape = shapes[rid % len(shapes)]
        f0 = jnp.asarray(
            rng.uniform(-1e-5, 1e-5, size=(1,) + shape), jnp.float32
        )
        queue.push(SimRequest(rid, f0, n_steps))
    return queue


def _vmap_reference(server: SimServer, reqs: list[SimRequest]):
    """The oracle the batched path must match: vmap of the SINGLE-member
    op over the stacked ensemble (B independent lowerings' numerics,
    one launch per member)."""
    key = reqs[0].bucket_key
    problem = DiffusionProblem(
        key[0], accuracy=server.accuracy, alpha=server.alpha
    )
    op = problem.step_op("hwc")
    fb = jnp.stack([r.f0 for r in reqs])
    return jax.vmap(lambda f: integrate(op, f, key[2]))(fb)


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            check=True, timeout=10,
        ).stdout.strip()
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def _write_bench(path: str, rows: list[dict], smoke: bool) -> None:
    """BENCH_*.json with the benchmarks/run.py row schema (name,
    us_per_call, derived + device/git_sha stamps) so the CI artifact
    pipeline treats serving throughput like any other perf row."""
    from repro.tuning.cache import current_backend

    device, sha = current_backend(), _git_sha()
    payload = {
        "schema": 1,
        "device": device,
        "git_sha": sha,
        "smoke": smoke,
        "rows": [
            {**row, "device": device, "git_sha": sha} for row in rows
        ],
    }
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=1, sort_keys=True)
        fh.write("\n")
    print(f"wrote {len(rows)} row(s) to {path}")


def _assert_parity(server, by_id, results) -> float:
    """Batched-vs-vmap parity over every COMPLETED request (f32
    workload, so bound the difference relative to the field scale);
    quarantined requests are excluded — they have no result to check.
    Returns the max abs error."""
    max_err = 0.0
    for key in {r.bucket_key for r in by_id.values()}:
        reqs = [
            r for r in by_id.values()
            if r.bucket_key == key and r.req_id in results
        ]
        if not reqs:
            continue
        expect = np.asarray(_vmap_reference(server, reqs))
        got = np.stack([results[r.req_id] for r in reqs])
        scale = float(np.abs(expect).max())
        err = float(np.abs(got - expect).max())
        max_err = max(max_err, err)
        assert err <= 1e-5 * max(scale, 1e-30), (
            f"batched-vs-vmap parity failed for bucket {key}: "
            f"max abs err {err:.2e} at field scale {scale:.2e}"
        )
    return max_err


def _assert_chaos_contract(server, injector, plan, by_id, results, cache):
    """The chaos acceptance contract: every healthy request completed,
    exactly the poison request is quarantined, the failing tuning
    candidate did not abort strategy="auto", and the corrupted
    cache.json was quarantined aside and rebuilt."""
    quarantined = set(server.error_reports)
    poison = plan["poison"]
    assert quarantined == {poison}, (
        f"expected exactly the poison request {poison} quarantined, "
        f"got {quarantined}"
    )
    assert server.request_status[poison] == "quarantined"
    assert poison not in results
    healthy = set(by_id) - {poison}
    assert set(results) == healthy, (
        f"missing healthy results: {healthy - set(results)}"
    )
    # The transient compile failure was retried to completion.
    assert plan["transient"] in results
    assert server.request_status[plan["transient"]] == "retried", (
        plan, server.request_status,
    )
    # A tuning candidate really failed — and auto still resolved
    # (ops were built and every healthy request produced a result).
    assert any(
        site == "tune.candidate" for site, _, _ in injector.fired
    ), f"tune.candidate fault never fired: {injector.fired}"
    # The garbled cache.json was quarantined aside and rebuilt.
    corpses = list(
        cache.file.parent.glob(cache.file.name + ".corrupt*")
    )
    assert corpses, "corrupt cache.json was not quarantined aside"
    from repro.tuning.cache import TuningCache

    assert cache.file.exists() and TuningCache().items(), (
        "tuning cache was not rebuilt after quarantine"
    )
    print(
        f"chaos contract OK: {len(injector.fired)} fault(s) fired, "
        f"request {poison} quarantined, request {plan['transient']} "
        f"retried, cache quarantined to {corpses[0].name}"
    )


def main() -> None:
    ap = argparse.ArgumentParser(
        description="Batched stencil-simulation serving loop"
    )
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--steps", type=int, default=8,
                    help="diffusion steps per request")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="largest ensemble batch per kernel launch")
    ap.add_argument("--strategy", default="swc",
                    choices=("hwc", "swc", "swc_stream", "tc", "auto"))
    ap.add_argument("--auto-tune", action="store_true",
                    help="resolve the batched kernel block from the "
                         "persistent tuning cache (block='auto': the "
                         "first batch of each bucket tunes and persists "
                         "a :b{B}-keyed record, later batches replay it)")
    ap.add_argument("--smoke", action="store_true",
                    help="small mixed-shape queue + batched-vs-vmap "
                         "parity assertion (CI serve-smoke job)")
    ap.add_argument("--chaos", action="store_true",
                    help="inject the seeded deterministic fault plan "
                         "(repro.ft.faults.chaos_specs) and assert the "
                         "recovery contract; forces strategy='auto' + "
                         "block='auto' so the failing-tuning-candidate "
                         "fault has a search to disrupt")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the --chaos fault plan (same seed, "
                         "same faults, every run)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write throughput rows as BENCH JSON (default "
                         "BENCH_serve.json under --smoke, "
                         "BENCH_serve_chaos.json under --chaos)")
    args = ap.parse_args()
    use_compile_cache()
    logging.basicConfig(level=logging.WARNING)

    shapes = [(16, 32), (12, 24)] if args.smoke else [(32, 64), (24, 48)]
    strategy = args.strategy
    block = "auto" if (args.auto_tune or strategy == "auto") else None
    if args.chaos:
        strategy, block = "auto", "auto"
    queue = demo_queue(shapes, args.steps, args.requests)
    by_id = {r.req_id: r for r in queue.snapshot()}

    injector = plan = cache = None
    if args.chaos:
        import os
        import tempfile

        from repro.tuning.cache import ENV_VAR, TuningCache

        # Chaos garbles cache.json on purpose; don't do that to the
        # developer's real cache — redirect to a scratch dir unless the
        # caller pinned one (CI does).
        if ENV_VAR not in os.environ:
            os.environ[ENV_VAR] = tempfile.mkdtemp(
                prefix="repro-chaos-cache-"
            )
            print(
                f"chaos: tuning cache redirected to {os.environ[ENV_VAR]}"
            )
        specs, plan = ftfaults.chaos_specs(
            args.fault_seed, list(by_id)
        )
        injector = FaultInjector(specs, slow_s=0.3)
        # Crashed-writer stand-in: garble cache.json BEFORE serving, so
        # the first tuning read must quarantine and rebuild it.
        cache = TuningCache()
        injector.corrupt_cache(cache.file)
        print(f"chaos plan (seed {args.fault_seed}): {plan}")

    server = SimServer(
        strategy=strategy, block=block, max_batch=args.max_batch,
        faults=injector,
    )

    t0 = time.time()
    if injector is not None:
        with ftfaults.active(injector):
            results = server.serve(queue)
    else:
        results = server.serve(queue)
    wall = time.time() - t0

    quarantined = set(server.error_reports)
    assert set(results) == set(by_id) - quarantined
    if not args.chaos:
        # Outside chaos every request must be served as asked: a retry
        # or a fall down the ladder would hide a kernel that fails.
        unclean = {
            rid: server.request_status.get(rid, "ok") for rid in by_id
        }
        unclean = {rid: s for rid, s in unclean.items() if s != "ok"}
        assert not unclean, (
            f"requests not served cleanly: {unclean} "
            f"{server.error_reports}"
        )

    members = sum(rep.batch for rep in server.reports)
    stragglers = sum(rep.straggler for rep in server.reports)
    status_counts = collections.Counter(
        server.request_status.get(rid, "ok") for rid in by_id
    )
    print(
        f"served {len(results)}/{args.requests} request(s) in "
        f"{len(server.reports)} batch(es) / {server.op_builds} op "
        f"build(s), {wall:.2f}s "
        f"({members * args.steps / wall:.1f} member-steps/s, "
        f"{stragglers} straggler(s), "
        + ", ".join(f"{k}={v}" for k, v in sorted(status_counts.items()))
        + ")"
    )

    rows = []
    for rep in server.reports:
        shape = "x".join(map(str, rep.key[0]))
        counts = collections.Counter(rep.statuses.values())
        status_s = ",".join(
            f"{k}:{v}" for k, v in sorted(counts.items())
        )
        rows.append({
            "name": f"serve/{shape}/b{rep.batch}",
            "us_per_call": rep.seconds * 1e6,
            "derived": (
                f"n_steps={rep.key[2]};batch={rep.batch};"
                f"strategy={rep.strategy};retries={rep.retries};"
                f"straggler={int(rep.straggler)};statuses={status_s}"
            ),
        })
    for rid in sorted(server.error_reports):
        report = server.error_reports[rid]
        rows.append({
            "name": f"serve/quarantine/r{rid}",
            "us_per_call": 0.0,
            "derived": (
                f"status=quarantined;bucket={report['bucket']};"
                f"strategy={report['strategy']};error={report['error']}"
            ),
        })

    if args.smoke or args.chaos:
        max_err = _assert_parity(server, by_id, results)
        rows.append({
            "name": "serve/parity",
            "us_per_call": 0.0,
            "derived": f"max_abs_err={max_err:.3e};status=ok",
        })
        print(f"batched-vs-vmap parity OK (max abs err {max_err:.2e})")

    if args.chaos:
        _assert_chaos_contract(
            server, injector, plan, by_id, results, cache
        )
        rows.append({
            "name": "serve/chaos",
            "us_per_call": 0.0,
            "derived": (
                f"fault_seed={args.fault_seed};"
                f"faults_fired={len(injector.fired)};"
                f"poison={plan['poison']};transient={plan['transient']};"
                f"quarantined={len(quarantined)};status=ok"
            ),
        })

    json_path = args.json or (
        "BENCH_serve_chaos.json" if args.chaos
        else ("BENCH_serve.json" if args.smoke else None)
    )
    if json_path:
        _write_bench(json_path, rows, args.smoke or args.chaos)
    print("serve_sim OK")


if __name__ == "__main__":
    main()

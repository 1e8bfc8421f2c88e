"""TuningSession — structural-rank → measure-top-k → record.

The paper's Sec. 5.1 protocol, made persistent: the structural cost
model prunes the block-shape space, the top-k survivors are timed on
hardware (warm-up + median of timed calls), and the winner is recorded
in the per-platform cache so every later process — and every
``block="auto"`` call site — reuses it without re-measurement.

Under ``jax.jit`` tracing no measurement is possible (there is no
concrete operand to time), so the session falls back to the structural
winner and records it as ``source="model"``; a later eager call or
``python -m repro.tuning warm`` upgrades the record to ``"measured"``.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Any, Callable, Sequence

import jax

from repro.ft import faults as ftfaults
from repro.tuning.cache import (
    TuningCache,
    TuningKey,
    TuningRecord,
    candidate_label,
    current_backend,
)
from repro.tuning.costmodel import (
    Candidate,
    VMEM_BUDGET,
    enumerate_candidates_1d,
    enumerate_candidates_nd,
    enumerate_cross_strategy_nd,
    time_candidate,
)

log = logging.getLogger("repro.tuning")

# Total hardware measurements taken by sessions in this process. Tests
# (and the acceptance criterion) assert a second process replays from the
# persisted record with this still at zero.
MEASURE_COUNT = 0

@dataclasses.dataclass
class TuningSession:
    """One tuning context: a cache plus the measurement protocol knobs
    (paper: 3 timed iterations after warm-up).

    Args (dataclass fields):
        cache: the persistent per-platform :class:`TuningCache` records
            are read from / written to.
        top_k: how many structurally-ranked candidates are measured.
        warmup / iters: per-candidate timing protocol (warm-up calls,
            then the median of ``iters`` timed calls).
        record_source: source stamped on measured records ("measured",
            or "smoke" for degraded single-iteration protocols, which
            later full-protocol callers are allowed to upgrade).

    Most callers never construct one — ``default_session()`` provides
    the process-wide instance every ``block="auto"`` site shares.

    Example (an isolated session against a throwaway cache)::

        >>> from repro.tuning.cache import TuningCache
        >>> from repro.tuning.session import TuningSession
        >>> sess = TuningSession(cache=TuningCache("/tmp/tune-doc"))
        >>> sess.top_k
        4
    """

    cache: TuningCache = dataclasses.field(default_factory=TuningCache)
    top_k: int = 4
    warmup: int = 1
    iters: int = 3
    # Source stamped on measured records. Degraded protocols (e.g. a
    # --smoke benchmark's single-iteration timing) pass "smoke" so the
    # record is treated as upgradeable, like "model", by full-protocol
    # callers instead of replayed forever.
    record_source: str = "measured"

    def tune(
        self,
        key: TuningKey,
        candidates: Sequence[Any],
        measure: Callable[[Any], float] | None = None,
        *,
        force: bool = False,
    ) -> TuningRecord:
        """Resolve ``key``: cache-hit fast path, else rank/measure/record.

        ``candidates`` are structurally ranked (best first) and must each
        expose a ``.block`` attribute (and optionally ``.fuse_steps``
        for joint block/temporal-depth searches).
        ``measure(candidate) -> seconds`` may raise to signal a
        discarded launch — the failure is recorded as a ``failed`` row
        of the persisted timing table (label → error summary), and
        later re-tunes of the same key skip those known-bad candidates
        instead of re-launching them; ``None`` (e.g. under tracing)
        selects the structural winner without hardware.
        """
        if not force:
            hit = self.cache.get(key)
            if hit is not None and not (
                hit.source in ("model", "smoke") and measure is not None
            ):
                # Fast path — except an upgradeable record (cost model
                # under jit tracing, or a degraded smoke-mode timing) is
                # re-tuned as soon as a caller CAN measure.
                return hit
        if not candidates:
            raise ValueError(f"no tuning candidates for {key.cache_id}")

        # Known-bad candidates from a prior record's failed rows are
        # carried forward and never re-launched (a compile failure or
        # RESOURCE_EXHAUSTED is not going to heal between processes).
        prior = self.cache.get(key)
        known_bad = dict(prior.failed) if prior is not None else {}
        failed: dict[str, str] = {
            label: err
            for label, err in known_bad.items()
            if any(_timing_label(c) == label for c in candidates)
        }
        pool = [
            c for c in candidates if _timing_label(c) not in known_bad
        ]

        record: TuningRecord | None = None
        if measure is not None:
            global MEASURE_COUNT
            timings: dict[str, float] = {}
            best: tuple[float, Any] | None = None
            for cand in pool[: self.top_k]:
                label = _timing_label(cand)
                try:
                    ftfaults.maybe_fail_candidate(label)
                    t = measure(cand)
                except Exception as e:
                    # The paper's discarded launch (not counted as a
                    # measurement) — but persisted, so warm re-tunes
                    # skip the candidate instead of rediscovering it.
                    failed[label] = f"{type(e).__name__}: {e}"
                    log.warning(
                        "tuning candidate %s failed for %s: %s",
                        label, key.cache_id, failed[label],
                    )
                    continue
                MEASURE_COUNT += 1
                timings[label] = t * 1e6
                if best is None or t < best[0]:
                    best = (t, cand)
            if best is not None:
                record = _candidate_record(
                    best[1], timings, self.record_source
                )
        if record is None:
            # No measure fn, or every attempted candidate was discarded:
            # fall back to the structural winner among the not-known-bad
            # pool (source="model" keeps the record upgradeable).
            fallback = pool[0] if pool else candidates[0]
            record = _candidate_record(fallback, {}, "model")
        record.failed = failed
        self.cache.put(key, record)
        return record


def _candidate_record(
    cand: Any, timings: dict[str, float], source: str
) -> TuningRecord:
    """Record for a winning candidate — persisting the temporal depth,
    the explicit-streaming flag, and (for cross-strategy searches) the
    resolved strategy, so a warm cache hit reproduces the whole lowering
    decision."""
    return TuningRecord(
        block=cand.block, timings_us=timings, source=source,
        fuse_steps=getattr(cand, "fuse_steps", 1),
        stream=getattr(cand, "stream", False),
        strategy_resolved=getattr(cand, "strategy", ""),
        unroll=getattr(cand, "unroll", 1),
    )


def _timing_label(cand: Any) -> str:
    """Timing-table key for one candidate — the shared
    :func:`repro.tuning.cache.candidate_label` derivation, which
    ``TuningRecord.winner_label`` mirrors for display code."""
    return candidate_label(
        cand.block,
        getattr(cand, "fuse_steps", 1),
        getattr(cand, "stream", False),
        getattr(cand, "strategy", ""),
    )


# One process-wide session so all `block="auto"` call sites share a
# cache view. Rebuilt if REPRO_TUNE_CACHE is re-pointed (tests do this).
_DEFAULT: TuningSession | None = None


def default_session() -> TuningSession:
    """The process-wide session every ``"auto"`` call site shares;
    rebuilt when $REPRO_TUNE_CACHE is re-pointed (tests do this)."""
    global _DEFAULT
    from repro.tuning.cache import default_cache_dir

    if _DEFAULT is None or _DEFAULT.cache.dir != default_cache_dir():
        _DEFAULT = TuningSession()
    return _DEFAULT


def _is_concrete(x) -> bool:
    return not isinstance(x, jax.core.Tracer)


# ---------------------------------------------------------------------------
# Fused stencil engine glue (`block="auto"` at any rank). Cache keys are
# the serialized plan identity (StencilPlan.tuning_key), so rank-1/2/3
# problems share one persistent cache with distinct, stable keys.
# ---------------------------------------------------------------------------


def fused_nd_key(
    domain: tuple[int, ...],
    radii: tuple[int, ...],
    n_f: int,
    n_out: int,
    dtype: str,
    strategy: str,
    backend: str | None = None,
    unroll: int = 1,
    fuse_steps: int | str = 1,
    batch: int = 1,
    accuracy: int = 0,
    n_aux: int = 0,
) -> TuningKey:
    """Plan-identity tuning key (mirrors ``StencilPlan.tuning_key``).

    The strategy id — stream axis (``swc_stream`` → ``:sz`` at rank 3,
    ``:sy`` at rank 2), unroll, ``fuse_steps``, ensemble ``batch``,
    aux-operand (``:a{N}``, aux-carrying plans only) and operator-order
    (``:o{A}``, non-default accuracy only) suffixes —
    comes from the plan layer's canonical ``strategy_sid``
    derivation, so this mirror can never diverge from
    ``StencilPlan.strategy_id``; depth-1 and depth-2 problems cache
    separately, the joint block/depth search keys as ``:fauto``, and a
    B-member ensemble problem keys as ``:b{B}``. The plan→key
    injectivity of the whole derivation is audited by
    ``repro.analysis.keys``.
    """
    from repro.kernels.plan import strategy_sid

    rank = len(domain)
    sid = strategy_sid(
        strategy, rank, unroll, fuse_steps, batch, accuracy, n_aux
    )
    return TuningKey(
        kernel=f"fused_stencil{rank}d",
        strategy=sid,
        domain=tuple(domain),
        radii=tuple(radii),
        n_f=n_f,
        n_out=n_out,
        dtype=str(dtype),
        backend=backend if backend is not None else current_backend(),
    )


def fused3d_key(
    domain: tuple[int, int, int],
    radii: tuple[int, int, int],
    n_f: int,
    n_out: int,
    dtype: str,
    strategy: str,
    backend: str | None = None,
) -> TuningKey:
    """Historical rank-3 alias.

    .. deprecated::
        ``fused3d_key`` is deprecated; use :func:`fused_nd_key`.
    """
    import warnings

    warnings.warn(
        "fused3d_key is deprecated; use fused_nd_key",
        DeprecationWarning,
        stacklevel=2,
    )
    return fused_nd_key(domain, radii, n_f, n_out, dtype, strategy, backend)


def fused_nd_candidates(
    domain: tuple[int, ...],
    radii: tuple[int, ...],
    n_f: int,
    n_out: int,
    itemsize: int,
    *,
    vmem_budget: int = VMEM_BUDGET,
    fuse_steps_options: Sequence[int] = (1,),
    stream: bool = False,
    tc: bool = False,
    tc_groups: Sequence[int] | None = None,
    batch: int = 1,
    flops_per_point: float | None = None,
) -> list[Candidate]:
    """Structurally-ranked (block, fuse_steps) configurations for a
    rank-1/2/3 domain (``stream=True`` scores every candidate with the
    explicit-streaming traffic/VMEM model — the ``swc_stream`` search
    space; ``tc=True`` enumerates only matrix-unit candidates scored on
    ``max(traffic, mxu)`` with ``tc_groups`` contraction groups per
    axis; ``batch > 1`` with the batched per-member VMEM/traffic
    model), with graceful degradation: if nothing fits the VMEM budget,
    re-enumerate without the filter and keep only the smallest-footprint
    shape so ``auto`` still resolves (marked ``fallback`` by the
    caller)."""
    stream_options = () if tc else (stream,)
    tc_options = (tc,)
    backend = current_backend()
    cands = enumerate_candidates_nd(
        domain, radii, n_f, n_out, itemsize, vmem_budget=vmem_budget,
        fuse_steps_options=fuse_steps_options,
        stream_options=stream_options, tc_options=tc_options,
        tc_groups=tc_groups, backend=backend, batch=batch,
        flops_per_point=flops_per_point,
    )
    if cands:
        return cands
    unfiltered = enumerate_candidates_nd(
        domain, radii, n_f, n_out, itemsize, vmem_budget=2**63,
        fuse_steps_options=fuse_steps_options,
        stream_options=stream_options, tc_options=tc_options,
        tc_groups=tc_groups, backend=backend, batch=batch,
        flops_per_point=flops_per_point,
    )
    if not unfiltered:
        return []
    smallest = min(unfiltered, key=lambda c: c.vmem_bytes)
    return [smallest]


def fused3d_candidates(
    domain: tuple[int, int, int],
    radii: tuple[int, int, int],
    n_f: int,
    n_out: int,
    itemsize: int,
    *,
    vmem_budget: int = VMEM_BUDGET,
) -> list[Candidate]:
    """Historical rank-3 alias.

    .. deprecated::
        ``fused3d_candidates`` is deprecated; use
        :func:`fused_nd_candidates`.
    """
    import warnings

    warnings.warn(
        "fused3d_candidates is deprecated; use fused_nd_candidates",
        DeprecationWarning,
        stacklevel=2,
    )
    return fused_nd_candidates(
        domain, radii, n_f, n_out, itemsize, vmem_budget=vmem_budget
    )


def auto_block_nd(
    f_padded,
    ops,
    phi,
    n_out: int,
    *,
    aux=None,
    strategy: str = "swc",
    unroll: int = 1,
    fuse_steps: int = 1,
    interpret: bool = False,
    session: TuningSession | None = None,
    vmem_budget: int = VMEM_BUDGET,
) -> tuple[int, ...]:
    """Resolve ``block="auto"`` for the fused engine at any rank (the
    temporal depth is FIXED here — ``auto_fuse_nd`` runs the joint
    block/depth search).

    Eager call sites get the full protocol (measure top-k on the actual
    operand, persist); traced call sites get the cache or the structural
    winner. Returns a concrete rank-length block (x last).

    The cache key is derived from an actual planned ``StencilPlan`` (a
    probe lowering with the default block), so it always reflects the
    configuration the kernel will execute — e.g. an unroll factor the
    planner degrades to 1 is keyed as 1. A batched
    (batch, n_f, *padded) ensemble operand keys as ``:b{B}`` and ranks
    candidates with the batched VMEM/per-member traffic model."""
    from repro.kernels.plan import (
        DEFAULT_BLOCKS,
        plan_stencil,
        tc_groups_per_axis,
    )

    sess = session if session is not None else default_session()
    batched = f_padded.ndim == ops.ndim + 2
    n_aux = 0
    if aux is not None:
        n_aux = aux.shape[1] if batched else aux.shape[0]
    probe = plan_stencil(
        ops, f_padded.shape, n_out, strategy=strategy,
        dtype=str(f_padded.dtype), n_aux=n_aux,
        unroll=unroll, fuse_steps=fuse_steps,
    )
    rank, domain, radii = probe.rank, probe.interior, probe.radii
    n_f = probe.n_f
    itemsize = f_padded.dtype.itemsize
    key = probe.tuning_key()
    cands = fused_nd_candidates(
        domain, radii, n_f, n_out, itemsize, vmem_budget=vmem_budget,
        fuse_steps_options=(fuse_steps,),
        stream=probe.strategy == "swc_stream",
        tc=probe.strategy == "tc",
        tc_groups=tc_groups_per_axis(ops),
        batch=probe.batch,
        flops_per_point=ops.flops_per_point(n_f),
    )
    if not cands:  # degenerate domain: let the planner clamp a default
        return DEFAULT_BLOCKS[rank]
    if cands[0].vmem_bytes > vmem_budget:
        # Nothing fits VMEM: degrade to the smallest-footprint shape
        # without measuring (a real launch could OOM), and persist it so
        # the decision is visible in `repro.tuning show`.
        rec = sess.cache.get(key)
        if rec is None:
            rec = TuningRecord(
                block=cands[0].block, timings_us={}, source="fallback",
                fuse_steps=fuse_steps, unroll=probe.unroll,
            )
            sess.cache.put(key, rec)
        return tuple(rec.block)

    measure = None
    if _is_concrete(f_padded):
        from repro.kernels import ops as kops

        def measure(cand):
            """Median seconds for one candidate block (paper protocol)."""
            def fn():
                """One timed fused-stencil launch at ``cand.block``."""
                return kops.fused_stencil_nd(
                    f_padded, ops, phi, n_out, aux=aux,
                    block=cand.block, strategy=strategy,
                    unroll=probe.unroll, fuse_steps=fuse_steps,
                    interpret=interpret,
                )

            return time_candidate(
                fn, warmup=sess.warmup, iters=sess.iters
            )

    record = sess.tune(key, cands, measure)
    if record.unroll != probe.unroll:
        # Candidate objects don't carry the (fixed) unroll factor of
        # this search; stamp the planner-degraded value on the record
        # so ``plan_from_record`` round-trips ``:u{N}``-keyed records
        # (the repro.analysis left-inverse audit).
        record.unroll = probe.unroll
        sess.cache.put(key, record)
    return tuple(record.block)


def auto_fuse_nd(
    f_interior,
    ops,
    phi,
    n_out: int,
    *,
    aux=None,
    strategy: str = "swc",
    interpret: bool | None = None,
    session: TuningSession | None = None,
    vmem_budget: int = VMEM_BUDGET,
    depth_options: Sequence[int] = (1, 2, 3, 4),
) -> tuple[tuple[int, ...], int]:
    """Resolve ``fuse_steps="auto"``: the JOINT (block, temporal depth)
    search over an UNPADDED field stack (n_f, *spatial).

    Candidates are every (block, depth) pair the traffic-model-driven
    cost model admits (per-depth VMEM filter, tiny-block guard), ranked
    by modeled per-step HBM traffic plus weighted redundant-halo
    compute; with ``strategy="swc_stream"`` every candidate is scored
    with the streaming traffic model, so the search can pick a fused
    streaming configuration. Eager call sites measure the top-k —
    padding the operand by ``radius · depth`` per candidate so each
    depth times the kernel it would actually run — and persist the
    winner under one ``:fauto`` key (stream axis included for streaming
    plans); traced call sites take the cached or structural winner.
    Returns ``(block, fuse_steps)``.

    Depths that don't self-map (``n_out != n_f + n_aux``) can't fuse;
    only depth 1 is enumerated for them. A batched
    (batch, n_f, *spatial) ensemble stack keys as ``:b{B}`` and ranks
    with the batched VMEM/per-member traffic model.
    """
    sess = session if session is not None else default_session()
    batched = f_interior.ndim == ops.ndim + 2
    batch = int(f_interior.shape[0]) if batched else 1
    lead = 2 if batched else 1
    domain = tuple(f_interior.shape[lead:])
    radii = ops.radius_per_axis()
    n_f = f_interior.shape[lead - 1]
    n_aux = aux.shape[lead - 1] if aux is not None else 0
    itemsize = f_interior.dtype.itemsize
    if isinstance(phi, (tuple, list)):
        depth_options = (len(phi),)  # a φ sequence pins the depth
    if n_out != n_f + n_aux:
        depth_options = (1,)
    if batch > 1 and n_aux:
        # Mirrors StencilPlan: batched temporal fusion can't carry aux.
        depth_options = (1,)
    key = fused_nd_key(
        domain, radii, n_f, n_out, str(f_interior.dtype), strategy,
        fuse_steps="auto", batch=batch,
        accuracy=getattr(ops, "accuracy", 0), n_aux=n_aux,
    )
    from repro.kernels.plan import tc_groups_per_axis

    cands = fused_nd_candidates(
        domain, radii, n_f, n_out, itemsize, vmem_budget=vmem_budget,
        fuse_steps_options=tuple(depth_options),
        stream=strategy == "swc_stream",
        tc=strategy == "tc",
        tc_groups=tc_groups_per_axis(ops),
        batch=batch,
        flops_per_point=ops.flops_per_point(n_f),
    )
    if not cands:
        from repro.kernels.plan import DEFAULT_BLOCKS

        return DEFAULT_BLOCKS[len(domain)], 1
    if cands[0].vmem_bytes > vmem_budget:
        rec = sess.cache.get(key)
        if rec is None:
            rec = TuningRecord(
                block=cands[0].block, timings_us={}, source="fallback",
                fuse_steps=cands[0].fuse_steps,
            )
            sess.cache.put(key, rec)
        return tuple(rec.block), int(rec.fuse_steps)

    measure = None
    if _is_concrete(f_interior) and (aux is None or _is_concrete(aux)):
        measure = _interior_measure_fn(
            sess, f_interior, ops, phi, n_out, aux, radii,
            default_strategy=strategy, interpret=interpret,
        )

    record = sess.tune(key, cands, measure)
    return tuple(record.block), int(record.fuse_steps)


def _interior_measure_fn(
    sess: TuningSession,
    f_interior,
    ops,
    phi,
    n_out: int,
    aux,
    radii: tuple[int, ...],
    *,
    default_strategy: str = "swc",
    interpret: bool | None = None,
):
    """Measurement closure shared by the joint-depth and cross-strategy
    resolvers: median PER-STEP seconds for one candidate, on an UNPADDED
    operand padded per candidate (``radius · depth`` ghost cells, so
    each depth times the kernel it would actually run).

    Candidates carrying a ``strategy`` attribute are dispatched per
    strategy — ``hwc`` times the jitted XLA-managed reference (the
    measured baseline of the cross-strategy search), everything else
    the Pallas kernel at the candidate's block/depth/stream config.
    Works for plain (n_f, *spatial) and batched (batch, n_f, *spatial)
    operands alike — non-spatial leading axes are never padded, and the
    hwc baseline times the vmap'd batched oracle.
    """
    import jax as _jax
    import jax.numpy as jnp

    from repro.kernels import ops as kops
    from repro.kernels import ref as kref

    lead = f_interior.ndim - len(radii)  # 1, or 2 when batched

    def measure(cand):
        """Median per-step seconds for one candidate configuration."""
        depth = getattr(cand, "fuse_steps", 1)
        strategy = getattr(cand, "strategy", default_strategy) or (
            default_strategy
        )
        pad = [(0, 0)] * lead + [(r * depth,) * 2 for r in radii]
        fp = jnp.pad(f_interior, pad, mode="wrap")
        aux_p = aux
        if aux is not None and depth > 1:
            apad = [(0, 0)] * lead + [
                (r * (depth - 1),) * 2 for r in radii
            ]
            aux_p = jnp.pad(aux, apad, mode="wrap")

        if strategy == "hwc":
            # The XLA-managed path is always jitted when benchmarked —
            # time what the compiler-managed regime actually runs.
            if lead == 2:
                if depth == 1:
                    hwc = _jax.jit(
                        lambda f, a: kref.fused_stencil_batched(
                            f, ops, phi, aux=a
                        )
                    )
                else:
                    hwc = _jax.jit(
                        lambda f, a: kref.fused_stencil_steps_batched(
                            f, ops, phi, depth, aux=a
                        )
                    )
            elif depth == 1:
                hwc = _jax.jit(
                    lambda f, a: kref.fused_stencil(f, ops, phi, aux=a)
                )
            else:
                hwc = _jax.jit(
                    lambda f, a: kref.fused_stencil_steps(
                        f, ops, phi, depth, aux=a
                    )
                )

            def fn():
                """One timed XLA-managed (hwc) application."""
                return hwc(fp, aux_p)

        else:

            def fn():
                """One timed depth-``depth`` launch at ``cand.block``."""
                return kops.fused_stencil_nd(
                    fp, ops, phi, n_out, aux=aux_p, block=cand.block,
                    strategy=strategy, fuse_steps=depth,
                    interpret=interpret,
                )

        # One launch advances ``depth`` steps — candidates compete on
        # per-step time, not per-launch time.
        return time_candidate(
            fn, warmup=sess.warmup, iters=sess.iters
        ) / depth

    return measure


def auto_strategy_nd(
    f_interior,
    ops,
    phi,
    n_out: int,
    *,
    aux=None,
    fuse_steps: int | str = "auto",
    interpret: bool | None = None,
    session: TuningSession | None = None,
    vmem_budget: int = VMEM_BUDGET,
    depth_options: Sequence[int] = (1, 2, 3, 4),
) -> tuple[str, tuple[int, ...], int]:
    """Resolve ``strategy="auto"``: the CROSS-STRATEGY joint
    ``(strategy, block, fuse_steps, stream)`` search over an UNPADDED
    field stack (n_f, *spatial) — the paper's "no single caching regime
    wins everywhere" finding closed into one tuning loop.

    The candidate space is every ``swc``, ``swc_stream`` and ``tc``
    configuration the joint enumeration admits plus the ``hwc``
    baseline at the modeled-traffic floor
    (:func:`repro.tuning.costmodel.enumerate_cross_strategy_nd`);
    streaming candidates are enumerated only at rank ≥ 2 with no aux
    operand (the streaming kernel rejects carries), and matrix-unit
    (``tc``) candidates only for f32/bf16 operands — mirroring plan
    validation, so a structurally-winning regime is always lowerable. Eager call sites
    measure the top-k — the hwc candidate as the jitted XLA reference,
    the Pallas candidates padded per depth — and persist the winner
    under ONE ``auto:sauto`` key whose schema-v2 record carries the
    resolved strategy, block, depth, and stream flag; traced call sites
    take the cached or structural winner (no measurement). Returns
    ``(strategy, block, fuse_steps)`` — the stream decision is implied
    by the strategy (``swc_stream`` streams axis 0 by construction).

    ``fuse_steps``: ``"auto"`` sweeps ``depth_options`` jointly (keyed
    ``:fauto``); an int pins the search to that depth. A per-step φ
    sequence pins it to ``len(phi)``; ops that don't self-map
    (``n_out != n_f + n_aux``) only enumerate depth 1.
    """
    sess = session if session is not None else default_session()
    batched = f_interior.ndim == ops.ndim + 2
    batch = int(f_interior.shape[0]) if batched else 1
    lead = 2 if batched else 1
    domain = tuple(f_interior.shape[lead:])
    radii = ops.radius_per_axis()
    n_f = f_interior.shape[lead - 1]
    n_aux = aux.shape[lead - 1] if aux is not None else 0
    itemsize = f_interior.dtype.itemsize
    pinned = None  # explicitly requested depth (φ sequence or int)
    if isinstance(phi, (tuple, list)):
        pinned = len(phi)
    elif fuse_steps != "auto":
        pinned = int(fuse_steps)
    if pinned is not None:
        depth_options = (pinned,)
    if n_out != n_f + n_aux:
        if pinned is not None and pinned > 1:
            # Mirror StencilPlan validation instead of silently
            # clamping a depth the caller explicitly asked for.
            raise ValueError(
                "fuse_steps > 1 requires a self-map op with "
                f"n_out == n_f + n_aux (got n_out={n_out}, n_f={n_f}, "
                f"n_aux={n_aux}) — the cross-strategy search cannot "
                f"honor the pinned depth {pinned}"
            )
        depth_options = (1,)
    if batch > 1 and n_aux and tuple(depth_options) != (1,):
        # Mirrors StencilPlan: batched temporal fusion can't carry aux.
        depth_options = (1,)
    key = fused_nd_key(
        domain, radii, n_f, n_out, str(f_interior.dtype), "auto",
        fuse_steps=fuse_steps if fuse_steps == "auto" else depth_options[0],
        batch=batch,
        accuracy=getattr(ops, "accuracy", 0), n_aux=n_aux,
    )

    from repro.kernels.plan import tc_groups_per_axis

    cands = enumerate_cross_strategy_nd(
        domain, radii, n_f, n_out, itemsize, vmem_budget=vmem_budget,
        fuse_steps_options=tuple(depth_options),
        stream_ok=len(domain) >= 2 and n_aux == 0,
        tc_ok=str(f_interior.dtype) in ("float32", "bfloat16"),
        tc_groups=tc_groups_per_axis(ops),
        backend=current_backend(),
        batch=batch,
        flops_per_point=ops.flops_per_point(n_f),
    )
    measure = None
    if _is_concrete(f_interior) and (aux is None or _is_concrete(aux)):
        measure = _interior_measure_fn(
            sess, f_interior, ops, phi, n_out, aux, radii,
            interpret=interpret,
        )
        # The hwc baseline must ALWAYS be measured, not just modeled:
        # fused candidates model sub-compulsory traffic and can rank it
        # out of the top-k window, but the whole point of the cross-
        # strategy search is that the compiler-managed regime competes
        # on real time. Pull it into the measured window (keeping the
        # structural winner at index 0 — the traced/model fallback).
        if sess.top_k > 1:
            ih = next(
                i for i, c in enumerate(cands) if c.strategy == "hwc"
            )
            if ih >= sess.top_k:
                cands.insert(sess.top_k - 1, cands.pop(ih))
    record = sess.tune(key, cands, measure)
    return (
        record.resolved_strategy,
        tuple(record.block),
        int(record.fuse_steps),
    )


def auto_block_3d(
    f_padded,
    ops,
    phi,
    n_out: int,
    *,
    aux=None,
    strategy: str = "swc",
    interpret: bool = False,
    session: TuningSession | None = None,
    vmem_budget: int = VMEM_BUDGET,
) -> tuple[int, int, int]:
    """Historical rank-3 alias.

    .. deprecated::
        ``auto_block_3d`` is deprecated; use :func:`auto_block_nd`.
    """
    import warnings

    warnings.warn(
        "auto_block_3d is deprecated; use auto_block_nd",
        DeprecationWarning,
        stacklevel=2,
    )
    return auto_block_nd(
        f_padded, ops, phi, n_out, aux=aux, strategy=strategy,
        interpret=interpret, session=session, vmem_budget=vmem_budget,
    )


def lookup_fused_nd(
    f_interior,
    ops,
    n_out: int,
    strategy: str,
    session: TuningSession | None = None,
    unroll: int = 1,
    fuse_steps: int | str = 1,
    n_aux: int = 0,
) -> TuningRecord | None:
    """Cached record for a fused stencil call on an UNPADDED field
    stack (n_f, *spatial) — or batched (batch, n_f, *spatial), keying
    as ``:b{B}`` — the read-only mirror of the key derivation in
    ``auto_block_nd``/``auto_fuse_nd``, for benchmarks/examples that
    want to report which configuration ``"auto"`` resolved to. Pass
    ``fuse_steps="auto"`` to look up a joint block/depth record, and
    ``n_aux`` for an aux-carrying (``:a{N}``-keyed) call."""
    sess = session if session is not None else default_session()
    batched = f_interior.ndim == ops.ndim + 2
    lead = 2 if batched else 1
    key = fused_nd_key(
        tuple(f_interior.shape[lead:]),
        ops.radius_per_axis(),
        f_interior.shape[lead - 1],
        n_out,
        str(f_interior.dtype),
        strategy,
        unroll=unroll,
        fuse_steps=fuse_steps,
        batch=int(f_interior.shape[0]) if batched else 1,
        accuracy=getattr(ops, "accuracy", 0),
        n_aux=n_aux,
    )
    return sess.cache.get(key)


def lookup_fused3d(
    f_interior,
    ops,
    n_out: int,
    strategy: str,
    session: TuningSession | None = None,
) -> TuningRecord | None:
    """Historical rank-3 alias.

    .. deprecated::
        ``lookup_fused3d`` is deprecated; use :func:`lookup_fused_nd`.
    """
    import warnings

    warnings.warn(
        "lookup_fused3d is deprecated; use lookup_fused_nd",
        DeprecationWarning,
        stacklevel=2,
    )
    return lookup_fused_nd(
        f_interior, ops, n_out, strategy, session=session
    )


# ---------------------------------------------------------------------------
# 1-D kernel glue (xcorr1d block_size="auto").
# ---------------------------------------------------------------------------


def auto_block_xcorr1d(
    f_padded,
    g,
    *,
    strategy: str,
    unroll: int,
    interpret: bool,
    session: TuningSession | None = None,
) -> int:
    """Resolve ``block_size="auto"`` for the 1-D cross-correlation."""
    sess = session if session is not None else default_session()
    n_taps = g.shape[0]
    halo = n_taps - 1
    n = f_padded.shape[0] - halo
    key = TuningKey(
        kernel="xcorr1d",
        strategy=f"{strategy}:u{unroll}",
        domain=(n,),
        radii=(halo,),
        n_f=1,
        n_out=1,
        dtype=str(f_padded.dtype),
        backend=current_backend(),
    )
    cands = enumerate_candidates_1d(
        n, halo, itemsize=f_padded.dtype.itemsize
    )
    if not cands:
        return 2048

    measure = None
    if _is_concrete(f_padded) and _is_concrete(g):

        def measure(cand):
            """Median seconds for one candidate block length."""
            from repro.kernels import ops as kops

            def fn():
                """One timed xcorr1d call at ``cand.block``."""
                return kops.xcorr1d(
                    f_padded, g, strategy=strategy,
                    block_size=int(cand.block), unroll=unroll,
                    interpret=interpret,
                )

            return time_candidate(
                fn, warmup=sess.warmup, iters=sess.iters
            )

    return int(sess.tune(key, cands, measure).block)

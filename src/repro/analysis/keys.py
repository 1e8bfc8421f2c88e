"""Tuning-key injectivity and ``plan_from_record`` round-trip proofs.

Three theorems, each checked by exhaustive enumeration (the axis
product is small — a few thousand combinations):

1. **sid injectivity** — :func:`repro.kernels.plan.strategy_sid` is
   injective over the full valid axis product (strategy × rank ×
   unroll × fuse × batch × accuracy × n_aux × ghost) *modulo the one
   documented alias*: accuracy 0 ("unknown") and
   :data:`~repro.kernels.plan.DEFAULT_ACCURACY` both key unmarked.
   Two combos mapping to the same sid must be identical in every other
   axis. (Rank is a free axis here: it joins the TuningKey through
   ``kernel_name``, and the stream-axis letter already encodes it for
   streaming sids.)

2. **sid parsability** — the suffix grammar round-trips: a parser
   built from the documented grammar recovers every axis from the sid
   string. A suffix that failed to parse (or parsed to different
   values) would mean the grammar is ambiguous.

3. **record left-inverse** — for every audited plan,
   ``plan_from_record`` applied to a record carrying the plan's
   persisted decision (block, depth, stream flag, strategy, unroll)
   reconstructs the plan EXACTLY (dataclass equality). This is the
   warm-cache contract: a tuned decision replayed from disk must lower
   the same kernel that was measured.
"""
from __future__ import annotations

import itertools
import re
from typing import Any, Iterator

from repro.analysis.findings import Finding

# The audited functions (strategy_sid, plan_from_record) are resolved
# through the module at call time so the mutation harness's seeded
# key defects are what actually runs.
from repro.kernels import plan as plan_mod
from repro.kernels.plan import DEFAULT_ACCURACY, StencilPlan

# Enumerated axis values. These deliberately over-approximate what any
# single registry uses (batch 2 AND 4, accuracy up to 8, aux up to 2)
# so the proof covers values no current caller exercises yet.
_STRATEGIES = ("swc", "swc_stream", "tc", "auto")
_RANKS = (1, 2, 3)
_UNROLLS = (1, 2, 4)
_FUSES: tuple[Any, ...] = (1, 2, 3, "auto")
_BATCHES = (1, 2, 4)
_ACCURACIES = (0, 2, 4, 6, 8)
_AUXES = (0, 1, 2)
_GHOSTS = (None, "zero")

Combo = tuple[str, int, int, Any, int, int, int, Any]
# (strategy, rank, unroll, fuse, batch, accuracy, n_aux, ghost)


def _valid(c: Combo) -> bool:
    """Mirror of the plan/search-layer constraints on the axis product
    (kept independent of ``StencilPlan.__post_init__`` on purpose: the
    auditor restates the rules it is checking against)."""
    strategy, rank, unroll, fuse, batch, _acc, n_aux, ghost = c
    if ghost == "zero" and (strategy, rank, unroll, fuse, batch) != (
        "swc", 3, 1, 1, 1
    ):
        return False  # zero ghosts: rank-3, unbatched swc at depth 1
    if strategy == "swc_stream" and (rank == 1 or n_aux or unroll != 1):
        return False
    if strategy == "tc" and unroll != 1:
        return False
    if strategy == "auto" and unroll != 1:
        return False  # the cross-strategy search never keys unroll
    if unroll != 1 and fuse != 1:
        return False  # temporal fusion requires unroll=1
    if batch != 1 and n_aux and fuse not in (1,):
        return False  # batched temporal aux carries are rejected
    return True


def enumerate_combos() -> Iterator[Combo]:
    for c in itertools.product(
        _STRATEGIES, _RANKS, _UNROLLS, _FUSES, _BATCHES, _ACCURACIES,
        _AUXES, _GHOSTS,
    ):
        if _valid(c):
            yield c


_SID_RE = re.compile(
    r"^(?P<strategy>swc_stream|swc|tc|auto)"
    r"(?::s(?P<stream>auto|[zyx]))?"
    r"(?::u(?P<unroll>\d+))?"
    r"(?::f(?P<fuse>auto|\d+))?"
    r"(?::b(?P<batch>\d+))?"
    r"(?::a(?P<aux>\d+))?"
    r"(?::o(?P<acc>\d+))?"
    r"(?::g(?P<ghost>z))?$"
)


def parse_sid(sid: str) -> dict[str, Any] | None:
    """Parse a strategy id back into its axes per the documented
    grammar; ``None`` if the string does not match (a grammar break)."""
    m = _SID_RE.match(sid)
    if m is None:
        return None
    fuse = m["fuse"]
    return {
        "strategy": m["strategy"],
        "stream": m["stream"],
        "unroll": int(m["unroll"] or 1),
        "fuse": fuse if fuse == "auto" else int(fuse or 1),
        "batch": int(m["batch"] or 1),
        "n_aux": int(m["aux"] or 0),
        "accuracy": int(m["acc"]) if m["acc"] is not None else None,
        "ghost": "zero" if m["ghost"] else None,
    }


def _alias_ok(a: Combo, b: Combo) -> bool:
    """True iff two combos sharing a sid differ only through the
    documented accuracy alias ({0, DEFAULT_ACCURACY} key unmarked) —
    or only in rank for non-streaming strategies (rank joins the
    TuningKey via ``kernel_name``, not the sid)."""
    sa, ra, ua, fa, ba, aa, xa, ga = a
    sb, rb, ub, fb, bb, ab, xb, gb = b
    if (sa, ua, fa, ba, xa, ga) != (sb, ub, fb, bb, xb, gb):
        return False
    if sa == "swc_stream" and ra != rb:
        return False  # the stream letter must disambiguate ranks
    if aa != ab and {aa, ab} != {0, DEFAULT_ACCURACY}:
        return False
    return True


def audit_sid_injectivity() -> tuple[list[Finding], int]:
    """Prove theorems 1 and 2 over the full axis product. Returns
    (findings, number of combos checked)."""
    findings: list[Finding] = []
    by_sid: dict[str, list[Combo]] = {}
    n = 0
    for c in enumerate_combos():
        strategy, rank, unroll, fuse, batch, acc, n_aux, ghost = c
        sid = plan_mod.strategy_sid(
            strategy, rank, unroll, fuse, batch, acc, n_aux, ghost
        )
        n += 1
        by_sid.setdefault(sid, []).append(c)
        parsed = parse_sid(sid)
        if parsed is None:
            findings.append(Finding(
                "key", sid, f"sid does not match the suffix grammar "
                f"(combo {c})",
            ))
            continue
        expect_stream = (
            {2: "y", 3: "z"}[rank] if strategy == "swc_stream"
            else ("auto" if strategy == "auto" else None)
        )
        ok = (
            parsed["strategy"] == strategy
            and parsed["stream"] == expect_stream
            and parsed["unroll"] == unroll
            and parsed["fuse"] == fuse
            and parsed["batch"] == batch
            and parsed["n_aux"] == n_aux
            and parsed["ghost"] == ghost
            and (
                parsed["accuracy"] == acc
                if acc not in (0, DEFAULT_ACCURACY)
                else parsed["accuracy"] is None
            )
        )
        if not ok:
            findings.append(Finding(
                "key", sid,
                f"sid parse {parsed} does not round-trip combo {c}",
            ))
    for sid, combos in by_sid.items():
        for a, b in itertools.combinations(combos, 2):
            if not _alias_ok(a, b):
                findings.append(Finding(
                    "key", sid,
                    f"sid collision: combos {a} and {b} share the id "
                    "but differ beyond the documented accuracy alias",
                ))
    return findings, n


def _normalized_identity(plan: StencilPlan) -> tuple:
    """Everything a TuningKey must separate: all plan identity except
    the block (the tuned value) — accuracy collapsed through the
    documented alias."""
    acc = (
        DEFAULT_ACCURACY
        if plan.accuracy in (0, DEFAULT_ACCURACY)
        else plan.accuracy
    )
    return (
        plan.rank, plan.strategy, plan.radii, plan.interior, plan.n_f,
        plan.n_out, plan.dtype, plan.n_aux, plan.unroll,
        plan.fuse_steps, plan.batch, acc, plan.ghost,
    )


def audit_key_uniqueness(
    plans: list[StencilPlan],
) -> list[Finding]:
    """No two distinct audited plans may share a TuningKey identity
    (block aside — the block IS the tuned value)."""
    findings: list[Finding] = []
    seen: dict[tuple, tuple] = {}
    for p in plans:
        k = (
            p.kernel_name, p.strategy_id, p.interior, p.radii, p.n_f,
            p.n_out, p.dtype,
        )
        ident = _normalized_identity(p)
        prev = seen.setdefault(k, ident)
        if prev != ident:
            findings.append(Finding(
                "key", p.strategy_id,
                f"TuningKey collision: identities {prev} and {ident} "
                "share one cache key",
            ))
    return findings


def audit_record_roundtrip(
    plan: StencilPlan, ops: Any
) -> list[Finding]:
    """Theorem 3 for one plan: synthesize the record the tuner would
    persist for this plan's decision and prove ``plan_from_record`` is
    a left inverse."""
    from repro.tuning.cache import TuningRecord

    rec = TuningRecord(
        block=plan.block,
        timings_us={},
        source="model",
        fuse_steps=plan.fuse_steps,
        stream=plan.strategy == "swc_stream",
        strategy_resolved=plan.strategy,
        unroll=plan.unroll,
    )
    lead = (plan.batch,) if plan.batch > 1 else ()
    shape = lead + (plan.n_f,) + plan.interior
    back = plan_mod.plan_from_record(
        ops, shape, plan.n_out, rec, dtype=plan.dtype,
        n_aux=plan.n_aux, ghost=plan.ghost,
    )
    if back != plan:
        return [Finding(
            "key", plan.strategy_id,
            f"plan_from_record is not a left inverse: rebuilt "
            f"{back and back.strategy_id}/block="
            f"{back and back.block}/unroll={back and back.unroll} "
            f"from the persisted decision of block={plan.block}/"
            f"unroll={plan.unroll}",
        )]
    return []

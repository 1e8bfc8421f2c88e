"""Mutation harness: prove the auditor catches each defect class.

A static auditor that has never seen a bug is indistinguishable from
one that cannot see bugs. Each entry in :data:`MUTANTS` monkeypatches
one deliberately broken variant of real kernel/key arithmetic into the
audited modules (halo slice one element wide, streaming prologue one
halo short, carry skewed by a plane, the pre-fix VMEM model that
ignored unroll/aux, a strategy id that drops the batch suffix, a
record rebuild that drops unroll, a temporal sweep with skewed margin,
an unroll loop that skips the last sub-tile, a zero-ghost face copy
one plane short), runs the relevant audit on a small fixed plan set,
and asserts at least one finding of the
expected class appears. Every patch is applied through the owning
module's attribute (the auditor resolves them at call time) and always
restored.

Run via ``python -m repro.analysis --mutants`` (CI job) or
:func:`run_harness` directly.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Iterator

from repro.analysis.bounds import audit_plan
from repro.analysis.findings import Finding
from repro.analysis.keys import (
    audit_record_roundtrip,
    audit_sid_injectivity,
)
from repro.analysis.vmem import check_vmem
from repro.core.stencil import derivative_operator_set
from repro.kernels.plan import plan_stencil


# ---------------------------------------------------------------------------
# Broken variants (each mirrors the real code with ONE seeded defect)
# ---------------------------------------------------------------------------


def _block_derivs_wide(fblk, ops, radii, tile):
    """_block_derivs with the halo slice one element too wide — the
    classic off-by-one numpy would silently clamp."""
    import jax.numpy as jnp

    rank = len(tile)
    out = {}
    for spec in ops.ops:
        acc = None
        for off, c in zip(spec.offsets, spec.coeffs):
            sl = (slice(None),) + tuple(
                slice(
                    radii[a] + off[a],
                    radii[a] + off[a] + tile[a] + (1 if a == 0 else 0),
                )
                for a in range(rank)
            )
            term = jnp.asarray(c, dtype=fblk.dtype) * fblk[sl]
            acc = term if acc is None else acc + term
        out[spec.name] = acc
    return out


def _temporal_sweeps_skewed(cur, ops, radii, tile, phis, derivs_fn=None):
    """_temporal_sweeps evaluating every non-final sweep one margin
    too small — intermediate extents no longer match the schedule."""
    from repro.kernels import emit

    derivs_fn = derivs_fn or emit._block_derivs
    n_f = cur.shape[0]
    n_steps = len(phis)
    for s, phi in enumerate(phis):
        margin = n_steps - 1 - s
        bad = max(margin - 1, 0)  # seeded defect: margin skew
        sub_tile = tuple(t + 2 * r * bad for t, r in zip(tile, radii))
        derivs = derivs_fn(cur, ops, radii, sub_tile)
        val = phi(derivs)
        if margin:
            cur = val[:n_f]
    return val


def _kernel_pipelined_gap(
    f_ref, *rest, ops, radii, tile, phi, unroll, n_aux_refs,
    derivs_fn=None, z_chunk=None,
):
    """_kernel_pipelined that never computes the LAST unroll sub-tile
    — stores stay in bounds but the output tile has a hole."""
    from repro.kernels import emit

    derivs_fn = derivs_fn or emit._block_derivs
    aux_refs, o_ref = rest[:n_aux_refs], rest[n_aux_refs]
    fblk = f_ref[...]
    tx = tile[-1]
    rx = radii[-1]
    for e in range(max(unroll - 1, 1) if unroll > 1 else unroll):
        sub = fblk if unroll == 1 else fblk[..., e * tx : e * tx + tx + 2 * rx]
        derivs = derivs_fn(sub, ops, radii, tile)
        if aux_refs:
            ablk = emit._join_rows([r[...] for r in aux_refs])
            a_sub = ablk if unroll == 1 else ablk[..., e * tx : (e + 1) * tx]
            val = phi(derivs, a_sub)
        else:
            val = phi(derivs)
        if unroll == 1:
            o_ref[...] = val
        else:
            o_ref[..., e * tx : (e + 1) * tx] = val


def _make_kernel_stream_mutant(
    *, prologue_planes: int | None = None, carry_src_skew: int = 0
):
    """A copy of ``emit._kernel_stream`` with seeded streaming defects:
    ``prologue_planes`` overrides the 2·h₀ leading-halo copy width
    (short prologue → uninitialized planes), ``carry_src_skew`` offsets
    the carried-halo source (skew → plane provenance mismatch)."""

    def kernel(
        f_hbm, o_hbm, work, pf0, pf1, outbuf, sem_pf, sem_out, *,
        ops, radii, tile, phis, n_chunks, cross,
    ):
        from repro.kernels import emit

        pl, pltpu, jax_mod = emit.pl, emit.pltpu, emit.jax
        rank = len(tile)
        halo = tuple(r * len(phis) for r in radii)
        ts, hs = tile[0], halo[0]
        cross_off = tuple(
            pl.program_id(i) * tile[1 + i] for i in range(rank - 1)
        )
        cross_halo = tuple(pl.ds(o, w) for o, w in zip(cross_off, cross))
        cross_tile = tuple(
            pl.ds(o, t) for o, t in zip(cross_off, tile[1:])
        )
        pro = 2 * hs if prologue_planes is None else prologue_planes

        def fresh_copy(chunk, pf_ref, slot):
            return pltpu.make_async_copy(
                f_hbm.at[
                    (slice(None), pl.ds(chunk * ts + 2 * hs, ts))
                    + cross_halo
                ],
                pf_ref,
                None,
            )

        halo_cp = pltpu.make_async_copy(
            f_hbm.at[(slice(None), pl.ds(0, pro)) + cross_halo],
            work.at[:, pl.ds(0, pro)],
            None,
        )
        halo_cp.start()
        fresh_copy(0, pf0, 0).start()
        halo_cp.wait()

        def body(chunk, _):
            slot = jax_mod.lax.rem(chunk, 2)

            @pl.when(chunk + 1 < n_chunks)
            def _():
                @pl.when(slot == 0)
                def _():
                    fresh_copy(chunk + 1, pf1, 1).start()

                @pl.when(slot == 1)
                def _():
                    fresh_copy(chunk + 1, pf0, 0).start()

            @pl.when(slot == 0)
            def _():
                fresh_copy(chunk, pf0, 0).wait()
                work[:, pl.ds(2 * hs, ts)] = pf0[...]

            @pl.when(slot == 1)
            def _():
                fresh_copy(chunk, pf1, 1).wait()
                work[:, pl.ds(2 * hs, ts)] = pf1[...]

            outbuf[...] = emit._temporal_sweeps(
                work[...], ops, radii, tile, phis
            )
            out_cp = pltpu.make_async_copy(
                outbuf,
                o_hbm.at[(slice(None), pl.ds(chunk * ts, ts)) + cross_tile],
                None,
            )
            out_cp.start()
            work[:, pl.ds(0, 2 * hs)] = work[
                :, pl.ds(ts + carry_src_skew, 2 * hs)
            ]
            out_cp.wait()
            return 0

        jax_mod.lax.fori_loop(0, n_chunks, body, 0)

    return kernel


def _vmem_working_set_legacy(
    block, radii, n_f, n_out, itemsize, fuse_steps=1, stream=False,
    *, batch=1, unroll=1, n_aux=0, ghost=None,
):
    """The pre-fix cost model: unroll and aux residency ignored."""
    n_f = n_f * batch
    n_out = n_out * batch
    if stream:
        work, pf, mid, out = n_f, n_f, n_f if fuse_steps > 1 else 0, n_out
        for a, (t, r) in enumerate(zip(block, radii)):
            work *= t + 2 * r * fuse_steps
            pf *= t if a == 0 else t + 2 * r * fuse_steps
            mid *= t + 2 * r * (fuse_steps - 1)
            out *= t
        return (work + 2 * pf + mid + out) * itemsize
    inp = n_f
    mid = n_f if fuse_steps > 1 else 0
    out = n_out
    for t, r in zip(block, radii):
        inp *= t + 2 * r * fuse_steps
        mid *= t + 2 * r * (fuse_steps - 1)
        out *= t
    return (2 * inp + mid + out) * itemsize


def _strategy_sid_no_batch(
    strategy, rank, unroll=1, fuse_steps=1, batch=1, accuracy=0,
    n_aux=0, ghost=None,
):
    """strategy_sid that drops the ensemble suffix — batched and
    single-member plans collide."""
    from repro.kernels import plan as plan_mod

    return plan_mod._REAL_STRATEGY_SID(
        strategy, rank, unroll, fuse_steps, 1, accuracy, n_aux, ghost
    )


def _zero_ghost_copies_face_short(plan):
    """``emit.zero_ghost_copies`` whose low-z face copy starts one plane
    late: the first plane of the domain is never copied, and the window
    holds a zero where it belongs."""
    from repro.kernels import emit

    z_cases, y_cases = emit._REAL_ZERO_GHOST_COPIES(plan)
    z_cases = tuple(
        (first, last, off + 1, ext - 1) if first == 0 else
        (first, last, off, ext)
        for first, last, off, ext in z_cases
    )
    return z_cases, y_cases


# ---------------------------------------------------------------------------
# Patching + harness
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def _patched(module: Any, attr: str, value: Any) -> Iterator[None]:
    saved = getattr(module, attr)
    setattr(module, attr, value)
    try:
        yield
    finally:
        setattr(module, attr, saved)


def _fixture_plans() -> dict[str, Any]:
    """Small fixed plans, one per audited regime."""
    ops2 = derivative_operator_set(2, accuracy=2)
    ops3 = derivative_operator_set(3, accuracy=2)
    return {
        "ops": ops2,
        # pipelined, unrolled: interior (8, 256), block (8, 128), u2
        "unrolled": plan_stencil(
            ops2, (2, 10, 258), 2, strategy="swc", unroll=2
        ),
        # explicit streaming, depth 1: interior (64, 256), 4 chunks
        "stream": plan_stencil(
            ops2, (2, 66, 258), 2, strategy="swc_stream"
        ),
        # temporal fusion depth 2 (self-map: n_out == n_f)
        "temporal": plan_stencil(
            ops2, (2, 68, 260), 2, strategy="swc", fuse_steps=2
        ),
        # zero ghosts staged in the kernel: interior (24, 24, 128),
        # 3 x 3 blocks, so the low-face, interior and high-face copies
        # of z and y all run; mixed partials read the window's corners
        "ops3": ops3,
        "zero_ghost": plan_stencil(
            ops3, (1, 26, 26, 130), 1, block=(8, 8, 128), ghost="zero"
        ),
    }


def _fixture_ops(fix: dict, which: str) -> Any:
    return fix["ops3"] if which == "zero_ghost" else fix["ops"]


def _audit_bounds(fix: dict, which: str) -> list[Finding]:
    return audit_plan(fix[which], _fixture_ops(fix, which)).findings


def _audit_vmem(fix: dict, which: str) -> list[Finding]:
    res = audit_plan(fix[which], _fixture_ops(fix, which))
    return res.findings + check_vmem(fix[which], res.measured_vmem)


def _audit_keys_sid(fix: dict) -> list[Finding]:
    return audit_sid_injectivity()[0]


def _audit_keys_roundtrip(fix: dict) -> list[Finding]:
    return audit_record_roundtrip(fix["unrolled"], fix["ops"])


@dataclasses.dataclass(frozen=True)
class Mutant:
    name: str
    description: str
    expected: frozenset[str]  # finding classes that count as detection
    apply: Callable[[], Any]  # -> context manager installing the defect
    audit: Callable[[dict], list[Finding]]


def _mutants() -> tuple[Mutant, ...]:
    from repro.kernels import emit
    from repro.kernels import plan as plan_mod
    from repro.tuning import costmodel

    def sid_patch():
        # Stash the real derivation where the mutant can reach it even
        # while plan_mod.strategy_sid points at the mutant.
        plan_mod._REAL_STRATEGY_SID = plan_mod.strategy_sid
        return _patched(
            plan_mod, "strategy_sid", _strategy_sid_no_batch
        )

    def copies_patch():
        emit._REAL_ZERO_GHOST_COPIES = emit.zero_ghost_copies
        return _patched(
            emit, "zero_ghost_copies", _zero_ghost_copies_face_short
        )

    def record_patch():
        real = plan_mod.plan_from_record

        def dropping(ops, shape, n_out, record, **kw):
            rec = dataclasses.replace(record, unroll=1)
            return real(ops, shape, n_out, rec, **kw)

        return _patched(plan_mod, "plan_from_record", dropping)

    return (
        Mutant(
            "halo-slice-overrun",
            "tap slice one element past the staged window",
            frozenset({"bounds"}),
            lambda: _patched(emit, "_block_derivs", _block_derivs_wide),
            lambda fix: _audit_bounds(fix, "unrolled"),
        ),
        Mutant(
            "stream-prologue-short",
            "streaming prologue copies h0 planes instead of 2*h0",
            frozenset({"uninit"}),
            lambda: _patched(
                emit, "_kernel_stream",
                _make_kernel_stream_mutant(prologue_planes=1),
            ),
            lambda fix: _audit_bounds(fix, "stream"),
        ),
        Mutant(
            "stream-carry-skew",
            "carried halo planes sourced one plane early",
            frozenset({"bounds"}),
            lambda: _patched(
                emit, "_kernel_stream",
                _make_kernel_stream_mutant(carry_src_skew=-1),
            ),
            lambda fix: _audit_bounds(fix, "stream"),
        ),
        Mutant(
            "temporal-margin-skew",
            "non-final sweeps evaluated one margin too small",
            frozenset({"phi", "bounds"}),
            lambda: _patched(
                emit, "_temporal_sweeps", _temporal_sweeps_skewed
            ),
            lambda fix: _audit_bounds(fix, "temporal"),
        ),
        Mutant(
            "unroll-store-gap",
            "last unroll sub-tile never computed or stored",
            frozenset({"coverage"}),
            lambda: _patched(
                emit, "_kernel_pipelined", _kernel_pipelined_gap
            ),
            lambda fix: _audit_bounds(fix, "unrolled"),
        ),
        Mutant(
            "zero-ghost-face-short",
            "low-z face copy starts one plane late, a zero in its place",
            frozenset({"bounds", "uninit"}),
            copies_patch,
            lambda fix: _audit_bounds(fix, "zero_ghost"),
        ),
        Mutant(
            "vmem-model-legacy",
            "cost model ignores unroll and aux residency",
            frozenset({"vmem"}),
            lambda: _patched(
                costmodel, "vmem_working_set", _vmem_working_set_legacy
            ),
            lambda fix: _audit_vmem(fix, "unrolled"),
        ),
        Mutant(
            "sid-drops-batch",
            "strategy id omits the :b{B} ensemble suffix",
            frozenset({"key"}),
            sid_patch,
            _audit_keys_sid,
        ),
        Mutant(
            "record-drops-unroll",
            "plan_from_record ignores the persisted unroll factor",
            frozenset({"key"}),
            record_patch,
            _audit_keys_roundtrip,
        ),
    )


def run_harness() -> dict[str, dict[str, Any]]:
    """Apply every mutant, re-run the relevant audit, report detection.

    Returns ``{name: {detected, expected, classes, description}}``;
    the clean fixture set is also audited first and must be
    finding-free (a noisy auditor detects everything vacuously).
    """
    fix = _fixture_plans()
    results: dict[str, dict[str, Any]] = {}
    clean: list[Finding] = []
    for which in ("unrolled", "stream", "temporal", "zero_ghost"):
        clean.extend(_audit_vmem(fix, which))
    clean.extend(_audit_keys_sid(fix))
    clean.extend(_audit_keys_roundtrip(fix))
    results["__clean__"] = {
        "detected": not clean,
        "expected": [],
        "classes": sorted({f.cls for f in clean}),
        "description": "fixture plans audit clean before any mutation",
    }
    for m in _mutants():
        with m.apply():
            found = m.audit(fix)
        classes = {f.cls for f in found}
        results[m.name] = {
            "detected": bool(classes & m.expected),
            "expected": sorted(m.expected),
            "classes": sorted(classes),
            "description": m.description,
        }
    return results

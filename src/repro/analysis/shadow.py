"""Interval abstract domain for shadow-executing Pallas kernel bodies.

The auditor never runs real compute: it calls the kernel bodies in
``repro.kernels.emit`` directly (no ``pl.pallas_call``) with
:class:`ShadowRef` operands whose reads and writes are interval boxes
— the min/max index touched along every axis. Slicing is STRICT:
where ``numpy``/``jnp`` silently clamp an out-of-range slice (the
exact defect class that turns a halo-arithmetic bug into wrong answers
instead of a crash), a shadow access raises
:class:`~repro.analysis.findings.AuditError` with the offending box.

Arithmetic on :class:`ShadowArray` relies on JAX deferring binary ops
to unrecognized operand types (``jnp_scalar * shadow`` dispatches to
``shadow.__rmul__``), so the emitter's tap loops run unchanged. The
one data-dependent MXU op (``emit._contract``) dispatches to
:meth:`ShadowArray.shadow_contract`.

The streaming kernel additionally needs the Pallas/JAX module surface
(``pl.program_id``/``pl.ds``/``pl.when``, ``pltpu.make_async_copy``,
``jax.lax.fori_loop``/``rem``): :func:`shadow_shims` monkeypatches
``emit``'s module globals with concrete shims for the duration of a
shadow run — ``fori_loop`` becomes a Python loop, DMA a synchronous
shadow copy (start() lands the data; wait() is a no-op — DMA/compute
overlap hazards are out of scope, see docs/analysis.md).
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, Iterator, Sequence

import numpy as np

from repro.analysis.findings import AuditError

Box = tuple[tuple[int, int], ...]  # per-axis (lo, hi) half-open


# ---------------------------------------------------------------------------
# Box algebra
# ---------------------------------------------------------------------------


def normalize_index(
    idx: Any, shape: tuple[int, ...], label: str
) -> tuple[Box, tuple[bool, ...]]:
    """Resolve an index expression into a strict interval box.

    Returns ``(box, keep)`` where ``keep[a]`` is False for axes an
    integer index collapses. Raises :class:`AuditError` (class
    ``bounds``) for ANY component outside ``[0, dim]`` — negative
    indices, clamped slices and empty slices are all treated as proof
    failures, not conveniences.
    """
    if not isinstance(idx, tuple):
        idx = (idx,)
    if any(e is Ellipsis for e in idx):
        pos = idx.index(Ellipsis)
        fill = len(shape) - (len(idx) - 1)
        idx = idx[:pos] + (slice(None),) * fill + idx[pos + 1 :]
    if len(idx) > len(shape):
        raise AuditError(
            "bounds", f"{label}: {len(idx)} indices for rank {len(shape)}"
        )
    idx = idx + (slice(None),) * (len(shape) - len(idx))
    box: list[tuple[int, int]] = []
    keep: list[bool] = []
    for a, (e, dim) in enumerate(zip(idx, shape)):
        if isinstance(e, slice):
            if e.step not in (None, 1):
                raise AuditError(
                    "bounds", f"{label}: strided slice on axis {a}"
                )
            lo = 0 if e.start is None else int(e.start)
            hi = dim if e.stop is None else int(e.stop)
            if lo < 0 or hi > dim or lo >= hi:
                raise AuditError(
                    "bounds",
                    f"{label}: axis {a} slice [{lo}, {hi}) outside "
                    f"[0, {dim}) or empty",
                )
            box.append((lo, hi))
            keep.append(True)
        else:
            i = int(e)
            if i < 0 or i >= dim:
                raise AuditError(
                    "bounds",
                    f"{label}: axis {a} index {i} outside [0, {dim})",
                )
            box.append((i, i + 1))
            keep.append(False)
    return tuple(box), tuple(keep)


def box_extents(box: Box) -> tuple[int, ...]:
    return tuple(hi - lo for lo, hi in box)


def subtract_box(target: Box, cut: Box) -> list[Box]:
    """``target`` minus ``cut`` as a disjoint box list (axis sweep)."""
    inter = tuple(
        (max(tl, cl), min(th, ch))
        for (tl, th), (cl, ch) in zip(target, cut)
    )
    if any(lo >= hi for lo, hi in inter):
        return [target]
    out: list[Box] = []
    cur = list(target)
    for a, ((tl, th), (il, ih)) in enumerate(zip(target, inter)):
        if tl < il:
            out.append(tuple(cur[:a]) + ((tl, il),) + tuple(cur[a + 1 :]))
        if ih < th:
            out.append(tuple(cur[:a]) + ((ih, th),) + tuple(cur[a + 1 :]))
        cur[a] = (il, ih)
    return out


def uncovered(target: Box, cover: Sequence[Box]) -> list[Box]:
    """Sub-boxes of ``target`` not covered by the union of ``cover``."""
    remain = [target]
    for c in cover:
        remain = [piece for r in remain for piece in subtract_box(r, c)]
        if not remain:
            return []
    return remain


# ---------------------------------------------------------------------------
# Shadow values
# ---------------------------------------------------------------------------


class ShadowArray:
    """An abstract array value: shape + dtype, no data.

    ``src`` carries read provenance — the ``(ref, box)`` a direct ref
    read produced this value from — consumed by the streaming audit's
    plane-provenance hooks; any arithmetic or slicing drops it (the
    value is then derived, not a copy).
    """

    __slots__ = ("shape", "dtype", "src")

    def __init__(
        self,
        shape: tuple[int, ...],
        dtype: Any = np.float32,
        src: tuple["ShadowRef", Box] | None = None,
    ):
        self.shape = tuple(int(s) for s in shape)
        self.dtype = np.dtype(dtype)
        self.src = src

    @property
    def ndim(self) -> int:
        return len(self.shape)

    def astype(self, dtype: Any) -> "ShadowArray":
        return ShadowArray(self.shape, dtype)

    def __getitem__(self, idx: Any) -> "ShadowArray":
        box, keep = normalize_index(idx, self.shape, "shadow slice")
        ext = box_extents(box)
        return ShadowArray(
            tuple(e for e, k in zip(ext, keep) if k), self.dtype
        )

    def _binop(self, other: Any) -> "ShadowArray":
        if isinstance(other, ShadowArray):
            if other.shape != self.shape:
                raise AuditError(
                    "bounds",
                    f"shape mismatch in arithmetic: {self.shape} vs "
                    f"{other.shape}",
                )
            return ShadowArray(self.shape, self.dtype)
        # scalar / 0-d jnp operand: broadcast, keep our shape
        if getattr(other, "ndim", 0) != 0 and not np.isscalar(other):
            raise AuditError(
                "bounds",
                f"unsupported broadcast of {getattr(other, 'shape', other)}"
                f" against shadow {self.shape}",
            )
        return ShadowArray(self.shape, self.dtype)

    __add__ = __radd__ = __sub__ = __rsub__ = _binop
    __mul__ = __rmul__ = __truediv__ = __rtruediv__ = _binop
    __pow__ = __rpow__ = _binop

    def __neg__(self) -> "ShadowArray":
        return ShadowArray(self.shape, self.dtype)

    def shadow_contract(self, band: Any, axis: int) -> "ShadowArray":
        """Shadow of ``emit._contract``: validate the window/band
        geometry of one banded MXU contraction and return the
        contracted shape (f32, as the real path accumulates)."""
        ext_in, ext_out = int(band.shape[0]), int(band.shape[1])
        if self.shape[1 + axis] != ext_in:
            raise AuditError(
                "bounds",
                f"tc contraction axis {axis}: window extent "
                f"{self.shape[1 + axis]} != band rows {ext_in}",
            )
        shape = list(self.shape)
        shape[1 + axis] = ext_out
        return ShadowArray(tuple(shape), np.float32)

    def shadow_zero_lanes(self, lanes: int) -> "ShadowArray":
        """Shadow of ``emit._zero_lanes``: the block with ``lanes`` zero
        lanes joined on each side of its last axis."""
        return ShadowArray(
            self.shape[:-1] + (self.shape[-1] + 2 * lanes,), self.dtype
        )

    def shadow_join(self, blocks: Sequence["ShadowArray"]) -> "ShadowArray":
        """Shadow of ``emit._join_rows``: blocks joined along the row
        axis must agree on every other extent."""
        rest = {tuple(b.shape[1:]) for b in blocks}
        if len(rest) != 1:
            raise AuditError(
                "bounds", f"aux blocks of extents {sorted(rest)} joined"
            )
        rows = sum(b.shape[0] for b in blocks)
        return ShadowArray((rows,) + rest.pop(), self.dtype)

    def __repr__(self) -> str:
        return f"ShadowArray(shape={self.shape}, dtype={self.dtype})"


class ShadowView:
    """``ref.at[idx]`` — a deferred slice used as a DMA endpoint, or as
    a ref a kernel body indexes further (``view[idx2]`` reads the
    parent ref at the composed index, strictly)."""

    def __init__(self, ref: "ShadowRef", idx: Any):
        self.ref = ref
        self.idx = idx

    @property
    def shape(self) -> tuple[int, ...]:
        box, keep = normalize_index(self.idx, self.ref.shape, "view")
        return tuple(e for e, k in zip(box_extents(box), keep) if k)

    def _compose(self, idx: Any) -> tuple:
        """The parent index ``idx`` on this view selects."""
        box, keep = normalize_index(self.idx, self.ref.shape, "view")
        inner, inner_keep = normalize_index(
            idx, self.shape, f"view of {self.ref.name}"
        )
        parts = iter(zip(inner, inner_keep))
        out: list[Any] = []
        for (lo, _), k in zip(box, keep):
            if not k:
                out.append(lo)
                continue
            (ilo, ihi), ik = next(parts)
            out.append(slice(lo + ilo, lo + ihi) if ik else lo + ilo)
        return tuple(out)

    def read(self) -> ShadowArray:
        return self.ref.read(self.idx)

    def write(self, value: Any) -> None:
        self.ref.write(self.idx, value)

    def __getitem__(self, idx: Any) -> ShadowArray:
        return self.ref.read(self._compose(idx))

    def __setitem__(self, idx: Any, value: Any) -> None:
        self.ref.write(self._compose(idx), value)


class _AtIndexer:
    def __init__(self, ref: "ShadowRef"):
        self._ref = ref

    def __getitem__(self, idx: Any) -> ShadowView:
        return ShadowView(self._ref, idx)


class ShadowRef:
    """A shadow of one kernel operand/scratch Ref.

    Records every read and write box. Reads of a non-``initialized``
    ref must be fully covered by prior write boxes (uninitialized-read
    proof). ``read_hook(box)`` / ``write_hook(box, value)`` let the
    streaming audit layer plane-provenance tracking on top without the
    core knowing about chunks.
    """

    def __init__(
        self,
        name: str,
        shape: Sequence[int],
        dtype: Any = np.float32,
        *,
        initialized: bool = False,
    ):
        self.name = name
        self.shape = tuple(int(s) for s in shape)
        self.dtype = np.dtype(dtype)
        self.initialized = initialized
        self.reads: list[Box] = []
        self.writes: list[Box] = []
        self.read_hook: Callable[[Box], None] | None = None
        self.write_hook: Callable[[Box, Any], None] | None = None

    @property
    def at(self) -> _AtIndexer:
        return _AtIndexer(self)

    def read(self, idx: Any) -> ShadowArray:
        box, keep = normalize_index(idx, self.shape, f"read {self.name}")
        if not self.initialized:
            holes = uncovered(box, self.writes)
            if holes:
                raise AuditError(
                    "uninit",
                    f"read of {self.name}{box} touches never-written "
                    f"region {holes[0]}",
                )
        self.reads.append(box)
        if self.read_hook is not None:
            self.read_hook(box)
        ext = box_extents(box)
        return ShadowArray(
            tuple(e for e, k in zip(ext, keep) if k),
            self.dtype,
            src=(self, box),
        )

    def write(self, idx: Any, value: Any) -> None:
        box, keep = normalize_index(idx, self.shape, f"store {self.name}")
        ext = tuple(
            e for e, k in zip(box_extents(box), keep) if k
        )
        if isinstance(value, ShadowArray):
            if value.shape != ext:
                raise AuditError(
                    "bounds",
                    f"store {self.name}{box}: extents {ext} != value "
                    f"shape {value.shape}",
                )
        self.writes.append(box)
        if self.write_hook is not None:
            self.write_hook(box, value)

    # Ref syntax used by the kernel bodies
    def __getitem__(self, idx: Any) -> ShadowArray:
        return self.read(idx)

    def __setitem__(self, idx: Any, value: Any) -> None:
        self.write(idx, value)

    def full_box(self) -> Box:
        return tuple((0, s) for s in self.shape)

    def __repr__(self) -> str:
        return f"ShadowRef({self.name!r}, shape={self.shape})"


# ---------------------------------------------------------------------------
# Pallas / JAX module shims (streaming kernel surface)
# ---------------------------------------------------------------------------


class ShimContext:
    """Mutable state the shims thread through a shadow run: the grid
    position of the simulated step and a per-iteration callback the
    streaming audit uses to track the current chunk."""

    def __init__(self, program_ids: tuple[int, ...] = ()):
        self.program_ids = tuple(program_ids)
        self.on_iter: Callable[[int], None] | None = None


class ShadowCopy:
    """Shadow async DMA: ``start()`` performs the copy synchronously
    (read src box → write dst box, provenance attached); ``wait()`` is
    a no-op. The emitter constructs fresh copy objects for wait-only
    use, so the copy must happen at start(), never at wait()."""

    def __init__(self, src: Any, dst: Any):
        self.src = src
        self.dst = dst

    @staticmethod
    def _as_view(end: Any) -> ShadowView:
        if isinstance(end, ShadowView):
            return end
        if isinstance(end, ShadowRef):
            return ShadowView(end, Ellipsis)
        raise AuditError("bounds", f"DMA endpoint {end!r} is not a ref")

    def start(self) -> None:
        self._as_view(self.dst).write(self._as_view(self.src).read())

    def wait(self) -> None:
        pass


class ShimSem:
    """Inert stand-in for DMA semaphore refs (``sem.at[slot]``)."""

    @property
    def at(self) -> "ShimSem":
        return self

    def __getitem__(self, idx: Any) -> "ShimSem":
        return self


class ShimPl:
    def __init__(self, ctx: ShimContext):
        self._ctx = ctx

    def program_id(self, i: int) -> int:
        return self._ctx.program_ids[i]

    @staticmethod
    def ds(start: Any, size: Any) -> slice:
        return slice(int(start), int(start) + int(size))

    @staticmethod
    def when(cond: Any) -> Callable[[Callable[[], Any]], Any]:
        def deco(fn: Callable[[], Any]) -> Any:
            if bool(cond):
                fn()
            return fn

        return deco


class ShimPltpu:
    @staticmethod
    def make_async_copy(src: Any, dst: Any, sem: Any) -> ShadowCopy:
        return ShadowCopy(src, dst)


class _ShimLax:
    def __init__(self, ctx: ShimContext):
        self._ctx = ctx

    @staticmethod
    def rem(a: Any, b: Any) -> int:
        return int(a) % int(b)

    def fori_loop(
        self, lo: int, hi: int, body: Callable[[int, Any], Any], init: Any
    ) -> Any:
        carry = init
        for i in range(int(lo), int(hi)):
            if self._ctx.on_iter is not None:
                self._ctx.on_iter(i)
            carry = body(i, carry)
        return carry

    def __getattr__(self, name: str) -> Any:
        import jax

        return getattr(jax.lax, name)


class ShimJax:
    def __init__(self, ctx: ShimContext):
        self.lax = _ShimLax(ctx)

    def __getattr__(self, name: str) -> Any:
        import jax

        return getattr(jax, name)


@contextlib.contextmanager
def shadow_shims(ctx: ShimContext) -> Iterator[None]:
    """Swap ``emit``'s ``pl``/``pltpu``/``jax`` globals for shims while
    a kernel body runs in shadow; always restored on exit."""
    from repro.kernels import emit

    saved = (emit.pl, emit.pltpu, emit.jax)
    emit.pl, emit.pltpu, emit.jax = (
        ShimPl(ctx), ShimPltpu(), ShimJax(ctx),
    )
    try:
        yield
    finally:
        emit.pl, emit.pltpu, emit.jax = saved


# ---------------------------------------------------------------------------
# Synthetic φ
# ---------------------------------------------------------------------------


def make_synthetic_phis(
    plan: Any,
    expected_exts: Sequence[tuple[int, ...]] | None,
    *,
    observed_exts: list[tuple[int, ...]] | None = None,
) -> tuple[Callable[..., ShadowArray], ...]:
    """Auditor-supplied φ sequence (one per fused sweep).

    Each φ proves, at its call boundary, that (a) every operator's
    derivative block has identical spatial extents and ``n_f`` rows,
    (b) those extents equal the independently derived sweep geometry
    ``τ + 2r·(S-1-s)`` (when ``expected_exts`` is given), and (c) the
    aux carry, when present, is point-wise aligned with the derivative
    blocks. It returns a fresh ``(n_out, *ext)`` shadow — never runs
    user compute. ``observed_exts`` collects the extents each sweep
    actually saw, which the VMEM fidelity check replays as the measured
    carried-intermediate size.
    """

    def make_one(s: int) -> Callable[..., ShadowArray]:
        def phi(derivs: dict, aux: Any = None) -> ShadowArray:
            exts = {tuple(d.shape[1:]) for d in derivs.values()}
            rows = {int(d.shape[0]) for d in derivs.values()}
            if len(exts) != 1 or len(rows) != 1:
                raise AuditError(
                    "phi",
                    f"sweep {s}: misaligned derivative blocks "
                    f"(extents {sorted(exts)}, rows {sorted(rows)})",
                )
            (ext,) = exts
            (n_rows,) = rows
            if n_rows != plan.n_f:
                raise AuditError(
                    "phi",
                    f"sweep {s}: derivative rows {n_rows} != n_f "
                    f"{plan.n_f}",
                )
            if expected_exts is not None and ext != tuple(
                expected_exts[s]
            ):
                raise AuditError(
                    "phi",
                    f"sweep {s}: derivative extents {ext} != expected "
                    f"sweep geometry {tuple(expected_exts[s])}",
                )
            if plan.n_aux:
                if aux is None:
                    raise AuditError(
                        "phi", f"sweep {s}: aux-carrying plan called "
                        "φ without an aux operand"
                    )
                if tuple(aux.shape) != (plan.n_aux,) + ext:
                    raise AuditError(
                        "phi",
                        f"sweep {s}: aux carry shape "
                        f"{tuple(aux.shape)} not aligned with "
                        f"({plan.n_aux},) + {ext}",
                    )
            elif aux is not None:
                raise AuditError(
                    "phi", f"sweep {s}: unexpected aux operand"
                )
            if observed_exts is not None:
                observed_exts.append(ext)
            return ShadowArray((plan.n_out,) + ext, np.dtype(plan.dtype))

        return phi

    return tuple(make_one(s) for s in range(plan.fuse_steps))

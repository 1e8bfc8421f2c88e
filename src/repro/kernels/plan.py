"""StencilPlan — the explicit lowering contract between the fusion
engine, the rank-generic Pallas emitters, and the tuning subsystem.

A plan captures everything the emitter needs to lower one fused
φ(A·B) application — rank, caching strategy, block (tile) shape,
element-wise unroll factor, halo radii, field/output/aux counts and
dtype — and everything the tuning cache needs to key a record. The
pipeline is

    plan_stencil(...)  →  StencilPlan  →  emit.fused_stencil_pallas
         (planner)        (lowering IR)         (emitter)

with ``repro.tuning`` keying its persistent cache on the plan's
serialized identity (``StencilPlan.tuning_key()``), so ``block="auto"``
resolves through one cache for 1-D, 2-D and 3-D domains alike.

Array-axis convention (matches ``repro.core.stencil``): spatial axes
are ordered slowest→fastest, x always last (the TPU lane dimension);
blocks follow the same order, e.g. (τz, τy, τx) at rank 3.
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Sequence

from repro.core.stencil import OperatorSet, StencilSpec

if TYPE_CHECKING:
    from repro.tuning.cache import TuningKey, TuningRecord

STRATEGIES = ("swc", "swc_stream", "tc")

# The tc (matrix-unit) regime contracts each axis of the φ derivative
# sequence against a banded coefficient matrix of shape
# (tile + 2·halo, tile): its MXU work grows with the tile extent, not
# the tap count, so tiles are capped — a (8198, 8192) rank-1 band would
# be a quarter-gigabyte constant doing 16k FLOPs/point.
TC_MAX_TILE = 512

# Spatial-axis letters in array order (slowest→fastest, x last). The
# stream axis of an ``swc_stream`` plan is always axis 0 — z at rank 3,
# y at rank 2 — and its letter joins the strategy id / tuning key.
AXIS_LETTERS: dict[int, tuple[str, ...]] = {
    1: ("x",),
    2: ("y", "x"),
    3: ("z", "y", "x"),
}

# Per-rank default tiles: x spans the lane dimension (long 1-D blocks
# amortize per-grid-step pipeline overhead), y/z follow the paper's
# TPU-friendly bases.
DEFAULT_BLOCKS: dict[int, tuple[int, ...]] = {
    1: (2048,),
    2: (16, 128),
    3: (8, 8, 128),
}


# Scoped VMEM each fused-stencil kernel may use, handed to Mosaic as
# ``vmem_limit_bytes`` (its default is 16 MiB). A TPU v5e core has 128
# MiB of VMEM. The tuner budgets only the staged blocks against an
# eighth of it (``repro.tuning.costmodel.VMEM_BUDGET``); the rest holds
# φ's temporaries, which dominate for the MHD φ: its depth-2 RK-pair
# kernel at block (2, 8, 128) needs ~81 MiB.
VMEM_LIMIT_BYTES = 96 * 1024 * 1024

# Mosaic's (sublane, lane) tiling of the last two dims of a 32-bit
# VMEM block. A staged halo window (τ + 2r per axis) is rounded up to
# it on those two spatial axes; see :func:`staged_window`.
SUBLANE = 8
LANE = 128


def tile_aligned(extent: int, multiple: int) -> int:
    """``extent`` rounded up to a multiple of ``multiple``."""
    return -(-extent // multiple) * multiple


def tile_multiples(rank: int) -> tuple[int, ...]:
    """Multiple Mosaic requires of each axis of a rank-``rank`` block:
    (…, 1, 8, 128) — sublane then lane on the last two axes."""
    return ((1,) * rank + (SUBLANE, LANE))[-rank:]


def staged_window(window: Sequence[int]) -> tuple[int, ...]:
    """Extents Mosaic stages for a spatial halo ``window`` (x last): the
    last (lane) axis rounded up to a multiple of 128 and, at rank ≥ 2,
    the one before it (sublane) to a multiple of 8. Kernel bodies read
    only the leading ``window`` of the staged block — the ONE alignment
    rule shared by the emitter, the VMEM model and the auditor."""
    return tuple(
        tile_aligned(w, t)
        for w, t in zip(window, tile_multiples(len(window)))
    )


def tpu_tile_ok(block: Sequence[int], interior: Sequence[int]) -> bool:
    """Whether Mosaic accepts ``block`` as an output tile of a domain
    with ``interior`` extents: the last axis a multiple of 128 and, at
    rank ≥ 2, the one before it a multiple of 8 — or either equal to
    the full extent. The tuner discards other tiles statically."""
    return all(
        t % m == 0 or t == n
        for t, m, n in zip(block, tile_multiples(len(block)), interior)
    )


def largest_divisor_leq(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is ≤ ``cap`` (≥ 1)."""
    for t in range(min(cap, n), 0, -1):
        if n % t == 0:
            return t
    return 1


def tc_axis_groups(
    spec: StencilSpec, rank: int
) -> dict[tuple[int, tuple[int, ...]], list[tuple[int, float]]]:
    """Decompose one stencil's taps into per-axis contraction groups —
    the lowering contract of the ``tc`` (matrix-unit) regime.

    Each tap is assigned a contraction axis: the LAST nonzero axis of
    its offset (x for the center tap), so every arm of a star stencil
    becomes one dense 1-D contraction along its own axis, and a mixed
    partial like ∂xy falls apart into one x-contraction per y-offset.
    The group key is ``(axis, rest)`` where ``rest`` is the offset with
    the contraction-axis component zeroed; the value lists
    ``(offset_along_axis, coeff)`` taps. Multi-tap groups lower to a
    banded-matrix `dot_general` on the MXU; singleton groups stay
    scalar slice-multiplies on the VPU (a matmul per lone tap would be
    all overhead).
    """
    groups: dict[
        tuple[int, tuple[int, ...]], list[tuple[int, float]]
    ] = {}
    for off, c in zip(spec.offsets, spec.coeffs):
        nonzero = [a for a in range(rank) if off[a] != 0]
        axis = nonzero[-1] if nonzero else rank - 1
        rest = tuple(0 if a == axis else off[a] for a in range(rank))
        groups.setdefault((axis, rest), []).append(
            (int(off[axis]), float(c))
        )
    return groups


def tc_groups_per_axis(ops: OperatorSet) -> tuple[int, ...]:
    """Number of multi-tap (i.e. matmul-lowered) contraction groups per
    axis across an operator set — the ``tc`` compute model's input (its
    MXU FLOPs scale with groups × tile extent, not tap count)."""
    counts = [0] * ops.ndim
    for spec in ops.ops:
        for (axis, _), taps in tc_axis_groups(spec, ops.ndim).items():
            if len(taps) > 1:
                counts[axis] += 1
    return tuple(counts)


# The paper's fixed operator order: accuracy-6 plans key UNMARKED (the
# legacy strategy-id form), so every pre-existing cache record, warm
# entry and golden id stays valid; any other generated order joins the
# key as an explicit ``:o{A}`` suffix. 0 means "unknown" (hand-built
# taps without OperatorSpec metadata) and also keys unmarked.
DEFAULT_ACCURACY = 6


def strategy_sid(
    strategy: str,
    rank: int,
    unroll: int = 1,
    fuse_steps: int | str = 1,
    batch: int = 1,
    accuracy: int = 0,
    n_aux: int = 0,
) -> str:
    """Canonical strategy-id derivation — the ONE place the stream
    axis, unroll factor, temporal depth, ensemble batch extent and
    operator accuracy order join the cache key.

    Used by both :attr:`StencilPlan.strategy_id` and the tuning layer's
    key mirror (``repro.tuning.session.fused_nd_key``), so the two can
    never silently derive different cache ids. ``fuse_steps`` may be
    the string ``"auto"`` (the joint block/depth search's ``:fauto``
    suffix). ``strategy`` may be ``"auto"`` (the cross-strategy search,
    which also owns the stream-axis decision — keyed ``:sauto``, so an
    auto record never collides with a per-strategy one). ``batch > 1``
    appends ``:b{B}`` — a block tuned for a B-member ensemble launch is
    never replayed for a single-member one (the VMEM working set and
    amortized traffic both change with B).

    ``"tc"`` (the matrix-unit regime) needs no extra marker of its own:
    the bare strategy name distinguishes it, and the generic suffixes
    compose — a fused batched MXU plan keys as ``tc:f{S}:b{B}``, which
    can never collide with any ``swc``-family id.

    ``accuracy`` is the operator set's finite-difference order: any
    order other than the paper default (:data:`DEFAULT_ACCURACY` = 6)
    appends ``:o{A}``, so plans for the same domain at different
    generated orders cache separately (``:o4`` never replays an
    order-6 winner — the tap count, halo radii and compute/traffic
    balance all change with the order). Order 6 and 0 ("unknown",
    hand-built taps) key unmarked — the legacy id form, which keeps
    every pre-existing record and golden key valid; distinct orders
    still never collide because the per-axis radii (``accuracy/2``)
    are part of every tuning key. The auditor
    (``repro.analysis.keys``) proves this accuracy alias is the ONE
    collision class the whole suffix grammar admits.

    ``n_aux > 0`` appends ``:a{N}``: aux operands join the staged
    working set (an extra halo-free — or, fused, ``r·(S-1)``-widened —
    block per grid step), so a block tuned without the aux residency
    must never be replayed for a call that carries it. Aux-free plans
    key unmarked — the legacy form every pre-existing record uses.
    """
    sid = strategy
    if strategy == "swc_stream":
        sid += f":s{AXIS_LETTERS[rank][0]}"
    elif strategy == "auto":
        sid += ":sauto"
    if unroll != 1:
        sid += f":u{unroll}"
    if fuse_steps == "auto":
        sid += ":fauto"
    elif fuse_steps != 1:
        sid += f":f{fuse_steps}"
    if batch != 1:
        sid += f":b{batch}"
    if n_aux:
        sid += f":a{n_aux}"
    if accuracy not in (0, DEFAULT_ACCURACY):
        sid += f":o{accuracy}"
    return sid


@dataclasses.dataclass(frozen=True)
class StencilPlan:
    """One lowered fused-stencil configuration (see module docstring).

    ``block`` is the per-grid-step tile; at rank 1 the emitter computes
    ``unroll`` adjacent x sub-tiles per grid step from one staged input
    window (the paper's element-wise unrolling, generalized), so the
    effective x extent per step is ``block[-1] * unroll``.

    ``fuse_steps`` is the temporal-fusion depth: the fused op is applied
    that many times inside ONE kernel invocation on a VMEM-resident
    block whose staged halo is widened to ``radii * fuse_steps`` — the
    valid region shrinks by one radius per sweep and intermediate steps
    never touch HBM (classic temporal blocking: redundant halo compute
    traded for memory traffic). Depth > 1 requires the op to be a
    self-map, ``n_out == n_f + n_aux``, so each sweep's output provides
    the next sweep's field stack (rows 0..n_f) and carry (the rest).

    ``strategy="swc_stream"`` (ranks 2/3) streams the slowest spatial
    axis (:attr:`stream_axis`) with carried halo planes instead of
    tiling it in the Pallas grid; it composes with ``fuse_steps`` but
    rejects aux inputs and element-wise unrolling.

    ``strategy="tc"`` (ranks 1–3) keeps the pipelined ``swc`` staging
    but lowers each axis of the derivative evaluation to a banded
    coefficient-matrix contraction placed on the MXU (f32 accumulate);
    it composes with ``fuse_steps``, ``batch`` and aux inputs, requires
    dtype float32/bfloat16 and ``unroll=1``, and caps tiles at
    ``TC_MAX_TILE`` per axis (see :func:`tc_axis_groups`).

    Raises:
        ValueError: from ``__post_init__`` for any inconsistent
            combination — unknown strategy, rank/strategy mismatch,
            tuple lengths not matching the rank, non-divisible tiles,
            or unmet temporal-fusion prerequisites.

    Example (build through the planner, not the constructor)::

        >>> from repro.core.stencil import derivative_operator_set
        >>> from repro.kernels.plan import plan_stencil
        >>> ops = derivative_operator_set(2, 6, spacing=0.5)
        >>> plan = plan_stencil(ops, (1, 262, 262), 1,
        ...                     strategy="swc_stream")
        >>> plan.block, plan.strategy_id
        ((16, 128), 'swc_stream:sy')
    """

    rank: int
    strategy: str  # "swc" | "swc_stream" | "tc"
    block: tuple[int, ...]  # rank-length tile, x last
    radii: tuple[int, ...]  # halo width per axis
    interior: tuple[int, ...]  # unpadded spatial extents
    n_f: int
    n_out: int
    dtype: str
    n_aux: int = 0
    unroll: int = 1  # element-wise unroll along x
    fuse_steps: int = 1  # temporal fusion depth (in-kernel time steps)
    # Ensemble batch extent: the kernel walks `batch` independent members
    # per block (member-major along the leading field axis), sharing one
    # halo window/prologue per launch step. batch > 1 joins strategy_id
    # as :b{B} so batched records key separately.
    batch: int = 1
    # Finite-difference accuracy order of the operator set this plan
    # lowers (0 = unknown/hand-built taps). Derived by plan_stencil from
    # the OperatorSpec metadata the weight generator attaches; joins
    # strategy_id as :o{A} for non-default orders (see strategy_sid).
    accuracy: int = 0

    def __post_init__(self) -> None:
        if self.accuracy < 0 or self.accuracy % 2:
            raise ValueError(
                "accuracy must be 0 (unknown) or a positive even "
                f"finite-difference order, got {self.accuracy}"
            )
        if self.rank not in (1, 2, 3):
            raise ValueError(f"rank must be 1, 2 or 3, got {self.rank}")
        if self.batch < 1:
            raise ValueError(f"batch must be >= 1, got {self.batch}")
        if self.strategy not in STRATEGIES:
            raise ValueError(
                f"strategy {self.strategy!r} not in {STRATEGIES}"
            )
        if self.strategy == "swc_stream" and self.rank == 1:
            raise ValueError(
                "swc_stream (explicit streaming, paper Fig. 5b) streams "
                "the slowest spatial axis while the lane tile stays "
                "fixed — it requires rank 2 (y-stream) or 3 (z-stream); "
                "at rank 1 use strategy='swc'"
            )
        if self.strategy == "swc_stream" and self.n_aux:
            raise ValueError("aux inputs: use strategy='swc'")
        if self.strategy == "tc" and self.dtype not in (
            "float32", "bfloat16",
        ):
            raise ValueError(
                "strategy='tc' lowers the φ derivative sequence to MXU "
                "matmuls with float32 accumulation — dtype must be "
                "'float32' or 'bfloat16' (bf16 inputs, f32 accumulate); "
                f"got {self.dtype!r}. For float64 fields use "
                "strategy='swc' (VPU) or 'hwc'."
            )
        if self.strategy == "tc" and self.unroll != 1:
            raise ValueError(
                "tc lowers each axis to one banded contraction per "
                "block — element-wise unrolling does not compose; use "
                "unroll=1 with strategy='tc'"
            )
        for name, t in (
            ("block", self.block),
            ("radii", self.radii),
            ("interior", self.interior),
        ):
            if len(t) != self.rank:
                raise ValueError(
                    f"{name} {t} must have rank {self.rank} entries"
                )
        if self.unroll < 1:
            raise ValueError(f"unroll must be >= 1, got {self.unroll}")
        if self.strategy == "swc_stream" and self.unroll != 1:
            raise ValueError("swc_stream does not support unroll > 1")
        if self.fuse_steps < 1:
            raise ValueError(
                f"fuse_steps must be >= 1, got {self.fuse_steps}"
            )
        if self.batch > 1 and self.n_aux and self.fuse_steps > 1:
            raise ValueError(
                "batched temporal fusion with aux carries is not "
                "supported: the member-major output interleaves field "
                "and carry rows between sweeps — use batch=1 or "
                "fuse_steps=1 with aux inputs"
            )
        if self.fuse_steps > 1:
            if self.unroll != 1:
                raise ValueError(
                    "temporal fusion composes with the staged halo "
                    "window, not element-wise unrolling — use unroll=1 "
                    "with fuse_steps > 1"
                )
            if self.n_out != self.n_f + self.n_aux:
                raise ValueError(
                    "fuse_steps > 1 requires a self-map op with "
                    f"n_out == n_f + n_aux (got n_out={self.n_out}, "
                    f"n_f={self.n_f}, n_aux={self.n_aux}) so each "
                    "in-kernel sweep can feed the next"
                )
            if self.strategy == "swc_stream":
                carried = 2 * self.radii[0] * self.fuse_steps
                if self.interior[0] < carried + self.block[0]:
                    raise ValueError(
                        "swc_stream with temporal fusion walks the "
                        "stream axis carrying 2·r·fuse_steps halo "
                        f"planes ({carried} here), so the stream-axis "
                        f"extent must hold that carried halo plus one "
                        f"chunk (block[0]={self.block[0]}); got extent "
                        f"{self.interior[0]} < {carried + self.block[0]}"
                        " — shrink fuse_steps/block[0], grow the "
                        "domain, or use strategy='swc'"
                    )
        step = self.x_step
        for a in range(self.rank):
            t = self.block[a] if a < self.rank - 1 else step
            if self.interior[a] % t:
                raise ValueError(
                    f"axis {a} extent {self.interior[a]} not divisible "
                    f"by tile {t}"
                )

    @property
    def x_step(self) -> int:
        """Output extent covered along x per grid step."""
        return self.block[-1] * self.unroll

    @property
    def stream_axis(self) -> int | None:
        """Array axis the explicit-streaming kernel walks, or None.

        ``swc_stream`` plans always stream the slowest spatial axis
        (axis 0): z at rank 3, y at rank 2 — the cross-stream tile stays
        resident while halo planes are carried chunk to chunk.
        """
        return 0 if self.strategy == "swc_stream" else None

    @property
    def stream_axis_letter(self) -> str | None:
        """Letter of :attr:`stream_axis` ("z"/"y"), or None for
        non-streaming plans; recorded in :attr:`strategy_id`."""
        if self.stream_axis is None:
            return None
        return AXIS_LETTERS[self.rank][self.stream_axis]

    @property
    def halo(self) -> tuple[int, ...]:
        """Staged halo width per axis: one radius per fused sweep."""
        return tuple(r * self.fuse_steps for r in self.radii)

    @property
    def grid(self) -> tuple[int, ...]:
        """Grid extents in axis order (the emitter may reorder for
        streaming; at rank 3 the z axis iterates innermost)."""
        steps = self.block[:-1] + (self.x_step,)
        return tuple(n // t for n, t in zip(self.interior, steps))

    # -- serialization (the tuning layer keys on this) ----------------------

    @property
    def kernel_name(self) -> str:
        """Kernel family component of the cache key (rank-specific)."""
        return f"fused_stencil{self.rank}d"

    @property
    def strategy_id(self) -> str:
        """Strategy component of the cache key; the stream axis, unroll,
        temporal fusion depth and batch extent are codegen
        configuration, so they join the key (via :func:`strategy_sid`)
        — depth-1 and depth-2 plans cache separately, a y-streaming
        rank-2 plan (``swc_stream:sy``) never collides with a pipelined
        one, a B-member ensemble plan keys as ``:b{B}``, an aux-
        carrying plan as ``:a{N}``, and a non-default operator order as
        ``:o{A}``."""
        return strategy_sid(
            self.strategy, self.rank, self.unroll, self.fuse_steps,
            self.batch, self.accuracy, self.n_aux,
        )

    def tuning_key(self, backend: str | None = None) -> TuningKey:
        """The persistent-cache key for this plan's problem identity
        (block excluded — the block IS the tuned value)."""
        from repro.tuning.cache import TuningKey, current_backend

        return TuningKey(
            kernel=self.kernel_name,
            strategy=self.strategy_id,
            domain=self.interior,
            radii=self.radii,
            n_f=self.n_f,
            n_out=self.n_out,
            dtype=self.dtype,
            backend=backend if backend is not None else current_backend(),
        )


def plan_stencil(
    ops: OperatorSet,
    padded_shape: Sequence[int],
    n_out: int,
    *,
    strategy: str = "swc",
    block: Sequence[int] | int | None = None,
    dtype: str = "float32",
    n_aux: int = 0,
    unroll: int = 1,
    fuse_steps: int = 1,
    batch: int | None = None,
    accuracy: int | None = None,
) -> StencilPlan:
    """Lower a fused-stencil problem to a :class:`StencilPlan`.

    ``padded_shape`` is the (n_f, *spatial_padded) operand shape (spatial
    axes padded by ``ops.radius_per_axis() * fuse_steps`` — temporal
    fusion consumes one radius of ghost cells per in-kernel sweep), or
    the batched (batch, n_f, *spatial_padded) shape of an ensemble
    operand — a leading extent beyond rank+1 axes is read as the batch.
    An explicit ``batch`` kwarg must agree with a batched shape (and
    turns a rank+1 shape into a plan for a B-member launch).
    ``block`` may be ``None`` (per-rank default), an int (rank-1
    shorthand), or a tuple; a tuple longer than the rank keeps its
    trailing entries (x-last convention, so a 3-D default like
    (8, 8, 128) lowers to (8, 128) at rank 2), and each axis is clamped
    to the largest divisor of the interior extent — non-block-divisible
    domains shrink the tile instead of failing.
    ``accuracy`` defaults to the operator set's own finite-difference
    order (the OperatorSpec metadata attached by the weight generator;
    0 for hand-built tap sets), keying the plan per order.
    """
    rank = ops.ndim
    if accuracy is None:
        accuracy = getattr(ops, "accuracy", 0)
    radii = ops.radius_per_axis()
    if fuse_steps < 1:
        raise ValueError(f"fuse_steps must be >= 1, got {fuse_steps}")
    padded_shape = tuple(padded_shape)
    if len(padded_shape) == rank + 2:
        shape_batch = int(padded_shape[0])
        if batch is not None and int(batch) != shape_batch:
            raise ValueError(
                f"explicit batch={batch} disagrees with the batched "
                f"operand shape {padded_shape} (leading extent "
                f"{shape_batch})"
            )
        batch = shape_batch
        padded_shape = padded_shape[1:]
    elif batch is None:
        batch = 1
    if len(padded_shape) != rank + 1:
        raise ValueError(
            f"padded operand must be (n_f, *spatial) or "
            f"(batch, n_f, *spatial) with {rank} spatial dims, got "
            f"shape {tuple(padded_shape)}"
        )
    interior = tuple(
        padded_shape[1 + a] - 2 * radii[a] * fuse_steps
        for a in range(rank)
    )
    if any(n <= 0 for n in interior):
        raise ValueError(
            f"padded shape {tuple(padded_shape)} leaves no interior for "
            f"radii {radii} at fuse_steps={fuse_steps}"
        )

    if block is None:
        block = DEFAULT_BLOCKS[rank]
    if isinstance(block, int):
        block = (block,)
    block = tuple(int(b) for b in block)
    if len(block) > rank:
        block = block[-rank:]
    if strategy == "tc":
        # Every axis is a potential contraction axis: cap the tile so
        # the banded coefficient matrices (and the per-point MXU work,
        # which grows with the contraction extent) stay bounded.
        block = tuple(min(b, TC_MAX_TILE) for b in block)
    if len(block) != rank:
        raise ValueError(
            f"block {block} must have {rank} entries (or more, trailing "
            "kept; x last)"
        )

    # Clamp to divisors. The x axis accounts for the unroll factor: the
    # per-step extent block[-1] * unroll must divide the interior; if no
    # unrolled tiling fits, unroll degrades to 1.
    clamped = [
        largest_divisor_leq(interior[a], block[a]) for a in range(rank - 1)
    ]
    if strategy == "swc_stream" and fuse_steps > 1 and clamped:
        # The fused stream chunk must leave room for the carried halo
        # (2·r·S planes) on the stream axis: shrink the chunk when a
        # smaller divisor fits, and otherwise leave the block for
        # StencilPlan validation to reject with the clear error.
        cap = interior[0] - 2 * radii[0] * fuse_steps
        if cap >= 1:
            clamped[0] = largest_divisor_leq(
                interior[0], min(clamped[0], cap)
            )
    nx = interior[-1]
    if unroll > 1 and nx % unroll == 0:
        tx = largest_divisor_leq(nx // unroll, block[-1])
    else:
        unroll = 1
        tx = largest_divisor_leq(nx, block[-1])
    clamped.append(tx)

    return StencilPlan(
        rank=rank,
        strategy=strategy,
        block=tuple(clamped),
        radii=radii,
        interior=interior,
        n_f=int(padded_shape[0]),
        n_out=int(n_out),
        dtype=str(dtype),
        n_aux=int(n_aux),
        unroll=int(unroll),
        fuse_steps=int(fuse_steps),
        batch=int(batch),
        accuracy=int(accuracy),
    )


def plan_from_record(
    ops: OperatorSet,
    interior_shape: Sequence[int],
    n_out: int,
    record: TuningRecord,
    *,
    dtype: str = "float32",
    n_aux: int = 0,
) -> StencilPlan | None:
    """Reconstruct the :class:`StencilPlan` a resolved tuning record
    lowers to — the warm-cache side of the ``strategy="auto"`` contract.

    ``interior_shape`` is the UNPADDED (n_f, *spatial) — or batched
    (batch, n_f, *spatial) — operand shape and
    ``record`` a :class:`~repro.tuning.cache.TuningRecord` whose
    ``strategy_resolved``/``stream``/``block``/``fuse_steps``/
    ``unroll`` fields were persisted by the cross-strategy search.
    Returns ``None`` for a record that resolved to ``hwc`` (the
    compiler-managed path has no Pallas plan); otherwise the plan is
    built exactly as the kernel dispatch would build it, so
    ``plan.strategy_id``/``tuning_key()`` round-trip the decision —
    the left-inverse contract ``repro.analysis.keys`` audits per axis.
    """
    strategy = record.resolved_strategy
    if strategy == "hwc":
        return None
    depth = int(record.fuse_steps)
    # Additive schema-v2 field: records persisted before the unroll
    # axis was recorded lower with the factor they were keyed under
    # (unroll joins the key as :u{N}, so an unmarked key pins 1).
    unroll = int(getattr(record, "unroll", 1))
    radii = ops.radius_per_axis()
    lead = len(tuple(interior_shape)) - ops.ndim  # 1, or 2 when batched
    padded = tuple(interior_shape[:lead]) + tuple(
        n + 2 * r * depth for n, r in zip(interior_shape[lead:], radii)
    )
    return plan_stencil(
        ops, padded, n_out, strategy=strategy,
        block=tuple(record.block), dtype=dtype, n_aux=n_aux,
        unroll=unroll, fuse_steps=depth,
    )

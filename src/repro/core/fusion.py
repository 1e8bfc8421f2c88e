"""FusedStencilOp — the paper's contribution as a composable JAX module.

A fused stencil operation is the paper's chain φ(γ(ψ(f))) (Sec. 3.3):

  ψ  pad the spatial dimensions (boundary module),
  γ  evaluate ALL linear stencil operators for ALL fields — conceptually
     Q = A·B with A ∈ R^{n_s×n_k}, B ∈ R^{n_k×n_f} per point (Eq. 8),
  φ  nonlinear point-wise map producing the n_out field updates (Eq. 9).

``strategy`` selects the caching regime evaluated by the paper. Every
strategy lowers through the :class:`~repro.kernels.plan.StencilPlan`
pipeline (planner → rank-generic emitter → tuning cache) except
``hwc``, which is pure jnp:

  ============  =========  =====================================================
  strategy      ranks      on-chip residency
  ============  =========  =====================================================
  ``hwc``        1, 2, 3   compiler-managed (XLA fuses the tap loops; the
                           hardware-managed-cache analogue)
  ``swc``        1, 2, 3   Pallas kernel, VMEM residency owned by us, blocks
                           auto-pipelined (paper Fig. 5a on TPU)
  ``swc_stream``    2, 3   Pallas kernel, explicit streaming of the slowest
                           spatial axis (z at rank 3, y at rank 2) with
                           carried halo + prefetch DMA (paper Fig. 5b on
                           TPU); composes with ``fuse_steps``
  ``tc``         1, 2, 3   Pallas kernel, ``swc`` staging but tap evaluation
                           lowered to banded coefficient-matrix contractions
                           on the MXU (f32 accumulation; dtype f32/bf16
                           only); composes with ``fuse_steps`` and the
                           ensemble batch axis
  ============  =========  =====================================================

The same object also runs *distributed* over a device mesh: the domain is
decomposed over mesh axes and halos are exchanged with collective
permutes before each application (`apply_sharded`), which is the
shard_map analogue of Astaroth's MPI halo exchange. With
``overlap=True`` the interior (halo-independent) points are computed
from purely local data so XLA can overlap the collective-permute with
interior FLOPs (the compute/communication overlap decomposition).

``fuse_steps`` adds the temporal dimension to the fusion (the paper's
headline strategy taken one level further): one kernel invocation
advances ``fuse_steps`` time steps on a VMEM-resident block whose halo
is widened to ``radius * fuse_steps``, so intermediate steps never
write the field stack back to HBM — redundant halo compute traded for
memory traffic (classic temporal blocking). ``fuse_steps="auto"``
resolves the depth jointly with the block through the tuning
subsystem's traffic-model-driven search.

``strategy="auto"`` closes the loop over the caching regimes
themselves (the paper's central finding: no single regime wins
everywhere, "necessitating platform-specific tuning"): resolution
consults the tuning subsystem's cross-strategy search, which scores
``hwc`` (the measured XLA baseline, modeled at the compulsory-traffic
floor), ``swc``, and ``swc_stream`` candidates jointly over
``(block, fuse_steps, stream)`` and persists the whole decision —
strategy, stream axis, block, and depth — in one schema-v2 tuning
record, reproduced exactly on warm cache hits and under jit tracing
(structural winner, no measurement). ``strategy="auto"`` owns the
block (``block="auto"``, coerced from ``None``) and composes with
``fuse_steps`` being an int (strategy/block search at that depth) or
``"auto"`` (the full joint search).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Mapping, Sequence, Union

import jax
import jax.numpy as jnp

from repro.core import boundary
from repro.core.halo import exchange_halos_nd, interior_first
from repro.core.stencil import OperatorSet
from repro.kernels import ops as kops
from repro.kernels import ref as kref

Phi = Callable[[Mapping[str, jnp.ndarray]], jnp.ndarray]
# One callable (applied every fused step) or one per fused step.
PhiLike = Union[Phi, tuple]

STRATEGIES = ("hwc", "swc", "swc_stream", "tc", "auto")


@dataclasses.dataclass(frozen=True)
class FusedStencilOp:
    """One fused update step over an (n_f, *spatial) field stack.

    Args (dataclass fields):
        ops: the :class:`~repro.core.stencil.OperatorSet` of linear
            stencil operators (γ — every A·B product the update needs).
        phi: point-wise map from ``{op_name: (n_f, *spatial)}`` (plus an
            optional aux array) to the (n_out, *spatial) update; may be
            a sequence of ``fuse_steps`` per-sweep callables.
        n_out: number of output fields φ produces.
        boundary_mode: ψ — how ghost cells are filled ("periodic", …);
            scalar or one mode per spatial axis (e.g. a channel flow
            ``("dirichlet", "periodic")`` — walls along y, wrap along
            x).
        boundary_weights: replace the ghost-cell approximation within
            ``r`` cells of every non-periodic face by boundary-MODIFIED
            weight rows (offset/one-sided stencils of the full interior
            order, ``core.boundary.apply_operator_set_bc``), blended
            over the fast padded kernel output as a post-pass — so
            non-periodic domains keep the operator's nominal
            convergence order instead of degrading to the ghost fill's
            (Dirichlet 0th/1st, "neumann" 1st, "neumann2" 2nd).
            Requires generated operators (OperatorSpec metadata) and
            depth 1; a no-op on all-periodic axes.
        strategy: caching regime — "hwc", "swc", "swc_stream", "tc"
            (stencils on the matrix unit; f32/bf16 only), or
            "auto" (the cross-strategy tuning search picks the regime,
            block, depth and stream axis jointly and persists them in
            one record; see the module docstring).
        block: rank-length tile (x last), ``"auto"`` (persistent tuning
            cache), or None (the planner's default tile, derived from
            the shape for rank-3 ``swc``; coerced to ``"auto"`` under
            ``strategy="auto"``, which owns the block).
        fuse_steps: temporal-fusion depth (int ≥ 1, or ``"auto"`` for
            the joint block/depth search).

    Calling the op applies one (depth-fused) update::

        >>> import jax.numpy as jnp
        >>> from repro.core.fusion import FusedStencilOp
        >>> from repro.core.stencil import derivative_operator_set
        >>> ops = derivative_operator_set(2, 2, spacing=0.5)
        >>> op = FusedStencilOp(
        ...     ops, lambda d: d["val"] + 0.1 * (d["dxx"] + d["dyy"]),
        ...     n_out=1, strategy="swc")
        >>> out = op(jnp.zeros((1, 8, 16)))
        >>> out.shape
        (1, 8, 16)

    Raises:
        ValueError: on an invalid strategy, a strategy/rank mismatch
            (``swc_stream`` needs rank ≥ 2), a non-periodic boundary or
            ``swc_stream`` with depth > 1 prerequisites unmet, or a
            per-step φ sequence whose length disagrees with the depth.
    """

    ops: OperatorSet
    phi: PhiLike
    n_out: int
    # ψ — ghost-fill family, scalar or per spatial axis (x last).
    boundary_mode: str | tuple[str, ...] = "periodic"
    strategy: str = "hwc"
    # Rank-length tile (x last), "auto" to consult the persistent tuning
    # cache (repro.tuning: cache-hit fast path, rank-and-measure on an
    # eager miss, structural cost-model winner under jit tracing), or
    # None for the default tile (plan_stencil: derived from the shape
    # for rank-3 unbatched swc plans, the fixed per-rank tile otherwise).
    block: tuple[int, ...] | str | None = None
    # Temporal fusion depth: one call advances this many time steps in
    # ONE kernel (halo widened to radius·depth, intermediates VMEM-only).
    # "auto" resolves (block, depth) jointly from the tuning subsystem's
    # traffic-model search; requires strategy="swc"/"swc_stream" and
    # block="auto".
    fuse_steps: int | str = 1
    # Full-order boundary-modified weight rows at non-periodic faces
    # (post-pass blend; see the class docstring).
    boundary_weights: bool = False

    def __post_init__(self):
        if self.strategy not in STRATEGIES:
            raise ValueError(
                f"strategy {self.strategy!r} not in {STRATEGIES}"
            )
        # Validates mode names and the per-axis count up front.
        modes = self.boundary_modes
        if self.boundary_weights:
            missing = [s.name for s in self.ops.ops if s.spec is None]
            if missing:
                raise ValueError(
                    "boundary_weights=True needs OperatorSpec metadata "
                    "(derivative, accuracy, spacing) on every operator "
                    "to build the offset weight rows — missing on "
                    f"{missing}; build the set with axis_stencil/"
                    "laplacian_stencil/derivative_operator_set"
                )
        if self.strategy == "swc_stream" and self.ops.ndim < 2:
            raise ValueError(
                "swc_stream (explicit streaming of the slowest axis) "
                f"requires a 2-D or 3-D operator set; got "
                f"ndim={self.ops.ndim} — use strategy='swc'"
            )
        if self.strategy == "auto":
            # The cross-strategy search owns the block: None is coerced
            # to "auto", an explicit tile is contradictory.
            if self.block is None:
                object.__setattr__(self, "block", "auto")
            elif self.block != "auto":
                raise ValueError(
                    "strategy='auto' resolves the block through the "
                    "cross-strategy tuning search — pass block='auto' "
                    f"(or None), not {self.block!r}"
                )
        if isinstance(self.block, str) and self.block != "auto":
            raise ValueError(
                f"block must be a rank-length tuple, 'auto', or None, "
                f"got {self.block!r}"
            )
        if isinstance(self.fuse_steps, str):
            if self.fuse_steps != "auto":
                raise ValueError(
                    f"fuse_steps must be an int >= 1 or 'auto', got "
                    f"{self.fuse_steps!r}"
                )
            if self.strategy not in (
                "swc", "swc_stream", "tc", "auto"
            ) or (self.block != "auto"):
                raise ValueError(
                    "fuse_steps='auto' resolves through the joint "
                    "(block, depth) tuning search — it requires "
                    "strategy='swc', 'swc_stream', 'tc' or 'auto' and "
                    "block='auto'"
                )
        elif self.fuse_steps < 1:
            raise ValueError(
                f"fuse_steps must be >= 1, got {self.fuse_steps}"
            )
        if self._depth_or_none() != 1:
            if any(m != "periodic" for m in modes):
                raise ValueError(
                    "temporal fusion requires boundary_mode='periodic' "
                    "on every axis: intermediate in-kernel sweeps "
                    "consume pre-padded ghost cells and never re-impose "
                    "the boundary, which only composes exactly for the "
                    f"periodic wrap (got {self.boundary_mode!r})"
                )
        if isinstance(self.phi, (tuple, list)):
            depth = self._depth_or_none()
            if depth is None:
                raise ValueError(
                    "a per-step phi sequence pins the fusion depth to "
                    f"len(phi) = {len(self.phi)} — pass that as "
                    "fuse_steps instead of 'auto'"
                )
            if len(self.phi) != depth:
                raise ValueError(
                    f"phi sequence has {len(self.phi)} entries for "
                    f"fuse_steps={depth}"
                )

    def _depth_or_none(self) -> int | None:
        """Concrete fusion depth, or None when it is tuned ('auto')."""
        return None if self.fuse_steps == "auto" else int(self.fuse_steps)

    @property
    def needs_resolution(self) -> bool:
        """True while any lowering decision (strategy or depth) is still
        ``"auto"`` — ``resolved()`` turns such an op concrete."""
        return self.strategy == "auto" or self.fuse_steps == "auto"

    @property
    def radius_per_axis(self) -> tuple[int, ...]:
        """Per-axis halo radius of the operator set (ghost cells one
        un-fused application consumes on each side)."""
        return self.ops.radius_per_axis()

    @property
    def boundary_modes(self) -> tuple[str, ...]:
        """``boundary_mode`` normalized to one mode per spatial axis
        (x last), names validated."""
        return boundary._normalize_modes(
            self.boundary_mode, self.ops.ndim
        )

    @property
    def ghost_offer(self) -> str | None:
        """``"zero"`` when every face is a zero Dirichlet wall and no
        boundary-weight blend follows: the kernel may then fill the
        ghost cells itself (``StencilPlan.ghost``). ``None`` otherwise."""
        if self.boundary_weights or any(
            m != "dirichlet" for m in self.boundary_modes
        ):
            return None
        return "zero"

    def lowering_plan(
        self,
        interior_shape: Sequence[int],
        *,
        n_aux: int = 0,
        dtype: str = "float32",
    ):
        """The :class:`~repro.kernels.plan.StencilPlan` a call of this
        op lowers for an (unpadded) ``interior_shape`` field stack —
        ``(n_f, *spatial)`` or the batched ``(batch, n_f, *spatial)``:
        a zero-ghost plan where :attr:`ghost_offer` allows and the plan
        admits it, else the plan ``apply_padded`` lowers. ``None`` for
        the hwc regime (no Pallas plan). Requires every lowering
        decision to be concrete (``resolved()`` first) — the static
        auditor (``repro.analysis``) drives this to audit exactly the
        plan a call site will launch, without running it.
        """
        depth = self._depth_or_none()
        if depth is None or self.strategy == "auto":
            raise ValueError(
                "lowering_plan needs a concrete strategy and "
                "fuse_steps — resolve via op.resolved(f) first"
            )
        shape = tuple(interior_shape)
        lead = len(shape) - self.ops.ndim
        radii = self.radius_per_axis
        padded = shape[:lead] + tuple(
            n + 2 * r * depth for n, r in zip(shape[lead:], radii)
        )
        aux_shape = None
        if n_aux:
            aux_shape = shape[: lead - 1] + (n_aux,) + shape[lead:]
        return kops.plan_for_nd(
            self.ops, padded, self.n_out, aux_shape=aux_shape,
            strategy=self.strategy, block=self.block, dtype=dtype,
            fuse_steps=depth, ghost=self.ghost_offer,
        )

    # -- single device ------------------------------------------------------

    def resolved(
        self, f: jnp.ndarray, aux: kref.Aux = None
    ) -> "FusedStencilOp":
        """An equivalent, fully concrete op — the resolution contract.

        A no-op when nothing is ``"auto"``. With ``strategy="auto"``
        the cross-strategy search resolves (strategy, block, depth,
        stream) in one pass for the *unpadded* field stack ``f`` and the
        returned op carries all four (the stream axis is implied by the
        resolved strategy); with only ``fuse_steps="auto"`` the
        per-strategy joint (block, depth) search runs. Either way:
        measured on a cache miss when eager, replayed from the
        persistent record on a warm hit, the traffic-model winner under
        jit tracing — so the returned op is bit-identical across a
        cold-measure → cache-write → warm-hit cycle.
        """
        if not self.needs_resolution:
            return self
        # The tuner measures with the aux rows as one array.
        aux = kref.join_aux(aux, 1 if f.ndim == self.ops.ndim + 2 else 0)
        if self.strategy == "auto":
            from repro.tuning.session import auto_strategy_nd

            strategy, block, depth = auto_strategy_nd(
                f, self.ops, self.phi, self.n_out, aux=aux,
                fuse_steps=self.fuse_steps,
            )
            return dataclasses.replace(
                self, strategy=strategy, block=tuple(block),
                fuse_steps=int(depth),
            )
        from repro.tuning.session import auto_fuse_nd

        block, depth = auto_fuse_nd(
            f, self.ops, self.phi, self.n_out, aux=aux,
            strategy=self.strategy,
        )
        return dataclasses.replace(
            self, block=tuple(block), fuse_steps=int(depth)
        )

    def apply_padded(
        self, f_padded: jnp.ndarray, aux: kref.Aux = None
    ) -> jnp.ndarray:
        """Apply to an already-padded field stack (ghost cells present:
        ``radius * fuse_steps`` per axis — one radius per fused sweep).

        ``aux``: extra point-wise inputs forwarded to φ (fused axpy /
        RK carries, a leapfrog's previous level and coefficient fields
        — beyond-paper extension); (n_aux, *interior) at depth 1, padded
        by ``radius * (fuse_steps - 1)`` at depth > 1 so intermediate
        sweeps see an aligned carry. One array, or a tuple of arrays
        whose rows together are φ's aux rows: the Pallas regimes stage
        each as its own kernel operand, so arrays that live apart are
        not stacked in HBM first.

        A batched (batch, n_f, *padded) ensemble stack is accepted
        wherever an (n_f, *padded) stack is — detected by rank and
        lowered through the member-major batched kernel (hwc uses the
        ``vmap`` oracles)."""
        depth = self._depth_or_none()
        if depth is None or self.strategy == "auto":
            raise ValueError(
                "apply_padded needs a concrete strategy and fuse_steps "
                "(the kernel and its ghost-cell width depend on them) "
                "— resolve via op.resolved(f)(f) or __call__"
            )
        if self.strategy in ("swc", "swc_stream", "tc"):
            return kops.fused_stencil_nd(
                f_padded, self.ops, self.phi, self.n_out, aux=aux,
                strategy=self.strategy, block=self.block,
                fuse_steps=depth,
            )
        # hwc — XLA owns on-chip residency (the paper's compiler-managed
        # caching regime). A (batch, n_f, *spatial) ensemble stack
        # dispatches to the vmap'd oracles.
        if f_padded.ndim == self.ops.ndim + 2:
            if depth == 1:
                return kref.fused_stencil_batched(
                    f_padded, self.ops, self.phi, aux=aux
                )
            return kref.fused_stencil_steps_batched(
                f_padded, self.ops, self.phi, depth, aux=aux
            )
        if depth == 1:
            return kref.fused_stencil(
                f_padded, self.ops, self.phi, aux=aux
            )
        return kref.fused_stencil_steps(
            f_padded, self.ops, self.phi, depth, aux=aux
        )

    def __call__(
        self, f: jnp.ndarray, aux: kref.Aux = None
    ) -> jnp.ndarray:
        """ψ then φ(A·B): pad with the boundary function and apply —
        advancing ``fuse_steps`` time steps per call.

        ``f`` is (n_f, *spatial), or (batch, n_f, *spatial) for an
        ensemble stack — the extra leading axis is detected by rank and
        threaded through padding and the batched kernel lowering
        (``aux`` then carries the same leading axis).

        Where the plan stages zero ghost cells itself
        (:meth:`_zero_ghost_plan`), ``f`` goes to the kernel unpadded
        and no padded copy is made."""
        if self.needs_resolution:
            return self.resolved(f, aux)(f, aux)
        plan = self._zero_ghost_plan(f, aux)
        if plan is not None:
            return kops.fused_stencil_plan(
                f, self.ops, self.phi, plan, aux=aux
            )
        depth = int(self.fuse_steps)
        rads = self.radius_per_axis
        modes = self.boundary_modes
        lead = 2 if f.ndim == self.ops.ndim + 2 else 1
        fp = boundary.pad(
            f, [r * depth for r in rads], modes,
            spatial_axes=range(lead, f.ndim),
        )
        if aux is not None and depth > 1:
            aux = jax.tree.map(
                lambda a: boundary.pad(
                    a, [r * (depth - 1) for r in rads], modes,
                    spatial_axes=range(lead, a.ndim),
                ),
                aux,
            )
        out = self.apply_padded(fp, aux=aux)
        if self.boundary_weights and any(m != "periodic" for m in modes):
            out = self._blend_boundary_weights(f, out, aux, lead)
        return out

    def _zero_ghost_plan(self, f: jnp.ndarray, aux: kref.Aux):
        """The zero-ghost plan a call on ``f`` launches, or ``None``
        where the call pads: the op offers zero ghosts
        (:attr:`ghost_offer`), its Pallas ``swc`` block is concrete, and
        the planner admits the plan (rank 3, unbatched, depth 1, a tile
        spanning x — ``repro.kernels.plan.zero_ghost_fits``)."""
        if (
            self.ghost_offer is None
            or self.strategy != "swc"
            or isinstance(self.block, str)
        ):
            return None
        row_axis = f.ndim - self.ops.ndim - 1  # 1 for an ensemble stack
        rows = sum(a.shape[row_axis] for a in jax.tree.leaves(aux))
        plan = self.lowering_plan(f.shape, n_aux=rows, dtype=str(f.dtype))
        return plan if plan.ghost == "zero" else None

    def _blend_boundary_weights(
        self,
        f: jnp.ndarray,
        out: jnp.ndarray,
        aux: kref.Aux,
        lead: int,
    ) -> jnp.ndarray:
        """Overwrite the wall-adjacent cells of the kernel output with
        the boundary-accurate evaluation (post-pass of
        ``boundary_weights=True``, depth 1 only — guaranteed by
        ``__post_init__``, which pins non-periodic ops to depth 1).

        The interior (every point ≥ r from all non-periodic faces)
        keeps the kernel's value bit-for-bit: the centered stencil
        there never reads a ghost cell, so the two evaluations agree
        and only the contaminated shell is replaced — the blend adds a
        dense-matrix evaluation of a thin O(r · surface) region, not a
        second full-domain pass of compute semantics.
        """
        modes = self.boundary_modes
        rads = self.radius_per_axis
        phi = self.phi[0] if isinstance(self.phi, (tuple, list)) else self.phi
        aux = kref.join_aux(aux, lead - 1)

        def bc_output(fm, auxm):
            derivs = boundary.apply_operator_set_bc(
                fm, self.ops, modes,
                spatial_axes=tuple(range(1, fm.ndim)),
            )
            return phi(derivs) if auxm is None else phi(derivs, auxm)

        if lead == 2:  # batched ensemble stack: member-wise oracle
            if aux is None:
                bc = jax.vmap(lambda fm: bc_output(fm, None))(f)
            else:
                bc = jax.vmap(bc_output)(f, aux)
        else:
            bc = bc_output(f, aux)
        spatial = f.shape[lead:]
        mask = jnp.zeros(spatial, dtype=bool)
        for a, (n, r, m) in enumerate(zip(spatial, rads, modes)):
            if m == "periodic" or r == 0:
                continue
            idx = jnp.arange(n)
            near = (idx < r) | (idx >= n - r)
            shape = [1] * len(spatial)
            shape[a] = n
            mask = mask | near.reshape(shape)
        return jnp.where(mask, bc.astype(out.dtype), out)

    # -- distributed --------------------------------------------------------

    def apply_sharded(
        self,
        f_local: jnp.ndarray,
        mesh_axes: Sequence[str | None],
        aux: kref.Aux = None,
        *,
        overlap: bool = False,
    ) -> jnp.ndarray:
        """Apply inside ``shard_map``: exchange halos over the mesh axes
        assigned to each spatial dimension, then run the local fused
        kernel.

        Args:
            f_local: this shard's (n_f, *local_spatial) field block.
            mesh_axes: one entry per spatial dimension — the mesh-axis
                name sharding that dimension, or None for unsharded
                (local boundary padding).
            aux: optional (n_aux, *local_spatial) point-wise inputs
                forwarded to φ (exchanged at ``radius·(fuse_steps-1)``
                when depth > 1).
            overlap: emit the compute/communication overlap
                decomposition (below); numerics are unchanged.

        Returns:
            The (n_out, *local_spatial) update for this shard.

        Raises:
            ValueError: when ``mesh_axes`` does not have exactly one
                entry per spatial dimension.
            NotImplementedError: for non-periodic boundary modes.

        Example (2 shards on a "data" mesh axis over y)::

            jax.shard_map(
                lambda fl: op.apply_sharded(fl, (None, "data", None)),
                mesh=mesh,
                in_specs=P(None, None, "data", None),
                out_specs=P(None, None, "data", None),
            )(f)

        Periodic boundaries compose exactly with the ring permute: the
        wrap-around neighbor IS the periodic image.

        ``overlap=True`` emits the halo exchange first and computes the
        halo-independent interior from purely local data, so XLA's
        latency-hiding scheduler can overlap the collective-permute with
        interior FLOPs; the dependent edge slabs are computed from the
        exchanged array afterwards. Numerics are unchanged.

        With ``fuse_steps > 1`` the exchanged halo widens to
        ``radius * fuse_steps`` per sharded axis (and the carry ``aux``
        is exchanged at ``radius * (fuse_steps - 1)``): one exchange
        buys ``fuse_steps`` time steps, cutting ICI message count the
        same way the kernel cuts HBM round trips. The overlap
        decomposition composes with any depth: the halo-independent
        interior shrinks by ``radius * fuse_steps`` per sharded axis and
        the dependent edge slabs (with their ``radius * (fuse_steps-1)``
        aux windows) are computed from the exchanged array afterwards.
        """
        # The exchange moves one aux array.
        aux = kref.join_aux(aux)
        if self.needs_resolution:
            return self.resolved(f_local, aux).apply_sharded(
                f_local, mesh_axes, aux, overlap=overlap
            )
        depth = int(self.fuse_steps)
        n_spatial = f_local.ndim - 1
        if len(mesh_axes) != n_spatial:
            raise ValueError(
                f"mesh_axes has {len(mesh_axes)} entries but the field "
                f"stack has {n_spatial} spatial dims — pass one mesh-axis "
                "name (or None) per spatial dimension"
            )
        if any(m != "periodic" for m in self.boundary_modes):
            raise NotImplementedError(
                "sharded stencils currently support periodic boundaries "
                "(the paper's simulation setup)"
            )
        if overlap:
            out = self._apply_sharded_overlap(f_local, mesh_axes, aux)
            if out is not None:
                return out
        spatial_axes = tuple(range(1, f_local.ndim))
        fp = exchange_halos_nd(
            f_local, [r * depth for r in self.radius_per_axis],
            mesh_axes, spatial_axes=spatial_axes,
        )
        if aux is not None and depth > 1:
            aux = exchange_halos_nd(
                aux, [r * (depth - 1) for r in self.radius_per_axis],
                mesh_axes, spatial_axes=tuple(range(1, aux.ndim)),
            )
        return self.apply_padded(fp, aux=aux)

    def _apply_sharded_overlap(
        self,
        f_local: jnp.ndarray,
        mesh_axes: Sequence[str | None],
        aux: jnp.ndarray | None,
    ) -> jnp.ndarray | None:
        """Compute/communication overlap decomposition (module docstring).

        Generalized over the temporal-fusion depth ``S = fuse_steps``:
        the exchange (and the halo every output point consumes) widens
        to ``radius·S`` per sharded axis, so the halo-independent
        interior shrinks by ``radius·S`` per side and the dependent edge
        slabs are ``radius·S`` wide. The carry ``aux`` is consumed at
        ``radius·(S-1)`` ghost cells per sweep boundary, so it is
        exchanged at that width and every sub-computation slices its
        aligned aux window from the exchanged array.

        Returns None when the decomposition doesn't apply (no sharded
        axis, or a local extent too small to hold an interior) — the
        caller falls back to the plain exchange-then-apply path.
        """
        depth = int(self.fuse_steps)
        rads = self.radius_per_axis
        wrads = [r * depth for r in rads]  # halo consumed per output
        arads = [r * (depth - 1) for r in rads]  # aux ghost width
        spatial_axes = tuple(range(1, f_local.ndim))
        sharded = [
            (ax, w)
            for ax, w, name in zip(spatial_axes, wrads, mesh_axes)
            if name is not None and w > 0
        ]
        if not sharded:
            return None  # nothing to overlap with
        if any(f_local.shape[ax] <= 2 * w for ax, w in sharded):
            return None  # no interior: every point depends on halos

        # Emit the exchange FIRST: the permutes depend only on edge
        # planes, the interior compute below only on local data, so the
        # scheduler can run them concurrently.
        fp = exchange_halos_nd(
            f_local, wrads, mesh_axes, spatial_axes=spatial_axes,
        )
        # The carry is exchanged at its own (narrower) width; at depth 1
        # that width is zero and aux_p is aux itself. Unsharded axes get
        # the local periodic wrap inside exchange_halos_nd.
        aux_p = None
        if aux is not None:
            aux_p = exchange_halos_nd(
                aux, arads, mesh_axes,
                spatial_axes=tuple(range(1, aux.ndim)),
            )

        # Interior: along each sharded axis the local block IS the
        # interior plus its (not-yet-arrived) halo, so it only needs
        # local periodic padding on the unsharded axes.
        pad_width = [(0, 0)] * f_local.ndim
        for ax, w, name in zip(spatial_axes, wrads, mesh_axes):
            if name is None and w > 0:
                pad_width[ax] = (w, w)
        f_interior_padded = jnp.pad(f_local, pad_width, mode="wrap")
        interior_view, edges = interior_first(
            f_local, [w for _, w in sharded], [ax for ax, _ in sharded]
        )
        int_sl = [slice(None)] * f_local.ndim
        aux_sl = [slice(None)] * f_local.ndim
        for ax, w in sharded:
            int_sl[ax] = slice(w, f_local.shape[ax] - w)
            # aux_p leads local coords by arads; the interior's aux
            # window spans interior ± arads on every sharded axis.
            a = arads[ax - 1]
            aux_sl[ax] = slice(w, f_local.shape[ax] - w + 2 * a)
        aux_int = aux_p[tuple(aux_sl)] if aux_p is not None else None
        out_interior = self.apply_padded(f_interior_padded, aux=aux_int)
        assert out_interior.shape[1:] == interior_view.shape[1:]

        out = jnp.zeros(
            (self.n_out,) + f_local.shape[1:], out_interior.dtype
        )
        out = out.at[tuple(int_sl)].set(out_interior)

        # Edge slabs depend on the exchanged halos: recompute each slab
        # from the padded array. Slabs span the full extent of the other
        # axes, so corner regions are (idempotently) covered.
        for ax, sl in edges:
            n_ax = f_local.shape[ax]
            s = sl.start or 0
            e = n_ax if sl.stop is None else sl.stop
            w_ax = wrads[ax - 1]
            a_ax = arads[ax - 1]
            w_sl = [slice(None)] * fp.ndim
            w_sl[ax] = slice(s, e + 2 * w_ax)
            slab_out = self.apply_padded(
                fp[tuple(w_sl)],
                aux=None if aux_p is None else aux_p[
                    tuple(
                        slice(s, e + 2 * a_ax) if a == ax else slice(None)
                        for a in range(aux_p.ndim)
                    )
                ],
            )
            o_sl = [slice(None)] * out.ndim
            o_sl[ax] = slice(s, e)
            out = out.at[tuple(o_sl)].set(slab_out)
        return out


def integrate(
    op: FusedStencilOp, f0: jnp.ndarray, n_steps: int
) -> jnp.ndarray:
    """Iterate f ← φ(A·B(ψ(f))) for ``n_steps`` TIME steps with lax
    control flow (paper Fig. 1).

    With temporal fusion each scan iteration advances ``op.fuse_steps``
    steps in one kernel; a remainder ``n_steps % fuse_steps`` is
    finished with a shallower op so the step count is exact.
    ``fuse_steps="auto"`` (and ``strategy="auto"``) is resolved once,
    up front, against ``f0`` — except the remainder launch, which does
    NOT reuse the block tuned for the full depth: when the caller asked
    for ``block="auto"``, the depth-``rem`` op resolves through its own
    tuning key (a depth-``S`` winner is generally mistuned at depth
    ``rem`` — the halo, VMEM window, and traffic model all change with
    the depth). An explicit block is reused as given.

    Args:
        op: the fused update to iterate (one uniform φ — per-step φ
            sequences are driven by their solver, not ``integrate``).
        f0: initial (n_f, *spatial) field stack.
        n_steps: exact number of TIME steps to advance.

    Returns:
        The (n_f, *spatial) field stack after ``n_steps`` steps.

    Raises:
        ValueError: when ``op.phi`` is a per-step sequence.

    Example::

        >>> from repro.physics.diffusion import DiffusionProblem
        >>> from repro.core.fusion import integrate
        >>> p = DiffusionProblem((16, 32), accuracy=6)
        >>> op = p.step_op("swc", fuse_steps=2)
        >>> out = integrate(op, p.init_field(), 7)  # 3 fused + 1 plain
        >>> out.shape
        (1, 16, 32)
    """
    requested_block = op.block  # before resolution concretizes it
    op = op.resolved(f0)
    depth = int(op.fuse_steps)
    if depth > 1 and isinstance(op.phi, (tuple, list)):
        raise ValueError(
            "integrate() iterates one uniform map — per-step phi "
            "sequences (RK substep fusion) are driven by their solver"
        )
    full, rem = divmod(n_steps, depth)

    def body(f, _):
        """One fused launch: advance ``depth`` time steps."""
        return op(f), None

    out, _ = jax.lax.scan(body, f0, None, length=full)
    if rem:
        # The remainder runs at depth `rem`, not depth `S`: give it back
        # the caller's "auto" block so it resolves under its own
        # depth-`rem` tuning key instead of inheriting the depth-`S`
        # winner (an explicit block is reused as documented above).
        rem_block = "auto" if requested_block == "auto" else op.block
        out = dataclasses.replace(
            op, fuse_steps=rem, block=rem_block
        )(out)
    return out

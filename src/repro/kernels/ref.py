"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth for the per-kernel allclose sweeps in
``tests/test_kernels.py`` and double as the HWC ("hardware/XLA-managed
caching") strategy of the fusion engine: plain jnp code whose on-chip
residency is decided entirely by the compiler — the TPU analogue of the
paper's L1/L2-managed implementations.
"""
from __future__ import annotations

from typing import Callable, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.stencil import OperatorSet


def xcorr1d(f_padded: jnp.ndarray, g: jnp.ndarray) -> jnp.ndarray:
    """1-D discrete cross-correlation, paper Eq. 3.

    ``f_padded`` has shape (n + 2r,); ``g`` has shape (2r + 1,).
    Returns (n,): f'_i = Σ_j g_j · f̂_{i+j}.
    """
    n = f_padded.shape[0] - (g.shape[0] - 1)
    acc = jnp.zeros((n,), dtype=f_padded.dtype)
    for k in range(g.shape[0]):
        acc = acc + g[k].astype(f_padded.dtype) * jnp.asarray(f_padded[k : k + n])
    return acc


def apply_operator_set(
    f_padded: jnp.ndarray, ops: OperatorSet
) -> dict[str, jnp.ndarray]:
    """Evaluate every operator of ``ops`` over a padded multi-field array.

    ``f_padded``: (n_f, *spatial_padded) where each spatial axis is padded
    by the per-axis radius of the set. Returns {op_name: (n_f, *spatial)}.
    Shifted-slice multiply-accumulate with static offsets — XLA fuses the
    whole tap set into one loop (this IS the hardware-managed-cache path).
    """
    rad = ops.radius_per_axis()
    spatial = tuple(
        f_padded.shape[1 + a] - 2 * rad[a] for a in range(ops.ndim)
    )
    out: dict[str, jnp.ndarray] = {}
    for spec in ops.ops:
        acc = jnp.zeros((f_padded.shape[0],) + spatial, dtype=f_padded.dtype)
        for off, c in zip(spec.offsets, spec.coeffs):
            sl = tuple(
                slice(rad[a] + off[a], rad[a] + off[a] + spatial[a])
                for a in range(ops.ndim)
            )
            acc = acc + jnp.asarray(c, dtype=f_padded.dtype) * f_padded[(slice(None),) + sl]
        out[spec.name] = acc
    return out


Aux = Union[jnp.ndarray, tuple, None]


def join_aux(aux: Aux, axis: int = 0) -> jnp.ndarray | None:
    """Aux rows as the one array φ reads: a tuple of arrays is joined
    along the row ``axis`` (1 for a batched member stack); an array or
    None passes through."""
    if isinstance(aux, (tuple, list)):
        return jnp.concatenate(aux, axis=axis)
    return aux


def fused_stencil(
    f_padded: jnp.ndarray,
    ops: OperatorSet,
    phi: Callable[..., jnp.ndarray],
    aux: Aux = None,
) -> jnp.ndarray:
    """The paper's fused φ(A·B) evaluation (Eq. 9), reference form.

    Computes all linear operators (Q = A·B at every point) then the
    nonlinear point-wise map φ. ``phi`` maps {op_name: (n_f, *spatial)} to
    (n_out, *spatial). ``aux`` (n_aux, *spatial), if given, provides extra
    point-wise inputs (e.g. the RK3 carry) passed as phi's second arg; a
    tuple of arrays is joined row-wise first (:func:`join_aux`).
    """
    derivs = apply_operator_set(f_padded, ops)
    if aux is None:
        return phi(derivs)
    return phi(derivs, join_aux(aux))


def fused_stencil_steps(
    f_padded: jnp.ndarray,
    ops: OperatorSet,
    phi,
    n_steps: int,
    aux: Aux = None,
) -> jnp.ndarray:
    """Sequential reference for temporal fusion: apply the fused op
    ``n_steps`` times, shrinking the valid region by one radius per
    application — the oracle BOTH depth-fused Pallas kernels (the
    halo-widened pipelined ``swc`` kernel and the carried-halo
    ``swc_stream`` streaming kernel) must match bit-for-tolerance.

    ``f_padded`` is padded by ``radius * n_steps`` per axis; ``aux`` (if
    given) by ``radius * (n_steps - 1)``. ``phi`` is one callable (same
    map every step) or a sequence of ``n_steps`` callables (e.g. RK
    substeps with different coefficients). Steps before the last must be
    self-maps — rows 0..n_f of the output feed the next step's field
    stack, the following n_aux rows the next carry. Returns
    (n_out, *interior).
    """
    phis = (
        tuple(phi) if isinstance(phi, (tuple, list)) else (phi,) * n_steps
    )
    if len(phis) != n_steps:
        raise ValueError(
            f"got {len(phis)} phi callables for {n_steps} fused steps"
        )
    rad = ops.radius_per_axis()
    n_f = f_padded.shape[0]
    cur, cur_aux = f_padded, join_aux(aux)
    for s, phi_s in enumerate(phis):
        out = fused_stencil(cur, ops, phi_s, aux=cur_aux)
        if s == n_steps - 1:
            return out
        cur = out[:n_f]
        if cur_aux is not None:
            n_aux = cur_aux.shape[0]
            carry = out[n_f : n_f + n_aux]
            cur_aux = carry[
                (slice(None),)
                + tuple(
                    slice(r, carry.shape[1 + a] - r) if r else slice(None)
                    for a, r in enumerate(rad)
                )
            ]
    return out  # unreachable (n_steps >= 1); keeps type checkers happy


def fused_stencil_batched(
    f_padded: jnp.ndarray,
    ops: OperatorSet,
    phi: Callable[..., jnp.ndarray],
    aux: Aux = None,
) -> jnp.ndarray:
    """Batched (ensemble) oracle: ``vmap`` of :func:`fused_stencil`
    over a leading member axis.

    ``f_padded``: (batch, n_f, *spatial_padded); ``aux`` (if given):
    (batch, n_aux, *spatial). Returns (batch, n_out, *interior). This
    is the ground truth every batched Pallas lowering must match —
    member m of the batched kernel output is bit-tolerance-identical to
    the single-member path applied to member m alone.
    """
    if aux is None:
        return jax.vmap(lambda f: fused_stencil(f, ops, phi))(f_padded)
    return jax.vmap(
        lambda f, a: fused_stencil(f, ops, phi, aux=a)
    )(f_padded, aux)


def fused_stencil_steps_batched(
    f_padded: jnp.ndarray,
    ops: OperatorSet,
    phi,
    n_steps: int,
    aux: Aux = None,
) -> jnp.ndarray:
    """Batched sequential reference for temporal fusion: ``vmap`` of
    :func:`fused_stencil_steps` over a leading member axis (see
    :func:`fused_stencil_batched` for the operand convention)."""
    if aux is None:
        return jax.vmap(
            lambda f: fused_stencil_steps(f, ops, phi, n_steps)
        )(f_padded)
    return jax.vmap(
        lambda f, a: fused_stencil_steps(f, ops, phi, n_steps, aux=a)
    )(f_padded, aux)


def xcorr1d_numpy(f_padded: np.ndarray, g: np.ndarray) -> np.ndarray:
    """Float64 numpy oracle-of-the-oracle (used by property tests)."""
    f_padded = np.asarray(f_padded, dtype=np.float64)
    g = np.asarray(g, dtype=np.float64)
    n = f_padded.shape[0] - (g.shape[0] - 1)
    out = np.zeros(n)
    for k in range(g.shape[0]):
        out += g[k] * f_padded[k : k + n]
    return out

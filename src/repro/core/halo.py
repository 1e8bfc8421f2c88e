"""Distributed halo exchange over a device mesh (shard_map + ppermute).

The TPU-native mapping of Astaroth's MPI halo exchange (paper Sec. 4.4 /
ref. 6): each device owns a contiguous block of the computational domain;
before a stencil application it receives the ``r`` boundary planes of its
neighbors along every decomposed axis. On a torus-topology mesh axis,
``jax.lax.ppermute`` with a ring permutation is a single-hop ICI
transfer in each direction — the minimal-traffic exchange.

Overlap note (EXPERIMENTS.md §Perf): the sends depend only on edge
planes, the interior compute depends only on local data. We emit the
permutes FIRST and slice the interior compute so XLA's latency-hiding
scheduler can overlap the collective-permute with interior FLOPs. The
``interior_first`` helper structures that split explicitly.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax


def exchange_halo_1d(
    f: jnp.ndarray, radius: int, axis_name: str, *, axis: int
) -> jnp.ndarray:
    """Exchange ``radius`` planes with both ring neighbors along one
    sharded array axis. Must run inside shard_map with ``axis_name`` in
    scope. Returns the locally-padded array (local + 2·radius).

    Periodic global boundary: the ring wrap supplies the periodic image.
    """
    if radius == 0:
        return f
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    del idx  # symmetry: same program on every shard

    def take(sl):
        slicer = [slice(None)] * f.ndim
        slicer[axis] = sl
        return f[tuple(slicer)]

    right_edge = take(slice(f.shape[axis] - radius, None))  # goes right
    left_edge = take(slice(0, radius))  # goes left

    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    # What we receive from the LEFT neighbor is its right edge; it becomes
    # our left ghost zone (and vice versa).
    from_left = lax.ppermute(right_edge, axis_name, fwd)
    from_right = lax.ppermute(left_edge, axis_name, bwd)
    return jnp.concatenate([from_left, f, from_right], axis=axis)


def exchange_halos_nd(
    f: jnp.ndarray,
    radii: Sequence[int],
    mesh_axes: Sequence[str | None],
    *,
    spatial_axes: Sequence[int],
) -> jnp.ndarray:
    """Pad every spatial axis: ppermute where sharded, periodic wrap
    locally where not. Corner/edge regions become correct because the
    exchanges are applied sequentially on the already-padded faces — the
    standard dimension-by-dimension halo factorization.
    """
    if not (len(radii) == len(mesh_axes) == len(spatial_axes)):
        raise ValueError(
            f"radii ({len(radii)}), mesh_axes ({len(mesh_axes)}) and "
            f"spatial_axes ({len(spatial_axes)}) must have one entry per "
            "spatial dimension"
        )
    out = f
    for r, name, ax in zip(radii, mesh_axes, spatial_axes):
        if r == 0:
            continue
        if name is None:
            pad_width = [(0, 0)] * out.ndim
            pad_width[ax] = (r, r)
            out = jnp.pad(out, pad_width, mode="wrap")
        else:
            out = exchange_halo_1d(out, r, name, axis=ax)
    return out


def interior_first(
    f_local: jnp.ndarray,
    radii: Sequence[int],
    spatial_axes: Sequence[int],
) -> tuple[jnp.ndarray, list[tuple[int, slice]]]:
    """Split the local block into interior (computable before any halo
    arrives) and the dependent edge slabs — the compute/communication
    overlap decomposition. Returns the interior view and the edge slab
    slices (axis, slice) for the caller to schedule after the exchange.
    """
    slicer: list[slice] = [slice(None)] * f_local.ndim
    edges: list[tuple[int, slice]] = []
    for r, ax in zip(radii, spatial_axes):
        if r == 0:
            continue
        slicer[ax] = slice(r, f_local.shape[ax] - r)
        edges.append((ax, slice(0, r)))
        edges.append((ax, slice(f_local.shape[ax] - r, None)))
    return f_local[tuple(slicer)], edges

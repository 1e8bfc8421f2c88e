"""Overlapping-window BlockSpecs for the Pallas TPU lowering.

The kernels express overlapping input windows (block + halo) with
per-element block offsets (``pl.Element``). Mosaic puts two rules on
such a spec:

* every dimension is an ``Element`` (or squeezed) once any one is, so
  full-extent dimensions are spelled ``Element(extent)`` at offset 0;
* the last two block dimensions must be multiples of the (8, 128)
  sublane × lane tiling or equal the array's extent (rank-1 blocks: the
  lane tiling only).

A halo window (τ + 2r) is almost never tile-aligned, so each window
dimension among the last two is *staged* at the next tile multiple, and
``Element`` padding lets the last window overhang the array's end.
Kernel bodies read only the leading τ + 2r of the staged block (every
tap slice is an explicit offset), so the overhang is never consumed.
"""
from __future__ import annotations

from typing import Callable, Sequence

from jax.experimental import pallas as pl

from repro.kernels.plan import tile_aligned, tile_multiples


def element_window_spec(
    block_shape: Sequence[int | None],
    index_map: Callable[..., tuple],
    window_dims: Sequence[int],
) -> pl.BlockSpec:
    """BlockSpec whose ``window_dims`` take *element* offsets from the
    index map (overlapping halo windows); remaining dims span the full
    extent at offset 0 (or are squeezed with ``None``). Window dims are
    staged tile-aligned (module docstring)."""
    tiles = tile_multiples(len(block_shape))
    dims: list = []
    for d, (s, tile) in enumerate(zip(block_shape, tiles)):
        if s is None:
            dims.append(None)
        elif d in window_dims:
            staged = tile_aligned(s, tile)
            dims.append(pl.Element(staged, (0, staged - s)))
        else:
            dims.append(pl.Element(s))
    return pl.BlockSpec(tuple(dims), index_map)

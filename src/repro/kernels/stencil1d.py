"""1-D cross-correlation Pallas TPU kernel (paper Sec. 4.1, Figs. 8-9).

Reproduces the paper's hand-tuned CUDA/HIP baseline on the TPU target,
including its three tuning strategies:

* ``baseline``     — each grid step computes one output block; the
  multiply-accumulate loop over stencil points runs one tap per iteration.
* ``pointwise``    — *stencil point-wise unrolling*: the tap loop is
  unrolled by a static factor, deepening the instruction pipeline
  (paper: ``#pragma unroll`` over the MAC loop).
* ``elementwise``  — *element-wise unrolling*: each grid step computes
  ``unroll`` adjacent output sub-blocks from one (shared) tap coefficient
  load, raising ILP per coefficient fetch (paper: 4 outputs per thread).

TPU adaptation (DESIGN.md §2): the thread block becomes a VMEM output
block; the coefficient vector ``g`` lives wholly in VMEM (the constant-
memory analogue); overlapping input windows (block + 2r halo) are
expressed with ``pl.Element`` block dims and double-buffered HBM→VMEM by
the Pallas pipeline — the hardware equivalent of the paper's
shared-memory staging with prefetch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.compat import element_window_spec

STRATEGIES = ("baseline", "pointwise", "elementwise")


def _mac_loop(f_blk_ref, g_ref, n_taps: int, block: int, unroll: int,
              dtype) -> jnp.ndarray:
    """Tap loop with static unroll factor; taps beyond ``n_taps`` were
    zero-padded by the wrapper so the unrolled tail is safe."""
    n_iters = -(-n_taps // unroll)

    def body(it, acc):
        for u in range(unroll):  # static: unrolled at trace time
            k = it * unroll + u
            coeff = g_ref[k]
            acc = acc + coeff * f_blk_ref[pl.ds(k, block)]
        return acc

    acc0 = jnp.zeros((block,), dtype=dtype)
    return jax.lax.fori_loop(0, n_iters, body, acc0)


def _kernel_baseline(f_ref, g_ref, o_ref, *, n_taps, block, unroll):
    o_ref[...] = _mac_loop(f_ref, g_ref, n_taps, block, unroll, o_ref.dtype)


def _kernel_elementwise(f_ref, g_ref, o_ref, *, n_taps, block, unroll):
    """``unroll`` accumulators advance together through the tap loop,
    reusing each coefficient load (ILP across output sub-blocks)."""

    def body(k, accs):
        coeff = g_ref[k]
        return tuple(
            accs[e] + coeff * f_ref[pl.ds(k + e * block, block)]
            for e in range(unroll)
        )

    accs0 = tuple(jnp.zeros((block,), dtype=o_ref.dtype) for _ in range(unroll))
    accs = jax.lax.fori_loop(0, n_taps, body, accs0)
    for e in range(unroll):
        o_ref[pl.ds(e * block, block)] = accs[e]


def xcorr1d_pallas(
    f_padded: jnp.ndarray,
    g: jnp.ndarray,
    *,
    strategy: str = "baseline",
    block_size: int = 2048,
    unroll: int = 4,
    interpret: bool = False,
) -> jnp.ndarray:
    """f'_i = Σ_j g_j f̂_{i+j} over the valid region of ``f_padded``.

    ``f_padded``: (n + 2r,); ``g``: (2r + 1,). Requires ``block_size`` | n
    (the public wrapper in ``ops.py`` handles padding/slicing).
    """
    if strategy not in STRATEGIES:
        raise ValueError(f"strategy {strategy!r} not in {STRATEGIES}")
    n_taps = g.shape[0]
    n = f_padded.shape[0] - (n_taps - 1)
    halo = n_taps - 1

    if strategy == "elementwise":
        if (block_size % unroll) != 0:
            raise ValueError("block_size must divide by unroll for elementwise")
        sub = block_size // unroll
        kernel = functools.partial(
            _kernel_elementwise, n_taps=n_taps, block=sub, unroll=unroll
        )
        g_taps = n_taps
    else:
        u = unroll if strategy == "pointwise" else 1
        # Zero-pad taps to a multiple of the unroll factor so the unrolled
        # tail reads real memory (wrapper extended the halo to match).
        pad_taps = (-n_taps) % u
        if pad_taps:
            g = jnp.concatenate([g, jnp.zeros((pad_taps,), g.dtype)])
            halo = halo + pad_taps
            f_padded = jnp.concatenate(
                [f_padded, jnp.zeros((pad_taps,), f_padded.dtype)]
            )
        kernel = functools.partial(
            _kernel_baseline, n_taps=n_taps + pad_taps, block=block_size,
            unroll=u,
        )
        g_taps = n_taps + pad_taps

    if n % block_size:
        raise ValueError(f"block_size {block_size} must divide n {n}")
    grid = (n // block_size,)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            element_window_spec(
                (block_size + halo,),
                lambda i: (i * block_size,),
                window_dims=(0,),
            ),
            pl.BlockSpec((g_taps,), lambda i: (0,)),  # g: whole, VMEM
        ],
        out_specs=pl.BlockSpec((block_size,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), f_padded.dtype),
        interpret=interpret,
        name="stencil_1d",
    )(f_padded, g.astype(f_padded.dtype))

"""Distributed-optimization tricks: compressed + hierarchical gradient
synchronization, and microbatch gradient accumulation.

GSPMD inserts the data-parallel gradient all-reduce automatically inside
the backward pass; these utilities implement the cases where you want
MANUAL control of the wire format and topology:

* ``compressed_psum_tree`` — cast f32 grads to bf16 for the wire, psum,
  decompress: halves cross-pod DCI traffic. Error feedback (the residual
  of the cast is carried into the next step) keeps the compression
  unbiased over time.
* ``hierarchical_psum_tree`` — reduce-scatter within the pod (fast ICI),
  all-reduce the 1/N shard across pods (slow DCI), all-gather within the
  pod. Wire cost on the slow axis drops from full-gradient to 1/D.
* ``accumulate_grads`` — microbatch gradient accumulation under
  ``lax.scan`` with f32 accumulators (donated), the standard way to reach
  global batch 256×4k tokens without activation blow-up.

All operate inside ``shard_map``; tests validate vs plain psum on the
512-fake-device backend.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax


def compressed_psum_tree(
    grads: Any,
    axis_name: str,
    *,
    error_feedback: Any | None = None,
) -> tuple[Any, Any]:
    """bf16-on-the-wire psum over ``axis_name`` with error feedback.

    Returns (synced f32 grads, new error-feedback residuals).
    """

    def one(g, e):
        gf = g.astype(jnp.float32)
        if e is not None:
            gf = gf + e
        wire = gf.astype(jnp.bfloat16)
        residual = gf - wire.astype(jnp.float32)
        summed = lax.psum(wire, axis_name)
        return summed.astype(jnp.float32), residual

    if error_feedback is None:
        error_feedback = jax.tree.map(lambda g: None, grads,
                                      is_leaf=lambda x: x is None)
        out = jax.tree.map(lambda g: one(g, None), grads)
    else:
        out = jax.tree.map(one, grads, error_feedback)
    synced = jax.tree.map(lambda t: t[0], out,
                          is_leaf=lambda t: isinstance(t, tuple))
    resid = jax.tree.map(lambda t: t[1], out,
                         is_leaf=lambda t: isinstance(t, tuple))
    return synced, resid


def hierarchical_psum(
    x: jnp.ndarray, fast_axis: str, slow_axis: str
) -> jnp.ndarray:
    """reduce-scatter(fast) → all-reduce(slow) → all-gather(fast).

    Equivalent to psum over both axes; moves only 1/|fast| of the bytes
    over the slow (cross-pod) links.
    """
    n_fast = jax.lax.axis_size(fast_axis)
    orig_shape = x.shape
    pad = (-x.shape[0]) % n_fast
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0
        )
    shard = lax.psum_scatter(x, fast_axis, scatter_dimension=0, tiled=True)
    shard = lax.psum(shard, slow_axis)
    full = lax.all_gather(shard, fast_axis, axis=0, tiled=True)
    if pad:
        full = full[: orig_shape[0]]
    return full


def hierarchical_psum_tree(
    grads: Any, fast_axis: str, slow_axis: str
) -> Any:
    return jax.tree.map(
        lambda g: hierarchical_psum(g, fast_axis, slow_axis), grads
    )


def accumulate_grads(
    loss_fn: Callable,
    params: Any,
    microbatches: Any,  # pytree with leading (n_micro, ...) axes
) -> tuple[jnp.ndarray, Any]:
    """Scan microbatches, accumulating f32 grads. Returns (mean loss,
    mean grads)."""
    n = jax.tree_util.tree_leaves(microbatches)[0].shape[0]

    def body(carry, mb):
        loss_sum, acc = carry
        (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
        acc = jax.tree.map(
            lambda a, gi: a + gi.astype(jnp.float32), acc, g
        )
        return (loss_sum + loss, acc), None

    zeros = jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params
    )
    (loss_sum, acc), _ = lax.scan(
        body, (jnp.zeros((), jnp.float32), zeros), microbatches
    )
    scale = 1.0 / n
    return loss_sum * scale, jax.tree.map(lambda g: g * scale, acc)

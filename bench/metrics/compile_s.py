"""Seconds the set-up spent compiling (host clock).

The sum of JAX's own ``jaxpr_to_mlir_module`` and ``backend_compile``
durations during set-up, which cover lowering, compiling and loading a
program from the persistent compilation cache. The benchmark counts
them through ``jax.monitoring``, so the AOT ``lower().compile()`` of a
steady cell and the compiles a server triggers itself count alike.
"""
from __future__ import annotations


def read(run) -> float | None:
    return run.compile_s

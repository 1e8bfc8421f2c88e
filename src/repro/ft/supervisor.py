"""Fault-tolerant training supervision.

At thousand-node scale the failure model is: some host dies mid-step →
the job restarts (possibly on a different node count) → training must
resume from the last durable step with bit-identical data order. The
pieces here implement that contract in-process:

* ``Supervisor.run`` drives the step loop, checkpoints every
  ``ckpt_every`` steps, and on failure restores the last checkpoint and
  REPLAYS from its step — with a deterministic step function (every
  solver step here is one) the recovery is exact.
* ``SimulatedFailure`` + ``failure_at`` inject crashes for tests/examples
  (the CPU stand-in for a node loss). ``Supervisor.recoverable`` widens
  the checkpoint-restore trigger to real runtime errors (device loss,
  flaky filesystem) — ``SimulatedFailure`` is only the default.
* ``StragglerMonitor`` tracks per-step wall times; a step slower than
  ``factor ×`` the trailing median flags a straggler. On a real cluster
  the hook triggers re-layout / hot-spare swap (we log and count; the
  decision callback is pluggable).
* elastic restarts: pass a different ``restore_shardings`` after changing
  the mesh — checkpoints store unsharded leaves, so a 2-pod job can
  resume on 1 pod (degraded) and scale back later.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Callable

log = logging.getLogger("repro.ft")


class SimulatedFailure(RuntimeError):
    """Injected node failure (tests/examples)."""


@dataclasses.dataclass
class StragglerMonitor:
    factor: float = 1.5
    window: int = 20
    on_straggler: Callable[[int, float, float], None] | None = None
    _times: list = dataclasses.field(default_factory=list)
    flagged: list = dataclasses.field(default_factory=list)

    def record(self, step: int, seconds: float) -> bool:
        self._times.append(seconds)
        hist = self._times[-self.window - 1 : -1]
        if len(hist) >= 5:
            med = sorted(hist)[len(hist) // 2]
            if seconds > self.factor * med:
                self.flagged.append((step, seconds, med))
                log.warning(
                    "straggler at step %d: %.3fs vs median %.3fs",
                    step, seconds, med,
                )
                if self.on_straggler:
                    self.on_straggler(step, seconds, med)
                return True
        return False


@dataclasses.dataclass
class Supervisor:
    """Restart-on-failure driver around a step function.

    ``step_fn(state, step) -> state`` must be side-effect-free w.r.t.
    recovery (all persistent state in ``state`` + the step counter).
    """

    ckpt_manager: Any
    ckpt_every: int = 50
    max_restarts: int = 3
    straggler: StragglerMonitor = dataclasses.field(
        default_factory=StragglerMonitor
    )
    # Exception types that trigger checkpoint-restore instead of
    # propagating. The default keeps the historical behavior (only the
    # injected test failure); real deployments widen it, e.g.
    # ``(SimulatedFailure, jax.errors.JaxRuntimeError, OSError)`` so a
    # device loss or a flaky filesystem also restarts from the last
    # durable step. KeyboardInterrupt/SystemExit are never caught.
    recoverable: tuple[type[BaseException], ...] = (SimulatedFailure,)

    def run(
        self,
        state: Any,
        step_fn: Callable[[Any, int], Any],
        n_steps: int,
        *,
        start_step: int = 0,
        failure_at: int | None = None,
        restore_fn: Callable[[Any, int | None], tuple[Any, int]] | None = None,
        save_filter: Callable[[Any], Any] | None = None,
    ) -> tuple[Any, dict]:
        """Run to ``n_steps`` with checkpoint/restart. Returns
        (final_state, report). ``restore_fn(state_template, step)`` must
        rebuild device state from the checkpoint (elastic reshard hook).
        ``save_filter`` maps state → the checkpointable subtree."""
        restarts = 0
        step = start_step
        report = {"restarts": 0, "stragglers": 0, "failed_steps": []}
        injected = failure_at
        while step < n_steps:
            try:
                t0 = time.perf_counter()
                if injected is not None and step == injected:
                    injected = None  # fire once
                    raise SimulatedFailure(f"injected failure at step {step}")
                state = step_fn(state, step)
                dt = time.perf_counter() - t0
                if self.straggler.record(step, dt):
                    report["stragglers"] += 1
                step += 1
                if step % self.ckpt_every == 0 or step == n_steps:
                    to_save = save_filter(state) if save_filter else state
                    self.ckpt_manager.save(step, to_save)
            except self.recoverable as e:
                restarts += 1
                report["restarts"] = restarts
                report["failed_steps"].append(step)
                log.warning("failure at step %d: %s", step, e)
                if restarts > self.max_restarts:
                    raise RuntimeError(
                        f"exceeded max_restarts={self.max_restarts}"
                    ) from e
                if restore_fn is None:
                    raise
                self.ckpt_manager.wait()
                last = self.ckpt_manager.latest_step()
                state, step = restore_fn(state, last)
                log.warning("restored at step %d, resuming", step)
        self.ckpt_manager.wait()
        return state, report

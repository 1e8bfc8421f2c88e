"""Pieces every traffic driver shares: seeds, the compile log, the sample
reservoir, the comparison with the reference, the profiler and the
record a driver hands back to ``run.py``."""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import math
import tempfile
import time
from types import SimpleNamespace

import numpy as np

# A run's own host spans, on the profiler's clock beside the device's.
SPAN_PREFIX = "bench."


def jax_key(seed: int):
    """A JAX key from any whole number (the driver's seeds pass 2**32)."""
    import jax

    seed = int(seed) % (1 << 64)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def np_rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed) % (1 << 64))


class CompileLog:
    """Counts JAX's compile events as they happen, through
    ``jax.monitoring``: lowerings, backend compiles and the persistent
    cache's loads, with their host-clock seconds."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax

        self.events: list[tuple[str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name in (self.LOWER, self.COMPILE, self.CACHE_LOAD):
            self.events.append((name, float(secs)))

    def mark(self) -> int:
        return len(self.events)

    def seconds(self, since: int = 0, until: int | None = None) -> float:
        """Lowering plus backend-compile seconds (a cache load is timed
        inside the backend compile that it replaces)."""
        return sum(
            s for n, s in self.events[since:until]
            if n in (self.LOWER, self.COMPILE)
        )

    def counts(self, since: int = 0, until: int | None = None) -> dict:
        ev = self.events[since:until]
        compiles = sum(n == self.COMPILE for n, _ in ev)
        loads = sum(n == self.CACHE_LOAD for n, _ in ev)
        return {
            "compiles": compiles - loads,
            "cache_loads": loads,
            "lowerings": sum(n == self.LOWER for n, _ in ev),
        }


class Reservoir:
    """A uniform sample of ``k`` items from a stream of unknown length,
    drawn with ``rng`` (Algorithm R)."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.seen = k, rng, 0
        self.items: list = []

    def offer(self, item) -> None:
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.k:
                self.items[j] = item
        self.seen += 1


def rel_gap(got, ref) -> float:
    """max |got - ref| over max |ref|: the widest gap from the reference,
    on the state's own scale. NaN when ``got`` is not finite."""
    import jax.numpy as jnp

    got = jnp.asarray(got, jnp.float32)
    if not bool(jnp.all(jnp.isfinite(got))):
        return float("nan")
    err = float(jnp.max(jnp.abs(got - ref)))
    scale = float(jnp.max(jnp.abs(ref)))
    return err / scale if scale > 0 else float("inf")


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in devices
    )


@contextlib.contextmanager
def profiled(enabled: bool):
    """Trace the block when ``enabled``; the holder's ``trace`` is then
    the reduced trace (``bench.trace.Trace``), read after the block."""
    import jax

    from bench import trace as bench_trace

    holder = SimpleNamespace(trace=None)
    if not enabled:
        yield holder
        return
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the Python tracer makes traces huge
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            yield holder
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one xplane file, found {paths}")
        holder.trace = bench_trace.load(paths[0])


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


@dataclasses.dataclass
class Outcome:
    """What a driver measured in one run."""

    attempted: int
    failed: int
    e2e: dict[str, float]
    setup_s: float
    compile_s: float
    # name -> (value compared, limit); the run is correct when each
    # value is finite and at most its limit.
    checks: dict[str, tuple[float, float]]
    memory_peak_bytes: int
    spatial_rank: int
    window_steps: int
    info: dict
    trace: object | None = None
    batches: list[int] = dataclasses.field(default_factory=list)
    max_batch: int | None = None


def verdict(out: Outcome) -> bool:
    """A run is correct when every compared value is finite and at most
    its limit, and no request failed."""
    return out.failed == 0 and all(
        math.isfinite(v) and v <= lim
        for v, lim in out.checks.values()
    )


def now() -> float:
    return time.perf_counter()

"""Shot traffic: one seismic shot, the solver's jitted call of
``steps_per_call`` steps chained for the whole window, one call in
flight behind the one being waited on, as ``steady`` does.

Traffic keys: ``path`` (the configuration's solver settings),
``steps_per_call``, ``samples`` (calls of the window kept for the
check), optionally ``grid`` (else the configuration's).

The state ``(u, u_prev, t)`` crosses calls as separate arrays; the
receiver traces of every call stay on the device for the window, as
the shot gather a user keeps.

Correctness: a uniform sample of the window's calls, drawn from the
seed, keeps each call's input and output; once the window has closed,
the plain reference advances each sampled input as many steps, from
the call's own step number, and two gaps are compared: ``max_rel_gap``
over both levels and ``trace_rel_gap`` over the call's traces. The
solver's counters (steps, kernel launches, source injections, receiver
samples written) must match the steps the window issued:
``counter_mismatch`` counts those that do not.
"""
from __future__ import annotations

import math


def _grid(cell):
    return tuple(cell.traffic.get("grid") or cell.config["grid"])


def initial_levels(cell, grid, seed: int):
    """(u, u_prev), each a (1, *grid) stack, drawn on the device from
    ``seed`` at the configured amplitude."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from bench.harness import jax_key

    amp = float(cell.config["init"]["amplitude"])
    shape = (1,) + tuple(grid)

    def draw(k):
        k1, k2 = jax.random.split(k)
        return tuple(
            jax.random.uniform(kk, shape, jnp.float32, -amp, amp)
            for kk in (k1, k2)
        )

    one = SingleDeviceSharding(cell.devices[0])
    return jax.jit(draw, out_shardings=(one, one))(jax_key(seed))


def _reference_call(cell, grid, steps, dtype=None):
    import jax
    import jax.numpy as jnp

    ref = cell.reference()
    dtype = jnp.float32 if dtype is None else dtype
    return jax.jit(
        lambda f, v, t0: ref.advance_traces(
            cell.config, grid, f, steps, v, t0, dtype=dtype
        )
    )


def _start(cell, grid, seed: int):
    """The seed's velocity model and the seed's levels, stacked
    (2, *grid), after one call of the reference, with the step number
    the next call starts at."""
    import jax
    import jax.numpy as jnp

    ref = cell.reference()
    steps = int(cell.traffic["steps_per_call"])
    f = jnp.concatenate(initial_levels(cell, grid, seed))
    velocity, t0 = jax.jit(
        lambda: ref.inputs(cell.config, grid, None, seed=seed)
    )()
    f, _ = _reference_call(cell, grid, steps)(f, velocity, t0)
    return f, velocity, jnp.int32(int(t0) + steps)


def control_inputs(cell, seed: int) -> list:
    """(grid, steps, extra inputs, state) as the window would hand the
    program a call: the seed's shot advanced one call by the
    reference, for ``bench/control.py``."""
    grid = _grid(cell)
    f, velocity, t = _start(cell, grid, seed)
    return [(grid, int(cell.traffic["steps_per_call"]), (velocity, t), f)]


def control_trace_gaps(cell, seed: int) -> list[float]:
    """The bfloat16 reference's trace gap from the float32 one, for the
    input ``control_inputs`` gives: the control of ``trace_rel_gap``."""
    import jax.numpy as jnp

    from bench import harness

    grid = _grid(cell)
    steps = int(cell.traffic["steps_per_call"])
    f, velocity, t = _start(cell, grid, seed)
    _, want = _reference_call(cell, grid, steps)(f, velocity, t)
    _, low = _reference_call(cell, grid, steps, jnp.bfloat16)(f, velocity, t)
    return [harness.rel_gap(low, want)]


def run(cell, h) -> "h.Outcome":
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    cfg, tr = cell.config, cell.traffic
    system, ref = cell.system(), cell.reference()
    grid = _grid(cell)
    steps = int(tr["steps_per_call"])

    # -- set-up: model and levels from the seed on the device, compile,
    # warm ----------------------------------------------------------------
    one = SingleDeviceSharding(cell.devices[0])
    u, um = initial_levels(cell, grid, cell.seed)
    velocity, t = jax.jit(
        lambda: ref.inputs(cfg, grid, None, seed=cell.seed),
        out_shardings=one,
    )()
    sol = system.solver(cfg, tr["path"], grid, velocity)
    # Two warm calls: the second takes the first's outputs, exactly as
    # every call of the window does, so the window dispatches a program
    # that is already compiled for its arguments.
    x = sol.run(*sol.run(u, um, t, steps)[:3], steps)
    jax.block_until_ready(x)
    del u, um
    setup_s = h.now() - cell.t_start
    setup_mark = cell.compile_log.mark()

    # -- window -----------------------------------------------------------
    sample = h.Reservoir(int(tr["samples"]), h.np_rng(cell.seed))
    gather = []
    calls = 0
    with h.profiled(cell.trace) as prof:
        with h.span("window"):
            w_mark = cell.compile_log.mark()
            counts0 = dict(sol.counts)
            t_first = x[2]
            t0 = h.now()
            nxt = sol.run(*x[:3], steps)
            while True:
                cur = nxt
                with h.span("call"):
                    nxt = sol.run(*cur[:3], steps)  # queued behind ``cur``
                    jax.block_until_ready(cur)
                calls += 1
                gather.append(cur[3])
                sample.offer((x, cur))
                x = cur
                t1 = h.now()
                if t1 - t0 >= cell.seconds:
                    break
            w_end = cell.compile_log.mark()
        jax.block_until_ready(nxt)
    window_s = t1 - t0
    mem = h.peak_bytes(cell.devices[:1])

    # The window issued calls + 1 calls (the last still in flight when
    # the clock stopped); the device's own step counter says how many
    # steps the completed ones advanced.
    issued = (calls + 1) * steps
    want = {
        "steps": issued,
        "launches": issued,
        "injections": issued,
        "receiver_samples": issued * math.prod(gather[0].shape[1:]),
    }
    counts = {k: sol.counts[k] - counts0[k] for k in want}
    advanced = int(x[2]) - int(t_first)
    mismatch = sum(counts[k] != want[k] for k in want) + (
        advanced != calls * steps
    )
    del nxt, x, cur, gather, sol

    # -- correctness, once the window has closed --------------------------
    ref_exe = _reference_call(cell, grid, steps)
    gaps, trace_gaps = [], []
    while sample.items:
        (uin, umin, tin, _), (uout, umout, _, trout) = sample.items.pop()
        levels, traces = ref_exe(jnp.concatenate([uin, umin]), velocity, tin)
        del uin, umin
        gaps.append(h.rel_gap(jnp.concatenate([uout, umout]), levels))
        trace_gaps.append(h.rel_gap(trout, traces))
        del uout, umout, levels

    points = math.prod(grid) * steps * calls
    return h.Outcome(
        attempted=calls,
        failed=0,
        e2e={"point_updates_per_s": points / window_s / 1e9},
        setup_s=setup_s,
        compile_s=cell.compile_log.seconds(0, setup_mark),
        checks={
            "max_rel_gap": (max(gaps), cell.limits["max_rel_gap"]),
            "trace_rel_gap": (max(trace_gaps), cell.limits["trace_rel_gap"]),
            "counter_mismatch": (float(mismatch), 0.0),
        },
        memory_peak_bytes=mem,
        spatial_rank=len(grid),
        window_steps=calls * steps,
        info={
            "window_s": window_s,
            "calls": calls,
            "steps": calls * steps,
            "ms_per_call": 1e3 * window_s / calls,
            "sampled_gaps": gaps,
            "sampled_trace_gaps": trace_gaps,
            "counters": counts,
            "counters_expected": want,
            "device_steps_advanced": advanced,
            "window_compile_events": cell.compile_log.counts(w_mark, w_end),
        },
        trace=prof.trace,
    )

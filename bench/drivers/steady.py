"""Steady traffic: one domain, the jitted program call chained for the
whole window, one call in flight behind the one being waited on.

Traffic keys: ``path`` (the configuration's solver settings to use),
``steps_per_call``, ``samples`` (calls of the window kept for the check),
optionally ``grid`` (else the configuration's) and ``shard`` (``axis``,
``devices``: the grid split over a one-axis mesh, the program run under
``jax.shard_map``).

Correctness: a uniform sample of the window's calls, drawn from the
seed, keeps each call's input and output; once the window has closed,
the configuration's plain reference advances each sampled input as many
steps, and the widest gap, on the state's scale, is compared.
"""
from __future__ import annotations

import math

AXES = ("z", "y", "x")


def layout(cell, grid):
    """(sharding of the state, devices used, mesh axis per spatial axis,
    partition spec) for the traffic's ``shard`` key, or one chip."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    shard = cell.traffic.get("shard")
    if not shard:
        return SingleDeviceSharding(cell.devices[0]), cell.devices[:1], None, None
    n = int(shard["devices"])
    axes = AXES[-len(grid):]
    mesh_axes = tuple(shard["axis"] if a == shard["axis"] else None for a in axes)
    # Auto axes: the reference's slices of the global array are
    # partitioned by XLA, with its own collectives.
    mesh = jax.make_mesh(
        (n,), (shard["axis"],), devices=cell.devices[:n],
        axis_types=(jax.sharding.AxisType.Auto,),
    )
    spec = P(None, *mesh_axes)
    return NamedSharding(mesh, spec), cell.devices[:n], mesh_axes, spec


def initial_state(cell, grid, sharding, seed: int):
    """The state drawn on the device from ``seed``, at the configured
    amplitude, laid out by ``sharding``."""
    import jax
    import jax.numpy as jnp

    from bench.harness import jax_key

    cfg = cell.config
    amp = float(cfg["init"]["amplitude"])
    shape = (cfg["fields"],) + tuple(grid)
    return jax.jit(
        lambda k: jax.random.uniform(k, shape, jnp.float32, -amp, amp),
        out_shardings=sharding,
    )(jax_key(seed))


def control_inputs(cell, seed: int) -> list:
    """(grid, steps, extra inputs, state) as the window would hand the
    program a call: the seed's state advanced one call by the
    reference, for ``bench/control.py``."""
    import jax

    cfg, tr = cell.config, cell.traffic
    ref = cell.reference()
    grid = tuple(tr.get("grid") or cfg["grid"])
    steps = int(tr["steps_per_call"])
    sharding = layout(cell, grid)[0]
    f = initial_state(cell, grid, sharding, seed)
    extra = jax.jit(lambda x: ref.inputs(cfg, grid, x))(f)
    f = jax.jit(
        lambda x, *e: ref.advance(cfg, grid, x, steps, *e),
        out_shardings=sharding,
    )(f, *extra)
    return [(grid, steps, extra, f)]


def run(cell, h) -> "h.Outcome":
    import jax

    cfg, tr = cell.config, cell.traffic
    system, ref = cell.system(), cell.reference()
    grid = tuple(tr.get("grid") or cfg["grid"])
    steps = int(tr["steps_per_call"])
    shard = tr.get("shard")
    sharding, used, mesh_axes, spec = layout(cell, grid)
    if shard:
        fn = jax.shard_map(
            system.sharded_program(cfg, tr["path"], grid, steps, mesh_axes),
            mesh=sharding.mesh, in_specs=spec, out_specs=spec, check_vma=False,
        )
    else:
        fn = system.program(cfg, tr["path"], grid, steps)

    # -- set-up: state from the seed on the device, compile, warm --------
    f0 = initial_state(cell, grid, sharding, cell.seed)
    extra = jax.jit(lambda f: ref.inputs(cfg, grid, f))(f0)
    exe = jax.jit(fn).lower(f0, *extra).compile()
    x = exe(f0, *extra)
    x.block_until_ready()
    del f0
    setup_s = h.now() - cell.t_start
    setup_mark = cell.compile_log.mark()

    # -- window -----------------------------------------------------------
    sample = h.Reservoir(int(tr["samples"]), h.np_rng(cell.seed))
    calls = 0
    with h.profiled(cell.trace) as prof:
        with h.span("window"):
            w_mark = cell.compile_log.mark()
            t0 = h.now()
            nxt = exe(x, *extra)
            while True:
                cur = nxt
                with h.span("call"):
                    nxt = exe(cur, *extra)  # queued behind ``cur``
                    cur.block_until_ready()
                calls += 1
                sample.offer((x, cur))
                x = cur
                t1 = h.now()
                if t1 - t0 >= cell.seconds:
                    break
            w_end = cell.compile_log.mark()
        nxt.block_until_ready()
    del nxt, x, cur
    window_s = t1 - t0
    mem = h.peak_bytes(used)

    # -- correctness, once the window has closed --------------------------
    ref_exe = jax.jit(
        lambda f, *e: ref.advance(cfg, grid, f, steps, *e),
        out_shardings=sharding,
    )
    gaps = []
    while sample.items:
        xin, xout = sample.items.pop()
        gaps.append(h.rel_gap(xout, ref_exe(xin, *extra)))
        del xin, xout
    gap = max(gaps)

    points = math.prod(grid) * steps * calls
    return h.Outcome(
        attempted=calls,
        failed=0,
        e2e={"point_updates_per_s": points / window_s / 1e9},
        setup_s=setup_s,
        compile_s=cell.compile_log.seconds(0, setup_mark),
        checks={"max_rel_gap": (gap, cell.limits["max_rel_gap"])},
        memory_peak_bytes=mem,
        spatial_rank=len(grid),
        window_steps=calls * steps,
        info={
            "window_s": window_s,
            "calls": calls,
            "steps": calls * steps,
            "ms_per_call": 1e3 * window_s / calls,
            "sampled_gaps": gaps,
            "window_compile_events": cell.compile_log.counts(w_mark, w_end),
        },
        trace=prof.trace,
    )

"""Closed-loop serving traffic: a fixed set of clients, each waiting for
its result before it sends the next request.

Traffic keys: ``path`` (the configuration's server settings),
``clients`` (groups of ``count`` clients sending members of ``grid``),
``steps_per_request``, ``samples_per_grid`` (requests of each grid kept
for the check).

Every round, each client submits one fresh member, drawn on the device
from (seed, round, client), in an order drawn from the seed; the
harness pushes them into a ``RequestQueue`` and calls ``SimServer.serve``,
which hands every result back on the host when the queue is drained. A
request's latency runs from the start of its round's submission to that
hand-back. The same sizes are served on every seed; only the member
values and the submission order change.

Correctness: a uniform sample of the window's completed requests, per
grid, drawn from the seed; once the window has closed the plain
reference advances each sampled member's input, and the widest gap on
the member's scale is compared.
"""
from __future__ import annotations

import math

import numpy as np


def member_fn(cfg: dict, grid):
    """``fn(key, round, client)``: one member of ``grid`` drawn on the
    device at the configured amplitude."""
    import jax
    import jax.numpy as jnp

    amp = float(cfg["init"]["amplitude"])
    shape = (cfg["fields"],) + tuple(grid)
    return jax.jit(
        lambda k, r, c: jax.random.uniform(
            jax.random.fold_in(jax.random.fold_in(k, r), c),
            shape, jnp.float32, -amp, amp,
        )
    )


def control_inputs(cell, seed: int) -> list:
    """(grid, steps, extra inputs, member) for one client of each grid
    in the first round of the window, for ``bench/control.py``."""
    from bench.harness import jax_key

    tr = cell.traffic
    out, c = [], 0
    for g in tr["clients"]:
        grid = tuple(g["grid"])
        f = member_fn(cell.config, grid)(jax_key(seed), 1, c)
        out.append((grid, int(tr["steps_per_request"]), (), f))
        c += g["count"]
    return out


def run(cell, h) -> "h.Outcome":
    import jax

    cfg, tr = cell.config, cell.traffic
    system, ref = cell.system(), cell.reference()
    steps = int(tr["steps_per_request"])
    clients = [
        tuple(g["grid"]) for g in tr["clients"] for _ in range(g["count"])
    ]
    grids = sorted(set(clients))
    key = h.jax_key(cell.seed)
    rng = h.np_rng(cell.seed)
    server = system.server(cfg, tr["path"])

    make = {g: member_fn(cfg, g) for g in grids}

    def one_round(r: int):
        """Submit every client's request, serve the queue; returns
        (t0, t1, {rid: (grid, f0)}, results, batch reports)."""
        t0 = h.now()
        queue = system.request_queue()
        sent = {}
        for c in rng.permutation(len(clients)):
            rid = r * len(clients) + int(c)
            f0 = make[clients[c]](key, r, int(c))
            sent[rid] = (clients[c], f0)
            queue.push(system.request(rid, f0, steps))
        first = len(server.reports)
        results = server.serve(queue)
        return t0, h.now(), sent, results, server.reports[first:]

    # -- set-up: one round warms every batch shape the window serves -----
    one_round(0)
    setup_s = h.now() - cell.t_start
    setup_mark = cell.compile_log.mark()

    # -- window -----------------------------------------------------------
    samples = {
        g: h.Reservoir(int(tr["samples_per_grid"]), rng) for g in grids
    }
    latencies, batches = [], []
    attempted = failed = points = 0
    r = 0
    with h.profiled(cell.trace) as prof:
        with h.span("window"):
            w_mark = cell.compile_log.mark()
            start = h.now()
            while True:
                r += 1
                with h.span("round"):
                    t0, t1, sent, results, reports = one_round(r)
                batches += [rep.batch for rep in reports]
                for rid, (grid, f0) in sent.items():
                    attempted += 1
                    ok = rid in results and (
                        server.request_status.get(rid, "ok") == "ok"
                    )
                    if not ok:
                        failed += 1
                        continue
                    latencies.append(t1 - t0)
                    points += math.prod(grid) * steps
                    samples[grid].offer((f0, results[rid]))
                end = h.now()
                if end - start >= cell.seconds:
                    break
            w_end = cell.compile_log.mark()
    window_s = end - start
    mem = h.peak_bytes(cell.devices[:1])
    del results, sent

    # -- correctness, once the window has closed --------------------------
    gaps = []
    for grid in grids:
        ref_exe = jax.jit(lambda f, g=grid: ref.advance(cfg, g, f, steps))
        for f0, got in samples[grid].items:
            gaps.append(h.rel_gap(got, ref_exe(f0)))
    gap = max(gaps) if gaps else float("nan")

    lat_ms = 1e3 * np.asarray(latencies)
    return h.Outcome(
        attempted=attempted,
        failed=failed,
        e2e={
            "member_updates_per_s": points / window_s / 1e9,
            "request_p95_ms": float(np.percentile(lat_ms, 95)),
        },
        setup_s=setup_s,
        compile_s=cell.compile_log.seconds(0, setup_mark),
        checks={"max_rel_gap": (gap, cell.limits["max_rel_gap"])},
        memory_peak_bytes=mem,
        spatial_rank=len(grids[0]),
        window_steps=r * steps,
        info={
            "window_s": window_s,
            "rounds": r,
            "requests": len(latencies),
            "request_p50_ms": float(np.percentile(lat_ms, 50)),
            "batches": len(batches),
            "sampled_gaps": gaps,
            "window_compile_events": cell.compile_log.counts(w_mark, w_end),
        },
        trace=prof.trace,
        batches=batches,
        max_batch=server.max_batch,
    )

"""The program's diffusion paths, as a configuration of ``system:
diffusion`` runs them: jitted ``integrate``, ``SimServer`` and the
sharded ``apply_sharded``."""
from __future__ import annotations


def _op(config: dict, path: str, grid):
    from repro.physics.diffusion import DiffusionProblem

    num, s = config["numerics"], config["paths"][path]
    problem = DiffusionProblem(
        tuple(grid), accuracy=num["order"], alpha=num["alpha"],
        safety=num["dt_safety"],
    )
    block = None if s["block"] is None else tuple(s["block"])
    return problem.step_op(s["strategy"], block, s["fuse_steps"])


def program(config: dict, path: str, grid, steps: int):
    """``fn(f)``: ``integrate(op, f, steps)``."""
    from repro.core.fusion import integrate

    op = _op(config, path, grid)
    return lambda f: integrate(op, f, steps)


def sharded_program(config: dict, path: str, grid, steps: int, mesh_axes):
    """``fn(f_local)`` for ``shard_map``: ``steps`` steps of
    ``apply_sharded`` at the configured depth; ``grid`` is the global
    grid, which fixes the spacing and dt."""
    op = _op(config, path, grid)
    depth = int(op.fuse_steps)
    if steps % depth:
        raise ValueError(f"steps {steps} is not a multiple of depth {depth}")
    overlap = config["paths"][path]["overlap"]

    def fn(f_local):
        for _ in range(steps // depth):
            f_local = op.apply_sharded(f_local, mesh_axes, overlap=overlap)
        return f_local

    return fn


def server(config: dict, path: str):
    from repro.launch.serve_sim import SimServer

    num, s = config["numerics"], config["paths"][path]
    if num["dt_safety"] != 0.2 or s["fuse_steps"] != 1:
        raise ValueError(
            "SimServer integrates one step a launch at the default dt "
            "safety 0.2"
        )
    block = None if s["block"] is None else tuple(s["block"])
    return SimServer(
        strategy=s["strategy"], block=block, accuracy=num["order"],
        alpha=num["alpha"], max_batch=s["max_batch"],
    )


def request(req_id: int, f0, steps: int):
    from repro.launch.serve_sim import SimRequest

    return SimRequest(req_id, f0, steps)


def request_queue():
    from repro.launch.serve_sim import RequestQueue

    return RequestQueue()

"""The program's MHD solver, as a configuration of ``system: mhd`` runs it."""
from __future__ import annotations


def _solver(config: dict, path: str, grid):
    from repro.physics.mhd import MHDParams, MHDSolver

    num, s = config["numerics"], config["paths"][path]
    return MHDSolver(
        tuple(grid),
        params=MHDParams(**num["params"]),
        accuracy=num["order"],
        strategy=s["strategy"],
        block=tuple(s["block"]),
        fuse_rk_pairs=s["fuse_rk_pairs"],
    )


def program(config: dict, path: str, grid, steps: int):
    """``fn(f, dt)``: ``steps`` chained ``MHDSolver.step`` calls."""
    solver = _solver(config, path, grid)

    def fn(f, dt):
        for _ in range(steps):
            f = solver.step(f, dt)
        return f

    return fn

"""The program's acoustic solver, as a configuration of ``system:
acoustic`` runs it: ``AcousticSolver.run``, one jitted scan a call."""
from __future__ import annotations


def problem(config: dict, grid):
    from repro.physics.acoustic import AcousticProblem

    num = config["numerics"]
    src, rec = num["source"], num["receivers"]
    return AcousticProblem(
        tuple(grid),
        spacing=num["spacing"],
        accuracy=num["order"],
        layer=num["absorbing"]["layer"],
        reflection=num["absorbing"]["reflection"],
        cfl=num["cfl"],
        peak_frequency=src["peak_frequency"],
        source=tuple(src["point"]),
        source_amplitude=src["amplitude"],
        source_period=src["period_steps"],
        receiver_depth=rec["depth"],
        receiver_stride=rec["stride"],
    )


def solver(config: dict, path: str, grid, velocity):
    """The solver for ``velocity``; ``solver.run(u, um, t, steps)`` is
    the program's call, ``solver.counts`` its counters."""
    from repro.physics.acoustic import AcousticSolver

    s = config["paths"][path]
    if s["fuse_steps"] != 1:
        raise ValueError("the acoustic solver runs one step a launch")
    block = None if s["block"] is None else tuple(s["block"])
    return AcousticSolver(
        problem(config, grid), velocity, strategy=s["strategy"], block=block
    )

"""Seconds a step of the engine's batched Krylov solve: the program's
``engine.solve`` span, which ends when the answers reach the host, over the
traced window."""

from chipbench.loops.timestep import per_step


def read(rec):
    return per_step(rec, "engine.solve")

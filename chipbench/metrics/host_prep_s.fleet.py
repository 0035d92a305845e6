"""Host seconds a step of the engine's batch preparation: the program's
``engine.prep`` span (keying, dominance, padding, the right-hand sides'
stack) over the traced window."""

from chipbench.loops.timestep import per_step


def read(rec):
    return per_step(rec, "engine.prep")

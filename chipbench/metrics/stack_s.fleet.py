"""Host seconds a step of assembling the batch's factorizations: the
program's ``engine.stack`` span (fresh factorizations sliced into the cache,
the batch's stacked), over the traced window."""

from chipbench.loops.timestep import per_step


def read(rec):
    return per_step(rec, "engine.stack")

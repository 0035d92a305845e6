"""Seconds a step of the engine's batched factor of the cache misses: the
program's ``engine.factor`` span, which ends in ``block_until_ready``, over
the traced window."""

from chipbench.loops.timestep import per_step


def read(rec):
    return per_step(rec, "engine.factor")

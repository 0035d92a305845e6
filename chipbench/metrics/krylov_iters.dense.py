"""Mean Krylov iterations per answer (``SaPSolveResult.iterations``, a
program counter; BiCGStab(2) counts quarter iterations)."""


def read(rec):
    steps = rec.get("steps")
    if not steps:
        return None
    return sum(s["iterations"] for s in steps) / len(steps)

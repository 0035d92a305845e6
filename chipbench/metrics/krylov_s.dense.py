"""Mean wall seconds of ``solve_many`` per step, from the benchmark's own
timestamps around the call, which ends in ``block_until_ready``."""


def read(rec):
    steps = rec.get("steps")
    if not steps:
        return None
    return sum(s["t_solve"] for s in steps) / len(steps)

"""Mean wall seconds of ``factor`` per step (plan included), from the
benchmark's own timestamps around the call, which ends in
``block_until_ready``."""


def read(rec):
    steps = rec.get("steps")
    if not steps:
        return None
    return sum(s["t_factor"] for s in steps) / len(steps)

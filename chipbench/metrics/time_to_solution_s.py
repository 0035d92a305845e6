"""Seconds per step of a closed loop: the window's seconds over the steps
completed in it, a step being all R answers for one matrix (host clock)."""


def read(rec):
    steps = rec.get("steps")
    return rec["window_s"] / len(steps) if steps else None

"""Idle share of the device over the traced window of a closed loop, in
percent: 100 * (1 - busy / window), busy being the union of the device's
operations in the profiler's trace."""


def read(rec):
    t = rec.get("trace")
    if not t or not rec.get("steps"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])

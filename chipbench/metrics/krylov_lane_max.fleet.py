"""Iterations of each batch's slowest lane, mean over the window's batches:
the engine's ``krylov_lane_max_total`` counter, by difference over the
window.  The vmapped Krylov loop runs every lane this long."""


def read(rec):
    e = rec.get("engine") or {}
    if not e.get("steps") or "krylov_lane_max_total" not in e:
        return None
    return e["krylov_lane_max_total"] / e["steps"]

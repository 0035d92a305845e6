"""Share of the window's requests served from the engine's factorization
cache, in percent: the engine's ``cache_hits`` and ``cache_misses``
counters, by difference over the window.  The time-stepping schedule fixes
it (48 of 64 a step: 75.0)."""


def read(rec):
    e = rec.get("engine") or {}
    total = e.get("cache_hits", 0) + e.get("cache_misses", 0)
    return 100.0 * e["cache_hits"] / total if total else None

"""Share of its roofline that the engine's batched factor reaches, in
percent: the least time of the window's factored systems
(``work.factor_stage`` each, ``peaks.json``) over the device's busy time
inside the program's ``engine.factor`` spans, which end in
``block_until_ready``."""

from chipbench import work


def read(rec):
    spans = (rec.get("program") or {}).get("spans", {})
    factored = (rec.get("engine") or {}).get("factored_systems")
    busy = spans.get("sap.engine.factor", {}).get("device_s")
    if not factored or not busy:
        return None
    cfg = rec["config"]
    one = work.factor_stage(cfg["n"], cfg["k"], cfg["p"], cfg["variant"])
    least, _bound = work.least_time({w: v * factored for w, v in one.items()},
                                    work.peaks(rec["device"]["kind"]))
    return 100.0 * least / busy

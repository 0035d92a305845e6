"""Share of its roofline that the factor stage reaches, in percent.

The least time is the larger of the stage's least flops over the float32
(matmul precision HIGHEST) peak and its least bytes over HBM bandwidth
(``work.factor_stage``, ``peaks.json``); the stage's time is the device's
busy time inside the benchmark's ``bench.factor`` spans of the trace.
"""

from chipbench import work


def read(rec):
    t = rec.get("trace")
    if not t or not t["span_count"].get("factor") or not t["device_s_in_span"].get("factor"):
        return None
    cfg = rec["config"]
    least, _bound = work.least_time(
        work.factor_stage(cfg["n"], cfg["k"], cfg["p"], cfg["variant"]),
        work.peaks(rec["device"]["kind"]))
    return 100.0 * least * t["span_count"]["factor"] / t["device_s_in_span"]["factor"]

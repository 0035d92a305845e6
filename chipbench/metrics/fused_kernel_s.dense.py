"""Device seconds per step of the fused factor kernel, ``pallas_call`` name
``sap_fused_factor_spike`` (``kernels/fused_spike.py``): its operations in
the trace's ranking of device time by operation (every program and HLO
instance of it that the ranking lists), over the benchmark's ``bench.factor``
spans, one a step.  The kernel is the step's largest operation, so the
ranking, which keeps the ten largest, holds it."""

from chipbench.program_trace import kernel_name

KERNEL = "sap_fused_factor_spike"


def read(rec):
    t = rec.get("trace")
    if not t or not t["span_count"].get("factor"):
        return None
    seconds = [s for name, s in t["device_ops"] if kernel_name(name) == KERNEL]
    if not seconds:
        return None
    return sum(seconds) / t["span_count"]["factor"]

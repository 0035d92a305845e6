"""Device seconds a step of the fused factor kernel, ``pallas_call`` name
``sap_fused_factor_spike`` (``kernels/fused_spike.py``): every operation of
that name, its HLO ``.N`` suffix stripped, in the program reduction of the
traced window, per step."""

KERNEL = "sap_fused_factor_spike"


def read(rec):
    kernels = (rec.get("program") or {}).get("kernels", {})
    if not rec.get("steps") or KERNEL not in kernels:
        return None
    return kernels[KERNEL] / len(rec["steps"])

"""Roofline analysis from compiled XLA artifacts (no hardware needed).

Three terms per (shape x mesh) cell, all in seconds:

  compute    = HLO_FLOPs_per_device   / peak_FLOPs_per_chip
  memory     = HLO_bytes_per_device   / HBM_bandwidth_per_chip
  collective = collective_bytes_per_device / ICI_link_bandwidth

FLOPs/bytes come from ``compiled.cost_analysis()`` (the SPMD-partitioned
per-device module).  Collective bytes are *not* in cost_analysis: we parse
the post-optimization HLO text, build a symbol table of instruction
result sizes, and sum operand sizes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute.

Hardware constants: TPU v5e -- 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI (values given by the assignment).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict

PEAK_FLOPS = 197e12  # bf16 per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Peak rates of one chip, the two roofline ceilings.

    ``peak_flops`` and ``hbm_bw`` bound the compute and memory terms of a
    stage's roofline time (``max(flops / peak_flops, bytes / hbm_bw)``).
    The tpu/gpu defaults below are datasheet numbers; the cpu entry is
    *measured* on the repo's benchmark runner class by
    :mod:`repro.launch.calibrate` (a jitted gemm / stream micro-bench).
    Deployments on different hardware override via ``REPRO_PEAK_FLOPS`` /
    ``REPRO_HBM_BW``, or set ``REPRO_CALIBRATE=1`` to have
    :func:`repro.obs.cost.hardware_spec` run the calibration itself once
    per process.
    """

    name: str
    peak_flops: float  # flops/s
    hbm_bw: float  # bytes/s


BACKEND_SPECS = {
    # TPU v5e: the assignment's numbers (same constants as the module
    # globals the dry-run roofline uses).
    "tpu": HardwareSpec("tpu-v5e", PEAK_FLOPS, HBM_BW),
    # A100-40GB-class: 19.5 TF/s f32 tensor, 1.55 TB/s HBM2e.
    "gpu": HardwareSpec("gpu-a100", 19.5e12, 1.555e12),
    # Measured on the single-core CI runner class this repo benches on,
    # via ``python -m repro.launch.calibrate`` (median of repeated jitted
    # 1024^2 f32 gemm / 256 MiB stream passes): ~125 GFLOP/s, ~4.5 GB/s.
    # The old nominal entry guessed the bandwidth ~10x too high (50 GB/s
    # is a many-channel server socket, not one pinned core).  Re-measure
    # with the same command when the runner class changes, or override
    # per-machine via REPRO_PEAK_FLOPS / REPRO_HBM_BW / REPRO_CALIBRATE=1
    # (see repro.obs.cost.hardware_spec).
    "cpu": HardwareSpec("cpu-calibrated", 1.25e11, 4.5e9),
}


def backend_spec(backend: str) -> HardwareSpec:
    """Per-backend peak rates (falls back to the cpu placeholder)."""
    return BACKEND_SPECS.get(backend, BACKEND_SPECS["cpu"])


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^=]*?\)|\S+)\s+([\w\-]+)\("
)


def _type_bytes(type_str: str) -> int:
    """Bytes of an HLO type string; tuples summed."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_WHILE_RE = re.compile(r"condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_CALLED_RE = re.compile(r"(?:to_apply|calls)=%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_INT_CONST_RE = re.compile(r"[su]\d+\[\]\s+constant\((\d+)\)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')


def _parse_computations(hlo_text: str):
    """Split HLO text into computations; returns ({name: [lines]}, entry)."""
    comps: Dict[str, list] = {}
    current = None
    entry = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and (
            line.startswith("%") or line.startswith("ENTRY")
        ):
            is_entry = line.startswith("ENTRY")
            tok = line.split()[1] if is_entry else line.split("(")[0].strip()
            name = tok.split("(")[0].strip().lstrip("%").rstrip()
            current = name
            comps[current] = []
            if is_entry:
                entry = current
            continue
        if current is not None:
            if line.strip() == "}":
                current = None
            else:
                comps[current].append(line)
    return comps, entry


def _trip_count(line: str, cond_lines) -> int:
    """Trip count of a while: prefer XLA's known_trip_count backend config,
    fall back to the largest integer constant in the loop condition
    (scans compare the counter against the length)."""
    m = _TRIP_RE.search(line)
    if m:
        return int(m.group(1))
    best = 1
    for ln in cond_lines:
        for mm in _INT_CONST_RE.finditer(ln):
            best = max(best, int(mm.group(1)))
    return best


def _collective_bytes_in(lines, sizes) -> Dict[str, dict]:
    stats: Dict[str, dict] = {}
    for line in lines:
        m = _DEF_RE.match(line)
        if not m:
            continue
        _, _, opcode = m.groups()
        base = None
        for c in _COLLECTIVES:
            if opcode == c or opcode == c + "-start":
                base = c
                break
        if base is None:
            continue
        idx = line.find(opcode + "(")
        args = line[idx + len(opcode) + 1 :]
        depth, end = 1, 0
        for end, ch in enumerate(args):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
        args = args[:end]
        op_bytes = 0
        for piece in _split_top(args):
            piece = piece.strip()
            tb = _type_bytes(piece)
            if tb:
                op_bytes += tb
            else:
                ref = piece.lstrip("%").split(" ")[-1].lstrip("%")
                op_bytes += sizes.get(ref, 0)
        ent = stats.setdefault(base, {"bytes": 0, "count": 0})
        ent["bytes"] += op_bytes
        ent["count"] += 1
    return stats


def collective_bytes(hlo_text: str) -> Dict[str, dict]:
    """Collective operand bytes from post-optimization HLO text.

    Loop-aware: collectives inside ``while`` bodies (scanned layers!) are
    multiplied by the loop trip count, propagated through nested loops and
    called computations -- a static parse would undercount a scanned
    24-layer model by 24x.
    """
    comps, entry = _parse_computations(hlo_text)
    # symbol table of result sizes across all computations (names unique)
    sizes: Dict[str, int] = {}
    for lines in comps.values():
        for line in lines:
            m = _DEF_RE.match(line)
            if m:
                sizes[m.group(1)] = _type_bytes(m.group(2))

    # multiplier propagation over the call graph
    mult: Dict[str, float] = {c: 0.0 for c in comps}
    if entry is None:  # fallback: flat scan
        return _collective_bytes_in(hlo_text.splitlines(), sizes)
    mult[entry] = 1.0
    order = [entry]
    seen = {entry}
    while order:
        cur = order.pop(0)
        for line in comps.get(cur, ()):
            wm = _WHILE_RE.search(line)
            if wm:
                cond, body = wm.groups()
                trips = _trip_count(line, comps.get(cond, ()))
                if body in comps:
                    mult[body] = mult.get(body, 0.0) + mult[cur] * trips
                    if body not in seen:
                        seen.add(body)
                        order.append(body)
                continue
            bm = _BRANCHES_RE.search(line)
            if bm:
                for b in bm.group(1).split(","):
                    b = b.strip().lstrip("%")
                    if b in comps:
                        mult[b] = mult.get(b, 0.0) + mult[cur]
                        if b not in seen:
                            seen.add(b)
                            order.append(b)
                continue
            cm = _CALLED_RE.search(line)
            if cm and "fusion(" not in line:
                callee = cm.group(1)
                if callee in comps:
                    mult[callee] = mult.get(callee, 0.0) + mult[cur]
                    if callee not in seen:
                        seen.add(callee)
                        order.append(callee)

    total: Dict[str, dict] = {}
    for name, lines in comps.items():
        w = mult.get(name, 0.0)
        if w <= 0:
            continue
        local = _collective_bytes_in(lines, sizes)
        for op, ent in local.items():
            agg = total.setdefault(op, {"bytes": 0, "count": 0})
            agg["bytes"] += int(ent["bytes"] * w)
            agg["count"] += int(ent["count"] * w)
    return total


def _split_top(s: str):
    depth = 0
    cur = []
    for ch in s:
        if ch == "," and depth == 0:
            yield "".join(cur)
            cur = []
            continue
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        cur.append(ch)
    if cur:
        yield "".join(cur)


@dataclasses.dataclass
class Roofline:
    flops: float  # per-device HLO flops
    bytes_accessed: float  # per-device
    coll_bytes: float  # per-device
    coll_detail: dict
    chips: int
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float
    useful_ratio: float

    def to_dict(self):
        return dataclasses.asdict(self)


def analyze(
    cost: dict,
    hlo_text: str,
    chips: int,
    model_flops_global: float,
) -> Roofline:
    """Roofline terms from the loop-aware HLO analyzer (hlo_stats); the XLA
    cost_analysis dict is kept only as a cross-reference (it counts while
    bodies once -- see hlo_stats docstring)."""
    from .hlo_stats import analyze_hlo

    st = analyze_hlo(hlo_text)
    flops = float(st.flops)
    bytes_acc = float(st.hbm_bytes)
    coll = st.coll
    cbytes = float(st.coll_bytes)
    compute_s = flops / PEAK_FLOPS
    memory_s = bytes_acc / HBM_BW
    coll_s = cbytes / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    bottleneck = max(terms, key=terms.get)
    mf_per_dev = model_flops_global / chips
    useful = mf_per_dev / flops if flops > 0 else 0.0
    return Roofline(
        flops=flops,
        bytes_accessed=bytes_acc,
        coll_bytes=cbytes,
        coll_detail=coll,
        chips=chips,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=coll_s,
        bottleneck=bottleneck,
        model_flops=model_flops_global,
        useful_ratio=useful,
    )

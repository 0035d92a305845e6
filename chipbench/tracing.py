"""Host spans of the benchmark and the reduction of a profiler trace.

The benchmark marks each call into the solver with a host span
(``jax.profiler.TraceAnnotation`` named ``bench.<what>``), so the spans
land in the profiler's own trace on the same clock as the device's
operations.  The reduction works on a flat list of :class:`Event` and
gives the device's busy time (the union of its operations' intervals), its
idle share, device time per operation (named ``<program>:<op>``), device time inside each kind
of host span, and the idle gaps attributed to the host span that covers
them.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
from collections import defaultdict
from pathlib import Path

import jax
import numpy as np

SPAN_PREFIX = "bench."
# The line of a device plane that holds the operations the device ran, and
# the one that holds the programs (XLA modules) they belong to.
OPS, MODULES = "XLA Ops", "XLA Modules"


def annotate(what: str):
    """Host span ``bench.<what>`` in the profiler's trace (no-op when off)."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + what)


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def load_events(path: str) -> list[Event]:
    """The device operations and the benchmark's host spans of one
    ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = is_device_plane(plane.name)
        for line in plane.lines:
            if device and line.name not in (OPS, MODULES):
                continue
            for e in line.events:
                if device or e.name.startswith(SPAN_PREFIX):
                    out.append(Event(plane.name, line.name, e.name,
                                     float(e.start_ns), float(e.duration_ns)))
    return out


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def save_events(events: list[Event], path: str) -> None:
    Path(path).write_text(json.dumps([dataclasses.astuple(e) for e in events]))


def read_events(path: str) -> list[Event]:
    return [Event(*row) for row in json.loads(Path(path).read_text())]


def short_op(name: str) -> str:
    """``%fusion.5 = f32[...] fusion(...)`` -> ``fusion.5``."""
    return name.split(" = ", 1)[0].lstrip("%")


def short_module(name: str) -> str:
    """``jit_solve(123456)`` -> ``jit_solve``."""
    return name.split("(", 1)[0]


def op_names(ops: list[Event], modules: list[Event]) -> list[str]:
    """``<module>:<op>`` for each operation, the module being the program
    execution on the same chip that contains it."""
    by_plane: dict[str, list[Event]] = defaultdict(list)
    for m in modules:
        by_plane[m.plane].append(m)
    starts = {}
    for plane, ms in by_plane.items():
        ms.sort(key=lambda e: e.start_ns)
        starts[plane] = np.array([m.start_ns for m in ms])
    names = []
    for e in ops:
        ms, label = by_plane.get(e.plane), "?"
        if ms:
            i = int(np.searchsorted(starts[e.plane], e.start_ns, side="right")) - 1
            if i >= 0 and ms[i].end_ns >= e.end_ns:
                label = short_module(ms[i].name)
        names.append(f"{label}:{short_op(e.name)}")
    return names


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


class Cover:
    """Disjoint sorted intervals, asked how much of [lo, hi] they cover."""

    def __init__(self, disjoint):
        self.starts = np.array([s for s, _ in disjoint], np.float64)
        self.ends = np.array([e for _, e in disjoint], np.float64)
        self.cum = np.concatenate([[0.0], np.cumsum(self.ends - self.starts)])

    def __call__(self, lo: float, hi: float) -> float:
        if hi <= lo:
            return 0.0
        i = int(np.searchsorted(self.ends, lo, side="right"))  # first ending after lo
        j = int(np.searchsorted(self.starts, hi, side="left"))  # first starting at/after hi
        if j <= i:
            return 0.0
        total = self.cum[j] - self.cum[i]
        total -= max(0.0, lo - self.starts[i])
        total -= max(0.0, self.ends[j - 1] - hi)
        return float(total)


def reduce(events: list[Event], window: tuple[float, float] | None = None,
           top: int = 10) -> dict:
    """Per-chip busy time, idle share, device time by operation and by host
    span, and idle gaps by host span, over ``window`` (ns; default: the
    benchmark's ``bench.window`` span).  Times are returned in seconds."""
    spans = [e for e in events if e.name.startswith(SPAN_PREFIX)]
    ops = [e for e in events if is_device_plane(e.plane) and e.line == OPS]
    modules = [e for e in events if is_device_plane(e.plane) and e.line == MODULES]
    if window is None:
        marks = [e for e in spans if e.name == SPAN_PREFIX + "window"]
        if not marks:
            raise ValueError("no bench.window span in the trace")
        window = (marks[0].start_ns, marks[0].end_ns)
    lo, hi = window
    chips = sorted({e.plane for e in ops})
    if not chips:
        raise ValueError("no device operations in the trace")
    busy_by_chip = {
        c: union([(max(e.start_ns, lo), min(e.end_ns, hi)) for e in ops
                  if e.plane == c and e.end_ns > lo and e.start_ns < hi])
        for c in chips
    }
    covers = [Cover(b) for b in busy_by_chip.values()]
    busy_ns = sum(cv(lo, hi) for cv in covers) / len(chips)

    by_op: dict[str, float] = defaultdict(float)
    for e, name in zip(ops, op_names(ops, modules)):
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            by_op[name] += (t - s) / len(chips)

    # device time inside each kind of host span, and the spans' own length
    in_span: dict[str, float] = defaultdict(float)
    span_len: dict[str, float] = defaultdict(float)
    span_count: dict[str, int] = defaultdict(int)
    inner = [e for e in spans if e.name != SPAN_PREFIX + "window"]
    for sp in inner:
        s, t = max(sp.start_ns, lo), min(sp.end_ns, hi)
        if t <= s:
            continue
        key = sp.name[len(SPAN_PREFIX):]
        span_len[key] += t - s
        span_count[key] += 1
        in_span[key] += sum(cv(s, t) for cv in covers) / len(chips)

    # idle gaps of the first chip, each given to the span that covers most of it
    gaps: dict[str, float] = defaultdict(float)
    inner.sort(key=lambda e: e.start_ns)
    starts = np.array([e.start_ns for e in inner], np.float64)
    longest = max((e.dur_ns for e in inner), default=0.0)
    ref = busy_by_chip[chips[0]]
    edges = [lo] + [x for iv in ref for x in iv] + [hi]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        best, best_cover = "no span", 0.0
        first = int(np.searchsorted(starts, g0 - longest, side="left"))
        last = int(np.searchsorted(starts, g1, side="left"))
        for sp in inner[first:last]:
            c = min(sp.end_ns, g1) - max(sp.start_ns, g0)
            if c > best_cover:
                best, best_cover = sp.name[len(SPAN_PREFIX):], c
        gaps[best] += g1 - g0

    ns = 1e-9
    ranked = lambda d: [[k, v * ns] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "chips": len(chips),
        "window_s": (hi - lo) * ns,
        "busy_s": busy_ns * ns,
        "device_s_in_span": {k: v * ns for k, v in in_span.items()},
        "span_s": {k: v * ns for k, v in span_len.items()},
        "span_count": dict(span_count),
        "device_ops": ranked(by_op),
        "idle_gaps": ranked(gaps),
    }

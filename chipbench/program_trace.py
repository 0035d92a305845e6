"""The solver's own spans in a profiler trace, and what the device did inside each.

The program annotates the profiler's trace with host spans named
``sap.<span>`` (``repro.obs.trace``) and leaves a zero-length
``sap.backend_compile`` marker on the compiling thread at each backend
compile.  This reduction reads them from the same ``.xplane.pb`` file as
``tracing.py``, beside the benchmark's own ``bench.*`` spans and the device's
``XLA Ops`` and ``XLA Modules``, and over the benchmark's ``bench.window``
span gives, for each span name of the program or of the benchmark:

- ``count``, ``seconds`` and ``self_s`` (seconds less what its child spans on
  the same thread cover);
- ``device_s``: the device's busy time inside it;
- ``idle_s``: each idle gap of the first chip goes to the span that covers
  most of it, the innermost of those that cover as much (``"no span"`` in
  ``idle_by_span`` when none covers any of it);
- ``programs``: ``XLA Modules`` executions that start inside it;
- ``compiles``: markers whose innermost span it is; ``compiles_inside``:
  markers inside it at any depth;
- ``kernels``: device seconds per operation name, the HLO ``.N`` suffix
  stripped, inside it.

Inside means in time: the device runs asynchronously, so an operation counts
under the spans that are open on the host while it runs, not under the span
that dispatched it, and the program's spans close when their work is
dispatched.  A host event matches on its name up to the first ``#``.

    python3 chipbench/program_trace.py --workload <cell> --seed <n> --seconds <s> --out <dir>

runs the cell once with the profiler on, prints the result line of a traced
run, and the table of spans to stderr, and writes the reduction and
one step's events to ``<dir>``.
"""

from __future__ import annotations

import argparse
import bisect
import dataclasses
import json
import re
import sys
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import tracing  # noqa: E402
from chipbench.tracing import MODULES, OPS, Event, is_device_plane  # noqa: E402

PROGRAM = "sap."
COMPILE = PROGRAM + "backend_compile"
WINDOW = tracing.SPAN_PREFIX + "window"
NO_SPAN = "no span"
_SUFFIX = re.compile(r"\.\d+$")


def kernel_name(op: str) -> str:
    """``<program>:sap_bts_forward.12``, or the operation's HLO text
    (``%sap_bts_forward.12 = f32[..]{..:T(8,128)} custom-call(..)``), ->
    ``sap_bts_forward``."""
    return _SUFFIX.sub("", tracing.short_op(op).rsplit(":", 1)[-1])


def host_name(name: str) -> str:
    """An annotation's name up to the first ``#`` (where attributes begin)."""
    return name.split("#", 1)[0]


def load(path: str) -> list[Event]:
    """The device's operations and programs, and the host's ``sap.*`` and
    ``bench.*`` events, of one ``.xplane.pb`` file.  A host event's ``line``
    names its thread line uniquely within its plane."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = is_device_plane(plane.name)
        for i, line in enumerate(plane.lines):
            if device and line.name not in (OPS, MODULES):
                continue
            label = line.name if device else f"{i}:{line.name}"
            for e in line.events:
                name = e.name if device else host_name(e.name)
                if device or name.startswith((PROGRAM, tracing.SPAN_PREFIX)):
                    out.append(Event(plane.name, label, name,
                                     float(e.start_ns), float(e.duration_ns)))
    return out


def _nest(spans: list[Event]) -> dict[int, int | None]:
    """Index of each span's parent on its own thread line (None for a root),
    spans being properly nested per line."""
    parent: dict[int, int | None] = {}
    by_line: dict[tuple, list[int]] = defaultdict(list)
    for i, sp in enumerate(spans):
        by_line[(sp.plane, sp.line)].append(i)
    for idx in by_line.values():
        idx.sort(key=lambda i: (spans[i].start_ns, -spans[i].end_ns))
        stack: list[int] = []
        for i in idx:
            while stack and spans[stack[-1]].end_ns <= spans[i].start_ns:
                stack.pop()
            parent[i] = stack[-1] if stack else None
            stack.append(i)
    return parent


def _innermost(spans: list[Event], t: float, plane: str, line: str) -> int | None:
    """The shortest span on this thread line that holds instant ``t``."""
    best = None
    for i, sp in enumerate(spans):
        if (sp.plane, sp.line) == (plane, line) and sp.start_ns <= t <= sp.end_ns:
            if best is None or sp.dur_ns < spans[best].dur_ns:
                best = i
    return best


def reduce(events: list[Event], window: tuple[float, float] | None = None) -> dict:
    """Per span name: count, seconds, self seconds, device busy and
    idle seconds, programs started, compiles and kernel seconds, over
    ``window`` (ns; default: the ``bench.window`` span).  Seconds out."""
    if window is None:
        marks = [e for e in events if e.name == WINDOW]
        if not marks:
            raise ValueError("no bench.window span in the trace")
        window = (marks[0].start_ns, marks[0].end_ns)
    lo, hi = window
    ops = [e for e in events if is_device_plane(e.plane) and e.line == OPS]
    modules = [e for e in events if is_device_plane(e.plane) and e.line == MODULES]
    chips = sorted({e.plane for e in ops})
    if not chips:
        raise ValueError("no device operations in the trace")
    host = [e for e in events if not is_device_plane(e.plane)]
    markers = [e for e in host if e.name == COMPILE and lo <= e.start_ns <= hi]
    spans = [e for e in host if e.name not in (COMPILE, WINDOW)
             and e.name.startswith((PROGRAM, tracing.SPAN_PREFIX))
             and e.end_ns > lo and e.start_ns < hi]
    clip = [(max(sp.start_ns, lo), min(sp.end_ns, hi)) for sp in spans]
    key = [sp.name for sp in spans]
    parent = _nest(spans)

    busy_by_chip = {
        c: tracing.union([(max(e.start_ns, lo), min(e.end_ns, hi)) for e in ops
                          if e.plane == c and e.end_ns > lo and e.start_ns < hi])
        for c in chips
    }
    covers = [tracing.Cover(b) for b in busy_by_chip.values()]
    n_chips = len(chips)

    out: dict[str, dict] = {}

    def entry(name: str) -> dict:
        if name not in out:
            out[name] = {"count": 0, "seconds": 0.0, "self_s": 0.0, "device_s": 0.0,
                         "idle_s": 0.0, "programs": 0.0, "compiles": 0,
                         "compiles_inside": 0, "kernels": defaultdict(float)}
        return out[name]

    child_len = defaultdict(float)
    for i, p in parent.items():
        if p is not None:
            child_len[p] += clip[i][1] - clip[i][0]

    ops_sorted = sorted(ops, key=lambda e: e.start_ns)
    op_starts = [e.start_ns for e in ops_sorted]
    longest_op = max((e.dur_ns for e in ops), default=0.0)
    mod_starts = sorted(m.start_ns for m in modules)
    for i, (s, t) in enumerate(clip):
        d = entry(key[i])
        d["count"] += 1
        d["seconds"] += t - s
        d["self_s"] += t - s - child_len[i]
        d["device_s"] += sum(cv(s, t) for cv in covers) / n_chips
        d["programs"] += (bisect.bisect_left(mod_starts, t)
                          - bisect.bisect_left(mod_starts, s)) / n_chips
        first = bisect.bisect_left(op_starts, s - longest_op)
        for e in ops_sorted[first:bisect.bisect_left(op_starts, t)]:
            inside = min(e.end_ns, t) - max(e.start_ns, s)
            if inside > 0:
                d["kernels"][kernel_name(e.name)] += inside / n_chips

    compiles_outside = 0
    for m in markers:
        i = _innermost(spans, m.start_ns, m.plane, m.line)
        if i is None:
            compiles_outside += 1
            continue
        entry(key[i])["compiles"] += 1
        while i is not None:
            entry(key[i])["compiles_inside"] += 1
            i = parent[i]

    # idle gaps of the first chip, each to the span covering most of it
    idle: dict[str, float] = defaultdict(float)
    order = sorted(range(len(spans)), key=lambda i: clip[i][0])
    starts = [clip[i][0] for i in order]
    longest = max((t - s for s, t in clip), default=0.0)
    edges = [lo] + [x for iv in busy_by_chip[chips[0]] for x in iv] + [hi]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        best, best_key = NO_SPAN, (0.0, 0.0)
        for i in order[bisect.bisect_left(starts, g0 - longest):bisect.bisect_left(starts, g1)]:
            s, t = clip[i]
            c = min(t, g1) - max(s, g0)
            if c > 0 and (c, -(t - s)) > best_key:
                best, best_key = key[i], (c, -(t - s))
        idle[best] += g1 - g0
        if best != NO_SPAN:
            out[best]["idle_s"] += g1 - g0

    ns = 1e-9
    kernels: dict[str, float] = defaultdict(float)
    for e in ops:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            kernels[kernel_name(e.name)] += (t - s) / n_chips
    busy = sum(cv(lo, hi) for cv in covers) / n_chips
    for d in out.values():
        for k in ("seconds", "self_s", "device_s", "idle_s"):
            d[k] *= ns
        d["kernels"] = {k: v * ns for k, v in sorted(d["kernels"].items(), key=lambda kv: -kv[1])}
    return {
        "chips": n_chips,
        "window_s": (hi - lo) * ns,
        "busy_s": busy * ns,
        "spans": out,
        "idle_by_span": {k: v * ns for k, v in sorted(idle.items(), key=lambda kv: -kv[1])},
        "compiles": len(markers),
        "compiles_outside_spans": compiles_outside,
        "kernels": {k: v * ns for k, v in sorted(kernels.items(), key=lambda kv: -kv[1])},
    }


def step_metrics(program: dict | None) -> dict:
    """Per step, a step being one ``sap.factor`` (or ``sap.krylov``) span:
    the device's idle seconds inside the factor (its span's length less the
    busy time inside it), the programs that start and the compiles inside
    it, and the device seconds in the window of the fused factor kernel and
    of the preconditioner apply (both ``bts`` kernels).  The kernels are
    counted over the window, not inside the spans, because a span closes
    when its work is dispatched.  None where the trace has nothing to read."""
    spans = (program or {}).get("spans", {})
    fac, kry = spans.get(PROGRAM + "factor"), spans.get(PROGRAM + "krylov")
    out = dict.fromkeys(("factor_idle_s", "factor_programs", "factor_compiles",
                         "fused_kernel_s", "precond_apply_s"))
    if fac and fac["count"]:
        n = fac["count"]
        out["factor_idle_s"] = (fac["seconds"] - fac["device_s"]) / n
        out["factor_programs"] = fac["programs"] / n
        out["factor_compiles"] = fac["compiles_inside"] / n
        fused = program["kernels"].get("sap_fused_factor_spike")
        out["fused_kernel_s"] = None if fused is None else fused / n
    if kry and kry["count"]:
        bts = [program["kernels"].get(k) for k in ("sap_bts_forward", "sap_bts_backward")]
        if all(v is not None for v in bts):
            out["precond_apply_s"] = sum(bts) / kry["count"]
    return out


def table(program: dict) -> str:
    """The spans, longest first: seconds, self, device busy and idle
    seconds, programs started and compiles, and the idle time outside them."""
    rows = [f"{'span':<24} {'count':>6} {'seconds':>9} {'self_s':>9} {'device_s':>9} "
            f"{'idle_s':>9} {'programs':>9} {'compiles':>8}"]
    for name, d in sorted(program["spans"].items(), key=lambda kv: -kv[1]["seconds"]):
        rows.append(f"{name:<24} {d['count']:>6} {d['seconds']:>9.4f} {d['self_s']:>9.4f} "
                    f"{d['device_s']:>9.4f} {d['idle_s']:>9.4f} {d['programs']:>9.1f} "
                    f"{d['compiles']:>8}")
    rows.append(f"idle outside spans: {program['idle_by_span'].get(NO_SPAN, 0.0):.4f} s; "
                f"window {program['window_s']:.4f} s, busy {program['busy_s']:.4f} s, "
                f"compiles {program['compiles']} ({program['compiles_outside_spans']} outside spans)")
    return "\n".join(rows)


def one_step(events: list[Event]) -> list[Event]:
    """The events of the middle step of a closed loop: from the start of a
    ``bench.factor`` span to the end of the ``bench.solve`` span after it,
    with a ``bench.window`` span around them, operations named short."""
    factors = sorted((e for e in events if e.name == tracing.SPAN_PREFIX + "factor"),
                     key=lambda e: e.start_ns)
    solves = [e for e in events if e.name == tracing.SPAN_PREFIX + "solve"]
    lo = factors[(len(factors) - 1) // 2].start_ns
    hi = min(s.end_ns for s in solves if s.start_ns >= lo)
    kept = [dataclasses.replace(e, name=tracing.short_op(e.name)) if e.line == OPS else e
            for e in events if e.name != WINDOW and e.end_ns > lo and e.start_ns < hi]
    return [Event("/host:CPU", "bench", WINDOW, lo, hi - lo)] + kept


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--out", required=True)
    args = parser.parse_args(argv)

    import importlib

    from chipbench import harness
    from chipbench.run import Window

    cell = harness.load_cell(args.workload)
    try:
        device = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"program_trace: {e}; no result", file=sys.stderr)
        return 3

    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    class ProgramWindow(Window):
        def reduce_trace(self):
            events = load(tracing.find_xplane(self.trace_dir))
            self.program = reduce(events)
            tracing.save_events(one_step(events), str(out_dir / f"step_{args.seed}.json"))
            return super().reduce_trace()

    counter = harness.CompileCounter()
    win = ProgramWindow(counter, True)
    loop = importlib.import_module(f"chipbench.loops.{cell.traffic['loop']}")
    rec = loop.run(cell.config, cell.traffic, args.seed, args.seconds, win)
    rec["compiles_in_window"] = win.c1["compiles"] - win.c0["compiles"]
    rec["device"] = {**device, "memory_peak_bytes": win.memory_peak_bytes}
    rec["trace"] = win.reduce_trace()
    rec["config"], rec["traffic"] = cell.config, cell.traffic
    line = harness.result_line(cell, rec, True)
    steps = step_metrics(win.program)
    line["time_to_solution_s"] = rec["window_s"] / len(rec["steps"])
    line["program"] = steps
    (out_dir / f"program_{args.seed}.json").write_text(
        json.dumps({"result": line, "program": win.program}, indent=1))
    print(table(win.program), file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Cells, metric readers and the result line of the chip benchmark.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; the
harness finds each by that name: ``configs/<config>.json`` and
``traffic/<traffic>.json`` under this directory.  Each metric is a reader
``metrics/<metric>.py`` with a function ``read(rec)`` over the run's
record, which returns a number or None when the run has nothing for it.
Nothing here names a cell.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
import threading
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
BENCHMARK = ROOT / "BENCHMARK.json"

# The backend-compile event fires for every compile, also one that the
# persistent cache answers.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = ("/jax/compilation_cache/cache_hits",
                "/jax/compilation_cache/cache_misses")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, benchmark: Path = BENCHMARK) -> Cell:
    bench = json.loads(Path(benchmark).read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {benchmark}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name, chips=w["chips"], config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def load_reader(metric: str):
    """``read`` of ``metrics/<metric>.py``; the file name may hold dots."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{metric}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_chips(chips: int) -> dict:
    """The device record; raises NoChip without a TPU or with too few."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {d0.platform} ({d0.device_kind})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devices)}")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices)}


class CompileCounter:
    """Counts backend compiles (and persistent-cache hits and misses) from
    JAX's monitoring events.  JAX offers no way to unregister a listener,
    so each process makes one counter."""

    def __init__(self):
        from jax import monitoring

        self._lock = threading.Lock()
        self.compiles = 0
        self.compile_s = 0.0
        self.cache = {e: 0 for e in CACHE_EVENTS}

        def on_duration(event: str, seconds: float, **_kw) -> None:
            if event == COMPILE_EVENT:
                with self._lock:
                    self.compiles += 1
                    self.compile_s += seconds

        def on_event(event: str, **_kw) -> None:
            if event in self.cache:
                with self._lock:
                    self.cache[event] += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def snapshot(self) -> dict:
        with self._lock:
            return {"compiles": self.compiles, "compile_s": self.compile_s,
                    "cache_hits": self.cache[CACHE_EVENTS[0]],
                    "cache_misses": self.cache[CACHE_EVENTS[1]]}


def finite(x: float) -> float:
    """JSON has no infinity: an infinite number, such as the residual of a
    NaN answer, is written as 1e12."""
    return x if math.isfinite(x) else 1e12


def result_line(cell: Cell, rec: dict, trace: bool) -> dict:
    """The last stdout line: end-to-end metrics with ``--trace 0``,
    per-layer metrics with ``--trace 1``; readers that find nothing to
    read are left out.  ``compared`` comes last."""
    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        value = load_reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": finite(float(value)), "unit": m["unit"]}
    out = {
        "correct": rec["correct"],
        "attempted": rec["attempted"],
        "failed": rec["failed"],
        "metrics": metrics,
        "device": rec["device"],
    }
    if trace:
        t = rec["trace"]
        out["device"] = {**rec["device"], "busy_s": t["busy_s"], "window_s": t["window_s"]}
        out["breakdown"] = {"device_ops": t["device_ops"], "idle_gaps": t["idle_gaps"]}
    out["compared"] = rec["compared"]
    return out


def print_compared(compared: dict) -> None:
    """Each compared number beside its limit, as the last lines of stderr."""
    for name, c in compared.items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)

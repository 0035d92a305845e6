"""Cost observatory: per-stage roofline accounting + compile/memory telemetry.

The tracer (:mod:`repro.obs.trace`) answers *where the seconds went*; this
module answers the two questions next to it:

1. **What should those seconds have been?**  Every jitted solver stage
   (the batched factor stages, the batched Krylov solve, the raw
   btf/bts/bcr kernels) is lowered ONCE per bucket shape and run through
   ``compiled.cost_analysis()`` plus the loop-aware
   :func:`repro.launch.hlo_stats.analyze_hlo` walk over the
   post-optimization HLO.  The result is a :class:`StageCost`: flops, HBM
   bytes, arithmetic intensity, and the roofline-predicted seconds
   ``max(flops / peak_flops, bytes / hbm_bw)`` under the current
   backend's :class:`~repro.launch.roofline.HardwareSpec`.  Dividing the
   roofline prediction by a measured wall time gives the
   achieved-vs-roofline fraction that ``BENCH_batched.json`` rows carry.

2. **How much compiling and memory is the serving path paying?**  A
   process-wide :class:`CompileLog` counts every XLA backend compile
   (ground truth via ``jax.monitoring``'s backend_compile event, with a
   :func:`timed_compile` fallback when the listener API is unavailable),
   attributing labeled compiles (`factor.batch` AOT misses, cost-layer
   lowerings) and emitting ``compile`` trace spans.
   :func:`device_memory_bytes` samples the live device footprint
   (``device.memory_stats()`` where the backend reports it -- TPU/GPU --
   falling back to summing ``jax.live_arrays()`` on CPU), which the
   engine folds into a ``peak_device_bytes`` watermark.

Import cycles: :mod:`repro.core.batched` imports the telemetry
primitives (:func:`timed_compile`) from here, so everything that reaches
back into the solver (:func:`solver_stage_costs`) imports lazily.

The loop-aware HLO walk multiplies ``while`` bodies by their trip count,
so a Krylov executable's cost is ~``maxiter`` sweeps.  Real solves stop
earlier: :meth:`StageCost.per_iteration` divides the cost back down so
callers can scale by the iterations a solve actually ran.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
import time
from typing import Any, Dict, Optional, Tuple

import jax

from ..launch.hlo_stats import analyze_hlo
from ..launch.roofline import HardwareSpec, backend_spec
from .trace import mark, span

__all__ = [
    "COMPILES",
    "CompileLog",
    "StageCost",
    "cost_of",
    "cost_of_compiled",
    "device_memory_bytes",
    "hardware_spec",
    "install_compile_listener",
    "solver_stage_costs",
    "timed_compile",
]


# ---------------------------------------------------------------------------
# Hardware spec resolution
# ---------------------------------------------------------------------------


# Result of the one-shot REPRO_CALIBRATE=1 micro-benchmark; measured
# once per process the first time hardware_spec() needs it.
_CALIBRATED: Optional[HardwareSpec] = None
_CALIBRATED_LOCK = threading.Lock()


def hardware_spec(backend: Optional[str] = None) -> HardwareSpec:
    """The active backend's peak rates, with env overrides.

    ``REPRO_PEAK_FLOPS`` / ``REPRO_HBM_BW`` (floats, flops/s and bytes/s)
    override the per-backend defaults in
    :data:`repro.launch.roofline.BACKEND_SPECS` -- measured-machine
    calibration without touching code.  ``REPRO_CALIBRATE=1`` instead
    *measures* this machine's ceilings once per process via
    :func:`repro.launch.calibrate.calibrate` (a ~1 s gemm + stream
    micro-bench); explicit env numbers still win over the measurement.
    """
    spec = backend_spec(backend or jax.default_backend())
    if os.environ.get("REPRO_CALIBRATE") == "1":
        global _CALIBRATED
        with _CALIBRATED_LOCK:
            if _CALIBRATED is None:
                from ..launch.calibrate import calibrate

                _CALIBRATED = calibrate()
            spec = _CALIBRATED
    pf = os.environ.get("REPRO_PEAK_FLOPS")
    bw = os.environ.get("REPRO_HBM_BW")
    if pf or bw:
        spec = dataclasses.replace(
            spec,
            name=spec.name + "+env",
            peak_flops=float(pf) if pf else spec.peak_flops,
            hbm_bw=float(bw) if bw else spec.hbm_bw,
        )
    return spec


# ---------------------------------------------------------------------------
# Compile telemetry
# ---------------------------------------------------------------------------


class CompileLog:
    """Thread-safe process-wide compile counters.

    ``total_count`` / ``total_seconds`` are ground truth from the XLA
    backend-compile monitoring event (every jit cache miss in the
    process, not just instrumented call sites).  ``labels`` attributes
    the compiles that went through :func:`timed_compile` -- their wall
    time includes tracing + lowering, so a label's seconds can exceed its
    share of ``total_seconds``.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._count = 0
        self._seconds = 0.0
        self._labels: Dict[str, Dict[str, float]] = {}
        self.listener_installed = False

    def _on_event(self, seconds: float) -> None:
        with self._lock:
            self._count += 1
            self._seconds += seconds

    def _on_labeled(self, label: str, seconds: float) -> None:
        with self._lock:
            ent = self._labels.setdefault(label, {"count": 0, "seconds": 0.0})
            ent["count"] += 1
            ent["seconds"] += seconds
            if not self.listener_installed:
                # no monitoring API: the labeled sites are the best totals
                self._count += 1
                self._seconds += seconds

    def snapshot(self) -> dict:
        """``{"recompiles_total", "compile_seconds_total", "labels"}``."""
        with self._lock:
            return {
                "recompiles_total": self._count,
                "compile_seconds_total": self._seconds,
                "labels": {k: dict(v) for k, v in self._labels.items()},
            }

    def totals(self) -> Tuple[int, float]:
        """(compile count, cumulative compile seconds) observed so far."""
        with self._lock:
            return self._count, self._seconds


COMPILES = CompileLog()
_LISTENER_LOCK = threading.Lock()

# every backend compile fires this jax.monitoring duration event
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def install_compile_listener() -> bool:
    """Register the process-wide backend-compile listener (idempotent).

    Returns True when the ``jax.monitoring`` listener is active.  JAX
    offers registration but no removal, so this is once-per-process --
    the callback bumps two counters under a lock and, while the profiler
    runs, leaves a zero-length ``sap.backend_compile`` marker on the
    compiling thread, inside the span that caused the compile.
    """
    with _LISTENER_LOCK:
        if COMPILES.listener_installed:
            return True
        try:
            from jax import monitoring

            def _listener(event: str, duration: float, **kw: Any) -> None:
                if event == _COMPILE_EVENT:
                    COMPILES._on_event(duration)
                    mark("backend_compile")

            monitoring.register_event_duration_secs_listener(_listener)
            COMPILES.listener_installed = True
        except Exception:  # pragma: no cover - older/stripped jax builds
            COMPILES.listener_installed = False
        return COMPILES.listener_installed


install_compile_listener()


@contextlib.contextmanager
def timed_compile(label: str, **attrs: Any):
    """Bracket a ``.lower().compile()`` (or first jit call): emits a
    ``compile`` trace span and attributes the wall time to ``label`` in
    :data:`COMPILES`.  The process totals come from the monitoring
    listener; this adds the *which call site* dimension.
    """
    t0 = time.perf_counter()
    with span("compile", label=label, **attrs):
        yield
    COMPILES._on_labeled(label, time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# Device memory
# ---------------------------------------------------------------------------


def device_memory_bytes(device: Optional[Any] = None) -> int:
    """Current device memory footprint in bytes.

    Prefers the backend allocator's ``memory_stats()["bytes_in_use"]``
    (TPU/GPU); CPU backends report no allocator stats, so the fallback
    sums ``jax.live_arrays()`` -- live committed arrays, which is the
    watermark that matters for the solver's factorization cache.
    """
    devices = [device] if device is not None else jax.local_devices()
    total = 0
    reported = False
    for d in devices:
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if ms and "bytes_in_use" in ms:
            total += int(ms["bytes_in_use"])
            reported = True
    if reported:
        return total
    try:
        return int(sum(a.nbytes for a in jax.live_arrays()))
    except Exception:  # pragma: no cover - live_arrays unavailable
        return 0


# ---------------------------------------------------------------------------
# Stage cost records
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StageCost:
    """Roofline accounting of one compiled solver stage.

    ``flops`` / ``hbm_bytes`` come from the loop-aware HLO walk
    (:func:`~repro.launch.hlo_stats.analyze_hlo`); ``xla_flops`` /
    ``xla_bytes`` keep ``compiled.cost_analysis()`` as a cross-reference
    (it counts while bodies once, so it undercounts iterative stages).
    ``loop_iters`` marks costs that bake a while-loop trip count in
    (Krylov: ``maxiter`` sweeps) -- :meth:`per_iteration` removes it.
    """

    stage: str
    flops: float
    hbm_bytes: float
    intensity: float  # flops / hbm_bytes
    compute_s: float
    memory_s: float
    roofline_s: float  # max(compute_s, memory_s)
    bottleneck: str  # "compute" | "memory"
    hw: str
    xla_flops: float
    xla_bytes: float
    loop_iters: Optional[int] = None

    def scale(self, factor: float) -> "StageCost":
        """Linear rescale (e.g. per-batch-element cost x batch size)."""
        return dataclasses.replace(
            self,
            flops=self.flops * factor,
            hbm_bytes=self.hbm_bytes * factor,
            compute_s=self.compute_s * factor,
            memory_s=self.memory_s * factor,
            roofline_s=self.roofline_s * factor,
            xla_flops=self.xla_flops * factor,
            xla_bytes=self.xla_bytes * factor,
        )

    def per_iteration(self) -> "StageCost":
        """Cost of ONE loop sweep for stages with a baked-in trip count."""
        if not self.loop_iters or self.loop_iters <= 1:
            return self
        out = self.scale(1.0 / self.loop_iters)
        return dataclasses.replace(out, loop_iters=None)

    def achieved_fraction(self, measured_s: float) -> float:
        """roofline_s / measured_s: 1.0 = running at the hardware ceiling."""
        if measured_s <= 0.0:
            return float("nan")
        return self.roofline_s / measured_s

    def to_dict(self, measured_s: Optional[float] = None) -> dict:
        """JSON-ready record; includes roofline_frac when measured_s given."""
        d = {
            "stage": self.stage,
            "flops": float(self.flops),
            "hbm_bytes": float(self.hbm_bytes),
            "intensity": round(self.intensity, 4),
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "roofline_s": self.roofline_s,
            "bottleneck": self.bottleneck,
            "hw": self.hw,
            "xla_flops": float(self.xla_flops),
            "xla_bytes": float(self.xla_bytes),
        }
        if self.loop_iters is not None:
            d["loop_iters"] = int(self.loop_iters)
        if measured_s is not None:
            d["measured_s"] = measured_s
            d["roofline_frac"] = round(self.achieved_fraction(measured_s), 6)
        return d


def _xla_cost(compiled) -> Tuple[float, float]:
    """(flops, bytes accessed) from ``compiled.cost_analysis()``; the jax
    0.4.x shape is a list with one dict per partition."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return 0.0, 0.0
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return 0.0, 0.0
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0))


def cost_of_compiled(
    stage: str,
    compiled,
    hw: Optional[HardwareSpec] = None,
    loop_iters: Optional[int] = None,
) -> StageCost:
    """Roofline-account an already-compiled executable."""
    hw = hw or hardware_spec()
    st = analyze_hlo(compiled.as_text())
    xf, xb = _xla_cost(compiled)
    flops = float(st.flops)
    hbm = float(st.hbm_bytes)
    compute_s = flops / hw.peak_flops
    memory_s = hbm / hw.hbm_bw
    return StageCost(
        stage=stage,
        flops=flops,
        hbm_bytes=hbm,
        intensity=flops / hbm if hbm > 0 else 0.0,
        compute_s=compute_s,
        memory_s=memory_s,
        roofline_s=max(compute_s, memory_s),
        bottleneck="compute" if compute_s >= memory_s else "memory",
        hw=hw.name,
        xla_flops=xf,
        xla_bytes=xb,
        loop_iters=loop_iters,
    )


def cost_of(
    fn,
    *avals,
    stage: str = "stage",
    static: Optional[dict] = None,
    hw: Optional[HardwareSpec] = None,
    loop_iters: Optional[int] = None,
) -> StageCost:
    """Lower + compile ``fn`` on abstract ``avals`` and roofline-account it.

    ``fn`` may already be jit-wrapped (anything with ``.lower``);
    ``static`` passes static kwargs through to the lowering.  The compile
    is counted and spanned via :func:`timed_compile` under
    ``cost:<stage>``.
    """
    jfn = fn if hasattr(fn, "lower") else jax.jit(fn)
    lowered = jfn.lower(*avals, **(static or {}))
    with timed_compile(f"cost:{stage}"):
        compiled = lowered.compile()
    return cost_of_compiled(stage, compiled, hw=hw, loop_iters=loop_iters)


# ---------------------------------------------------------------------------
# Solver stage costs (per bucket shape)
# ---------------------------------------------------------------------------

_SOLVER_COSTS: Dict[tuple, Dict[str, StageCost]] = {}
_SOLVER_COSTS_LOCK = threading.Lock()


def solver_stage_costs(
    bucket: Tuple[int, int, int],
    s: int = 1,
    opts=None,
    variant: Optional[str] = None,
    dtype=None,
) -> Dict[str, StageCost]:
    """Roofline costs of every solver stage for one bucket shape.

    ``bucket`` is the compiled shape ``(N', K', P)`` (the engine's
    currency, from :func:`repro.core.batched.bucket_shape`); ``s`` is the
    system-batch size the executables are lowered at.  Returns a dict of
    :class:`StageCost` keyed by stage:

      * ``"factor"`` -- the vmapped batched factor stages, compiled via
        the SAME AOT cache ``batch_factor`` executes from, so asking for
        the cost of a bucket the engine already served is free.
      * ``"krylov"`` -- the batched solve executable.  Its HLO cost bakes
        in ``maxiter`` sweeps (``loop_iters``); use ``per_iteration()``
        and scale by the iterations a solve actually ran.
      * ``"btf"`` / ``"bts"`` -- the raw block-tridiagonal kernels at the
        bucket's (P, M, K') partition grid (the factor/solve inner loop).
      * ``"bcr"`` -- the log-depth reduced-chain kernels, present when the
        variant solves an exact reduced system (``"E"``) with P > 1.

    Results are cached per (bucket, s, variant, relevant options,
    backend); repeated calls cost a dict lookup.
    """
    from ..core import batched
    from ..core.sap import SaPOptions

    nb, kb, p = bucket
    opts = opts or SaPOptions(p=p)
    if variant is None:
        variant = opts.variant if opts.variant != "auto" else "C"
    dtype = jax.numpy.dtype(dtype or jax.numpy.float32)
    hw = hardware_spec()
    key = (
        bucket, s, variant, batched._factor_key(opts),
        opts.tol, opts.maxiter, opts.use_cg, opts.iter_dtype, opts.solver,
        str(dtype), jax.default_backend(), hw.name,
    )
    with _SOLVER_COSTS_LOCK:
        hit = _SOLVER_COSTS.get(key)
    if hit is not None:
        return hit

    costs: Dict[str, StageCost] = {}
    bands = jax.ShapeDtypeStruct((s, nb, 2 * kb + 1), dtype)

    # -- factor: shared AOT executable (also serves batch_factor) ----------
    compiled = batched.factor_stages_compiled(
        kb, p, variant, batched._factor_key(opts), bands
    )
    costs["factor"] = cost_of_compiled("factor", compiled, hw=hw)

    # -- krylov: abstract factorization -> the batched solve executable ----
    stages = batched._factor_stages_fn(
        kb, p, variant, batched._factor_key(opts)
    )
    pc_struct, d_struct = jax.eval_shape(stages, bands)
    from ..core import sap as sap_mod
    from ..core.operators import BandedOperator
    from ..core.sap import SaPFactorization

    perm = jax.ShapeDtypeStruct((s, nb), jax.numpy.int32)
    fac = SaPFactorization(
        op=BandedOperator(band=bands, n=nb, k=kb),
        pc=pc_struct,
        b_perm=perm,
        x_perm=perm,
        n=nb,
        k=kb,
        tol=opts.tol,
        maxiter=opts.maxiter,
        use_cg=opts.use_cg,
        iter_dtype=opts.iter_dtype,
        solver=sap_mod.resolve_solver(opts.solver, opts.use_cg),
        d_factor=d_struct,
    )
    b_struct = jax.ShapeDtypeStruct((s, nb), dtype)
    lowered = batched._solve_batch.lower(fac, b_struct, record_history=False)
    with timed_compile("cost:krylov", bucket=f"{nb}x{kb}", s=s):
        krylov_exec = lowered.compile()
    costs["krylov"] = cost_of_compiled(
        "krylov", krylov_exec, hw=hw, loop_iters=opts.maxiter
    )

    # -- raw kernels at the bucket's partition grid ------------------------
    from ..kernels import ops as kops

    m = max(nb // (p * kb), 1)
    blk = jax.ShapeDtypeStruct((p, m, kb, kb), dtype)
    costs["btf"] = cost_of(
        lambda d, e, f: kops.block_tridiag_factor(d, e, f),
        blk, blk, blk, stage="btf", hw=hw,
    )
    fac_struct = jax.eval_shape(
        lambda d, e, f: kops.block_tridiag_factor(d, e, f), blk, blk, blk
    )
    rhs = jax.ShapeDtypeStruct((p, m, kb, 1), dtype)
    costs["bts"] = cost_of(
        lambda fc, b: kops.block_tridiag_solve(fc, b),
        fac_struct, rhs, stage="bts", hw=hw,
    )
    if variant == "E" and p > 1:
        m2 = p - 1
        blk2 = jax.ShapeDtypeStruct((m2, 2 * kb, 2 * kb), dtype)
        rhs2 = jax.ShapeDtypeStruct((m2, 2 * kb, 1), dtype)
        bcr_struct = jax.eval_shape(
            lambda d, e, f: kops.bcr_factor(d, e, f), blk2, blk2, blk2
        )
        bcr_f = cost_of(
            lambda d, e, f: kops.bcr_factor(d, e, f),
            blk2, blk2, blk2, stage="bcr", hw=hw,
        )
        bcr_s = cost_of(
            lambda fc, b: kops.bcr_solve(fc, b),
            bcr_struct, rhs2, stage="bcr", hw=hw,
        )
        # one record for the reduced-system sweep: factor + solve
        merged = dataclasses.replace(
            bcr_f,
            flops=bcr_f.flops + bcr_s.flops,
            hbm_bytes=bcr_f.hbm_bytes + bcr_s.hbm_bytes,
            compute_s=bcr_f.compute_s + bcr_s.compute_s,
            memory_s=bcr_f.memory_s + bcr_s.memory_s,
            xla_flops=bcr_f.xla_flops + bcr_s.xla_flops,
            xla_bytes=bcr_f.xla_bytes + bcr_s.xla_bytes,
        )
        total_f = merged.flops
        total_b = merged.hbm_bytes
        merged = dataclasses.replace(
            merged,
            intensity=total_f / total_b if total_b > 0 else 0.0,
            roofline_s=max(merged.compute_s, merged.memory_s),
            bottleneck="compute"
            if merged.compute_s >= merged.memory_s else "memory",
        )
        costs["bcr"] = merged

    with _SOLVER_COSTS_LOCK:
        _SOLVER_COSTS.setdefault(key, costs)
        return _SOLVER_COSTS[key]

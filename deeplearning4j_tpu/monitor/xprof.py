"""Compile-time / profiler observability: the device-free half of the
telemetry core.

Runtime telemetry (registry + tracer + collectors) needs a live
process doing work; everything in this module works with **no
accelerator attached**, because it operates at the compiled-program
level — the design point both TensorFlow's whole-dataflow-graph cost
model (arXiv:1605.08695 §3.2.1) and the Julia→TPU AOT pipeline
(arXiv:1810.09868) argue for: analyze the program XLA will run, not
the silicon you may not have.

Three pieces:

- `roofline()` — the classic two-ceiling model (arithmetic intensity
  vs a compute peak and a memory-bandwidth peak) that turns an AOT
  cost analysis (total FLOPs + bytes accessed) into a predicted step
  time and a predicted MFU. Pure math, unit-tested.
- cost-report registry — `publish_cost_report()` stores the JSON
  artifacts `benchtools/hlo_cost.py` emits (``PROFILE_*/cost_*.json``)
  and mirrors the headline figures onto the metrics registry as
  ``aot_cost_*`` gauges; `cost_reports(scan=True)` is what the
  UIServer's ``/profile`` route renders (falling back to scanning the
  working directory for committed artifacts).
- `ProfilerCapture` — the programmatic `jax.profiler` seam: start/stop
  an xplane trace around fit-loop spans from driver code. Works on
  CPU too (host plane only).
"""

from __future__ import annotations

import glob
import json
import os
import threading
import time
from typing import Dict, Optional

__all__ = [
    "ProfilerCapture", "roofline", "publish_cost_report",
    "cost_reports", "clear_cost_reports", "load_cost_reports",
]


# ---------------------------------------------------------------- roofline
def roofline(flops: float, bytes_accessed: float, peak_flops: float,
             peak_bytes_per_sec: float) -> Dict[str, float]:
    """Two-ceiling roofline for one training step.

    `peak_flops` should be the *measured* matmul ceiling where one
    exists (bench.py's speed-of-light probe — what the silicon
    demonstrably sustains), not the datasheet number: a predicted MFU
    against an unreachable peak is not falsifiable.

    Returns arithmetic intensity (FLOP/byte), the critical intensity
    where the ceilings cross, which ceiling binds, per-ceiling step
    times, and the predicted step time / throughput / MFU at the
    binding ceiling. `bytes_accessed` from unoptimized HLO overstates
    traffic (fusion elides intermediates), so the memory ceiling is an
    upper bound on step time and `predicted_mfu` a lower bound —
    callers should report `mfu_if_compute_bound` alongside it.
    """
    flops = float(flops)
    bytes_accessed = float(bytes_accessed)
    if flops <= 0 or peak_flops <= 0 or peak_bytes_per_sec <= 0:
        raise ValueError("roofline needs positive flops and peaks")
    ai = flops / max(bytes_accessed, 1.0)
    critical_ai = peak_flops / peak_bytes_per_sec
    t_compute = flops / peak_flops
    t_memory = bytes_accessed / peak_bytes_per_sec
    t = max(t_compute, t_memory)
    return {
        "arithmetic_intensity_flop_per_byte": ai,
        "critical_intensity_flop_per_byte": critical_ai,
        "bound": "compute" if t_compute >= t_memory else "memory",
        "step_seconds_compute_bound": t_compute,
        "step_seconds_memory_bound": t_memory,
        "predicted_step_seconds": t,
        "predicted_flops_per_sec": flops / t,
        "predicted_mfu": (flops / t) / peak_flops,
        "mfu_if_compute_bound": 1.0,
    }


# ------------------------------------------------------ cost-report store
_REPORTS: Dict[str, dict] = {}
_REPORTS_LOCK = threading.Lock()

_GAUGE_FIELDS = (
    # (gauge name, report path) — headline figures mirrored to /metrics
    ("aot_cost_flops_per_step", ("per_op", "total_flops_per_step")),
    ("aot_cost_bytes_per_step", ("per_op", "total_bytes_per_step")),
    ("aot_cost_arithmetic_intensity",
     ("roofline", "arithmetic_intensity_flop_per_byte")),
    ("aot_cost_predicted_step_seconds", ("roofline", "predicted_step_seconds")),
    ("aot_cost_predicted_mfu", ("predicted", "mfu")),
    # program section (scan-over-layers observability): how big the
    # compiled train step is and what compiling it cost
    ("aot_compile_seconds", ("program", "compile_seconds")),
    ("aot_compile_jaxpr_eqns", ("program", "jaxpr_eqn_count")),
    ("aot_compile_peak_temp_bytes", ("program", "peak_temp_bytes")),
    ("aot_compile_code_size_bytes",
     ("program", "generated_code_size_in_bytes")),
    # gradient-exchange payload (threshold-encoded gradient sharing —
    # parallel/gradient_sharing.py wire format vs dense fp32)
    ("aot_comm_bytes_dense", ("program", "comm_bytes",
                              "dense_bytes_per_step")),
    ("aot_comm_bytes_threshold", ("program", "comm_bytes",
                                  "threshold_bytes_per_step")),
    ("aot_comm_bytes_reduction", ("program", "comm_bytes", "reduction")),
    # exposed-vs-overlapped comm bytes of the bucketed exchange
    # (benchtools/hlo_cost.comm_overlap_block; headline = the sync
    # trainers' default bucketed-dense program)
    ("aot_comm_overlap_exposed_bytes", ("program", "comm_overlap",
                                        "exposed_bytes")),
    ("aot_comm_overlap_overlapped_bytes", ("program", "comm_overlap",
                                           "overlapped_bytes")),
    ("aot_comm_overlap_exposed_fraction", ("program", "comm_overlap",
                                           "exposed_fraction")),
    # dtype-policy (mixed-precision) evidence — fp32-vs-bf16 bytes per
    # step of the SAME program (benchtools/hlo_cost.precision_block)
    ("aot_precision_fp32_bytes_per_step", ("precision", "float32",
                                           "bytes_per_step")),
    ("aot_precision_bf16_bytes_per_step", ("precision", "mixed_bf16",
                                           "bytes_per_step")),
    ("aot_precision_bytes_reduction", ("precision", "bytes_reduction")),
    ("aot_precision_wire_reduction", ("precision", "wire_reduction")),
)


def _dig(d, path):
    for p in path:
        if not isinstance(d, dict):
            return None
        d = d.get(p)
    return d


def publish_cost_report(report: dict, registry=None) -> dict:
    """Store one cost report (keyed by its ``model`` field) for the
    ``/profile`` route and mirror its headline numbers onto the metrics
    registry as ``aot_cost_*{model=...}`` gauges. `registry=None` uses
    the monitor's active registry. Returns the report."""
    model = str(report.get("model", "unknown"))
    with _REPORTS_LOCK:
        _REPORTS[model] = report
    if registry is None:
        from deeplearning4j_tpu import monitor
        registry = monitor.registry()
    for gname, path in _GAUGE_FIELDS:
        val = _dig(report, path)
        if isinstance(val, (int, float)):
            registry.gauge(
                gname, help="AOT HLO cost analysis (benchtools/hlo_cost.py)",
                model=model).set(float(val))
    return report


def clear_cost_reports():
    with _REPORTS_LOCK:
        _REPORTS.clear()


def load_cost_reports(root: str = ".") -> Dict[str, dict]:
    """Scan committed artifacts (``PROFILE_*/cost_*.json`` under
    `root`) — lets a UI-only process serve /profile from the repo's
    checked-in cost tables without re-running the analysis."""
    out: Dict[str, dict] = {}
    for path in sorted(glob.glob(os.path.join(root, "PROFILE_*",
                                              "cost_*.json"))):
        try:
            with open(path) as f:
                rep = json.load(f)
        except (OSError, ValueError):
            continue
        if isinstance(rep, dict):
            out[str(rep.get("model",
                            os.path.basename(path)[5:-5] or path))] = rep
    return out


def cost_reports(scan: bool = False, root: str = ".") -> Dict[str, dict]:
    """Reports published in-process; with `scan=True`, disk artifacts
    fill in models nothing has published yet (published wins)."""
    with _REPORTS_LOCK:
        published = dict(_REPORTS)
    if not scan:
        return published
    merged = load_cost_reports(root)
    merged.update(published)
    return merged


# ------------------------------------------------------- profiler capture
class ProfilerCapture:
    """Programmatic `jax.profiler` trace seam.

    The ProfilerListener (optimize/listeners.py) picks iterations from
    inside a fit loop; this seam is for *driver* code that brackets an
    arbitrary window — a whole bench run, one fused dispatch, a sweep::

        from deeplearning4j_tpu.monitor import ProfilerCapture
        with ProfilerCapture("PROFILE_live/trace"):
            bench.bench_resnet50(accel=True)

    start()/stop() may also be called explicitly (stop() is idempotent
    and returns the logdir, or None if nothing was active). Captures
    record `profiler_captures_total` / `profiler_capture_seconds` on
    the monitor registry when monitoring is enabled, and a
    `profiler/capture` span on the tracer — so capture windows are
    visible on the same timeline as the fit spans they wrap."""

    def __init__(self, logdir: str, *, host_tracer_level: int = 2,
                 python_tracer_level: int = 0):
        self.logdir = str(logdir)
        self.host_tracer_level = host_tracer_level
        self.python_tracer_level = python_tracer_level
        self.active = False
        self._t0: Optional[float] = None
        self._span = None

    def start(self) -> "ProfilerCapture":
        if self.active:
            raise RuntimeError(
                f"ProfilerCapture already active (logdir={self.logdir})")
        import jax
        os.makedirs(self.logdir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = self.host_tracer_level
        options.python_tracer_level = self.python_tracer_level
        jax.profiler.start_trace(self.logdir, profiler_options=options)
        self.active = True
        self._t0 = time.perf_counter()
        from deeplearning4j_tpu import monitor
        if monitor.is_enabled():
            monitor.registry().counter(
                "profiler_captures_total",
                help="xplane capture windows started").inc()
            self._span = monitor.span("profiler/capture", logdir=self.logdir)
            self._span.__enter__()
        return self

    def stop(self) -> Optional[str]:
        if not self.active:
            return None
        import jax
        try:
            jax.profiler.stop_trace()
        finally:
            self.active = False
        dur = time.perf_counter() - (self._t0 or time.perf_counter())
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        from deeplearning4j_tpu import monitor
        if monitor.is_enabled():
            monitor.registry().gauge(
                "profiler_capture_seconds",
                help="duration of the last xplane capture window").set(dur)
        return self.logdir

    def __enter__(self) -> "ProfilerCapture":
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

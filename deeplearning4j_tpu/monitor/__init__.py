"""Unified telemetry core: metrics registry + span tracer + collectors.

One substrate answering "where did the step time go" across host, XLA
compile, and device (the per-phase timeline + counters discipline of
TensorFlow's runtime instrumentation, arXiv:1605.08695; the fleet
efficiency/resilience tracking the TPU survey arXiv:2606.15870 leans
on) — replacing the scattered clocks in `optimize/listeners.py`,
`ui/stats.py` and `parallel/stats.py` with one registry + one tracer
and three sinks:

- Prometheus text exposition at the UIServer's `/metrics` route,
- Chrome trace-event JSON (`export_chrome_trace`) for Perfetto,
- JSONL event logs (`Tracer.export_jsonl`, `MetricsRegistry.dump_jsonl`).

Usage::

    from deeplearning4j_tpu import monitor
    monitor.enable()                 # global registry + tracer live
    net.fit(x, y, epochs=2)          # spans + counters flow automatically
    monitor.tracer().export_chrome_trace("fit.trace.json")
    print(monitor.registry().exposition())

Overhead contract: with monitoring DISABLED (the default) the fit loops
pay one attribute check per iteration and insert **zero** additional
`block_until_ready` device syncs; enabling the registry/tracer adds
host-side float math only. The only opt-in syncs in the framework
remain `PerformanceListener(sync=True)` and `TrainingMasterStats`
phase timing — exactly as `parallel/stats.py` documents.
"""

from __future__ import annotations

import threading
from typing import List, Optional

from deeplearning4j_tpu.monitor.registry import (
    GLOBAL_REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Timer,
)
from deeplearning4j_tpu.monitor.tracer import (
    GLOBAL_TRACER,
    NOOP_SPAN,
    Span,
    Tracer,
)
from deeplearning4j_tpu.monitor.collectors import (
    DeviceMemoryCollector,
    JitCompileCollector,
    record_transfer as _record_transfer_impl,
)
from deeplearning4j_tpu.monitor.listener import MonitorListener, bind_master_stats
from deeplearning4j_tpu.monitor import diagnostics
from deeplearning4j_tpu.monitor.diagnostics import (
    Diagnostics,
    DiagnosticsConfig,
    NonFiniteGradientsError,
    resolve_diagnostics,
)
from deeplearning4j_tpu.monitor import xprof
from deeplearning4j_tpu.monitor.xprof import (
    ProfilerCapture,
    publish_cost_report,
    roofline,
)
from deeplearning4j_tpu.monitor import reqtrace
from deeplearning4j_tpu.monitor.reqtrace import (
    RequestTrace,
    clear_exemplar_sink,
    mint_trace_id,
    set_exemplar_sink,
)
from deeplearning4j_tpu.monitor import federate
from deeplearning4j_tpu.monitor.federate import (
    FederationCollector,
    FederationPublisher,
    MetricsAggregator,
    export_snapshot,
)
from deeplearning4j_tpu.monitor import slo
from deeplearning4j_tpu.monitor.slo import SLOObjective, SLOTracker
from deeplearning4j_tpu.monitor import flightrec
from deeplearning4j_tpu.monitor.flightrec import (
    GLOBAL_FLIGHT_RECORDER,
    FlightRecorder,
    flight_recorder,
)
from deeplearning4j_tpu.monitor import goodput
from deeplearning4j_tpu.monitor.goodput import (
    GOODPUT_CLASSES,
    GoodputLedger,
    ttft_decomposition,
)
from deeplearning4j_tpu.monitor import alerts
from deeplearning4j_tpu.monitor.alerts import (
    AlertEngine,
    AlertRule,
    default_rule_pack,
)

__all__ = [
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "Timer",
    "Tracer", "Span", "MonitorListener",
    "JitCompileCollector", "DeviceMemoryCollector",
    "enable", "disable", "is_enabled", "enabled", "registry", "tracer",
    "span", "record_transfer", "bind_master_stats", "attach_master_stats",
    "extra_listeners", "compile_collector", "memory_collector",
    "xprof", "ProfilerCapture", "roofline", "publish_cost_report",
    "diagnostics", "Diagnostics", "DiagnosticsConfig",
    "NonFiniteGradientsError", "resolve_diagnostics",
    "reqtrace", "RequestTrace", "mint_trace_id",
    "set_exemplar_sink", "clear_exemplar_sink",
    "federate", "MetricsAggregator", "FederationPublisher",
    "FederationCollector", "export_snapshot",
    "slo", "SLOObjective", "SLOTracker",
    "flightrec", "FlightRecorder", "flight_recorder",
    "GLOBAL_FLIGHT_RECORDER",
    "goodput", "GoodputLedger", "GOODPUT_CLASSES", "ttft_decomposition",
    "alerts", "AlertEngine", "AlertRule", "default_rule_pack",
]


class _MonitorState:
    def __init__(self):
        self.lock = threading.Lock()
        self.enabled = False
        self.registry: MetricsRegistry = GLOBAL_REGISTRY
        self.tracer: Tracer = GLOBAL_TRACER
        self.listener: Optional[MonitorListener] = None
        self.compile_collector: Optional[JitCompileCollector] = None
        self.memory_collector: Optional[DeviceMemoryCollector] = None


_STATE = _MonitorState()


def enable(registry: Optional[MetricsRegistry] = None,
           tracer: Optional[Tracer] = None, *,
           jit_compile: bool = True,
           device_memory: bool = True) -> MetricsRegistry:
    """Turn the telemetry substrate on (idempotent). Returns the active
    registry. `jit_compile` installs the compile-event collector;
    `device_memory` creates the HBM gauge collector (a no-op on
    backends without `memory_stats()`). Neither inserts device syncs."""
    with _STATE.lock:
        if registry is not None:
            _STATE.registry = registry
        if tracer is not None:
            _STATE.tracer = tracer
        _STATE.tracer.enabled = True
        # one emission, two clocks: every span also enters the profiler's
        # annotation, so it lies beside the device planes of a
        # `jax.profiler` trace (jax is imported here, not with the module)
        from jax.profiler import TraceAnnotation
        _STATE.tracer.annotation = TraceAnnotation
        # surface ring-buffer overflow: the tracer drops its OLDEST
        # event silently, so the loss count must be a visible metric
        _STATE.tracer._drop_counter = _STATE.registry.counter(
            "tracer_events_dropped_total",
            help="trace events evicted by the tracer ring buffer")
        _STATE.listener = MonitorListener(_STATE.registry)
        # a collector pointed at a superseded registry must be torn down
        # (jax's listener list is append-only: an orphaned active
        # collector would keep feeding — and pinning — the old registry)
        if (_STATE.compile_collector is not None
                and _STATE.compile_collector.registry is not _STATE.registry):
            _STATE.compile_collector.uninstall()
            _STATE.compile_collector = None
        if jit_compile:
            if _STATE.compile_collector is None:
                _STATE.compile_collector = JitCompileCollector(_STATE.registry)
            _STATE.compile_collector.install()
        elif _STATE.compile_collector is not None:
            _STATE.compile_collector.uninstall()
        if device_memory:
            _STATE.memory_collector = DeviceMemoryCollector(_STATE.registry)
        else:
            _STATE.memory_collector = None
        _STATE.enabled = True
        return _STATE.registry


def disable():
    """Back to zero-cost: fit loops skip spans/counters entirely."""
    with _STATE.lock:
        _STATE.enabled = False
        _STATE.tracer.enabled = False
        _STATE.tracer.annotation = None
        if _STATE.compile_collector is not None:
            _STATE.compile_collector.uninstall()
        _STATE.listener = None


def is_enabled() -> bool:
    return _STATE.enabled


enabled = is_enabled  # alias


def registry() -> MetricsRegistry:
    return _STATE.registry


def resolve_cached_metrics(obj, cache_attr: str, build):
    """Shared resolve-and-cache for hot-loop metric families (the
    serving scheduler, fleet publisher, router, registry and the
    ParallelInference collector all use this): None when monitoring is
    off; otherwise whatever `build(registry)` returns, resolved ONCE
    per active registry — child lookups hit the registry lock, and an
    `enable(registry=)` swap invalidates the cache by identity. The
    cache lives on `obj.<cache_attr>` as an `(registry, families)`
    pair."""
    if not is_enabled():
        return None
    reg = _STATE.registry
    cache = getattr(obj, cache_attr, None)
    if cache is not None and cache[0] is reg:
        return cache[1]
    m = build(reg)
    setattr(obj, cache_attr, (reg, m))
    return m


def tracer() -> Tracer:
    return _STATE.tracer


def compile_collector() -> Optional[JitCompileCollector]:
    return _STATE.compile_collector


def memory_collector() -> Optional[DeviceMemoryCollector]:
    return _STATE.memory_collector


def span(name: str, **args):
    """`with monitor.span("fit/forward_backward"): ...` — NOOP_SPAN when
    disabled (no allocation, no clock read); enabled, one span in the
    tracer's ring and, while a `jax.profiler` trace is open, the event
    `dl4tpu/<name>` on this thread's line of its host plane."""
    if not _STATE.enabled:
        return NOOP_SPAN
    return _STATE.tracer.span(name, **args)


def record_transfer(nbytes: int, direction: str = "h2d"):
    """Host↔device placement counter hook (called by
    `parallel/placement.gput`); no-op when disabled."""
    if _STATE.enabled:
        _record_transfer_impl(_STATE.registry, nbytes, direction)


def extra_listeners() -> List:
    """The auto-attached listener set for fit loops: `[MonitorListener]`
    when enabled, `[]` when not. Containers call this when composing
    their listener bus so every fit feeds the registry."""
    l = _STATE.listener
    return [l] if (_STATE.enabled and l is not None) else []


def attach_master_stats(stats):
    """Route a TrainingMasterStats' phase events onto the active
    registry/tracer (no-op when disabled; idempotent per stats object —
    the trainers call this at every fit()). The binding resolves the
    registry/tracer at EVENT time, so a later `enable(registry=...)`
    swap redirects an already-bound stats object to the new sinks (and
    `disable()` mutes it). Returns `stats`."""
    if (_STATE.enabled and stats is not None
            and not getattr(stats, "_monitor_bound", False)):
        from deeplearning4j_tpu.monitor.listener import record_master_event
        t0_perf = getattr(stats, "_t0", None)

        def on_event(ev):
            if _STATE.enabled:
                record_master_event(ev, _STATE.registry, _STATE.tracer,
                                    t0_perf)

        stats.add_listener(on_event)
        stats._monitor_bound = True
    return stats

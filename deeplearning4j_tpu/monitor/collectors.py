"""JAX runtime collectors feeding the metrics registry.

Four windows into the runtime the host-side spans can't see:

- `JitCompileCollector` — compile-cache tracking via `jax.monitoring`
  stage events (`/jax/core/compile/*`): compile count + compile seconds
  by stage, each stage counted once where its events nest, so
  bench/dashboards can split warmup (trace + lowering + XLA compile)
  from steady-state device time.
- `GcPauseCollector` — Python's collector pauses by generation, for
  the duration of a compile phase only.
- `DeviceMemoryCollector` — per-device HBM gauges from
  `device.memory_stats()` where the backend provides it (TPU/GPU; CPU
  returns None and the collector reports itself unavailable).
- transfer counters — host→device placements recorded by the trainers'
  placement helpers (`parallel/placement.gput`) when monitoring is on.

None of these insert device syncs: compile events are host callbacks,
`memory_stats()` reads allocator bookkeeping, and transfer counters
count the placements the program was doing anyway — the "zero extra
syncs when disabled" contract (see parallel/stats.py) extends to
"zero extra syncs when ENABLED" for every collector here.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Dict, Optional

from deeplearning4j_tpu.monitor.registry import MetricsRegistry


class JitCompileCollector:
    """Counts jit compiles and accumulates compile seconds by stage.

    Two `jax.monitoring` listeners: JAX records a scalar (the start
    time) as each stage of `/jax/core/compile/*` begins and the stage's
    duration as it ends. Stages of one kind NEST: every `jax.jit` (and,
    under JAX 0.9, every primitive) traced inside an outer trace records
    its own `jaxpr_trace_duration` within the outer one's. So the
    collector keeps a per-thread, per-stage depth and adds a stage's
    seconds only when its OUTERMOST event ends: each stage's seconds are
    counted once per thread, and no more than the wall clock that holds
    them. `jax_compiles_total` counts every backend compile event
    (compiled, or loaded from the persistent cache) as it always did.
    `uninstall()` unregisters both listeners.
    """

    _PREFIX = "/jax/core/compile/"
    # the event that fires once per actual XLA compilation
    _BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, registry: MetricsRegistry):
        self.registry = registry
        self._active = False
        self._registered = False
        self._depth = threading.local()

    @property
    def installed(self) -> bool:
        return self._registered

    def install(self) -> "JitCompileCollector":
        self._active = True
        if not self._registered:
            import jax.monitoring
            jax.monitoring.register_scalar_listener(self._on_start)
            jax.monitoring.register_event_duration_secs_listener(
                self._on_event)
            self._registered = True
        return self

    def uninstall(self):
        self._active = False
        if self._registered:
            import jax.monitoring
            jax.monitoring.unregister_scalar_listener(self._on_start)
            jax.monitoring.unregister_event_duration_listener(self._on_event)
            self._registered = False

    def _open(self) -> Dict[str, int]:
        d = getattr(self._depth, "stages", None)
        if d is None:
            d = self._depth.stages = {}
        return d

    def _on_start(self, event: str, value, **kwargs):
        if self._active and event.startswith(self._PREFIX):
            d = self._open()
            d[event] = d.get(event, 0) + 1

    def _on_event(self, event: str, duration_secs: float, **kwargs):
        if not self._active or not event.startswith(self._PREFIX):
            return
        d = self._open()
        # an end with no start seen (installed mid-stage) counts whole
        depth = max(0, d.get(event, 0) - 1)
        d[event] = depth
        if depth == 0:
            self.registry.counter(
                "jax_compile_seconds_total",
                help="jit compile time by stage, the outermost event of "
                     "each stage on each thread",
                stage=event[len(self._PREFIX):]).inc(duration_secs)
        if event == self._BACKEND_EVENT:
            self.registry.counter(
                "jax_compiles_total",
                help="number of XLA backend compilations").inc()

    # convenience readers (bench warmup/steady-state split)
    def compile_count(self) -> float:
        return self.registry.counter("jax_compiles_total").value

    def stage_seconds(self) -> Dict[str, float]:
        fam = self.registry._families.get("jax_compile_seconds_total")
        if fam is None:
            return {}
        with self.registry._lock:
            return {dict(k)["stage"]: c.value
                    for k, c in fam.children.items()}

    def compile_seconds(self) -> float:
        return sum(self.stage_seconds().values())


class GcPauseCollector:
    """Python's collector pauses, from `gc.callbacks`: at each
    collection's `stop` the pause since its `start` on the same thread
    (`time.perf_counter`) is added to
    `process_gc_pause_seconds_total{generation}`. No span per
    collection. Installed only round a phase that asks for it
    (`monitor.compile_phase`), never while a server serves."""

    def __init__(self, registry: MetricsRegistry):
        self.registry = registry
        self._start = threading.local()

    def install(self) -> "GcPauseCollector":
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)
        return self

    def uninstall(self):
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: Dict):
        if phase == "start":
            self._start.t = time.perf_counter()
            return
        t0 = getattr(self._start, "t", None)
        if t0 is None:
            return
        self._start.t = None
        self.registry.counter(
            "process_gc_pause_seconds_total",
            help="Python collector pauses by generation",
            generation=str(info.get("generation"))).inc(
                time.perf_counter() - t0)

    def seconds(self) -> float:
        fam = self.registry._families.get("process_gc_pause_seconds_total")
        if fam is None:
            return 0.0
        return sum(c.value for c in fam.children.values())


class DeviceMemoryCollector:
    """Device memory gauges from `device.memory_stats()`.

    `collect()` refreshes the gauges; call it wherever a fresh reading
    matters (epoch end, /metrics scrape). Backends without allocator
    stats (XLA:CPU) make this a no-op with `available == False`."""

    _KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
             "largest_alloc_size")

    def __init__(self, registry: MetricsRegistry):
        self.registry = registry
        self.available: Optional[bool] = None

    def collect(self) -> bool:
        import jax
        seen = False
        for d in jax.local_devices():
            try:
                stats = d.memory_stats()
            except Exception:  # noqa: BLE001 — backend-dependent API
                stats = None
            if not stats:
                continue
            seen = True
            for key in self._KEYS:
                if key in stats:
                    self.registry.gauge(
                        "jax_device_memory_bytes",
                        help="device allocator stats",
                        device=str(d.id), kind=key).set(float(stats[key]))
        self.available = seen
        return seen


def record_transfer(registry: MetricsRegistry, nbytes: int, direction: str = "h2d"):
    """One host↔device placement: bump count + byte counters."""
    registry.counter("jax_transfers_total",
                     help="array placements host<->device",
                     direction=direction).inc()
    registry.counter("jax_transfer_bytes_total",
                     help="bytes moved host<->device",
                     direction=direction).inc(float(max(0, nbytes)))

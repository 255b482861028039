"""Bridges from the existing telemetry surfaces onto the registry.

- `MonitorListener` rides the `TrainingListener` bus every container
  already fans out to (`optimize/listeners.py`), turning iteration/epoch
  callbacks into registry counters/gauges/histograms. When monitoring
  is enabled the fit loops attach one automatically (see
  `monitor.extra_listeners()`), so ANY fit feeds `/metrics` without
  code changes at the call site.
- `bind_master_stats` hooks a `TrainingMasterStats` (parallel trainers'
  per-phase round timing) via its `add_listener` seam: every phase
  event lands in the registry as a labeled phase timer AND in the
  tracer as a complete-event span, so the distributed phases appear on
  the same Perfetto timeline as the single-model fit spans.
"""

from __future__ import annotations

import time
from typing import Optional

from deeplearning4j_tpu.monitor.registry import MetricsRegistry
from deeplearning4j_tpu.monitor.tracer import Tracer

from deeplearning4j_tpu.optimize.listeners import TrainingListener


class MonitorListener(TrainingListener):
    """TrainingListener → MetricsRegistry adapter.

    Pure host-side arithmetic on values the fit loop already computed —
    no device syncs, honoring the zero-cost contract."""

    def __init__(self, registry: MetricsRegistry, model_label: str = "default"):
        self.registry = registry
        self.model_label = model_label

    def iteration_done(self, model, iteration, epoch, score, **info):
        reg = self.registry
        lbl = {"model": self.model_label}
        reg.counter("training_iterations_total",
                    help="fit iterations completed", **lbl).inc()
        batch = info.get("batch_size", 0)
        if batch:
            reg.counter("training_examples_total",
                        help="examples trained", **lbl).inc(float(batch))
        score = float(score)
        if score == score:  # skip NaN (score not read back this step)
            reg.gauge("training_score", help="last minibatch loss",
                      **lbl).set(score)
        etl_ms = info.get("etl_ms")
        if etl_ms:
            reg.histogram("training_etl_seconds",
                          help="dataset ETL time per batch",
                          **lbl).observe(float(etl_ms) / 1e3)

    def on_epoch_end(self, model, epoch):
        self.registry.counter("training_epochs_total",
                              help="fit epochs completed",
                              model=self.model_label).inc()

    def on_fit_start(self, model):
        self.registry.counter("training_fits_total",
                              help="fit() calls started",
                              model=self.model_label).inc()


def record_master_event(ev, registry: MetricsRegistry,
                        tracer: Optional[Tracer] = None,
                        t0_perf: Optional[float] = None):
    """Land one `TrainingMasterStats` phase event in the registry
    (+ tracer), on the tracer's one clock. `t0_perf` is the stats
    object's `time.perf_counter()` epoch, against which the event's
    `start_ms` is read; a stats object that keeps none gets the span
    ended now, which is when a phase's event is recorded."""
    phase = ev.get("phase", "unknown")
    dur_s = ev.get("duration_ms", 0.0) / 1e3
    registry.counter("parallel_phase_total",
                     help="distributed-training phase occurrences",
                     phase=phase).inc()
    registry.timer("parallel_phase_seconds",
                   help="distributed-training phase durations",
                   phase=phase).observe(dur_s)
    if tracer is not None:
        extra = {k: v for k, v in ev.items()
                 if k not in ("phase", "start_ms", "duration_ms")}
        start = (time.perf_counter() - dur_s if t0_perf is None
                 else t0_perf + ev.get("start_ms", 0.0) / 1e3)
        tracer.complete_between(f"master/{phase}", start, start + dur_s,
                                **extra)


def bind_master_stats(stats, registry: MetricsRegistry,
                      tracer: Optional[Tracer] = None):
    """Route every `TrainingMasterStats` phase event onto the registry
    (+ tracer). Returns `stats` for chaining."""
    t0_perf = getattr(stats, "_t0", None)

    def on_event(ev):
        record_master_event(ev, registry, tracer, t0_perf)

    stats.add_listener(on_event)
    return stats

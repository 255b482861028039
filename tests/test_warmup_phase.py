"""Set-up timed where it happens (ISSUE 39, tracing).

Contracts:

- `JitCompileCollector` counts each compile stage once per thread where
  its events nest (a `jax.jit` traced inside a trace records its own
  `jaxpr_trace_duration` within the outer one's), so a stage's seconds
  never pass the wall clock that holds them; `jax_compiles_total` still
  counts every backend compile event;
- `uninstall()` unregisters its `jax.monitoring` listeners: none is left
  that still fires;
- monitoring on, `GenerationServer.warmup()` is the compile phase
  `serve/warmup`: one span, a `serve/warmup/wave` span a wave, and the
  families `serving_warmup_seconds_total`,
  `serving_warmup_stage_seconds_total{stage}` and
  `serving_warmup_programs_total`, read off the one collector; the
  collector and the `gc` hook it needs are installed for the grid alone
  and put back as they were;
- monitoring off, `warmup()` leaves `gc.callbacks`, JAX's listeners and
  the registry exactly as they were.
"""

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring as jax_monitoring

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.monitor import (GcPauseCollector, JitCompileCollector,
                                        MetricsRegistry, Tracer)
from deeplearning4j_tpu.serving import GenerationServer
from deeplearning4j_tpu.zoo.transformer import TransformerLM

STAGES = ("trace", "lower", "compile", "gc")


def _listeners():
    return (jax_monitoring.get_event_listeners(),
            jax_monitoring.get_event_duration_listeners(),
            jax_monitoring.get_event_time_span_listeners(),
            jax_monitoring.get_scalar_listeners())


def _monitor_off():
    monitor.disable()
    monitor._STATE.registry = monitor.GLOBAL_REGISTRY
    monitor._STATE.tracer = monitor.GLOBAL_TRACER
    monitor._STATE.compile_collector = None


@pytest.fixture
def mon_nojit():
    """Monitoring as the benchmark's traced serving run enables it."""
    reg, tr = MetricsRegistry(), Tracer()
    monitor.enable(registry=reg, tracer=tr, jit_compile=False,
                   device_memory=False)
    yield reg, tr
    _monitor_off()


def _server(n_slots=2):
    # a fresh net each time: its serving programs are its own, so the
    # grid traces and compiles (or loads) them anew
    net = TransformerLM(vocab_size=23, d_model=16, n_layers=2, n_heads=4,
                        max_len=32, seed=3).init()
    return GenerationServer(net, n_slots=n_slots, n_blocks=16, block_len=4)


def _family(reg, name, **labels):
    fam = reg.snapshot().get(name)
    if fam is None:
        return None
    return sum(v["value"] for v in fam["values"]
               if all(v["labels"].get(k) == x for k, x in labels.items()))


class TestCollectorOncePerStage:
    def test_nested_jit_trace_counted_once(self):
        reg = MetricsRegistry()
        coll = JitCompileCollector(reg).install()
        try:
            @jax.jit
            def inner(x):
                return jnp.sin(x) * 2.0

            @jax.jit
            def outer(x):
                return inner(x) + inner(x * 3.0) + jnp.cos(x)

            t0 = time.perf_counter()
            outer(jnp.ones(41, jnp.float32)).block_until_ready()
            wall = time.perf_counter() - t0
        finally:
            coll.uninstall()
        stages = coll.stage_seconds()
        assert stages.get("jaxpr_trace_duration", 0.0) > 0.0
        for stage, secs in stages.items():
            assert secs <= wall, (stage, secs, wall)
        assert coll.compile_seconds() <= wall
        assert coll.compile_count() >= 1

    def test_depth_counts_the_outermost_event_alone(self):
        reg = MetricsRegistry()
        coll = JitCompileCollector(reg)
        coll._active = True
        ev = "/jax/core/compile/jaxpr_trace_duration"
        coll._on_start(ev, 0.0)        # outer begins
        coll._on_start(ev, 0.0)        # a nested jit begins
        coll._on_event(ev, 0.25)       # ... and ends: inside the outer
        coll._on_event(ev, 1.0)        # the outer ends
        coll._on_event("/jax/core/compile/backend_compile_duration", 0.5)
        assert coll.stage_seconds() == {"jaxpr_trace_duration": 1.0,
                                        "backend_compile_duration": 0.5}
        assert coll.compile_count() == 1

    def test_depth_is_per_thread(self):
        import threading
        reg = MetricsRegistry()
        coll = JitCompileCollector(reg)
        coll._active = True
        ev = "/jax/core/compile/jaxpr_trace_duration"
        coll._on_start(ev, 0.0)        # this thread's trace stays open
        t = threading.Thread(target=lambda: (coll._on_start(ev, 0.0),
                                             coll._on_event(ev, 0.75)))
        t.start()
        t.join()
        # the other thread's outermost event counts whole
        assert coll.stage_seconds()["jaxpr_trace_duration"] == 0.75
        coll._on_event(ev, 2.0)
        assert coll.stage_seconds()["jaxpr_trace_duration"] == 2.75


class TestUninstall:
    def test_uninstall_leaves_no_listener_that_fires(self):
        before = _listeners()
        reg = MetricsRegistry()
        coll = JitCompileCollector(reg).install()
        assert coll.installed
        assert _listeners() != before
        coll.uninstall()
        assert not coll.installed
        assert _listeners() == before

        @jax.jit
        def f(x):
            return (x * 5.0 - 2.0).max()

        f(np.arange(53, dtype=np.float32)).block_until_ready()
        assert coll.compile_count() == 0
        assert coll.stage_seconds() == {}

    def test_install_twice_registers_once(self):
        before = _listeners()
        coll = JitCompileCollector(MetricsRegistry()).install().install()
        coll.uninstall()
        coll.uninstall()
        assert _listeners() == before

    def test_gc_collector_counts_pauses_and_goes(self):
        before = list(gc.callbacks)
        reg = MetricsRegistry()
        gcc = GcPauseCollector(reg).install()
        try:
            gc.collect()
        finally:
            gcc.uninstall()
        assert gc.callbacks == before
        assert gcc.seconds() > 0.0
        fam = reg.snapshot()["process_gc_pause_seconds_total"]
        assert {v["labels"]["generation"] for v in fam["values"]} >= {"2"}


class TestWarmupPhase:
    def test_warmup_fills_the_families_and_spans(self, mon_nojit):
        reg, tr = mon_nojit
        srv = _server()
        # an independent count of the backend compile events of the grid
        probe = JitCompileCollector(MetricsRegistry()).install()
        try:
            t0 = time.perf_counter()
            srv.warmup(6)
            wall = time.perf_counter() - t0
        finally:
            probe.uninstall()
            srv.stop()
        warm = _family(reg, "serving_warmup_seconds_total")
        assert 0.0 < warm <= wall
        stages = {s: _family(reg, "serving_warmup_stage_seconds_total",
                             stage=s) for s in STAGES}
        assert all(v is not None and v >= 0.0 for v in stages.values())
        assert stages["trace"] > 0.0 and stages["compile"] > 0.0
        assert (stages["trace"] + stages["lower"] + stages["compile"]
                <= warm * 1.05)
        programs = _family(reg, "serving_warmup_programs_total")
        assert programs == probe.compile_count() > 0
        names = tr.span_names()
        assert names.get("serve/warmup") == 1
        top = next(e for e in tr.events() if e["name"] == "serve/warmup")
        assert top["args"]["widths"] == [1, 2]
        assert top["args"]["programs_before"] == 0
        buckets = top["args"]["buckets"]
        waves = [e for e in tr.events() if e["name"] == "serve/warmup/wave"]
        # every (width, bucket) pair, greedy and sampled, inside the grid
        assert sorted((e["args"]["width"], e["args"]["bucket"],
                       e["args"]["sampled"]) for e in waves) == sorted(
            (w, b, s) for w in (1, 2) for b in buckets for s in (False, True))
        assert all(top["ts"] <= e["ts"] and e["ts"] + e["dur"]
                   <= top["ts"] + top["dur"] for e in waves)
        # the phase put back what `enable(jit_compile=False)` left
        assert monitor.compile_collector() is None

    def test_collector_of_jit_compile_true_stays_installed(self):
        reg, tr = MetricsRegistry(), Tracer()
        monitor.enable(registry=reg, tracer=tr, device_memory=False)
        try:
            coll = monitor.compile_collector()
            assert coll is not None and coll.installed
            srv = _server()
            srv.warmup(4)
            srv.stop()
            assert monitor.compile_collector() is coll
            assert coll.installed
            assert _family(reg, "serving_warmup_programs_total") > 0
        finally:
            _monitor_off()

    def test_uninstalled_collector_is_put_back_uninstalled(self, mon_nojit):
        reg, _ = mon_nojit
        listeners, hooks = _listeners(), list(gc.callbacks)
        srv = _server()
        srv.warmup(4)
        srv.stop()
        assert _listeners() == listeners
        assert gc.callbacks == hooks
        assert monitor.compile_collector() is None
        assert _family(reg, "serving_warmup_stage_seconds_total",
                       stage="gc") >= 0.0

    def test_nested_phases_restore_once(self, mon_nojit):
        reg, _ = mon_nojit
        listeners, hooks = _listeners(), list(gc.callbacks)
        with monitor.compile_phase("outer", "outer_phase"):
            assert monitor.compile_collector().installed
            with monitor.compile_phase("inner", "inner_phase"):
                pass
            # the outer phase still counts after the inner one closed
            assert monitor.compile_collector().installed
            assert len(gc.callbacks) == len(hooks) + 1
        assert _listeners() == listeners and gc.callbacks == hooks
        assert _family(reg, "outer_phase_seconds_total") > 0.0
        assert _family(reg, "inner_phase_programs_total") == 0.0


class TestWarmupMonitoringOff:
    def test_warmup_installs_nothing(self):
        assert not monitor.is_enabled()
        srv = _server()
        listeners, hooks = _listeners(), list(gc.callbacks)
        reg_before = monitor.registry().snapshot()
        ring_before = monitor.tracer().events()
        collector = monitor.compile_collector()
        srv.warmup(6)
        srv.stop()
        assert _listeners() == listeners
        assert gc.callbacks == hooks
        assert monitor.registry().snapshot() == reg_before
        assert monitor.tracer().events() == ring_before
        assert monitor.compile_collector() is collector

    def test_phase_off_is_the_noop_span(self):
        with monitor.compile_phase("serve/warmup", "serving_warmup") as sp:
            assert sp is monitor.NOOP_SPAN

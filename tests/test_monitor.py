"""Unified telemetry core tests: metrics registry, span tracer, JAX
runtime collectors, fit-loop integration, `/metrics` exposition on
UIServer, Perfetto (Chrome trace) export — and the overhead contract:
a fit with monitoring disabled performs ZERO additional device syncs.
"""

import json
import re
import urllib.request

import numpy as np
import pytest

import jax

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.common.updaters import Adam
from deeplearning4j_tpu.monitor import (
    DeviceMemoryCollector,
    JitCompileCollector,
    MetricsRegistry,
    MonitorListener,
    Tracer,
    bind_master_stats,
)
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.listeners import PerformanceListener
from deeplearning4j_tpu.ui import UIServer


def _net(seed=11):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-2))
            .list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
            .layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                               loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _data(n=32):
    rng = np.random.default_rng(1)
    x = rng.standard_normal((n, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return x, y


@pytest.fixture
def mon():
    """Fresh registry+tracer swapped in globally; full restore after."""
    reg, tr = MetricsRegistry(), Tracer()
    monitor.enable(registry=reg, tracer=tr)
    yield reg, tr
    monitor.disable()
    monitor._STATE.registry = monitor.GLOBAL_REGISTRY
    monitor._STATE.tracer = monitor.GLOBAL_TRACER


# the exposition grammar we promise scrapers (Prometheus text 0.0.4)
_EXPO_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"(\\.|[^\"\\])*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"(\\.|[^\"\\])*\")*\})?"
    r" (\+Inf|-Inf|NaN|[-+0-9.e]+)$")


def _assert_exposition_parses(text):
    assert text.endswith("\n")
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert _EXPO_LINE.match(line), f"bad exposition line: {line!r}"


class TestMetricsRegistry:
    def test_counter_gauge_semantics(self):
        reg = MetricsRegistry()
        c = reg.counter("reqs_total", help="requests")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)
        g = reg.gauge("queue_depth")
        g.set(7)
        g.dec(3)
        assert g.value == 4.0
        g.set_function(lambda: 42.0)
        assert g.value == 42.0

    def test_labeled_children_are_stable(self):
        reg = MetricsRegistry()
        a = reg.counter("phase_total", phase="fit")
        b = reg.counter("phase_total", phase="eval")
        assert a is not b
        assert reg.counter("phase_total", phase="fit") is a

    def test_kind_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("x_total")
        with pytest.raises(ValueError):
            reg.gauge("x_total")

    def test_histogram_buckets_cumulative(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        assert h.count == 3 and h.sum == pytest.approx(5.55)
        assert h.cumulative_counts() == [1, 2, 3]

    def test_timer_context_manager(self):
        reg = MetricsRegistry()
        t = reg.timer("step_seconds")
        with t.time():
            pass
        assert t.count == 1 and t.sum >= 0.0

    def test_exposition_format(self):
        reg = MetricsRegistry()
        reg.counter("a_total", help="a counter", model="m\"x\n").inc()
        reg.gauge("b").set(float("inf"))
        reg.histogram("h_seconds", buckets=(1.0,)).observe(0.5)
        text = reg.exposition()
        _assert_exposition_parses(text)
        assert "# TYPE a_total counter" in text
        assert "# TYPE h_seconds histogram" in text
        assert 'le="+Inf"' in text and "h_seconds_count" in text

    def test_snapshot_and_jsonl(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("n_total", phase="x").inc(3)
        reg.histogram("d_seconds").observe(0.2)
        snap = reg.snapshot()
        assert snap["n_total"]["values"][0]["value"] == 3.0
        p = reg.dump_jsonl(str(tmp_path / "metrics.jsonl"), run="r1")
        rec = json.loads(open(p).read().splitlines()[0])
        assert rec["kind"] == "metrics" and rec["run"] == "r1"


class TestTracer:
    def test_span_roundtrip_and_nesting(self):
        tr = Tracer()
        with tr.span("outer", phase="fit"):
            with tr.span("inner"):
                pass
        names = tr.span_names()
        assert names == {"outer": 1, "inner": 1}
        evs = {e["name"]: e for e in tr.events()}
        # inner's window sits inside outer's (Perfetto reconstructs
        # nesting from enclosing timestamps)
        assert evs["inner"]["ts"] >= evs["outer"]["ts"]
        assert (evs["inner"]["ts"] + evs["inner"]["dur"]
                <= evs["outer"]["ts"] + evs["outer"]["dur"] + 1e-3)
        assert evs["outer"]["args"]["phase"] == "fit"

    def test_chrome_trace_json_roundtrip(self, tmp_path):
        tr = Tracer()
        with tr.span("s1"):
            pass
        tr.instant("marker", note="here")
        path = str(tmp_path / "trace.json")
        doc = json.loads(tr.export_chrome_trace(path))
        assert json.loads(open(path).read()) == doc
        assert {e["name"] for e in doc["traceEvents"]} == {"s1", "marker"}
        for ev in doc["traceEvents"]:
            assert {"name", "ph", "ts", "pid", "tid"} <= set(ev)

    def test_disabled_tracer_records_nothing(self):
        tr = Tracer(enabled=False)
        with tr.span("x"):
            pass
        tr.instant("y")
        tr.complete_between("z", 0.0, 1.0)
        assert tr.events() == []

    def test_ring_buffer_bounds_memory(self):
        tr = Tracer(max_events=10)
        for i in range(50):
            with tr.span(f"s{i}"):
                pass
        assert len(tr.events()) == 10

    def test_error_span_tagged(self):
        tr = Tracer()
        with pytest.raises(RuntimeError):
            with tr.span("boom"):
                raise RuntimeError("x")
        assert tr.events()[0]["args"]["error"] == "RuntimeError"

    def test_export_jsonl(self, tmp_path):
        tr = Tracer()
        with tr.span("a"):
            pass
        p = tr.export_jsonl(str(tmp_path / "spans.jsonl"))
        rec = json.loads(open(p).read().splitlines()[0])
        assert rec["kind"] == "span" and rec["name"] == "a"


class TestCollectors:
    def test_jit_compile_collector_events(self):
        reg = MetricsRegistry()
        coll = JitCompileCollector(reg)
        coll._active = True
        coll._on_event("/jax/core/compile/backend_compile_duration", 1.5)
        coll._on_event("/jax/core/compile/jaxpr_to_mlir_module_duration", 0.5)
        coll._on_event("/jax/unrelated/event", 9.0)
        assert coll.compile_count() == 1
        assert coll.compile_seconds() == pytest.approx(2.0)
        coll.uninstall()
        coll._on_event("/jax/core/compile/backend_compile_duration", 1.0)
        assert coll.compile_count() == 1

    def test_real_compile_lands_in_registry(self, mon):
        reg, _ = mon
        # a never-seen shape forces a fresh XLA compile; the installed
        # jax.monitoring listener must route its duration into the registry
        @jax.jit
        def f(x):
            return (x * 2.0 + 1.0).sum()

        f(np.arange(37, dtype=np.float32)).block_until_ready()
        fam = reg._families.get("jax_compile_seconds_total")
        assert fam is not None and len(fam.children) >= 1

    def test_device_memory_collector_no_crash(self):
        reg = MetricsRegistry()
        coll = DeviceMemoryCollector(reg)
        ok = coll.collect()
        assert coll.available is ok
        if ok:  # TPU/GPU: gauges exist
            assert "jax_device_memory_bytes" in reg.exposition()

    def test_transfer_counters_gated_on_enabled(self, mon):
        reg, _ = mon
        monitor.record_transfer(1024, "h2d")
        assert reg.counter("jax_transfers_total", direction="h2d").value == 1
        assert reg.counter("jax_transfer_bytes_total",
                           direction="h2d").value == 1024
        monitor.disable()
        monitor.record_transfer(1024, "h2d")
        assert reg.counter("jax_transfers_total", direction="h2d").value == 1


class TestMonitorListener:
    def test_iteration_feeds_registry(self):
        reg = MetricsRegistry()
        lst = MonitorListener(reg)
        lst.on_fit_start(None)
        lst.iteration_done(None, 0, 0, 0.7, batch_size=16, etl_ms=2.0)
        lst.iteration_done(None, 1, 0, float("nan"), batch_size=16)
        lst.on_epoch_end(None, 0)
        assert reg.counter("training_iterations_total",
                           model="default").value == 2
        assert reg.counter("training_examples_total",
                           model="default").value == 32
        # NaN score (not read back) must not clobber the gauge
        assert reg.gauge("training_score", model="default").value == 0.7
        assert reg.histogram("training_etl_seconds",
                             model="default").count == 1
        assert reg.counter("training_epochs_total",
                           model="default").value == 1


class TestFitIntegration:
    def test_fit_feeds_metrics_and_spans(self, mon):
        reg, tr = mon
        net = _net()
        x, y = _data()
        net.fit(x, y, epochs=2, batch_size=8)
        # counters: 4 batches x 2 epochs
        assert reg.counter("training_iterations_total",
                           model="default").value == 8
        assert reg.counter("training_examples_total",
                           model="default").value == 64
        assert reg.counter("training_fits_total", model="default").value == 1
        assert reg.counter("training_epochs_total", model="default").value == 2
        text = reg.exposition()
        _assert_exposition_parses(text)
        assert "training_iterations_total" in text
        # >= 1 span per fit phase, loadable Chrome trace JSON
        names = tr.span_names()
        for phase in ("fit/etl", "fit/forward_backward", "fit/update"):
            assert names.get(phase, 0) >= 1, names
        doc = json.loads(tr.export_chrome_trace())
        assert len(doc["traceEvents"]) >= 3

    def test_metrics_route_serves_exposition(self, mon):
        reg, _ = mon
        net = _net()
        x, y = _data()
        net.fit(x, y, epochs=1, batch_size=8)
        server = UIServer().start()
        try:
            resp = urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics")
            body = resp.read().decode()
            assert resp.headers["Content-Type"].startswith("text/plain")
            _assert_exposition_parses(body)
            assert "training_iterations_total" in body
        finally:
            server.stop()

    def test_metrics_route_with_explicit_registry(self):
        reg = MetricsRegistry()
        reg.counter("custom_total").inc(5)
        server = UIServer(registry=reg).start()
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics").read().decode()
            assert "custom_total 5.0" in body
        finally:
            server.stop()

    def test_disabled_fit_untouched(self):
        assert not monitor.is_enabled()
        before = monitor.GLOBAL_REGISTRY.snapshot()
        net = _net()
        x, y = _data()
        net.fit(x, y, epochs=1, batch_size=8)
        assert monitor.GLOBAL_REGISTRY.snapshot() == before
        assert monitor.extra_listeners() == []


class TestOverheadContract:
    """Monitoring must never insert device syncs behind the user's back:
    zero `block_until_ready` calls with it disabled AND enabled; the
    only opt-in is PerformanceListener(sync=True)."""

    @pytest.fixture
    def sync_counter(self, monkeypatch):
        calls = {"n": 0}
        real = jax.block_until_ready

        def counting(*a, **k):
            calls["n"] += 1
            return real(*a, **k)

        monkeypatch.setattr(jax, "block_until_ready", counting)
        return calls

    def test_disabled_fit_zero_syncs(self, sync_counter):
        net = _net()
        x, y = _data()
        net.fit(x, y, epochs=2, batch_size=8)
        assert sync_counter["n"] == 0

    def test_enabled_fit_zero_syncs(self, mon, sync_counter):
        net = _net()
        x, y = _data()
        net.fit(x, y, epochs=2, batch_size=8)
        assert sync_counter["n"] == 0

    def test_performance_listener_sync_opt_in(self, sync_counter):
        net = _net()
        x, y = _data()
        net.set_listeners(PerformanceListener(printer=lambda s: None))
        net.fit(x, y, epochs=1, batch_size=8)
        assert sync_counter["n"] == 0  # default stays async
        net.set_listeners(PerformanceListener(printer=lambda s: None,
                                              sync=True))
        net.fit(x, y, epochs=1, batch_size=8)
        assert sync_counter["n"] == 4  # one per iteration


class TestPerformanceListener:
    def test_zero_dt_emits_zero_not_inf(self, monkeypatch):
        import deeplearning4j_tpu.optimize.listeners as L
        monkeypatch.setattr(L.time, "perf_counter", lambda: 123.0)
        lst = PerformanceListener(printer=lambda s: None)
        lst.iteration_done(None, 0, 0, 0.5, batch_size=8)
        lst.iteration_done(None, 1, 0, 0.5, batch_size=8)
        rec = lst.history[-1]
        assert rec["batches_per_sec"] == 0.0
        assert rec["samples_per_sec"] == 0.0
        json.dumps(rec)  # inf would raise in strict JSON consumers


class TestStatsRssNormalization:
    def test_linux_kb_and_darwin_bytes(self, monkeypatch):
        import deeplearning4j_tpu.ui.stats as S

        class RU:
            ru_maxrss = 512 * 1024  # 512 MB expressed in KB (Linux)

        monkeypatch.setattr(S.resource, "getrusage", lambda _: RU)
        monkeypatch.setattr(S.sys, "platform", "linux")
        assert S._rss_mb() == pytest.approx(512.0)
        RU.ru_maxrss = 512 * 1024 * 1024  # same 512 MB in bytes (macOS)
        monkeypatch.setattr(S.sys, "platform", "darwin")
        assert S._rss_mb() == pytest.approx(512.0)


class TestMasterStatsBridge:
    def test_bind_master_stats_routes_phases(self):
        from deeplearning4j_tpu.parallel import TrainingMasterStats
        reg, tr = MetricsRegistry(), Tracer()
        stats = bind_master_stats(TrainingMasterStats(), reg, tr)
        stats.record("broadcast", 0.010, round=0)
        stats.record("local_fit", 0.200, round=0)
        stats.record("local_fit", 0.150, round=1)
        assert reg.counter("parallel_phase_total", phase="local_fit").value == 2
        timer = reg.timer("parallel_phase_seconds", phase="local_fit")
        assert timer.count == 2 and timer.sum == pytest.approx(0.35)
        names = tr.span_names()
        assert names["master/broadcast"] == 1
        assert names["master/local_fit"] == 2
        _assert_exposition_parses(reg.exposition())


class TestProfilerCapture:
    def test_capture_writes_trace_and_records_metrics(self, tmp_path):
        import glob

        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.monitor import ProfilerCapture

        logdir = str(tmp_path / "trace")
        reg = monitor.enable(registry=MetricsRegistry())
        try:
            cap = ProfilerCapture(logdir)
            try:
                cap.start()
            except Exception as e:  # noqa: BLE001 — profiler availability
                pytest.skip(f"jax.profiler unavailable: {e}")
            assert cap.active
            with pytest.raises(RuntimeError):
                cap.start()          # double-start is a caller bug
            f = jax.jit(lambda v: (v @ v).sum())
            f(jnp.ones((16, 16))).block_until_ready()
            assert cap.stop() == logdir
            assert not cap.active
            assert cap.stop() is None          # idempotent
            assert glob.glob(logdir + "/**/*", recursive=True), \
                "capture wrote nothing"
            assert reg.counter("profiler_captures_total").value == 1
            assert reg.gauge("profiler_capture_seconds").value > 0
            assert monitor.tracer().span_names().get(
                "profiler/capture", 0) >= 1
        finally:
            monitor.disable()

    def test_context_manager_roundtrip_without_monitoring(self, tmp_path):
        from deeplearning4j_tpu.monitor import ProfilerCapture

        assert not monitor.is_enabled()
        logdir = str(tmp_path / "trace2")
        try:
            with ProfilerCapture(logdir) as cap:
                assert cap.active
        except Exception as e:  # noqa: BLE001
            pytest.skip(f"jax.profiler unavailable: {e}")
        assert not cap.active

"""The program's own spans and timers through the benchmark: the serving
rehearsal's traced run prints every per-layer metric that reads them,
and `tools/idle_by_span.py` names idle gaps by them."""

import json
import math
import os

import pytest

import harness
import trace_reduce

BENCH = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
SERVE = [w["name"] for w in BENCH["workloads"]
         if harness.load_cell(w["name"])[1]["kind"] == "serve"]
FAMILY_READERS = ("registry_histogram", "registry_histogram_mean",
                  "registry_counter")


def from_program_spans(cell):
    """The cell's per-layer metrics read from the serving loop's
    families (`serving_sched_*`, `serving_decode_*`, `serving_admit_*`)."""
    out = []
    for m in harness.cell_metrics(BENCH, cell, "per_layer"):
        spec = harness.load_json(os.path.join(
            harness.HERE, "layer_metrics", f"{m['name']}.json"))
        family = spec.get("args", {}).get("family", "")
        if spec["reader"] in FAMILY_READERS and family.startswith(
                ("serving_sched_", "serving_decode_", "serving_admit_")):
            out.append(m["name"])
    return out


@pytest.mark.parametrize("cell", SERVE)
def test_traced_rehearsal_prints_every_program_span_metric(run_cell, cell):
    names = from_program_spans(cell)
    assert len(names) >= 7
    res, err = run_cell(cell, "--trace", "1")
    assert res["correct"] is True, err
    for name in names:
        value = res["metrics"][name]["value"]
        assert math.isfinite(value) and value >= 0, (name, value)
    # the loop from inside adds up to the loop from outside: the outside
    # timing of a decode dispatch is its host part and its wait
    m = {k: v["value"] for k, v in res["metrics"].items()}
    inside = m["decode_host_mean_ms.serve"] + m["decode_wait_mean_ms.serve"]
    assert inside == pytest.approx(m["decode_step_mean_ms.serve"], rel=0.1)
    assert m["admit_wait_mean_ms.serve"] <= m["admit_wave_mean_ms.serve"]
    assert m["admit_waves.serve"] >= 1 and m["decode_batch_mean.serve"] >= 1


def test_a_missing_family_reads_as_nothing():
    """On a program without the families (this PR's parent) the readers
    return None and the line leaves the metric out."""
    reader = harness.load_module("readers", "registry_histogram_mean")
    snap = {"other": {"values": [{"sum": 1.0, "count": 2}]}}
    assert reader.read({"registry": (snap, snap)}, "serving_decode_batch_slots") is None
    assert reader.read({}, "serving_decode_batch_slots") is None
    after = {"f": {"values": [{"sum": 30.0, "count": 3}, {"sum": 6.0, "count": 1}]}}
    before = {"f": {"values": [{"sum": 4.0, "count": 2}]}}
    assert reader.read({"registry": (before, after)}, "f") == 16.0


@pytest.mark.parametrize("cell", SERVE)
def test_idle_by_span_runs_the_one_reduction(capsys, cell):
    tool = harness.load_module("tools", "idle_by_span")
    assert tool.main([cell, "--seed", "2147483659", "--rehearse-cpu"]) == 0
    out = capsys.readouterr().out
    last = json.loads([ln for ln in out.splitlines() if ln.strip()][-1])
    assert last["group"] == "per_layer" and last["correct"] is True
    assert trace_reduce.SPAN_PREFIX == "bench/"      # put back for this process
    idle, rows = tool.table({
        "device": {"window_s": 3.0, "busy_s": 2.0},
        "breakdown": {"idle_gaps": [["dl4tpu/serve/decode/wait", 0.6],
                                    ["unattributed", 0.1]]}})
    assert idle == 1.0 and [r[0] for r in rows][:2] == [
        "dl4tpu/serve/decode/wait", "unattributed"]
    assert rows[0][2] == pytest.approx(0.6) and rows[2][1] == pytest.approx(0.3)


def test_the_reduction_keeps_spans_of_either_prefix(tmp_path, monkeypatch):
    """`load` with the tool's tuple keeps the program's spans beside the
    benchmark's own, from one profile taken here on the CPU."""
    import jax

    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.monitor import MetricsRegistry, Tracer

    monitor.enable(registry=MetricsRegistry(), tracer=Tracer(),
                   jit_compile=False, device_memory=False)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            with monitor.span("serve/loop", it=1):
                with monitor.span("serve/decode/wait", it=1):
                    pass
    finally:
        jax.profiler.stop_trace()
        monitor.disable()
        monitor._STATE.registry = monitor.GLOBAL_REGISTRY
        monitor._STATE.tracer = monitor.GLOBAL_TRACER
    import glob
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    assert {s[2] for s in trace_reduce.load(path)["spans"]} == {trace_reduce.WINDOW}
    monkeypatch.setattr(trace_reduce, "SPAN_PREFIX", ("bench/", "dl4tpu/"))
    assert {s[2] for s in trace_reduce.load(path)["spans"]} == {
        trace_reduce.WINDOW, "dl4tpu/serve/loop", "dl4tpu/serve/decode/wait"}

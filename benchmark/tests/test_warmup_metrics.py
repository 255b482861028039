"""Set-up's split through the benchmark (ISSUE 39): every serving cell's
traced rehearsal prints the six `warm-up grid` metrics, read off the
registry's snapshot at the window's opening; a program without the
`serving_warmup_*` families (the parent) gives a line without them."""

import contextlib
import math
import os

import pytest

import harness

BENCH = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
SERVE = [w["name"] for w in BENCH["workloads"]
         if harness.load_cell(w["name"])[1]["kind"] == "serve"]
SIX = ("warmup_s.setup", "warmup_trace_s.setup", "warmup_lower_s.setup",
       "warmup_compile_s.setup", "warmup_gc_s.setup",
       "warmup_programs.setup")


def test_the_six_are_the_warmup_grids_and_every_serving_cells():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in SIX:
        m = by_name[name]
        assert m["layer"] == "warm-up grid" and m["moves"] == "setup_s"
        assert m["workloads"] == SERVE
        spec = harness.load_json(os.path.join(
            harness.HERE, "layer_metrics", f"{name}.json"))
        assert spec["reader"] == "registry_setup"


def test_reader_sums_a_family_at_the_windows_opening():
    reader = harness.load_module("readers", "registry_setup")
    fam = "serving_warmup_stage_seconds_total"
    at_open = {fam: {"values": [
        {"labels": {"stage": "trace"}, "value": 12.5},
        {"labels": {"stage": "lower"}, "value": 3.0},
        {"labels": {"stage": "gc"}, "value": 0.25}]},
        "serving_warmup_programs_total": {"values": [
            {"labels": {}, "value": 83.0}]}}
    at_close = {fam: {"values": [{"labels": {"stage": "trace"},
                                  "value": 99.0}]}}
    ctx = {"registry": (at_open, at_close)}
    assert reader.read(ctx, fam, {"stage": "trace"}) == 12.5
    assert reader.read(ctx, fam, {"stage": "gc"}) == 0.25
    assert reader.read(ctx, fam) == 15.75
    assert reader.read(ctx, "serving_warmup_programs_total") == 83.0
    # nothing to read: no registry, no family, no value with the labels
    assert reader.read({}, fam) is None
    assert reader.read({"registry": (None, None)}, fam) is None
    assert reader.read({"registry": ({"other": {"values": []}},
                                     at_close)}, fam) is None
    assert reader.read(ctx, fam, {"stage": "compile"}) is None


@pytest.mark.parametrize("cell", SERVE)
def test_traced_rehearsal_prints_the_six(run_cell, cell):
    res, err = run_cell(cell, "--trace", "1")
    assert res["correct"] is True, err
    m = {k: res["metrics"][k]["value"] for k in SIX}
    for name, value in m.items():
        assert math.isfinite(value) and value >= 0, (name, value)
    assert m["warmup_s.setup"] > 0 and m["warmup_programs.setup"] >= 1
    assert (m["warmup_trace_s.setup"] + m["warmup_lower_s.setup"]
            + m["warmup_compile_s.setup"]) <= m["warmup_s.setup"] * 1.05
    assert m["warmup_programs.setup"] == int(m["warmup_programs.setup"])


def test_a_program_without_the_phase_gives_a_line_without_the_six(
        run_cell, monkeypatch):
    """The parent's warm-up is no compile phase: its registry holds none
    of the families, and the line leaves the six out."""
    from deeplearning4j_tpu import monitor

    @contextlib.contextmanager
    def parents(name, family, **args):
        yield monitor.NOOP_SPAN

    monkeypatch.setattr(monitor, "compile_phase", parents)
    res, err = run_cell(SERVE[0], "--trace", "1")
    assert res["correct"] is True, err
    assert not set(SIX) & set(res["metrics"])
    assert "decode_step_mean_ms.serve" in res["metrics"]

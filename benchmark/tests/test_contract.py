"""`BENCHMARK.json` against the contract's limits on names, units and
files, and every name resolved to the file the harness will look for."""

import os
import re

import harness

B = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= B["run_seconds"] <= 51 and isinstance(B["run_seconds"], int)
    assert B["paths"] == ["benchmark"] and len(B["command"]) <= 32
    assert os.path.getsize(os.path.join(harness.REPO, "BENCHMARK.json")) < 65536
    cells = len(B["workloads"])
    assert sum(w["chips"] == 4 for w in B["workloads"]) <= max(1, cells // 4)
    assert 2 + 14 * 24 * (B["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_lines():
    names = []
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
        assert c["file"].startswith("benchmark/") and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        names.append(w["name"])
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        names.append(m["name"])
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES
        names.append(m["name"])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for text in ([c["why"] for c in B["configs"]]
                 + [c["source"] for c in B["configs"]]
                 + [w["why"] for w in B["workloads"]]
                 + [m["layer"] for m in B["per_layer"]] + B["command"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert any(m["name"] == "setup_s" for m in B["end_to_end"])


def test_every_name_resolves_to_its_files():
    e2e = {m["name"] for m in B["end_to_end"]}
    cells = {w["name"] for w in B["workloads"]}
    pairs = {(w["config"], w["traffic"]) for w in B["workloads"]}
    assert len(pairs) == len(cells)
    used = set()
    for w in B["workloads"]:
        _, cell, cfg = harness.load_cell(w["name"])
        used.add(w["config"])
        for kind in ("models", "reference", "work"):
            harness.load_module(kind, w["config"])
        harness.load_module("traffic", cell["kind"])
        assert len(harness.cell_metrics(B, w["name"], "end_to_end")) >= 2
        assert harness.cell_metrics(B, w["name"], "per_layer")
        assert set(cfg["reduced"]) == set(next(
            c["reduced"] for c in B["configs"] if c["name"] == w["config"]))
    assert used == {c["name"] for c in B["configs"]}
    for m in B["per_layer"]:
        assert m["moves"] in e2e
        spec = harness.load_json(os.path.join(
            harness.HERE, "layer_metrics", f"{m['name']}.json"))
        assert hasattr(harness.load_module("readers", spec["reader"]), "read")
        for c in m.get("workloads", []):
            assert c in cells
            assert m["moves"] in {x["name"] for x in
                                  harness.cell_metrics(B, c, "end_to_end")}
    for m in B["end_to_end"]:
        assert set(m.get("workloads", [])) <= cells


def test_files_under_paths_have_contract_names():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for root, dirs, files in os.walk(harness.HERE):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), harness.REPO)
            assert ok.match(rel) and len(rel) <= 200, rel


def test_peaks_table_refuses_an_unknown_chip():
    import flops
    import pytest

    assert flops.device_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(ValueError):
        flops.device_peaks("cpu")


def test_memory_peak_is_one_reading_and_no_sum():
    """`memory_peak_bytes` is the fullest chip's `peak_bytes_in_use` as
    the allocator gives it: the reserved peak and the window's samples
    stand beside it and are added to nothing, and nothing is cut off at
    the allocator's limit."""
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

        def __init__(self, **st):
            self.st = st

        def memory_stats(self):
            return self.st

    devs = [Dev(peak_bytes_in_use=7, peak_bytes_reserved=11, bytes_limit=16,
                bytes_in_use=3),
            Dev(peak_bytes_in_use=9, peak_bytes_reserved=2, bytes_limit=16,
                bytes_in_use=5)]
    line = harness.device_line(devs, window_bytes=[4, 6, 5])
    assert line["memory_peak_bytes"] == 9
    assert line["memory_reserved_peak_bytes"] == 2
    assert line["memory_window_bytes"] == 6 and line["count"] == 2
    assert harness.bytes_in_use(devs) == 5
    assert "memory_window_bytes" not in harness.device_line(devs)

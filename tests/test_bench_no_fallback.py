"""`bench.py` measures on the chip or fails: what replaced the
last-known-good echo.

The old bench survived a dead transport by printing a committed file's
numbers with `rc 0` (`BENCH_r03.json` was a failed run that exited 0).
The contract now: one peaks table keyed by `device_kind` where an
unknown kind raises; `main()` exits non-zero on the CPU, on a failing
primary and on a failing extra; it prints no record unless every block
measured; it starts no process (one process holds the chip); and no
code path reads a number from a file.
"""

import json
import os
import subprocess
import sys
import types

import pytest

from deeplearning4j_tpu import bench

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ peaks table
def test_v5e_row_carries_published_peaks_and_source():
    row = bench.device_peaks("TPU v5 lite")
    assert row["bf16_tflops"] == 197.0
    assert row["hbm_gbps"] == 819.0
    assert row["hbm_gib"] == 16.0
    assert "TPU v5e" in row["source"]


@pytest.mark.parametrize("kind", ["TPU v9 imaginary", "tpu v5 lite", "",
                                  "v5e"])
def test_unknown_device_kind_raises(kind):
    """Keyed by the exact string JAX reports: no substring match, no
    'unknown TPU-class part: assume v5e'."""
    with pytest.raises(ValueError, match="no published peaks"):
        bench.device_peaks(kind)


def _fake_devices(monkeypatch, platform, kind):
    import jax
    dev = types.SimpleNamespace(platform=platform, device_kind=kind,
                                memory_stats=lambda: None)
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [dev])


def test_device_info_on_cpu_has_no_peak():
    plat, kind, accel, peak = bench._device_info()
    assert (plat, accel, peak) == ("cpu", False, None)


def test_device_info_unknown_accelerator_raises(monkeypatch):
    _fake_devices(monkeypatch, "tpu", "TPU v9 imaginary")
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        bench._device_info()


def test_device_info_known_accelerator(monkeypatch):
    _fake_devices(monkeypatch, "tpu", "TPU v5 lite")
    assert bench._device_info() == ("tpu", "TPU v5 lite", True, 197.0)


# ------------------------------------------------------- main() fails loudly
def test_require_accelerator_names_what_it_found():
    with pytest.raises(SystemExit) as e:
        bench.require_accelerator()
    assert "platform='cpu'" in str(e.value)
    assert e.value.code not in (0, None)


def test_main_on_cpu_exits_nonzero_and_prints_nothing(capsys):
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


@pytest.fixture
def on_fake_chip(monkeypatch):
    """main() past the platform check, with every block stubbed."""
    monkeypatch.setattr(bench, "require_accelerator",
                        lambda: ("tpu", "TPU v5 lite"))
    import deeplearning4j_tpu.nd as nd
    monkeypatch.setattr(nd, "enable_compilation_cache", lambda *a, **k: "")
    for name in ("bench_resnet50", "bench_lenet", "bench_lstm_charnn",
                 "bench_transformer_lm", "bench_word2vec"):
        monkeypatch.setattr(
            bench, name,
            lambda accel, _n=name, **kw: {"metric": _n, "value": 1.0})
    return monkeypatch


def test_main_prints_one_record_when_every_block_measured(on_fake_chip,
                                                          capsys):
    bench.main()
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert rec["metric"] == "bench_resnet50"
    assert sorted(rec["extras"]) == ["lenet_mnist", "lstm_char_rnn",
                                     "transformer_lm", "word2vec"]
    assert "scaling_cpu8" not in rec["extras"]     # no CPU time in it
    assert "stale" not in rec and "regression_check" not in rec


def test_main_failing_primary_raises_and_prints_no_record(on_fake_chip,
                                                          capsys):
    def boom(accel, **kw):
        raise RuntimeError("XlaRuntimeError: RESOURCE_EXHAUSTED")

    on_fake_chip.setattr(bench, "bench_resnet50", boom)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        bench.main()
    assert capsys.readouterr().out == ""


def test_main_failing_extra_raises_instead_of_error_string(on_fake_chip,
                                                           capsys):
    def boom(accel, **kw):
        raise ValueError("shapes differ")

    on_fake_chip.setattr(bench, "bench_word2vec", boom)
    with pytest.raises(ValueError, match="shapes differ"):
        bench.main()
    assert capsys.readouterr().out == ""


def test_main_starts_no_process(on_fake_chip, monkeypatch):
    """A chip belongs to one process: the measuring entry point spawns
    none (the old main ran a JAX child and a probe child)."""
    def refuse(*a, **k):
        raise AssertionError("bench.main() started a process")

    monkeypatch.setattr(subprocess, "Popen", refuse)
    monkeypatch.setattr(subprocess, "run", refuse)
    bench.main()
    assert not hasattr(bench, "subprocess")


def test_no_file_backed_fallback_remains():
    """Nothing in the module can load, save or echo a committed record,
    probe for a transport, or assume a peak."""
    suspects = ("lastgood", "probe_", "connectivity", "emit_failure",
                "scaling_subprocess", "default_tpu_peak")
    left = [n for n in dir(bench)
            if any(s in n.lower() for s in suspects)]
    assert left == []
    import glob
    assert glob.glob(os.path.join(ROOT, "*GOOD*.json")) == []


def test_python_bench_py_without_a_chip_exits_nonzero():
    """The driver's entry point, end to end: on this CPU sandbox it
    must fail with the platform named and print no JSON line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "bench.py")],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=ROOT)
    assert proc.returncode != 0
    assert "needs an accelerator" in proc.stderr
    assert "platform='cpu'" in proc.stderr
    assert proc.stdout.strip() == ""

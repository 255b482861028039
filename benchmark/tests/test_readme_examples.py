"""README.md's worked examples: a cell, a per-layer metric and a
configuration added by new files and new entries alone, in a copy of the
benchmark, and run under `--rehearse-cpu`.  No file that is there is
edited except `BENCHMARK.json`, which gains entries."""

import json
import os
import shutil
import subprocess
import sys

import harness


def test_added_by_files_and_entries_alone(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(harness.HERE, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(harness.REPO, "deeplearning4j_tpu"),
               root / "deeplearning4j_tpu")
    bench = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
    b = root / "benchmark"

    # example 3: a configuration (GPT-2 small: the same block, other sizes)
    cfg = harness.load_json(b / "configs" / "gpt2-medium.json")
    cfg.update(name="gpt2-small", n_embd=768, n_layer=12, n_head=12,
               source="https://huggingface.co/openai-community/gpt2")
    (b / "configs" / "gpt2-small.json").write_text(json.dumps(cfg))
    for kind in ("models", "reference", "work"):
        shutil.copy(b / kind / "gpt2-medium.py", b / kind / "gpt2-small.py")
    bench["configs"].append({
        "name": "gpt2-small", "source": cfg["source"],
        "file": "benchmark/configs/gpt2-small.json",
        "reduced": cfg["reduced"], "why": "worked example"})

    # example 1: two cells, data only
    cell = harness.load_json(b / "workloads" / "gpt2m_train_t1024.json")
    for name, config in (("gpt2m_train_t512", "gpt2-medium"),
                         ("gpt2s_train_t512", "gpt2-small")):
        new = dict(cell, name=name, config=config, batch=8, seq_len=512)
        new["rehearsal"] = dict(cell["rehearsal"], seq_len=32)
        (b / "workloads" / f"{name}.json").write_text(json.dumps(new))
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": "train_t512", "chips": 1,
                                   "why": "worked example"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "gpt2m_train_t1024" in m.get("workloads", []):
                m["workloads"].append(name)

    # example 2: a per-layer metric on an existing reader
    (b / "layer_metrics" / "fused_adam_roofline.train.json").write_text(
        json.dumps({"reader": "trace_kernel_roofline",
                    "args": {"pattern": "dl4tpu_fused_adam",
                             "work_fn": "fused_adam"}}))
    bench["per_layer"].append({
        "name": "fused_adam_roofline.train", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_step_ms", "workloads": ["gpt2m_train_t512"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for name in ("gpt2m_train_t512", "gpt2s_train_t512"):
        out = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", name,
             "--seed", "2147483900", "--rehearse-cpu", "--trace", "1"],
            cwd=root, env=env, capture_output=True, text=True, timeout=900)
        assert out.returncode == 0, out.stderr[-3000:]
        res = json.loads(out.stdout.strip().splitlines()[-1])
        assert res["correct"] is True and res["workload"] == name
        assert "etl_ms.train" in res["metrics"]
        # a share of a roofline is never read from a CPU run: left out
        assert "fused_adam_roofline.train" not in res["metrics"]


def test_a_directory_without_the_program_runs_nothing(tmp_path):
    root = tmp_path / "bare"
    shutil.copytree(harness.HERE, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(harness.REPO, "BENCHMARK.json"), root)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "gpt2m_train_t1024", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and not out.stdout.strip()


def test_no_accelerator_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "gpt2m_train_t1024", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.REPO, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert not any(ln.startswith("{") for ln in out.stdout.splitlines())
    assert "needs a TPU" in out.stderr

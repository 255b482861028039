"""Batch/transformer sweep on the attached accelerator.

Runs the headline bench functions at alternative configs to find the
best-throughput operating points (the headline BENCH artifact keeps its
fixed config for round-over-round comparability; this sweep documents
where the ceiling is). One JSON line per config to stdout + appended to
the sweep artifact (`DL4J_SWEEP_OUT`, default repo-root SWEEP.jsonl).
Needs an accelerator; a config that fails stops the sweep with its
error.

Usage: python benchtools/bench_sweep.py [resnet|transformer|all]
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deeplearning4j_tpu import bench  # noqa: E402

OUT = os.environ.get(
    "DL4J_SWEEP_OUT",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "SWEEP.jsonl"))


def emit(tag, rec):
    rec = {"sweep": tag, **rec}
    # device_diagnostics repeats per record; keep the first only
    line = json.dumps(rec)
    print(line, flush=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def sweep_resnet(accel):
    # batch sweep: vary steps at b256 to separate working-set effects
    # (the fused window stacks steps x batch images on HBM) from
    # per-step compute
    for batch, steps in ((64, 20), (128, 20), (192, 20), (256, 20),
                         (256, 10), (256, 5)):
        r = bench.bench_resnet50(accel, batch=batch, steps=steps,
                                 with_etl=False)
        r.pop("device_diagnostics", None)
        emit(f"resnet50_b{batch}_s{steps}", r)


def sweep_transformer(accel):
    configs = [
        # (B, T, d_model, n_layers, n_heads) — the headline config first
        (16, 256, 256, 4, 8),
        (32, 512, 256, 4, 8),     # longer sequences, flash attn sweet spot
        (32, 512, 512, 8, 8),     # GPT-2-small-ish block shape
        (8, 2048, 512, 8, 8),     # long-context: flash attention tiling
    ]
    for B, T, d, L, H in configs:
        r = bench.bench_transformer_lm(accel, B=B, T=T, d_model=d,
                                       n_layers=L, n_heads=H)
        emit(f"transformer_B{B}_T{T}_d{d}_L{L}", r)


def main():
    what = sys.argv[1] if len(sys.argv) > 1 else "all"
    plat, kind = bench.require_accelerator()
    accel = True
    from deeplearning4j_tpu.nd import enable_compilation_cache
    enable_compilation_cache()
    emit("env", {"platform": plat, "device_kind": kind,
                 "diagnostics": bench._device_diagnostics()})
    if what in ("resnet", "all"):
        sweep_resnet(accel)
    if what in ("transformer", "all"):
        sweep_transformer(accel)


if __name__ == "__main__":
    main()

"""One-shot on-chip profile of the ResNet-50 train step.

Runs the SAME AOT fused executable the headline bench times, under
`jax.profiler.trace`, then post-processes the captured xplane into a
per-op cost table (self-time aggregated by HLO category and by op
name), printed as JSON and written to `DL4J_PROFILE_OUT` (default
PROFILE_live/).

Usage: python benchtools/profile_resnet.py [batch] [steps]
(defaults 128 / 20 — the headline operating point).

Role match: `PerformanceListener.java:87-88` measurement tooling; the
xplane parse uses tensorflow's profiler proto (tensorflow ships in the
image as the keras backend — CPU-only, used here purely as a proto
reader).
"""

import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OUTDIR = os.environ.get(
    "DL4J_PROFILE_OUT",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "PROFILE_live"))


def _xplane_proto():
    import importlib
    for mod in ("tensorflow.tsl.profiler.protobuf.xplane_pb2",
                "tensorflow.core.profiler.protobuf.xplane_pb2",
                "xprof.protobuf.xplane_pb2"):
        try:
            return importlib.import_module(mod)
        except ImportError:
            continue
    raise ImportError("no xplane_pb2 proto module found")


def parse_xplane(logdir):
    """Aggregate device-plane event self-times by event name."""
    xplane_pb2 = _xplane_proto()
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    totals = {}     # name -> duration ps
    device_total = 0
    for path in paths:
        xs = xplane_pb2.XSpace()
        with open(path, "rb") as f:
            xs.ParseFromString(f.read())
        for plane in xs.planes:
            pname = plane.name.lower()
            if "tpu" not in pname and "device" not in pname and \
                    "/device:" not in pname and "xla" not in pname:
                continue
            ev_names = {k: v for k, v in plane.event_metadata.items()}
            for line in plane.lines:
                for ev in line.events:
                    md = ev_names.get(ev.metadata_id)
                    name = md.name if md else str(ev.metadata_id)
                    dur = ev.duration_ps
                    totals[name] = totals.get(name, 0) + dur
                    device_total += dur
    return totals, device_total


def categorize(name: str) -> str:
    low = name.lower()
    for key, cat in (("convolution", "conv"), ("conv", "conv"),
                     ("dot", "matmul"), ("fusion", "fusion"),
                     ("reduce-window", "pooling"), ("reduce", "reduce"),
                     ("all-reduce", "collective"), ("copy", "copy"),
                     ("transpose", "transpose"), ("scatter", "scatter"),
                     ("dynamic", "dynamic-slice"), ("select", "select"),
                     ("broadcast", "broadcast"), ("infeed", "infeed"),
                     ("outfeed", "outfeed")):
        if key in low:
            return cat
    return "other"


def main():
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 128
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 20
    os.makedirs(OUTDIR, exist_ok=True)

    from deeplearning4j_tpu import bench
    bench.require_accelerator()
    from deeplearning4j_tpu.nd import enable_compilation_cache
    enable_compilation_cache()

    import jax
    logdir = os.path.join(OUTDIR, f"trace_b{batch}")
    # run the headline bench once with the profiler wrapped around it —
    # the timed windows inside are exactly the fused executable
    with jax.profiler.trace(logdir):
        result = bench.bench_resnet50(True, batch=batch, steps=steps,
                                      with_etl=False)
    parsed = parse_xplane(logdir)
    if not parsed or not parsed[0]:
        raise SystemExit(
            f"profile_resnet: the trace under {logdir} holds no device "
            f"plane — nothing to reduce")
    report = {"bench": {k: result[k] for k in
                        ("value", "mfu", "achieved_tflops", "batch",
                         "seconds") if k in result}}
    totals, device_total = parsed
    by_cat = {}
    for name, ps in totals.items():
        by_cat[categorize(name)] = by_cat.get(categorize(name), 0) + ps
    top_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:25]
    report["device_total_ms"] = device_total / 1e9
    report["by_category_pct"] = {
        k: round(100.0 * v / max(device_total, 1), 2)
        for k, v in sorted(by_cat.items(), key=lambda kv: -kv[1])}
    report["top_ops"] = [
        {"name": n[:120], "ms": round(ps / 1e9, 3),
         "pct": round(100.0 * ps / max(device_total, 1), 2)}
        for n, ps in top_ops]
    out_path = os.path.join(OUTDIR, f"profile_b{batch}.json")
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report)[:4000])
    print(f"\nwritten: {out_path}")


if __name__ == "__main__":
    main()

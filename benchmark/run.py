#!/usr/bin/env python3
"""Run one cell of the benchmark once, in this process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's model on the device from the seed, warms the shapes
this cell uses, measures for `--seconds`, checks what the timed path
produced against the plain reference, and prints one JSON object as the
last line of standard output.  It fails on anything but a TPU with the
chips the cell asks for.  `--rehearse-cpu` runs the same code at the
tiny sizes each file keeps under `rehearsal`; it reports `platform: cpu`
and `"rehearsal": true`, so it can never be read as a result.
"""

from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import harness  # noqa: E402


def main(argv=None, hooks=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on the CPU; never a result")
    args = ap.parse_args(argv)
    harness.T0 = T0 if argv is None else time.monotonic()

    if not os.path.isdir(os.path.join(harness.REPO, "deeplearning4j_tpu")):
        raise SystemExit("benchmark: the program (deeplearning4j_tpu/) is not "
                         "in this directory. Nothing was run.")
    bench, cell, cfg = harness.load_cell(args.workload, args.rehearse_cpu)
    if args.seconds is None:
        args.seconds = float(cell.get("seconds", bench["run_seconds"])
                             if args.rehearse_cpu else bench["run_seconds"])
    devs = harness.find_device(cell["chips"], args.rehearse_cpu)
    counters = harness.Counters()
    tracer = harness.Tracer(bool(args.trace),
                            seconds=cell.get("trace_seconds", 3.0),
                            host_level=cell.get("trace_host_level", 1))
    harness.say(f"cell {cell['name']} seed {args.seed} on {len(devs)} x "
                f"{devs[0].device_kind} ({devs[0].platform}); compile cache "
                f"{counters.cache_dir}")

    kind = harness.load_module("traffic", cell["kind"])
    out = kind.run(bench, cell, cfg, args, devs, counters, tracer, hooks=hooks)

    device = out["device"]
    breakdown = None
    if args.trace:
        group = "per_layer"
        metrics = harness.read_layer_metrics(bench, cell, out["ctx"])
        red = out["ctx"]["trace"]
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
    else:
        group = "end_to_end"
        units = {m["name"]: m["unit"]
                 for m in harness.cell_metrics(bench, cell["name"], group)}
        metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in out["end_to_end"].items() if k in units}
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device,
              "workload": cell["name"], "seed": args.seed, "group": group,
              "seconds": args.seconds}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if args.rehearse_cpu:
        result["rehearsal"] = True
    harness.print_result(result, out["compared"])
    return 0


if __name__ == "__main__":
    sys.exit(main())

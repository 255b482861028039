#!/usr/bin/env python3
"""A cell's traced run with its idle gaps named by the program's spans.

    python3 benchmark/tools/idle_by_span.py <cell> --seed <n>

`trace_reduce.load` keeps the host events whose name starts with
`SPAN_PREFIX`, the benchmark's own `bench/`, so the driver's
`breakdown.idle_gaps` names no span of the program.  The program's spans
(`monitor.span`) are in the same profile as `dl4tpu/<name>`: this tool
sets the prefix to both, in its own process, and runs `run.main` with
`--trace 1`, so the one reduction the benchmark has does the naming.  It
prints the run's result line as `run.py` does, and before it one line a
span: the seconds in which no operation ran on the device while that
span was the innermost open one, and their share of all idle seconds.
The table is also written to chiprun_out/idle_by_span/<cell>.<seed>.json.
Not run by the driver.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import harness  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402


def table(result):
    """[(span, idle seconds, share of all idle seconds)] from a result
    line; the reduction keeps the ten longest, the rest is one row."""
    dev = result["device"]
    idle = dev["window_s"] - dev["busy_s"]
    gaps = result["breakdown"]["idle_gaps"]
    rows = [(name, s, s / idle) for name, s in gaps]
    rest = idle - sum(s for _, s in gaps)
    if rest > 1e-9:
        rows.append(("(spans beyond the ten longest)", rest, rest / idle))
    return idle, rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    trace_reduce.SPAN_PREFIX = ("bench/", "dl4tpu/")    # str.startswith takes a tuple
    print_result = harness.print_result

    def with_table(result, compared):
        if result.get("breakdown"):        # a CPU rehearsal has no device plane
            idle, rows = table(result)
            harness.say(f"idle {idle:.4f}s of a window of "
                        f"{result['device']['window_s']:.4f}s, by the innermost "
                        f"open span:")
            for name, s, share in rows:
                harness.say(f"  {name:40s} {s:9.4f}s {100 * share:6.2f}%")
            out = os.path.join(harness.REPO, "chiprun_out", "idle_by_span",
                               f"{args.cell}.{args.seed}.json")
            os.makedirs(os.path.dirname(out), exist_ok=True)
            with open(out, "w") as f:
                json.dump({"idle_s": idle, "rows": rows, "result": result,
                           "compared": compared}, f, indent=1)
        print_result(result, compared)

    harness.print_result = with_table
    try:
        return run.main(["--workload", args.cell, "--seed", str(args.seed),
                         "--trace", "1"]
                        + (["--rehearse-cpu"] if args.rehearse_cpu else []))
    finally:
        harness.print_result = print_result
        trace_reduce.SPAN_PREFIX = "bench/"


if __name__ == "__main__":
    sys.exit(main())

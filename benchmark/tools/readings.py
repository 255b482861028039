#!/usr/bin/env python3
"""The readings a training cell's limits are set from, in one process:
for each seed the program's numbers against the float32 reference (the
lower reading), and for `--control-seeds` the control (the reference in
fp8, put in the program's place) and the fault "half of the batch left
out, the mean taken over the rest" planted in the reference (the upper
readings).  A state returned unchanged reads 1 by the measure and needs
no run.  Every reading then goes through `correct.judge` under the
cell's own `limits`, as a run's does: the program has to come out
correct on every seed, the control and the fault on none.  Writes
chiprun_out/readings/<cell>.json; `--judge-only` reads that file again
and judges it under the limits as they stand now (no chip).

    python3 benchmark/tools/readings.py <cell> --seeds 12 --control-seeds 3
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import correct  # noqa: E402
import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147500000)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--judge-only", action="store_true")
    args = ap.parse_args(argv)
    bench, cell, cfg = harness.load_cell(args.cell, args.rehearse_cpu)
    out = os.path.join(harness.REPO, "chiprun_out", "readings",
                       f"{args.cell}.json")
    if args.judge_only:
        with open(out) as f:
            return verdicts(json.load(f), cell["limits"])
    harness.find_device(cell["chips"], args.rehearse_cpu)
    harness.Counters()
    train = harness.load_module("traffic", "train")
    tr = train.Trainer(cell, cfg, harness.Tracer(False))
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t = time.monotonic()
        tr.install(seed)
        prog = tr.follow()
        tr.release()
        refr = tr.reference(seed, other=tr.first_gradient)
        row = {"seed": seed,
               "program": correct.training_numbers(prog, refr),
               "losses": {"program": prog["losses"],
                          "reference": refr["losses"]}}
        if i < args.control_seeds:
            for name, kw in (("control_fp8", {"mode": "fp8"}),
                             ("fault_half_batch",
                              {"rows": cell["batch"] // 2})):
                put = tr.reference(seed, keep=True, **kw)
                g = put.pop("first_gradient")
                refr2 = tr.reference(seed, other=g)
                tr.common.free(g)
                row[name] = correct.training_numbers(put, refr2)
        rows.append(row)
        print(json.dumps(row), flush=True)
        harness.say(f"seed {seed} read in {time.monotonic() - t:.1f}s")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return verdicts(rows, cell["limits"])


KINDS = ("program", "control_fp8", "fault_half_batch")


def verdicts(rows, limits):
    """Each kind's range of every number, and what `correct.judge` says
    of each seed's reading under `limits`.  -> 0 where the program is
    correct on every seed and the control and the fault on none."""
    bad = 0
    for kind in KINDS:
        have = [r for r in rows if kind in r]
        for name in have[0][kind] if have else ():
            vals = [r[kind][name][0] for r in have]
            print(f"{kind:18s} {name:22s} min {min(vals):.3e} max "
                  f"{max(vals):.3e} n {len(vals)} limit {limits.get(name)}",
                  flush=True)
        for r in have:
            ok, compared = correct.judge(
                {n: tuple(v) for n, v in r[kind].items()}, limits)
            failed = [n for n, c in compared.items() if not c["ok"]]
            print(f"JUDGED {kind} seed {r['seed']}: correct {ok}"
                  + (f", failed by {failed}" if failed else ""), flush=True)
            bad += ok != (kind == "program")
    print(f"{bad} verdicts are not as they have to be", flush=True)
    return int(bad > 0)


if __name__ == "__main__":
    sys.exit(main())

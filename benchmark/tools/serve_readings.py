#!/usr/bin/env python3
"""Readings for a serving cell, after ONE warm-up, in one process.

`--sweep r1,r2,...`: a window at each fixed rate, to find the knee (the
highest rate at which nothing is left unfinished for long after the
close and the tail of time to first token stays flat).
`--seeds N --control-seeds K`: for each seed new weights, a short window
at the cell's own rate, and the sample's widest gap against the float32
reference (the lower reading); for K of them the fp8 control at the same
positions (the upper reading).  Every reading then goes through
`correct.judge` under the cell's own `limits`: the program has to come
out correct on every seed, the control on none.  Writes
chiprun_out/readings/<cell>.json; `--judge-only` reads that file again
and judges it under the limits as they stand now (no chip).
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
    ap.add_argument("--sweep", default="")
    ap.add_argument("--seeds", type=int, default=0)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--first-seed", type=int, default=2147600000)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--judge-only", action="store_true")
    args = ap.parse_args(argv)
    bench, cell, cfg = harness.load_cell(args.cell, args.rehearse_cpu)
    out = os.path.join(harness.REPO, "chiprun_out", "readings",
                       f"{args.cell}.json")
    if args.judge_only:
        with open(out) as f:
            return verdicts(json.load(f), cell["limits"])
    from deeplearning4j_tpu.serving import GenerationServer

    harness.find_device(cell["chips"], args.rehearse_cpu)
    counters = harness.Counters()
    serve = harness.load_module("traffic", "serve")
    model = harness.load_module("models", cell["config"])
    ref = harness.load_module("reference", cell["config"])
    net = model.build(cfg)
    serve.install_weights(net, cell, cfg, args.first_seed)
    server = GenerationServer(net, **cell["server"])
    t = time.monotonic()
    server.warmup(int(cell["warmup_prompt_len"]))
    harness.say(f"warm-up took {time.monotonic() - t:.1f}s, "
                f"{counters.compiles()} compiles, "
                f"{counters.compile_seconds():.1f}s compiling, cache "
                f"{counters.cache_hits}/{counters.cache_requests}")
    server.start()
    off = harness.Tracer(False)
    rows = []
    for rate in [float(r) for r in args.sweep.split(",") if r]:
        c = dict(cell, rate_per_s=rate)
        d = serve.drive(server, c, cfg, args.first_seed + int(rate * 10),
                        args.seconds, off)
        row = {"rate_per_s": rate, **d["e2e"], "backlog": d["backlog"],
               "drained_s": d["drained_s"], "failed": d["failed"],
               "sent": len(d["streams"])}
        rows.append(row)
        print("SWEEP " + json.dumps(row), flush=True)
    for i in range(args.seeds):
        seed = args.first_seed + 104729 * (i + 1)
        serve.install_weights(net, cell, cfg, seed)
        d = serve.drive(server, cell, cfg, seed, args.seconds, off)
        sample = serve.pick_sample(cell, seed, d["requests"], d["outs"])
        row = {"seed": seed, "served_tokens": sum(len(o) for _, o in sample),
               "program": ref.served_gap(cfg, seed, sample), **d["e2e"],
               "failed": d["failed"], "wrong": d["wrong"]}
        if i < args.control_seeds:
            row["control_fp8"] = ref.served_gap(cfg, seed, sample, mode="fp8")
        rows.append(row)
        print("READING " + json.dumps(row), flush=True)
    server.drain()
    server.stop()
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return verdicts(rows, cell["limits"])


def verdicts(rows, limits):
    """What `correct.judge` says of each seed's gap, the program's and
    the control's.  -> 0 where the program is correct on every seed and
    the control on none."""
    bad = 0
    for r in rows:
        for kind in ("program", "control_fp8"):
            if kind in r:
                ok, _ = correct.judge({"served_logit_gap": (r[kind], None)},
                                      limits)
                print(f"JUDGED {kind} seed {r['seed']}: gap {r[kind]:.5f} "
                      f"limit {limits['served_logit_gap']} correct {ok}",
                      flush=True)
                bad += ok != (kind == "program")
    print(f"{bad} verdicts are not as they have to be", flush=True)
    return int(bad > 0)


if __name__ == "__main__":
    sys.exit(main())

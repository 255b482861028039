#!/usr/bin/env python3
"""`serve_readings.py` for a configuration whose program and reference do
not fit the chip together: the same sweep and the same readings after
ONE warm-up, but before the reference reads a seed's sample the
program's weights and pool are freed (as `traffic/serve.py::run` does),
and made anew for the next seed.  Writes
chiprun_out/readings/<cell>.json; judged like `serve_readings.py`
(whose `--judge-only` reads the same file).
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

import harness  # noqa: E402


def wait_idle(server, limit_s=600.0):
    """A window that overloads the server leaves work behind it (and
    requests the generator gave up on after its 60 s): the next window
    starts from an empty server or reads the last one's backlog."""
    t = time.monotonic()
    while (server.queue_depth() or server.engine.active.any()) \
            and time.monotonic() - t < limit_s:
        time.sleep(0.2)
    return time.monotonic() - t


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--sweep", default="")
    ap.add_argument("--seeds", type=int, default=0)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--bf16-seeds", type=int, default=0,
                    help="seeds on which the reference computed in the "
                         "configuration's own precision is read too")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--sweep-seconds", type=float, default=30.0)
    ap.add_argument("--first-seed", type=int, default=2147600000)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    bench, cell, cfg = harness.load_cell(args.cell, args.rehearse_cpu)
    import jax.numpy as jnp

    from deeplearning4j_tpu.serving import GenerationServer

    devs = harness.find_device(cell["chips"], args.rehearse_cpu)
    counters = harness.Counters()
    serve = harness.load_module("traffic", "serve")
    readings = harness.load_module("tools", "serve_readings")
    common = harness.load_module("reference", "common")
    model = harness.load_module("models", cell["config"])
    ref = harness.load_module("reference", cell["config"])
    net = model.build(cfg)
    serve.install_weights(net, cell, cfg, args.first_seed)
    server = GenerationServer(net, **cell["server"])
    t = time.monotonic()
    with harness.PeakWatch(devs, counters) as peak:
        server.warmup(int(cell["warmup_prompt_len"]))
    harness.say(f"warm-up took {time.monotonic() - t:.1f}s, "
                f"{counters.compiles()} compiles, "
                f"{counters.compile_seconds():.1f}s compiling, cache "
                f"{counters.cache_hits}/{counters.cache_requests}; live "
                f"bytes {peak.most[0]:,} at the most with {peak.most[1]} "
                f"programs done, {harness.bytes_in_use(devs):,} once warm")
    server.start()
    off = harness.Tracer(False)
    rows = []
    for rate in [float(r) for r in args.sweep.split(",") if r]:
        c = dict(cell, rate_per_s=rate)
        d = serve.drive(server, c, cfg, args.first_seed + int(rate * 10),
                        args.sweep_seconds, off, devs)
        row = {"rate_per_s": rate, **d["e2e"], "backlog": d["backlog"],
               "drained_s": d["drained_s"], "failed": d["failed"],
               "sent": len(d["streams"]), "live_bytes": max(d["live"])}
        row["idle_after_s"] = wait_idle(server)
        rows.append(row)
        print("SWEEP " + json.dumps(row), flush=True)
    pool_like = [[(a.shape, a.dtype) for a in arrays]
                 for arrays in server.engine.pool.kv]
    for i in range(args.seeds):
        seed = args.first_seed + 104729 * (i + 1)
        serve.install_weights(net, cell, cfg, seed)
        d = serve.drive(server, cell, cfg, seed, args.seconds, off)
        wait_idle(server)
        sample = serve.pick_sample(cell, seed, d["requests"], d["outs"])
        common.free(net.params, net.net_state, server.engine.pool.kv)
        t = time.monotonic()
        row = {"seed": seed, "served_tokens": sum(len(o) for _, o in sample),
               "longest": max(len(p) + len(o) for p, o in sample),
               "program": ref.served_gap(cfg, seed, sample), **d["e2e"],
               "failed": d["failed"], "wrong": d["wrong"]}
        if i < args.control_seeds:
            row["control_fp8"] = ref.served_gap(cfg, seed, sample, mode="fp8")
        if i < args.bf16_seeds:
            row["stated_bf16"] = ref.served_gap(cfg, seed, sample, mode="bf16")
        row["reference_s"] = time.monotonic() - t
        server.engine.pool.kv = tuple(
            tuple(jnp.zeros(shape, dtype) for shape, dtype in arrays)
            for arrays in pool_like)
        rows.append(row)
        print("READING " + json.dumps(row), flush=True)
    server.drain()
    server.stop()
    out = os.path.join(harness.REPO, "chiprun_out", "readings",
                       f"{args.cell}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return readings.verdicts([r for r in rows if "seed" in r], cell["limits"])


if __name__ == "__main__":
    sys.exit(main())

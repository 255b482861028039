#!/usr/bin/env python3
"""`dl4tpu_paged_decode` with grouped queries, alone on the chip at a
serving cell's shapes: its share of its roofline by a microbenchmark.

For a cell of a configuration whose `work/<config>.py` has
`gqa_paged_decode`: the cell's slots, every slot at the same position
(`--positions`), a full layer (every position read) and a window layer
(the ring, from the window's first position), the kernel timed over
`--iters` calls between two `block_until_ready`, the least time from the
operations and bytes `work/` gives over the chip's peaks.  A serving run
cannot give this number: its counter covers the window and the trace
three seconds of it (PERF.md section 7).  Writes
chiprun_out/readings/gqa_decode_roofline.<cell>.json.
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

import flops  # noqa: E402
import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--positions", default="1024,4096,9216")
    ap.add_argument("--groups", default="",
                    help="page-group sizes to try (positions); default: "
                         "the layer's own")
    ap.add_argument("--iters", type=int, default=50)
    args = ap.parse_args(argv)
    _, cell, cfg = harness.load_cell(args.cell)
    devs = harness.find_device(cell["chips"], False)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.kernels.paged_attention import (
        paged_decode_attention)

    work = harness.load_module("work", cell["config"])
    peaks = flops.device_peaks(devs[0].device_kind)
    srv = cell["server"]
    S, bl = srv["n_slots"], srv["block_len"]
    H, Hkv, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    W = cfg["sliding_window"]
    ring = -(-W // bl) + 1
    max_blocks = srv["max_positions"] // bl
    key = jax.random.PRNGKey(0)
    rows = []
    groups = [int(g) for g in args.groups.split(",") if g] or [None]
    for windowed in (False, True):
        cols = ring if windowed else max_blocks
        n_blocks = srv["window_blocks"] if windowed else srv["n_blocks"]
        pool = jax.random.normal(key, (n_blocks, bl, Hkv * Dh), jnp.bfloat16)
        table = jnp.asarray(
            1 + np.arange(S * cols, dtype=np.int32).reshape(S, cols)
            % (n_blocks - 1))
        q = jax.random.normal(key, (S, 1, H * Dh), jnp.bfloat16)
        for n in [int(p) for p in args.positions.split(",")]:
            lengths = jnp.full((S,), n, jnp.int32)
            starts = jnp.maximum(lengths - W, 0) if windowed else None
            read = S * (min(n, W) if windowed else n)
            need = work.gqa_paged_decode(cfg, read)
            least = max(need["flops"] / peaks["bf16_flops_per_s"],
                        need["bytes"] / peaks["hbm_bytes_per_s"])
            for g in groups:
                fn = jax.jit(lambda q, k, v, t, ln, st, g=g:
                             paged_decode_attention(
                                 q, k, v, t, ln, n_heads=H, n_kv_heads=Hkv,
                                 starts=st, group_positions=g))
                fn(q, pool, pool, table, lengths, starts).block_until_ready()
                t = time.monotonic()
                for _ in range(args.iters):
                    out = fn(q, pool, pool, table, lengths, starts)
                out.block_until_ready()
                dt = (time.monotonic() - t) / args.iters
                row = {"layer": "window" if windowed else "full",
                       "positions": n, "read_positions": read,
                       "group_positions": g, "seconds": dt,
                       "flops": need["flops"], "bytes": need["bytes"],
                       "least_seconds": least,
                       "roofline_pct": 100.0 * least / dt,
                       "gb_per_s": need["bytes"] / dt / 1e9,
                       "device": devs[0].device_kind}
                rows.append(row)
                print("ROOFLINE " + json.dumps(row), flush=True)
    out = os.path.join(harness.REPO, "chiprun_out", "readings",
                       f"gqa_decode_roofline.{args.cell}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

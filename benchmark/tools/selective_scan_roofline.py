#!/usr/bin/env python3
"""`dl4tpu_selective_scan` alone on the chip at a serving cell's prefill
shapes, beside the two XLA formulations it replaced: its seconds and its
share of its roofline by a microbenchmark.

For a cell of a configuration whose `work/<config>.py` has
`selective_scan`: every prompt bucket the cell warms (`min_prefill_bucket`
to the bucket of `warmup_prompt_len`) at one row and at the widest wave
`max_prefill_tokens` allows, every row full (no padding), one Mamba
layer.  Three formulations of the same recurrence over `[k, T]`
positions: the kernel; a `lax.scan` over time (T dependent steps); XLA's
associative scan over `[T, N, C]` float32 pairs (one row at a time where
k rows would not fit: its `rows` says so and its seconds are scaled to
k).  Each is timed over `--iters` calls between two `block_until_ready`.

The recurrence's operations are the vector unit's and `peaks.json` has
no vector peak, so the least time is the MEMORY's: the bytes `work/`
gives (x, Delta and y once each in float32, B and C) over the chip's
bandwidth; `vector_gops_per_s` is printed beside it for what a vector
peak would be held against.  A serving run cannot give this number: its
trace covers three seconds of waves of every shape (PERF.md section 7).
Writes chiprun_out/readings/selective_scan_roofline.<cell>.json.
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

ASSOCIATIVE_BYTES = 3 << 30     # of [k, T, N, C] float32 pairs, at most


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    _, cell, cfg = harness.load_cell(args.cell, args.rehearse_cpu)
    devs = harness.find_device(cell["chips"], args.rehearse_cpu)
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.kernels.selective_scan import (
        selective_scan, selective_scan_reference, unsupported_reason)

    work = harness.load_module("work", cell["config"])
    peaks = None if args.rehearse_cpu else flops.device_peaks(
        devs[0].device_kind)
    srv = cell["server"]
    C = cfg["mamba_expand"] * cfg["hidden_size"]
    N = cfg["mamba_d_state"]
    f32 = jnp.float32

    def associative(x, delta, a, b, c, d, h0, lengths):
        # h_t = A_t h_{t-1} + U_t as a scan over pairs (A, U) under
        # (A1, U1) o (A2, U2) = (A1 A2, A2 U1 + U2)
        decay = jnp.exp(delta[:, :, None, :] * a)
        push = (delta * x)[:, :, None, :] * b[..., None]
        A, U = jax.lax.associative_scan(
            lambda l, r: (l[0] * r[0], r[0] * l[1] + r[1]),
            (decay, push), axis=1)
        h = A * h0[:, None] + U
        return jnp.sum(h * c[..., None], axis=2) + d * x, h[:, -1]

    buckets, b = [], srv.get("min_prefill_bucket", 1)
    while b < cell["warmup_prompt_len"]:
        buckets.append(b)
        b *= 2
    buckets.append(b)
    key = jax.random.PRNGKey(0)
    rows = []
    for T in buckets:
        widest = min(srv["n_slots"], srv["max_prefill_tokens"] // T)
        for k in sorted({1, widest}):
            ks = jax.random.split(jax.random.fold_in(key, T * 131 + k), 5)
            x = jax.random.normal(ks[0], (k, T, C), f32)
            delta = jax.nn.softplus(jax.random.normal(ks[1], (k, T, C)) - 4)
            bm = jax.random.normal(ks[2], (k, T, N), f32)
            cm = jax.random.normal(ks[3], (k, T, N), f32)
            a = -jnp.broadcast_to(
                jnp.arange(1, N + 1, dtype=f32)[:, None], (N, C))
            d = jnp.ones((C,), f32)
            h0 = jnp.zeros((k, N, C), f32)
            lengths = jnp.full((k,), T, jnp.int32)
            need = work.selective_scan(cfg, k * T)
            least = (need["bytes"] / peaks["hbm_bytes_per_s"]
                     if peaks else None)
            forms = {"lax_scan": (selective_scan_reference, k),
                     "associative_scan": (
                         associative,
                         max(1, min(k, ASSOCIATIVE_BYTES
                                    // (2 * T * N * C * 4))))}
            if unsupported_reason(x.shape, N) is None:
                forms = dict(kernel=(selective_scan, k), **forms)
            want = None
            for name, (fn, kk) in forms.items():
                fn = jax.jit(fn)
                given = (x[:kk], delta[:kk], a, bm[:kk], cm[:kk], d, h0[:kk],
                         lengths[:kk])
                call = lambda: fn(*given)  # noqa: E731
                try:
                    y, h = jax.block_until_ready(call())
                except Exception as e:  # noqa: BLE001 - a form that does not fit is a row, not the end
                    print("ROOFLINE " + json.dumps(
                        {"form": name, "rows": k, "positions": T,
                         "rows_at_once": kk,
                         "error": f"{type(e).__name__}: {e}"[:300]}),
                        flush=True)
                    continue
                if want is None:
                    want = h
                err = float(jnp.max(jnp.abs(h - want[:kk])))
                t = time.monotonic()
                for _ in range(args.iters):
                    out = call()
                jax.block_until_ready(out)
                dt = (time.monotonic() - t) / args.iters * (k / kk)
                row = {"form": name, "rows": k, "positions": T,
                       "rows_at_once": kk, "seconds": dt,
                       "state_gap_to_first_form": err,
                       "vector_ops": need["flops"], "bytes": need["bytes"],
                       "least_seconds": least,
                       "roofline_pct": 100.0 * least / dt if least else None,
                       "vector_gops_per_s": need["flops"] / dt / 1e9,
                       "us_per_position": 1e6 * dt / (k * T),
                       "device": devs[0].device_kind}
                rows.append(row)
                print("ROOFLINE " + json.dumps(row), flush=True)
    out = os.path.join(harness.REPO, "chiprun_out", "readings",
                       f"selective_scan_roofline.{args.cell}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Time one expert layer's grouped SwiGLU alone on the chip: XLA's
`ragged_dot` as the layer called it before the kernel, the kernel
`dl4tpu_grouped_swiglu` at several F-tiles, and the kernel's bytes at the
chip's memory bandwidth.

    python3 scripts/moe_gmm_cost.py [--reps N] [--out FILE]     # one TPU chip
    JAX_PLATFORMS=cpu python3 scripts/moe_gmm_cost.py --rehearse

For each expert configuration's (D, F, E) -- `LFM2-8B-A1B` (2,048, 1,792,
32), `sarvam-105b` (4,096, 2,048, 32), `command-a-plus-05-2026` (4,096,
4,096, 16) -- and a rows array of M = 512 and 256 (`n_slots x k` of the
three cells' decode programs), rows sorted by expert with 1, 4, 8, 16 and
all E experts touched, two rows each (a decode dispatch: the other rows
lie in no group), each row prints ms a call (the least over three rounds
of `--reps` calls dispatched back to back, closed by
`block_until_ready`) and the share of the roofline: the touched experts'
weights once, the rows read and the result written, at the peak of
`benchmark/peaks.json` (the products' FLOPs are far under the compute
bound at these rows). Also:

- `ragged_dot` at the same sizes over a rows array of 64: whether its
  cost follows the rows it is handed (compute on rows of no group) or
  the weight bytes;
- both at M = 256, 512, 1,024 with every row routed, spread evenly over
  all experts (a one-prompt admission wave): where the kernel stops
  paying, which sets `grouped_swiglu.MAX_ROWS`.

The kernel's F-tile is `grouped_swiglu.f_tile`'s choice (`kernel`) and,
for the sweep that set `_STEP_WEIGHT_BYTES`, each of 256, 512, 896, 1,024,
1,792 and 2,048 that divides F (`kernel@T`, the module's tile function
replaced in this script; none is an option of the kernel; a tile VMEM
cannot hold prints the compiler's refusal).
Every row prints the largest gap of its routed rows from `ragged_dot`'s
over the latter's largest value. `--rehearse` runs tiny shapes in
interpret mode on the CPU and prints the gaps and no time. It exits
non-zero on any other device than a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = (("LFM2-8B-A1B", 2048, 1792, 32),
           ("sarvam-105b", 4096, 2048, 32),
           ("command-a-plus-05-2026", 4096, 4096, 16))
REHEARSAL_CONFIGS = (("rehearsal", 128, 256, 8),)


def _timed(fn, args, reps):
    import jax
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / reps)
    return best * 1e3, out


def _sizes(E, touched, per, M):
    """Rows of each expert: `touched` experts spread over the E, `per`
    rows each (the rest of the M rows lie in no group)."""
    import numpy as np
    sizes = np.zeros(E, np.int32)
    picked = np.linspace(0, E - 1, touched).round().astype(int)
    sizes[picked] = per
    assert sizes.sum() <= M
    return sizes


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None,
                    help="also write the rows to this JSON file")
    a = ap.parse_args()
    sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]

    import jax
    import jax.numpy as jnp
    import numpy as np

    import deeplearning4j_tpu  # noqa: F401 - before the first device lookup
    import harness
    from deeplearning4j_tpu.kernels import grouped_swiglu as gs

    dev = jax.devices()[0]
    if not a.rehearse and dev.platform != "tpu":
        raise SystemExit(f"moe_gmm_cost.py measures on a TPU; this is "
                         f"{dev.platform} ({dev.device_kind})")
    bw = None if a.rehearse else harness.load_json(os.path.join(
        ROOT, "benchmark", "peaks.json"))[dev.device_kind]["hbm_bytes_per_s"]
    interpret = a.rehearse
    reps = 1 if a.rehearse else a.reps
    call = gs._grouped_call.__wrapped__
    chosen_tile = gs.f_tile

    def kernel(tile):
        def run(rows, sizes, wg, wu, wd):
            gs.f_tile = (lambda d, f, i: tile) if tile else chosen_tile
            try:
                return call(rows, sizes, wg, wu, wd, interpret=interpret)
            finally:
                gs.f_tile = chosen_tile
        return jax.jit(run)

    xla = jax.jit(gs.grouped_swiglu_xla)
    rows_out = []
    for name, D, F, E in (REHEARSAL_CONFIGS if a.rehearse else CONFIGS):
        ks = jax.random.split(jax.random.PRNGKey(D + F + E), 4)
        bf = jnp.bfloat16
        wg = (0.02 * jax.random.normal(ks[0], (E, D, F), jnp.float32)
              ).astype(bf)
        wu = (0.02 * jax.random.normal(ks[1], (E, D, F), jnp.float32)
              ).astype(bf)
        wd = (0.02 * jax.random.normal(ks[2], (E, F, D), jnp.float32)
              ).astype(bf)
        tiles = [None] + [t for t in (256, 512, 896, 1024, 1792, 2048)
                          if F % t == 0 and t != gs.f_tile(D, F, 2)]
        variants = [("xla", xla)] + [
            (f"kernel@{t}" if t else "kernel", kernel(t)) for t in tiles]
        cases = []
        for M in ((64, 32) if a.rehearse else (512, 256)):
            for touched in sorted({1, 4, 8, 16, E} & set(range(1, E + 1))):
                cases.append((M, f"{touched} touched, 2 rows each",
                              touched, _sizes(E, touched, 2, M)))
        for M in ((32, 64) if a.rehearse else (256, 512, 1024)):
            cases.append((M, "every row routed", E,
                          np.full(E, M // E, np.int32)))
        for M, what, touched, sizes in cases:
            rows = jax.random.normal(jax.random.fold_in(ks[3], M), (M, D),
                                     jnp.float32).astype(bf)
            n = int(sizes.sum())
            weight_bytes = touched * 3 * D * F * 2
            need = weight_bytes + 2 * M * D * 2
            ref = None
            for label, fn in variants:
                if label != "xla" and M > gs.MAX_ROWS and label != "kernel":
                    continue
                try:
                    ms, out = _timed(fn, (rows, jnp.asarray(sizes), wg, wu,
                                          wd), reps)
                except Exception as e:      # a tile VMEM cannot hold
                    rows_out.append(dict(config=name, M=M, case=what,
                                         variant=label,
                                         failed=f"{type(e).__name__}: "
                                                f"{e}"[:200]))
                    continue
                out = out[:n].astype(jnp.float32)
                if ref is None:
                    ref = out
                gap = float(jnp.max(jnp.abs(out - ref))
                            / jnp.maximum(jnp.max(jnp.abs(ref)), 1e-30))
                r = dict(config=name, D=D, F=F, E=E, M=M, case=what,
                         touched=touched, routed=n, variant=label, ms=ms,
                         weight_gb=weight_bytes / 1e9, gap_vs_xla=gap)
                if bw is not None:
                    r["roofline_pct"] = 100.0 * need / bw / (ms / 1e3)
                rows_out.append(r)
                print(json.dumps(r), flush=True)
            if touched < E and M == (64 if a.rehearse else 512):
                small = rows[:32 if a.rehearse else 64]
                ms, _ = _timed(xla, (small, jnp.asarray(sizes), wg, wu, wd),
                               reps)
                r = dict(config=name, D=D, F=F, E=E, M=small.shape[0],
                         case=what, touched=touched, routed=n,
                         variant="xla", ms=ms, weight_gb=weight_bytes / 1e9)
                if bw is not None:
                    r["roofline_pct"] = 100.0 * (
                        weight_bytes + 4 * small.shape[0] * D) / bw / (
                            ms / 1e3)
                rows_out.append(r)
                print(json.dumps(r), flush=True)
        del wg, wu, wd
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            json.dump({"device": dev.device_kind, "rows": rows_out}, f,
                      indent=1)


if __name__ == "__main__":
    main()

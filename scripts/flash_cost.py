#!/usr/bin/env python3
"""Time the three flash attention kernels alone on the chip, the parent
commit's form beside this tree's, one change at a time.

    python3 scripts/flash_cost.py --parent DIR [--reps N] [--out FILE]  # one TPU chip
    JAX_PLATFORMS=cpu python3 scripts/flash_cost.py --parent DIR --rehearse

`DIR` is a checkout of the commit to compare with; its
`deeplearning4j_tpu/kernels/flash_attention.py` is loaded as a module of
its own. At `[4, 1024, 16, 64]` bfloat16 causal (the `gpt2m_train_t1024`
cell's attention) and at `[1, 8192, 16, 64]`, each row times
`dl4tpu_flash_fwd`, `dl4tpu_flash_bwd_dq` and `dl4tpu_flash_bwd_dkv` as
separate jitted calls (the [B,T,H,D] <-> [B,H,T,D] transposes around each
kernel included), dispatched back to back and closed by
`block_until_ready`:

- `xla`: XLA's attention (`_xla_attention`) and its vjp, for scale;
- `parent`: the parent's kernels at its blocks (512, 1024);
- `dtype`: this tree's kernels (operands in the input dtype) at the
  parent's blocks, every computed tile masked whole and every operand
  block fetched as in the parent;
- `dtype+tiles`: the same at this tree's causal tiles;
- `change`: this tree's kernels as `flash_attention` runs them (its
  tiles; the forward's diagonal tile whole and masked, the backward's
  cut into sub-blocks, only those on or under the diagonal computed and
  only those on it masked);
- `change/N`: the same with the backward's diagonal tile cut N ways a
  side (the forward's runs whole), and
  `change@B`: at B-square tiles: the sweep that chose `_CAUSAL_TILE`
  and `_DIAGONAL_SPLIT`.

"Every computed tile masked whole" is `_run_tile` replaced in this
script, the parent's blocks are `_tiles` and the two index maps
replaced, and N is `_DIAGONAL_SPLIT` set by it: none is an option of
the kernels. Each row prints ms a call and the share of the
roofline by `benchmark/work/gpt2-medium.py`'s counts (`flash_fwd`,
`flash_bwd` for one layer: the causal count), and the largest gap of its
outputs from the parent's over the parent's largest value. Rows go to
the JSON file `--out` too, where it is given. `--rehearse` runs tiny
shapes in interpret mode on the CPU and prints the gaps and no time. It
exits non-zero on any other device than a TPU.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHAPES = ((4, 1024, 16, 64), (1, 8192, 16, 64))
REHEARSAL_SHAPES = ((1, 128, 2, 16),)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _timed(fn, args, reps):
    """ms a call: the least over three rounds of `reps` calls dispatched
    back to back, after one that compiles."""
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


def _every_tile_masked(step, *, causal, qi, kj, edge, bq, bk, split):
    """`_run_tile` as the parent runs a causal tile: whole and masked
    wherever it runs."""
    from jax.experimental import pallas as pl
    pl.when(kj * bk <= (qi + 1) * bq - 1)(lambda: step(True, 0, bq, 0, bk))


def _parent_tiles(Tq, Tk, block_q, block_k, causal):
    """`_tiles` as the parent chose them: each block clamped to its
    length, rectangular causal tiles allowed."""
    return min(block_q, Tq), min(block_k, Tk)


def _every_block_fetched(causal):
    """The index maps as the parent's: no index clamped."""
    return lambda b, h, i, j: (b, h, j, 0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", required=True)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None,
                    help="also write the rows to this JSON file")
    a = ap.parse_args()
    sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]

    import jax
    import jax.numpy as jnp

    import deeplearning4j_tpu  # noqa: F401 - before the first device lookup
    import flops
    import harness
    fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")

    dev = jax.devices()[0]
    if not a.rehearse and dev.platform != "tpu":
        raise SystemExit(f"flash_cost.py measures on a TPU; this is "
                         f"{dev.platform} ({dev.device_kind})")
    parent = _load(os.path.join(a.parent, "deeplearning4j_tpu", "kernels",
                                "flash_attention.py"), "parent_flash")
    work = harness.load_module("work", "gpt2-medium")
    cfg = harness.load_json(os.path.join(ROOT, "benchmark", "configs",
                                         "gpt2-medium.json"))
    peaks = None if a.rehearse else harness.load_json(os.path.join(
        ROOT, "benchmark", "peaks.json"))[dev.device_kind]

    def kernels(mod, blocks):
        bq, bk = blocks
        kw = dict(block_q=bq, block_k=bk, causal=True, interpret=None)
        fwd = jax.jit(lambda q, k, v: mod._flash_forward(
            q, k, v, block_q=bq, block_k=bk, causal=True,
            interpret=fa._resolve_interpret(None)))
        dq = jax.jit(lambda q, k, v, g, lse, d: mod._bwd_dq_chunk(
            q, k, v, g, lse, d, **kw))
        dkv = jax.jit(lambda q, k, v, g, lse, d: mod._bwd_dkv_chunk(
            q, k, v, g, lse, d, **kw))
        return fwd, dq, dkv

    rows = []
    for B, T, H, D in (REHEARSAL_SHAPES if a.rehearse else SHAPES):
        ks = jax.random.split(jax.random.PRNGKey(T), 4)
        q, k, v, g = (jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
                      for kk in ks)
        o, lse = jax.jit(lambda q, k, v: fa._flash_forward(
            q, k, v, block_q=None, block_k=None, causal=True,
            interpret=fa._resolve_interpret(None)))(q, k, v)
        delta = fa.attention_delta(g, o)
        shape = {"batch": B, "seq_len": T}
        one = dict(cfg, n_layer=1, n_embd=H * D)
        need = {"fwd": work.flash_fwd(one, shape),
                "bwd": work.flash_bwd(one, shape)}
        chosen = fa._tiles(T, T, None, None, True)
        whole = {"_run_tile": _every_tile_masked}
        as_parent = dict(whole, _tiles=_parent_tiles,
                         _k_index_map=_every_block_fetched,
                         _q_index_map=_every_block_fetched)
        variants = [("parent", parent, (512, 1024), {}),
                    ("dtype", fa, (512, 1024), as_parent),
                    ("dtype+tiles", fa, chosen, whole),
                    ("change", fa, chosen, {})] + [
                   (f"change/{n}", fa, chosen, {"_DIAGONAL_SPLIT": n})
                   for n in (1, 2, 4) if n != fa._DIAGONAL_SPLIT] + [
                   (f"change@{b}", fa, (b, b), {})
                   for b in (256, 512) if b != chosen[0]]
        ref = None
        for label, mod, blocks, patch in variants:
            saved = {name: getattr(fa, name) for name in patch}
            for name, value in patch.items():
                setattr(fa, name, value)
            try:
                fwd, dq, dkv = kernels(mod, blocks)
                ms_f, out_f = _timed(fwd, (q, k, v), a.reps)
                ms_q, out_q = _timed(dq, (q, k, v, g, lse, delta), a.reps)
                ms_kv, out_kv = _timed(dkv, (q, k, v, g, lse, delta),
                                       a.reps)
            finally:
                for name, value in saved.items():
                    setattr(fa, name, value)
            outs = [out_f[0], out_q, *out_kv]
            if ref is None:
                ref = outs
            gap = max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                            - r.astype(jnp.float32)))
                            / jnp.max(jnp.abs(r.astype(jnp.float32))))
                      for x, r in zip(outs, ref))
            rows.append(dict(shape=[B, T, H, D], variant=label,
                             blocks=list(blocks), fwd_ms=ms_f, dq_ms=ms_q,
                             dkv_ms=ms_kv, gap_vs_parent=gap))
        xla_f = jax.jit(lambda q, k, v: fa._xla_attention(q, k, v, True))

        def xla_b(q, k, v, g):
            return jax.vjp(lambda *x: fa._xla_attention(*x, True),
                           q, k, v)[1](g)
        try:
            ms_f, _ = _timed(xla_f, (q, k, v), a.reps)
            ms_b, _ = _timed(jax.jit(xla_b), (q, k, v, g), a.reps)
            rows.append(dict(shape=[B, T, H, D], variant="xla",
                             blocks=None, fwd_ms=ms_f, bwd_ms=ms_b))
        except Exception as e:     # T = 8,192 may not fit the [T, T] tiles
            rows.append(dict(shape=[B, T, H, D], variant="xla",
                             failed=f"{type(e).__name__}: {e}"[:200]))
        for r in rows:
            if r["shape"][1] != T or "fwd_ms" not in r:
                continue
            bwd_ms = r.get("bwd_ms", r.get("dq_ms", 0) + r.get("dkv_ms", 0))
            r["bwd_ms"] = bwd_ms
            if peaks is not None:
                for part, ms in (("fwd", r["fwd_ms"]), ("bwd", bwd_ms)):
                    least = flops.roofline_seconds(
                        need[part]["flops"], need[part]["bytes"], peaks)
                    r[f"{part}_roofline_pct"] = (
                        100.0 * least["seconds"] / (ms / 1e3))

    for r in rows:
        if a.rehearse:
            print(f"{str(r['shape']):20s} {r['variant']:14s} "
                  f"gap {r.get('gap_vs_parent', float('nan')):.2e}"
                  f"{'  failed: ' + r['failed'] if 'failed' in r else ''}")
            continue
        if "failed" in r:
            print(f"{str(r['shape']):20s} {r['variant']:14s} {r['failed']}")
            continue
        print(f"{str(r['shape']):20s} {r['variant']:14s} "
              f"blocks {str(r['blocks']):12s} fwd {r['fwd_ms']:8.3f} ms "
              f"({r['fwd_roofline_pct']:5.1f}%)  "
              f"dq {r.get('dq_ms', float('nan')):8.3f}  "
              f"dkv {r.get('dkv_ms', float('nan')):8.3f}  "
              f"bwd {r['bwd_ms']:8.3f} ms ({r['bwd_roofline_pct']:5.1f}%)"
              f"  gap {r.get('gap_vs_parent', float('nan')):.2e}",
              flush=True)
    if a.out and not a.rehearse:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            json.dump({"device": dev.device_kind, "rows": rows}, f,
                      indent=1)


if __name__ == "__main__":
    main()

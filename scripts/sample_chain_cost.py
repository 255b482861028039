#!/usr/bin/env python3
"""Time the serving sampling chain on the chip, by the rows it is given.

    chiprun -- python3 scripts/sample_chain_cost.py
    chiprun -- python3 scripts/sample_chain_cost.py --decode [--repo DIR]
    chiprun -- python3 scripts/sample_chain_cost.py --ids

Without `--decode`: the chain `PagedDecodeEngine._sample_ids` runs for a
sampled row (log/clip, the division by the temperature,
`zoo.transformer.filter_logits` with a per-row top-p, `fold_in`,
`categorical`) alone at `[R, V]`, for R in 1..32 and the three
vocabularies the serving cells have. This is the table that fixed
`engine._SAMPLE_CHUNK_ROWS` (PERF.md, section 5).

With `--decode`: `gpt2m_serve_chat`'s two decode programs at full size,
dispatched back to back with 0, 1, 2, ... of the 32 slots live and
sampling (the greedy twin beside the full variant at zero sampled rows
is what ROADMAP D6 asks for). `--repo` times another checkout's engine,
the parent's for one.

With `--ids`: on the chip, is every row's id from `_sample_ids` the id the
chain over the whole `[32, V]` matrix gives (rows that differ: 0)?

Every time is the host's clock over dispatches that follow one another,
closed by `block_until_ready`: the device's period, where the device is
the slower of the two. It exits non-zero anywhere but on a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROWS = (1, 2, 4, 8, 16, 32)
VOCABS = (32768, 50257, 65536)
DECODE_STEPS = 200


def _timed(run, reps: int = 5) -> float:
    """Least seconds of `reps` calls of `run` (each blocks on its
    result), after one that compiles."""
    run()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best


def chain(probs, keys, emit_idx, temp, top_p):
    """The chain over every row it is given, each with a temperature."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.zoo.transformer import filter_logits
    logits = jnp.log(jnp.clip(probs, 1e-9, None)) / temp[:, None]
    logits = filter_logits(logits, None, top_p[:, None])
    skeys = jax.vmap(jax.random.fold_in)(keys, emit_idx)
    return jax.vmap(jax.random.categorical)(skeys, logits)


def rows_for(r: int, v: int, seed: int):
    """`r` rows of a peaked distribution over `v` ids, and their keys."""
    import jax
    import jax.numpy as jnp
    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    probs = jax.nn.softmax(
        3.0 * jax.random.normal(k[0], (r, v), jnp.float32), axis=-1)
    keys = jax.random.randint(k[1], (r, 2), 0, 2 ** 31 - 1
                              ).astype(jnp.uint32)
    return probs, keys


def chain_table(out):
    import jax
    import jax.numpy as jnp

    inner = 50

    @jax.jit
    def many(probs, keys, emit_idx, temp, top_p):
        # each pass's temperature hangs on the pass before it, so the
        # compiler can hoist nothing out of the loop
        def body(_, carry):
            temp, emit_idx, acc = carry
            ids = chain(probs, keys, emit_idx, temp, top_p)
            return (temp + ids.astype(temp.dtype) * 1e-9, emit_idx + 1,
                    acc + ids)
        return jax.lax.fori_loop(
            0, inner, body, (temp, emit_idx, jnp.zeros_like(emit_idx)))[2]

    for v in VOCABS:
        for r in ROWS:
            probs, keys = rows_for(r, v, seed=r)
            args = (probs, keys, jnp.zeros(r, jnp.int32),
                    jnp.full(r, 0.8, jnp.float32),
                    jnp.full(r, 0.95, jnp.float32))
            s = _timed(lambda: many(*args).block_until_ready())
            out(dict(what="chain", rows=r, vocab=v,
                     chain_us=round(1e6 * s / inner, 2)))


def ids_match(out):
    """The engine's `_sample_ids` beside the chain over the whole matrix,
    on the chip: the same id in every row? (The sandbox's tests hold the
    CPU to it; the chip's compiler tiles `[8, V]` and `[32, V]` as it
    likes.)"""
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.serving import PagedDecodeEngine

    def whole(probs, keys, emit_idx, temp, top_p):
        ids = chain(probs, keys, emit_idx, jnp.where(temp > 0, temp, 1.0),
                    top_p)
        return jnp.where(temp > 0, ids.astype(jnp.int32),
                         jnp.argmax(probs, axis=-1).astype(jnp.int32))

    engine = types.SimpleNamespace(top_k=None)
    chunked = jax.jit(lambda *a: PagedDecodeEngine._sample_ids(engine, *a))
    whole = jax.jit(whole)
    rng = np.random.default_rng(0)
    for v in VOCABS:
        rows = differ = 0
        for draw in range(16):
            probs, keys = rows_for(32, v, seed=100 + draw)
            temp = jnp.asarray(rng.choice(
                [0.0, 0.7, 0.8, 1.0, 1.3], 32).astype(np.float32))
            args = (probs, keys, jnp.full(32, draw, jnp.int32), temp,
                    jnp.asarray(rng.choice([1.0, 0.95, 0.9], 32
                                           ).astype(np.float32)))
            differ += int((np.asarray(chunked(*args))
                           != np.asarray(whole(*args))).sum())
            rows += int((np.asarray(temp) > 0).sum())
        out(dict(what="ids_match", vocab=v, sampled_rows=rows,
                 rows_that_differ=differ))


def decode_steps(out, repo: str):
    sys.path[:0] = [os.path.join(repo, "benchmark"), repo]
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deeplearning4j_tpu  # noqa: F401 - before the first device lookup
    import harness
    from deeplearning4j_tpu.serving import PagedDecodeEngine

    _, cell, cfg = harness.load_cell("gpt2m_serve_chat")
    net = harness.load_module("models", cell["config"]).build(cfg).init(0)
    eng = PagedDecodeEngine(net, **cell["server"])
    S, steps = eng.n_slots, DECODE_STEPS
    tables, _, _, keys, _, _ = eng._decode_args()
    no_fresh = jnp.zeros((5, S), jnp.int32)
    kv = eng.pool.kv
    for greedy, n_sampled in ((True, 0), (False, 0), (False, 1), (False, 2),
                              (False, 8), (False, 9), (False, 32)):
        decode = eng._build_decode(greedy_only=greedy)
        # two greedy slots decode beside the sampled ones, as in the cell
        live = np.zeros(S, np.int32)
        live[:min(S, n_sampled + 2)] = 1
        temp = np.zeros(S, np.float32)
        temp[:n_sampled] = 0.8
        # the released slots keep their temperatures (`_release`)
        temp[n_sampled + 2:] = 0.8
        fresh = jnp.asarray(np.stack(
            [np.ones(S, np.int32), np.arange(S, dtype=np.int32) + 5,
             np.zeros(S, np.int32), live * 10 ** 6, np.zeros(S, np.int32)]))
        temp, top_p = jnp.asarray(temp), jnp.full(S, 0.95, jnp.float32)

        def run(first):
            nonlocal kv
            carry, rows = jnp.zeros((4, S), jnp.int32), first
            for _ in range(steps):
                kv, toks, _, carry = decode(
                    eng._params, net.net_state, kv, tables, carry, rows,
                    keys, temp, top_p)
                rows = no_fresh
            toks.block_until_ready()

        s = _timed(lambda: run(fresh), reps=3)
        out(dict(what="decode", program="greedy" if greedy else "full",
                 sampled_rows=n_sampled, live_rows=int(live.sum()),
                 stale_temp_rows=int(S - live.sum()),
                 step_us=round(1e6 * s / steps, 2)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--decode", action="store_true")
    ap.add_argument("--ids", action="store_true")
    ap.add_argument("--repo", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--out", default="chiprun_out/sample_chain_cost.jsonl")
    a = ap.parse_args()
    sys.path.insert(0, a.repo)

    import jax

    import deeplearning4j_tpu  # noqa: F401 - before the first device lookup
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"a time is the chip's: this is {dev.platform}")
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    with open(a.out, "a") as f:
        def out(row):
            row = dict(row, device=dev.device_kind, repo=a.repo)
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
        if a.decode:
            decode_steps(out, a.repo)
        elif a.ids:
            ids_match(out)
        else:
            chain_table(out)


if __name__ == "__main__":
    main()

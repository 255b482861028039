#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives the normal path once, in ONE process, through the entry points a
user calls — conf -> `MultiLayerNetwork.fit` -> `ModelRegistry` ->
`GenerationServer` — at the full width of the widest language model in
the repo's records (`zoo.TransformerLM` d512 / L8 / H8 / T2048, vocab
512, `mixed_bf16`), with weights made from a seed and a corpus generated
here. It is a correctness-and-bring-up check, not a benchmark: what it
prints are facts about the run (compiles, peak bytes, which kernels
Mosaic compiled, whether donation was in effect), never rates.

    python3 chip_smoke.py                 # needs a TPU; anything else exits non-zero
    python3 chip_smoke.py --rehearse-cpu  # the same code at a tiny size on the CPU

The rehearsal exists so the script cannot rot between chip runs (a
tier-1 test runs it). It is chosen only by that argument, never by
detection; it reports `platform: cpu` and `"ok": false`, so it cannot be
read as a pass.

Any failed phase raises: there is no `except` that records an error and
carries on. The last line of stdout is one JSON object,
`{"ok": true, "device": {"platform", "kind", "count"}}`, printed only
when every phase passed on a TPU. Output goes under `chiprun_out/
chip_smoke/` beside this file (the directory the chip tool brings
back); the compile cache goes where `JAX_COMPILATION_CACHE_DIR` says or,
unset, to the checkout's fixed `.jax_cache/` (nd/cache.py).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)          # the package beside this file, no other
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

MODEL_NAME = "smoke-lm"
PERIOD = 8                        # the corpus is one token cycle

# the `long_context` point of the LM bench: the widest LM in any record.
# vocab is 512 because the training path takes dense one-hot labels
# [B, T, V] — 32 MiB a batch here, 3 GiB at a published 50k vocabulary.
FULL = dict(vocab=512, d_model=512, n_layers=8, n_heads=8, max_len=2048,
            batch=8, seq=2048, windows=64, epochs=75, spe=8,
            n_slots=8, block_len=16, prompt_lens=(5, 12, 20, 32),
            n_requests=32, min_tokens=16, token_stride=8,
            loss_ratio=0.2, par_steps=4)
# same code path, sized for a CPU minute
TINY = dict(vocab=64, d_model=32, n_layers=2, n_heads=4, max_len=64,
            batch=8, seq=64, windows=32, epochs=60, spe=4,
            n_slots=4, block_len=8, prompt_lens=(3, 6, 8),
            n_requests=12, min_tokens=4, token_stride=2,
            loss_ratio=0.5, par_steps=2)


def check(cond, msg):
    """A failed check fails the smoke (an `assert` would vanish under -O)."""
    if not cond:
        raise RuntimeError(f"chip_smoke: {msg}")


def say(msg):
    print(f"[chip_smoke +{time.monotonic() - T0:7.1f}s] {msg}", flush=True)


T0 = time.monotonic()


# --------------------------------------------------------------- the corpus
def cyclic_corpus(cfg):
    """Seeded periodic corpus (the recipe of serve_loadtest's
    `train_cyclic_lm`): one cycle of PERIOD distinct tokens, windows at
    every phase spanning the FULL position range, so every sinusoidal
    position decode will visit has been trained on. Returns
    (pattern, X [N, T] float ids, Y [N, T, V] one-hot)."""
    import numpy as np

    rng = np.random.default_rng(3)
    pattern = rng.choice(cfg["vocab"], PERIOD, replace=False)
    T, N = cfg["seq"], cfg["windows"]
    corpus = np.tile(pattern, (N + T) // PERIOD + 2)
    X = np.stack([corpus[i:i + T] for i in range(N)])
    Y = np.stack([corpus[i + 1:i + T + 1] for i in range(N)])
    return (pattern, X.astype(np.float32),
            np.eye(cfg["vocab"], dtype=np.float32)[Y])


def build_net(cfg):
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.zoo.transformer import TransformerLM

    conf = TransformerLM(
        vocab_size=cfg["vocab"], d_model=cfg["d_model"],
        n_layers=cfg["n_layers"], n_heads=cfg["n_heads"],
        max_len=cfg["max_len"], seed=11).conf()
    conf.dtype_policy = "mixed_bf16"
    return MultiLayerNetwork(conf).init(11)


def loss_log(on_step=None):
    """A TrainingListener collecting every per-step loss readback."""
    from deeplearning4j_tpu.optimize.listeners import TrainingListener

    class LossLog(TrainingListener):
        def __init__(self):
            self.losses = []

        def iteration_done(self, model, iteration, epoch, score, **info):
            self.losses.append(float(score))
            if on_step is not None:
                on_step(model, len(self.losses) - 1)

    return LossLog()


# ------------------------------------------------------------------- phases
def phase_train(cfg, facts, on_tpu):
    import jax

    from deeplearning4j_tpu.kernels import layernorm
    from deeplearning4j_tpu.kernels.flash_attention import (
        KERNEL_NAMES as FLASH_KERNELS)
    from deeplearning4j_tpu.nd.donation import donation_safe

    pattern, X, Y = cyclic_corpus(cfg)
    net = build_net(cfg)
    log = loss_log()
    net.set_listeners(log)
    # donated away by the first step
    first_leaf = jax.tree_util.tree_leaves(net.params)[0]
    say(f"train: {net.num_params():,} params, {net.dtype.name}, "
        f"B={cfg['batch']} T={cfg['seq']}, "
        f"{cfg['epochs'] * cfg['windows'] // cfg['batch']} steps, "
        f"steps_per_execution={cfg['spe']}")
    net.fit(X, Y, epochs=cfg["epochs"], batch_size=cfg["batch"],
            shuffle=False, steps_per_execution=cfg["spe"])
    losses = log.losses
    check(len(losses) == cfg["epochs"] * cfg["windows"] // cfg["batch"],
          f"expected one loss per step, got {len(losses)}")
    check(all(math.isfinite(v) for v in losses),
          f"non-finite loss at step "
          f"{[i for i, v in enumerate(losses) if not math.isfinite(v)][:5]}")
    check(losses[-1] < cfg["loss_ratio"] * losses[0],
          f"loss did not fall clearly: first {losses[0]:.4f} "
          f"last {losses[-1]:.4f}")
    facts["train"] = {"steps": len(losses), "loss_first": losses[0],
                      "loss_last": losses[-1]}
    say(f"train: loss {losses[0]:.4f} -> {losses[-1]:.4f}, all finite; "
        f"every {max(1, len(losses) // 8)} steps: "
        f"{[round(v, 4) for v in losses[::max(1, len(losses) // 8)]]}")

    # donation: the step was built with donate_argnums and the buffer the
    # net held before fit() is gone
    donated = bool(first_leaf.is_deleted())
    facts["donation"] = {"requested": donation_safe(),
                         "train_inputs_deleted": donated}
    if on_tpu:
        check(donation_safe() and donated,
              "donation is not in effect on the train step")

    # which Pallas kernels Mosaic compiled: the lowering of the EXACT
    # fused program fit() just ran names every kernel as a
    # tpu_custom_call (interpret mode would leave none)
    text = net.lower_train_step(X[:cfg["batch"]], Y[:cfg["batch"]],
                                steps=cfg["spe"]).as_text()
    mosaic = set(re.findall(r'kernel_name = "([^"]+)"', text))
    expected = FLASH_KERNELS + layernorm.KERNEL_NAMES
    facts["mosaic_kernels"] = {k: k in mosaic for k in expected}
    facts["tpu_custom_calls"] = text.count("tpu_custom_call")
    say(f"train: Mosaic-compiled kernels in the step: "
        f"{sorted(mosaic) or 'none (not on this platform)'}")
    if on_tpu:
        missing = [k for k in expected if k not in mosaic]
        check(not missing, f"kernels not compiled by Mosaic: {missing}")
    return net, pattern, losses


def phase_publish(cfg, net, facts):
    import jax
    import numpy as np

    from deeplearning4j_tpu.serving.registry import ModelRegistry

    root = os.path.join(OUT_DIR, "registry")
    shutil.rmtree(root, ignore_errors=True)
    reg = ModelRegistry(root)
    version = reg.publish(MODEL_NAME, net)
    served, got = reg.resolve(MODEL_NAME)
    check(got == version, f"resolved v{got}, published v{version}")
    check(served is not net, "registry returned the trained object")
    check(served.dtype.name == net.dtype.name,
          f"dtype policy lost in the zip: {served.dtype.name}")
    for a, b in zip(jax.tree_util.tree_leaves(net.params),
                    jax.tree_util.tree_leaves(served.params)):
        check(np.array_equal(np.asarray(a), np.asarray(b)),
              "resolved weights differ from the trained weights")
    facts["registry"] = {"version": version,
                         "zip_bytes": os.path.getsize(
                             reg.path(MODEL_NAME, version))}
    say(f"publish: v{version} published, resolved, weights bit-equal")
    return served


def make_requests(cfg, pattern):
    """Mixed prompt lengths / lengths / sampling, all on-cycle."""
    import numpy as np

    tiled = np.tile(pattern, max(cfg["prompt_lens"]) // PERIOD + 3)
    reqs = []
    for i in range(cfg["n_requests"]):
        plen = cfg["prompt_lens"][i % len(cfg["prompt_lens"])]
        phase = i % PERIOD
        reqs.append(dict(
            prompt=tiled[phase:phase + plen].astype(np.int32),
            n_tokens=cfg["min_tokens"] + cfg["token_stride"] * (i % 5),
            sampled=(i % 7 in (3, 6)), seed=i))
    return reqs


def phase_serve(cfg, served, pattern, facts, compiles, on_tpu):
    import numpy as np

    from deeplearning4j_tpu.serving import GenerationServer
    from deeplearning4j_tpu.serving.paged import blocks_needed
    from deeplearning4j_tpu.zoo.transformer import generate

    reqs = make_requests(cfg, pattern)
    n_max = max(r["n_tokens"] for r in reqs)

    # reference first (its compiles are not the server's): whole-batch
    # generate() on the SAME resolved weights, one call per prompt length
    refs = {}
    for plen in cfg["prompt_lens"]:
        idx = [i for i, r in enumerate(reqs)
               if len(r["prompt"]) == plen and not r["sampled"]]
        out = generate(served, np.stack([reqs[i]["prompt"] for i in idx]),
                       n_max, temperature=0)
        for row, i in zip(out, idx):
            refs[i] = np.asarray(row)
    # the trained cycle gives wide margins: the reference itself must
    # continue the cycle, or a later mismatch could be a tie
    cyc = np.tile(pattern, n_max // PERIOD + 6)
    off_cycle = {}
    for i, ref in refs.items():
        start = int(np.where(pattern == reqs[i]["prompt"][-1])[0][0]) + 1
        wrong = np.nonzero(ref != cyc[start:start + n_max])[0]
        if len(wrong):
            off_cycle[i] = (len(reqs[i]["prompt"]), int(wrong[0]))
    facts["reference_on_cycle"] = f"{len(refs) - len(off_cycle)}/{len(refs)}"
    check(not off_cycle,
          f"generate() left the trained cycle on {len(off_cycle)}/"
          f"{len(refs)} prompts — the model did not converge; request: "
          f"(prompt length, first wrong token) = {off_cycle}")

    per_seq = blocks_needed(max(cfg["prompt_lens"]) + n_max,
                            cfg["block_len"])
    server = GenerationServer(
        served, n_slots=cfg["n_slots"], block_len=cfg["block_len"],
        n_blocks=cfg["n_slots"] * per_seq + 1)
    c0 = compiles()
    server.warmup(max(cfg["prompt_lens"]))
    c1 = compiles()
    say(f"serve: warmup grid compiled {c1[0] - c0[0]:.0f} programs in "
        f"{c1[1] - c0[1]:.1f}s of XLA time")
    pool_leaf = server.engine.pool.kv[0][0]
    server.start()
    streams = []

    def submit(r):
        kw = {}
        if r["sampled"]:
            kw = dict(temperature=0.8,
                      rng=np.asarray([0, r["seed"]], np.uint32))
        streams.append(server.generate_async(r["prompt"], r["n_tokens"],
                                             **kw))

    # a first wave over-fills the slots; the rest arrive while those
    # decode, so every later admission joins a running batch
    head = cfg["n_slots"] + cfg["n_slots"] // 2
    for r in reqs[:head]:
        submit(r)
    deadline = time.monotonic() + 600
    while not any(len(s.tokens) >= 2 for s in streams):
        check(time.monotonic() < deadline, "no stream produced a token")
        time.sleep(0.002)
    for r in reqs[head:]:
        submit(r)
        time.sleep(0.002)
    outs = [s.result(timeout=900) for s in streams]
    server.drain()
    server.stop()
    c2 = compiles()

    vocab = cfg["vocab"]
    greedy_equal = 0
    for i, (r, out) in enumerate(zip(reqs, outs)):
        out = np.asarray(out)
        check(out.shape == (r["n_tokens"],),
              f"request {i}: {out.shape[0]} tokens, wanted {r['n_tokens']}")
        check(bool(((out >= 0) & (out < vocab)).all()),
              f"request {i}: token outside the vocabulary")
        if not r["sampled"]:
            check(np.array_equal(out, refs[i][:r["n_tokens"]]),
                  f"request {i} (greedy, prompt {len(r['prompt'])}): served "
                  f"{out.tolist()} != generate() "
                  f"{refs[i][:r['n_tokens']].tolist()}")
            greedy_equal += 1
    # admit-into-a-running-batch happened: some stream got its first
    # token strictly inside another stream's decode interval
    joined = sum(
        any(b.t_first < a.t_first < b.t_last for b in streams if b is not a)
        for a in streams)
    check(joined > 0, "no request was admitted into a running batch")
    after_warmup = c2[0] - c1[0]
    facts["serve"] = {
        "requests": len(reqs), "greedy": len(refs),
        "sampled": len(reqs) - len(refs),
        "greedy_equal_generate": f"{greedy_equal}/{len(refs)}",
        "admitted_into_running_batch": joined,
        "warmup_compiles": c1[0] - c0[0],
        "warmup_compile_seconds": round(c1[1] - c0[1], 2),
        "compiles_after_warmup": after_warmup,
    }
    donated = bool(pool_leaf.is_deleted())
    facts["donation"]["serving_pool_deleted"] = donated
    if on_tpu:
        check(donated, "donation is not in effect on the serving pool")
    check(after_warmup == 0,
          f"{after_warmup:.0f} XLA compiles happened after warmup(), "
          f"inside live serving")
    say(f"serve: {len(reqs)} streams ok, greedy == generate() on "
        f"{greedy_equal}/{len(refs)}, {joined} joined a running batch, "
        f"{after_warmup:.0f} compiles after warmup")


def phase_four_chips(cfg, one_device_losses, facts):
    """Sync data parallelism over four devices on the same LM: the loss
    tracks the one-device steps and the placement is really four-way."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.parallel import (MeshSpec, ParallelTrainer,
                                             make_mesh)

    n_dev = len(jax.devices())
    if n_dev < 4:
        facts["four_chips"] = f"did not run: {n_dev} device(s) visible"
        say(f"four chips: section did not run — jax sees {n_dev} "
            f"device(s), it needs 4")
        return
    _, X, Y = cyclic_corpus(cfg)
    k = cfg["par_steps"]
    X, Y = X[:k * cfg["batch"]], Y[:k * cfg["batch"]]
    net = build_net(cfg)                      # same seed as phase_train
    label_shape = (cfg["batch"],) + Y.shape[1:]
    before = {id(a) for a in jax.live_arrays()}
    placement = {}

    def look(model, step):
        if step or placement:
            return
        batch = [a for a in jax.live_arrays()
                 if id(a) not in before and a.shape == label_shape]
        check(batch, "no device-resident label batch found")
        placement["batch"] = [
            (s.device.id, tuple(s.data.shape))
            for s in batch[0].addressable_shards]
        live = model._live_state_provider()
        for name in ("params", "updater_state"):
            leaf = jax.tree_util.tree_leaves(live[name])[0]
            placement[name] = [(s.device.id, tuple(s.data.shape),
                                tuple(leaf.shape))
                               for s in leaf.addressable_shards]

    log = loss_log(on_step=look)
    net.set_listeners(log)
    trainer = ParallelTrainer(net, make_mesh(MeshSpec.of(data=4)),
                              mode="sync")
    trainer.fit(X, Y, epochs=1, batch_size=cfg["batch"])
    for name, shards in placement.items():
        say(f"four chips: {name} shards (device, shard shape"
            f"{', full shape' if name != 'batch' else ''}): {shards}")
    check(len({d for d, _ in placement["batch"]}) == 4
          and all(shape[0] == cfg["batch"] // 4
                  for _, shape in placement["batch"]),
          f"batch is not split over four devices: {placement['batch']}")
    for name in ("params", "updater_state"):
        check(len({d for d, _, _ in placement[name]}) == 4
              and all(shape == full for _, shape, full in placement[name]),
              f"{name} is not replicated on four devices: "
              f"{placement[name]}")
    ref = one_device_losses[:k]
    rel = [abs(a - b) / abs(b) for a, b in zip(log.losses, ref)]
    check(len(log.losses) == k and all(math.isfinite(v)
                                       for v in log.losses),
          f"four-device losses: {log.losses}")
    check(max(rel) < 0.05,
          f"four-device loss left the one-device steps: {log.losses} "
          f"vs {ref}")
    facts["four_chips"] = {"steps": k, "losses": log.losses,
                           "one_device_losses": ref,
                           "max_rel_diff": max(rel),
                           "devices": sorted({d for d, _
                                              in placement["batch"]})}
    say(f"four chips: {k} sync steps, loss within {max(rel):.2e} of the "
        f"one-device steps")


# --------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run the same path at a tiny size on the CPU; "
                         "reports platform cpu and is never a pass")
    args = ap.parse_args(argv)

    import jax
    if args.rehearse_cpu:
        jax.config.update("jax_platforms", "cpu")
    import deeplearning4j_tpu
    check(os.path.dirname(os.path.dirname(os.path.abspath(
        deeplearning4j_tpu.__file__))) == HERE,
        f"imported the package from {deeplearning4j_tpu.__file__}, not "
        f"from beside this script")

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say(f"jax {jax.__version__}  platform={dev.platform}  "
        f"device_kind={dev.device_kind}  devices={device['count']}")
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse_cpu:
        print(f"chip_smoke: needs a TPU; jax found platform="
              f"{dev.platform!r} ({dev.device_kind}). Nothing was run.",
              file=sys.stderr)
        return 1
    cfg = TINY if args.rehearse_cpu else FULL

    from deeplearning4j_tpu.monitor import (JitCompileCollector,
                                            MetricsRegistry)
    from deeplearning4j_tpu.nd import enable_compilation_cache
    cache_dir = enable_compilation_cache(min_compile_time_secs=0.0)
    coll = JitCompileCollector(MetricsRegistry()).install()
    cache_events = {"requests": 0, "hits": 0}

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            cache_events["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1

    jax.monitoring.register_event_listener(on_event)

    def compiles():
        return coll.compile_count(), coll.compile_seconds()

    os.makedirs(OUT_DIR, exist_ok=True)
    facts = {"jax": jax.__version__, "device": device,
             "rehearsal": bool(args.rehearse_cpu),
             "config": {k: cfg[k] for k in ("vocab", "d_model", "n_layers",
                                            "n_heads", "max_len", "batch",
                                            "seq")},
             "compile_cache_dir": cache_dir}

    net, pattern, losses = phase_train(cfg, facts, on_tpu)
    try:
        served = phase_publish(cfg, net, facts)
        phase_serve(cfg, served, pattern, facts, compiles, on_tpu)
    finally:
        # the zip is ~100 MiB at full width; the chip tool brings back
        # at most 64 MiB of output, and the reports are what matters
        shutil.rmtree(os.path.join(OUT_DIR, "registry"),
                      ignore_errors=True)
    phase_four_chips(cfg, losses, facts)

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    n, secs = compiles()
    facts["compile"] = {
        "xla_compiles": n, "compile_seconds": round(secs, 2),
        "persistent_cache_requests": cache_events["requests"],
        "persistent_cache_hits": cache_events["hits"]}
    facts["peak_bytes_in_use"] = (None if None in peaks else max(peaks))
    facts["wall_seconds"] = round(time.monotonic() - T0, 1)
    facts["claim"] = None
    with open(os.path.join(OUT_DIR, "reports.jsonl"), "a") as f:
        f.write(json.dumps(facts) + "\n")
    say(f"{n:.0f} XLA compiles, {secs:.1f}s compiling, "
        f"{cache_events['hits']}/{cache_events['requests']} persistent-"
        f"cache hits, peak bytes {facts['peak_bytes_in_use']}")
    print(json.dumps(facts), flush=True)
    print(json.dumps({"ok": on_tpu, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

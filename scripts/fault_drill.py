#!/usr/bin/env python
"""Fault-injection drill driver: real subprocess kills, auto-resume,
bit-parity verdict.

Smoke recipe (scripts/verify.sh stage [6/6]):

    python scripts/fault_drill.py --smoke [--with-corruption]

1. reference: a child process trains a tiny MLP for 30 steps
   (3 epochs x 10 shuffled batches) with NO fault machinery and dumps
   its final params + updater state.
2. drill: a second lineage trains the same run with an
   AsyncCheckpointer (freq 5, keep-last 3) and a scripted SIGTERM at
   step 15 — the process dies for real, mid-whatever-was-in-flight
   (the atomic tmp+fsync+rename commit protocol is what keeps the
   checkpoint directory sane through that). With --with-corruption the
   newest committed checkpoint is additionally bit-flipped before
   resuming, drilling the fallback-to-previous path.
3. auto-resume: the driver relaunches the child with --resume until it
   completes (each resume restores model + counters + iterator cursor
   from the newest VALID checkpoint).
4. verdict: final params/updater state of the resumed lineage must be
   BIT-IDENTICAL to the uninterrupted reference (same rng folds, same
   shuffle permutations, same updater step counts) — exit 0 iff so.

`--child` is the internal worker entry point; see
docs/FAULT_TOLERANCE.md for custom drill recipes.

A CPU drill: it starts one OS process per lineage / elastic member and
pins every one to `JAX_PLATFORMS=cpu`. A chip belongs to one process at
a time, so this driver cannot run its children on a TPU host's chips.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import tempfile

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

# deterministic tiny-MLP training problem shared by every child process
SEED = 7
N_FEATURES, N_HIDDEN, N_CLASSES = 4, 16, 3
N_EXAMPLES, BATCH = 80, 8          # 10 batches / epoch
EPOCHS = 3                          # 30 steps total


def _build_net():
    from deeplearning4j_tpu.common.updaters import Adam
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder().seed(SEED)
            .updater(Adam(0.01)).list()
            .layer(DenseLayer(n_in=N_FEATURES, n_out=N_HIDDEN,
                              activation="tanh"))
            .layer(OutputLayer(n_in=N_HIDDEN, n_out=N_CLASSES,
                               activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf)


def _make_iterator():
    import numpy as np
    from deeplearning4j_tpu.datasets.iterator import ArrayDataSetIterator

    rng = np.random.default_rng(0)
    x = rng.standard_normal((N_EXAMPLES, N_FEATURES)).astype(np.float32)
    w = rng.standard_normal((N_FEATURES, N_CLASSES))
    y = np.eye(N_CLASSES, dtype=np.float32)[np.argmax(x @ w, axis=1)]
    # shuffle=True on purpose: the drill must prove the cursor/seek
    # contract replays the interrupted epoch's exact permutation
    return ArrayDataSetIterator(x, y, batch_size=BATCH, shuffle=True,
                                seed=11)


def _dump_final(net, out_path):
    import numpy as np
    from deeplearning4j_tpu.fault import state as fs

    flat = {}
    flat.update({f"params{fs.SEP}{k}": v for k, v in
                 fs.flatten_arrays(net.params).items()})
    flat.update({f"updater{fs.SEP}{k}": v for k, v in
                 fs.flatten_arrays(net.updater_state).items()})
    flat["__counters__"] = np.asarray(
        [net.iteration_count, net.epoch_count])
    with open(out_path, "wb") as f:
        np.savez(f, **flat)


def run_child(args) -> int:
    from deeplearning4j_tpu import fault

    iterator = _make_iterator()
    if args.resume:
        try:
            net, _ = fault.resume(args.ckpt_dir, iterator=iterator)
        except FileNotFoundError:
            # preempted before the first commit ever landed: a resume
            # driver restarts from scratch (which reproduces the run
            # bit-exactly too — it replays from step 0)
            print("no committed checkpoint yet; cold restart")
            net = _build_net().init()
    else:
        net = _build_net().init()
    ckptr = None
    if args.ckpt_dir:
        ckptr = fault.AsyncCheckpointer(args.ckpt_dir, keep_last=3)
        net.add_listener(fault.CheckpointListener(
            ckptr, frequency=args.ckpt_freq, iterator=iterator))
    if args.kill_at:
        # TPU preemptions arrive with a notice; the drill's SIGTERM
        # honors the grace period by draining pending checkpoint writes
        # first (the no-grace torn-write path is what the atomic commit
        # protocol + corruption drills cover)
        net.add_listener(fault.PreemptionListener(
            args.kill_at, mode="sigterm", wait_for_checkpointer=ckptr))
    net.fit(iterator, epochs=EPOCHS - net.epoch_count)
    _dump_final(net, args.out)
    print(f"child done: {net.iteration_count} steps, "
          f"{net.epoch_count} epochs")
    return 0


def _spawn(out, ckpt_dir=None, kill_at=None, resume=False,
           ckpt_freq=5) -> int:
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--out", str(out), "--ckpt-freq", str(ckpt_freq)]
    if ckpt_dir:
        cmd += ["--ckpt-dir", str(ckpt_dir)]
    if kill_at:
        cmd += ["--kill-at", str(kill_at)]
    if resume:
        cmd += ["--resume"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(cmd, env=env, timeout=300)
    return proc.returncode


def _compare(ref_path, got_path) -> list:
    import numpy as np

    with np.load(ref_path) as a, np.load(got_path) as b:
        bad = []
        for k in sorted(set(a.files) | set(b.files)):
            if k not in a.files or k not in b.files:
                bad.append(f"{k}: missing on one side")
            elif a[k].dtype != b[k].dtype or a[k].shape != b[k].shape \
                    or not np.array_equal(a[k], b[k]):
                bad.append(f"{k}: differs")
        return bad


def smoke(with_corruption: bool) -> int:
    tmp = tempfile.mkdtemp(prefix="fault_drill_")
    ref_out = os.path.join(tmp, "reference.npz")
    got_out = os.path.join(tmp, "resumed.npz")
    ckpt_dir = os.path.join(tmp, "ckpts")

    print("== fault drill: uninterrupted reference (30 steps) ==")
    rc = _spawn(ref_out)
    if rc != 0:
        print(f"FAIL: reference run exited {rc}")
        return 1

    print("== fault drill: SIGTERM at step 15, checkpoint every 5 ==")
    rc = _spawn(got_out, ckpt_dir=ckpt_dir, kill_at=15)
    if rc == 0:
        print("FAIL: scripted kill did not fire")
        return 1
    print(f"child died as scripted (rc={rc})")

    if with_corruption:
        from deeplearning4j_tpu.fault import corrupt_checkpoint
        path = corrupt_checkpoint(ckpt_dir, mode="flip")
        print(f"injected bit-flip into {path} — resume must fall back")

    restarts = 0
    while restarts < 4:
        print(f"== fault drill: auto-resume attempt {restarts + 1} ==")
        rc = _spawn(got_out, ckpt_dir=ckpt_dir, resume=True)
        if rc == 0:
            break
        restarts += 1
    else:
        print("FAIL: resume did not complete within 4 restarts")
        return 1

    bad = _compare(ref_out, got_out)
    if bad:
        print("FAIL: resumed run is not bit-identical to the "
              "uninterrupted reference:")
        for b in bad[:10]:
            print(f"  {b}")
        return 1
    print("fault-drill smoke OK: kill@15 + resume reproduced the "
          "uninterrupted 30-step run bit-identically"
          + (" (with corrupted-newest fallback)" if with_corruption
             else ""))
    return 0


# =====================================================================
# elastic drill: coordinator-driven membership, SIGKILL shrink + grow
# =====================================================================
# deterministic elastic training problem: 240 examples / global batch
# 24 -> 10 steps per epoch, 5 epochs = 50 steps. Batch 24 divides by
# every replica count the drill visits (4 -> 3 -> 4, one CPU device
# per process).
E_FEATURES, E_HIDDEN, E_CLASSES = 8, 16, 3
E_EXAMPLES, E_BATCH, E_EPOCHS = 240, 24, 5
E_STEPS = (E_EXAMPLES // E_BATCH) * E_EPOCHS
E_KILL_AT = 15        # SIGKILL one worker here (shrink)
# re-add the victim once the fleet passes this step: only the re-formed
# 3-wide world can reach it (the 4-wide world dies at ~15-17, and stale
# pre-kill member info can't cross it either)
E_GROW_AT = 20
E_CKPT_FREQ = 5
# per-step throttle in the elastic children: reconfiguration latency
# (register + settle + drain + re-init + re-compile) must fit INSIDE
# the remaining run, or the survivors finish before the grow commits
E_STEP_SLEEP_S = 0.3


def _build_elastic_net():
    from deeplearning4j_tpu.common.updaters import Adam
    from deeplearning4j_tpu.nn.conf import (
        InputType,
        NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder().seed(SEED)
            .updater(Adam(0.01)).list()
            .layer(DenseLayer(n_in=E_FEATURES, n_out=E_HIDDEN,
                              activation="tanh"))
            .layer(OutputLayer(n_in=E_HIDDEN, n_out=E_CLASSES,
                               activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(E_FEATURES)).build())
    return MultiLayerNetwork(conf)


def _make_elastic_iterator():
    import numpy as np
    from deeplearning4j_tpu.datasets.iterator import ArrayDataSetIterator

    rng = np.random.default_rng(0)
    x = rng.standard_normal((E_EXAMPLES, E_FEATURES)).astype(np.float32)
    w = rng.standard_normal((E_FEATURES, E_CLASSES))
    y = np.eye(E_CLASSES, dtype=np.float32)[np.argmax(x @ w, axis=1)]
    return ArrayDataSetIterator(x, y, batch_size=E_BATCH, shuffle=True,
                                seed=11)


def _write_elastic_result(out, model, losses, history):
    import json

    import numpy as np
    from deeplearning4j_tpu.fault import state as fs

    flat = {f"params{fs.SEP}{k}": v for k, v in
            fs.flatten_arrays(model.params).items()}
    with open(out + ".npz", "wb") as f:
        np.savez(f, **flat)
    with open(out + ".json", "w") as f:
        json.dump({"losses": {str(k): v for k, v in losses.items()},
                   "history": history,
                   "iteration_count": int(model.iteration_count)}, f)


def run_elastic_child(args) -> int:
    """One elastic worker: joins the membership, trains the shared
    problem in threshold gradient-sharing mode, survives
    reconfigurations. `--kill-at` arms the SIGKILL preemption (the
    shrink victim)."""
    import json

    from deeplearning4j_tpu import fault
    from deeplearning4j_tpu.optimize.listeners import TrainingListener
    from deeplearning4j_tpu.parallel.elastic import (
        ElasticConfig,
        ElasticTrainer,
    )

    # the loss trajectory must survive THIS PROCESS being killed and
    # relaunched: seed from the previous life's flush file and flush
    # every step (a re-executed step overwrites its recorded loss, so
    # the final trajectory is the as-committed one)
    flush_path = args.out + ".losses.json"
    losses = {}
    if os.path.exists(flush_path):
        try:
            with open(flush_path) as f:
                losses = {int(k): v for k, v in json.load(f).items()}
        except (OSError, ValueError):
            # a previous life died mid-flush; resumed steps re-fill the
            # trajectory (a crash-loop on a torn file would burn every
            # relaunch attempt)
            losses = {}

    import time

    class Collect(TrainingListener):
        def iteration_done(self, model, iteration, epoch, score, **info):
            losses[int(iteration)] = float(score)
            # tmp+replace: this process can be shot mid-write (SIGKILL
            # drill, jax error poller) and the next life reloads the file
            tmp = flush_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump({str(k): v for k, v in losses.items()}, f)
            os.replace(tmp, flush_path)
            time.sleep(E_STEP_SLEEP_S)

    def extra_listeners(generation):
        extras = [Collect()]
        if args.kill_at:
            extras.append(fault.PreemptionListener(args.kill_at,
                                                   mode="sigkill"))
        return extras

    cfg = ElasticConfig(
        control_address=args.control, token=args.token,
        heartbeat_interval_s=0.25, on_fatal="exit",
        init_timeout_s=30.0, init_attempts=1,
        jax_heartbeat_interval_s=1.0, jax_max_missing_heartbeats=4)
    et = ElasticTrainer(
        lambda: _build_elastic_net(), config=cfg, ckpt_dir=args.ckpt_dir,
        ckpt_frequency=args.ckpt_freq, gradient_sharing="threshold")
    model = et.fit(_make_elastic_iterator, epochs=E_EPOCHS,
                   batch_size=E_BATCH, extra_listeners=extra_listeners)
    _write_elastic_result(args.out, model, losses, et.history)
    print(f"elastic worker {args.token} done: "
          f"{model.iteration_count} steps over generations "
          f"{[h['generation'] for h in et.history]}")
    # skip the interpreter's atexit `jax.distributed.shutdown`: its
    # barrier needs every peer, and a peer that died (or already left)
    # turns a COMPLETED run into an abort — the result files above are
    # the completion contract, the driver checks those
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)


def run_elastic_ref(args) -> int:
    """Uninterrupted reference at the FINAL replica count: one process,
    4 CPU devices, the same threshold-mode global program."""
    from deeplearning4j_tpu.optimize.listeners import TrainingListener
    from deeplearning4j_tpu.parallel.mesh import device_mesh
    from deeplearning4j_tpu.parallel.trainer import ParallelTrainer

    losses = {}

    class Collect(TrainingListener):
        def iteration_done(self, model, iteration, epoch, score, **info):
            losses[int(iteration)] = float(score)

    net = _build_elastic_net().init()
    net.add_listener(Collect())
    ParallelTrainer(net, device_mesh(4), mode="sync",
                    gradient_sharing="threshold").fit(
        _make_elastic_iterator(), epochs=E_EPOCHS, batch_size=E_BATCH)
    _write_elastic_result(args.out, net, losses, [])
    print(f"elastic reference done: {net.iteration_count} steps")
    return 0


def _spawn_elastic(token, control, ckpt_dir, out, kill_at=None):
    cmd = [sys.executable, os.path.abspath(__file__), "--elastic-child",
           "--token", token, "--control", control,
           "--ckpt-dir", str(ckpt_dir), "--out", str(out),
           "--ckpt-freq", str(E_CKPT_FREQ)]
    if kill_at:
        cmd += ["--kill-at", str(kill_at)]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=1"])
    return subprocess.Popen(cmd, env=env)


def elastic_smoke() -> int:
    """The survive-the-kill drill: 4-process gloo run, SIGKILL one
    worker at step ~15 (shrink to a 3-process mesh), re-add it once the
    survivors pass step ~20 (grow back to 4), finish 50 steps — with
    loss-trajectory parity vs an uninterrupted 4-replica reference and
    `elastic_*` metrics on /metrics."""
    import json
    import time
    import urllib.request

    import numpy as np

    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.parallel.elastic import (
        ElasticCoordinator,
        RESTART_EXIT_CODE,
    )

    tmp = tempfile.mkdtemp(prefix="elastic_drill_")
    ckpt_dir = os.path.join(tmp, "ckpts")
    ref_out = os.path.join(tmp, "reference")

    print("== elastic drill: uninterrupted 4-replica reference ==")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=4"])
    rc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--elastic-ref",
         "--out", ref_out], env=env, timeout=300).returncode
    if rc != 0:
        print(f"FAIL: reference run exited {rc}")
        return 1

    monitor.enable()
    # settle wide enough that the near-simultaneous relaunch of several
    # survivors coalesces into ONE new generation (a 1-member commit
    # would briefly train solo at different math); grace wide enough
    # that a jit-compile stall doesn't read as death
    co = ElasticCoordinator(grace_s=6.0, settle_s=2.0, tick_s=0.1,
                            min_members=4,
                            jax_port_base=_elastic_port_base()).start()
    print(f"== elastic drill: coordinator on {co.address}, launching 4 "
          f"workers (SIGKILL {E_KILL_AT=}, grow after {E_GROW_AT=}) ==")
    tokens = [f"w{i}" for i in range(4)]
    kill_token = "w2"
    outs = {t: os.path.join(tmp, f"worker_{t}") for t in tokens}
    procs = {t: _spawn_elastic(t, co.address, ckpt_dir, outs[t],
                               kill_at=E_KILL_AT if t == kill_token
                               else None)
             for t in tokens}
    relaunches = {t: 0 for t in tokens}
    done = {t: False for t in tokens}
    kill_seen = False
    regrown = False
    deadline = time.time() + 420
    try:
        while not all(done.values()):
            if time.time() > deadline:
                print(f"FAIL: drill timed out; done={done}")
                return 1
            time.sleep(0.5)
            status = co.status()
            max_step = max([m["info"].get("step", 0)
                            for m in status["members"].values()] or [0])
            for t in tokens:
                p = procs.get(t)
                if done[t] or p is None or p.poll() is None:
                    continue
                rc = p.returncode
                # the completion contract is the RESULT FILE, not the
                # exit code: a worker that finished can still be shot by
                # the jax error poller (a peer died before it exited)
                if rc == 0 or _elastic_finished(outs[t]):
                    if rc != 0:
                        print(f"worker {t} completed; exit poisoned by "
                              f"distributed teardown (rc={rc})")
                    done[t] = True
                    continue
                if t == kill_token and not regrown:
                    if not kill_seen and rc == -9:
                        kill_seen = True
                        print(f"worker {t} SIGKILLed as scripted "
                              f"(rc={rc}); survivors must re-form")
                        procs[t] = None
                        continue
                    if not kill_seen:
                        # incidental pre-kill death: relaunch with the
                        # scripted kill still armed
                        relaunches[t] += 1
                        if relaunches[t] > 6:
                            print(f"FAIL: worker {t} needed >6 "
                                  f"relaunches")
                            return 1
                        print(f"relaunching {t} (rc={rc} before the "
                              f"scripted kill, attempt {relaunches[t]})")
                        procs[t] = _spawn_elastic(
                            t, co.address, ckpt_dir, outs[t],
                            kill_at=E_KILL_AT)
                        continue
                    continue
                # survivor died (wedged-in-collective abort, or a
                # controlled RESTART_EXIT_CODE): relaunch it — the
                # restart-shaped recovery path
                relaunches[t] += 1
                if relaunches[t] > 6:
                    print(f"FAIL: worker {t} needed >6 relaunches")
                    return 1
                why = ("restart requested" if rc == RESTART_EXIT_CODE
                       else f"rc={rc}")
                print(f"relaunching {t} ({why}, attempt {relaunches[t]}, "
                      f"fleet step ~{max_step})")
                procs[t] = _spawn_elastic(t, co.address, ckpt_dir, outs[t])
            if kill_seen and not regrown and max_step >= E_GROW_AT:
                print(f"== grow: re-adding {kill_token} at fleet step "
                      f"~{max_step} ==")
                procs[kill_token] = _spawn_elastic(
                    kill_token, co.address, ckpt_dir, outs[kill_token])
                regrown = True
    finally:
        for p in procs.values():
            if p is not None and p.poll() is None:
                p.kill()
                p.wait()

    status = co.status()
    print(f"final membership status: generation {status['generation']}, "
          f"completed {status['completed']}")
    if not kill_seen or not regrown:
        print(f"FAIL: drill did not execute shrink+grow "
              f"(kill_seen={kill_seen}, regrown={regrown})")
        return 1
    if status["generation"] < 3:
        print(f"FAIL: expected >=3 membership generations "
              f"(initial, shrink, grow), got {status['generation']}")
        return 1

    # ---- verdict: trajectory parity + elastic state markers
    with open(ref_out + ".json") as f:
        ref = json.load(f)
    ref_losses = {int(k): v for k, v in ref["losses"].items()}
    init_loss = ref_losses[0]
    failures = []
    histories = {}
    for t in tokens:
        with open(outs[t] + ".json") as f:
            rec = json.load(f)
        histories[t] = rec["history"]
        got = {int(k): v for k, v in rec["losses"].items()}
        if rec["iteration_count"] != E_STEPS:
            failures.append(f"{t}: finished at step "
                            f"{rec['iteration_count']} != {E_STEPS}")
            continue
        # steps before the first checkpointed resume point ran at the
        # same 4-replica math as the reference: tight parity
        tight = [i for i in range(E_CKPT_FREQ) if i in got]
        if not tight:
            failures.append(f"{t}: no pre-checkpoint steps recorded")
        for i in tight:
            if abs(got[i] - ref_losses[i]) > 1e-4 * max(
                    1.0, abs(ref_losses[i])):
                failures.append(
                    f"{t}: step {i} loss {got[i]} != ref "
                    f"{ref_losses[i]} (tight band)")
        # the full trajectory (including the 3-replica segment) must
        # track the 4-replica reference within the threshold drift
        # band. The SIGKILLed worker legitimately misses the middle
        # segment (the survivors ran it without him) — he must still
        # cover the start, his post-rejoin segment, and the finish.
        for i, r in ref_losses.items():
            if i not in got:
                if t != kill_token:
                    failures.append(f"{t}: no loss recorded for step {i}")
            elif abs(got[i] - r) > 0.25 * init_loss:
                failures.append(
                    f"{t}: step {i} loss {got[i]} drifted past the "
                    f"band from ref {r} (init {init_loss})")
        if (E_STEPS - 1) not in got:
            failures.append(f"{t}: final step {E_STEPS - 1} not recorded")
        elif got[E_STEPS - 1] > 0.6 * init_loss:
            failures.append(f"{t}: final loss {got[E_STEPS-1]} shows no "
                            f"learning (init {init_loss})")

    # elastic state markers: some generation ran 3-wide with the
    # re-sharded residual restored, and the final generation is 4-wide
    all_hist = [h for t in tokens for h in histories[t]]
    shrunk = [h for h in all_hist
              if h["n_workers"] == 3 and h["residual_restored"]]
    if not shrunk:
        failures.append("no worker resumed a 3-replica generation with "
                        "the re-sharded threshold residual")
    final_gens = [histories[t][-1] for t in tokens]
    if not all(h["n_workers"] == 4 for h in final_gens):
        failures.append(f"final generations not 4-wide: {final_gens}")
    if not any(h["residual_restored"] for h in final_gens):
        failures.append("grow generation resumed without the threshold "
                        "residual")

    # final params: bit-identical across workers (replicated program),
    # near the reference within the threshold replica-drift band
    flats = {}
    for t in tokens:
        with np.load(outs[t] + ".npz") as d:
            flats[t] = {k: d[k] for k in d.files}
    for t in tokens[1:]:
        for k in flats[tokens[0]]:
            if not np.array_equal(flats[tokens[0]][k], flats[t][k]):
                failures.append(f"final params diverge across workers "
                                f"at {k} ({tokens[0]} vs {t})")
                break
    with np.load(ref_out + ".npz") as d:
        ref_flat = {k: d[k] for k in d.files}
    for k, v in ref_flat.items():
        diff = float(np.abs(flats[tokens[0]][k] - v).max())
        if diff > 0.15:
            failures.append(f"final params {k} off reference by {diff}")

    # metrics surface: the coordinator's gauges must reach /metrics
    from deeplearning4j_tpu.ui import UIServer
    server = UIServer().start()
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/metrics",
            timeout=10).read().decode()
    finally:
        server.stop()
    for fam in ("elastic_reconfigurations_total", "elastic_live_processes",
                "elastic_generation"):
        if fam not in body:
            failures.append(f"{fam} missing from /metrics")
    co.stop()

    if failures:
        print("FAIL: elastic drill verdict:")
        for b in failures[:12]:
            print(f"  {b}")
        return 1
    print(f"elastic-drill smoke OK: SIGKILL@{E_KILL_AT} shrank 4->3 "
          f"(residual re-sharded), grow re-added {kill_token}, "
          f"{status['generation']} generations, trajectory within band, "
          f"elastic_* metrics live")
    return 0


def _elastic_finished(out) -> bool:
    """True when a worker's result file records a COMPLETED run."""
    import json

    try:
        with open(out + ".json") as f:
            return json.load(f).get("iteration_count") == E_STEPS
    except (OSError, ValueError):
        return False


def _elastic_port_base() -> int:
    """A fresh ephemeral port to anchor the per-generation jax
    coordinator ports (base + generation)."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="run the kill/resume bit-parity smoke drill")
    ap.add_argument("--with-corruption", action="store_true",
                    help="additionally corrupt the newest checkpoint "
                         "before resuming (drills the fallback path)")
    ap.add_argument("--elastic-smoke", dest="elastic_smoke",
                    action="store_true",
                    help="run the 4-process SIGKILL shrink + grow "
                         "membership drill")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--elastic-child", dest="elastic_child",
                    action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--elastic-ref", dest="elastic_ref",
                    action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--token", help=argparse.SUPPRESS)
    ap.add_argument("--control", help=argparse.SUPPRESS)
    ap.add_argument("--out", help=argparse.SUPPRESS)
    ap.add_argument("--ckpt-dir", dest="ckpt_dir", help=argparse.SUPPRESS)
    ap.add_argument("--ckpt-freq", dest="ckpt_freq", type=int, default=5,
                    help=argparse.SUPPRESS)
    ap.add_argument("--kill-at", dest="kill_at", type=int,
                    help=argparse.SUPPRESS)
    ap.add_argument("--resume", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        sys.exit(run_child(args))
    if args.elastic_child:
        sys.exit(run_elastic_child(args))
    if args.elastic_ref:
        sys.exit(run_elastic_ref(args))
    if args.elastic_smoke:
        sys.exit(elastic_smoke())
    if args.smoke or args.with_corruption:
        sys.exit(smoke(args.with_corruption))
    ap.print_help()
    sys.exit(2)


if __name__ == "__main__":
    main()

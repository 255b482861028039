"""Multi-process distributed-training smoke proof.

The reference proves its distributed path in-process on every CI run
(`dl4j-spark/src/test/java/.../BaseSparkTest.java:89` — Spark
`local[N]`). The TPU-native equivalent: N OS processes around a
`jax.distributed` coordinator on the CPU backend, each owning 2 virtual
local devices, all running the SAME global-view `ParallelTrainer` sync
program over one global mesh. XLA's collectives ride the distributed
runtime exactly as they would across TPU hosts over DCN.

Usage (also wired into `__graft_entry__.dryrun_multichip` and
`tests/test_multihost.py`):

    python -m deeplearning4j_tpu.parallel.multihost_smoke --n 2

Exit 0 iff (a) both processes see the 4-device global mesh, (b) sync
training runs, and (c) the loss trajectory matches a single-process run
on the same 4-device mesh (same global batch, same seeds) to float
tolerance — proving the multi-process path computes the same global
program.

A CPU drill: every process it starts is pinned to `JAX_PLATFORMS=cpu`
with virtual devices. On a TPU host a chip belongs to one process at a
time, so N children cannot share the host's chips this way.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys

_LOCAL_DEVICES = 2   # virtual CPU devices per process


def _build_model():
    from deeplearning4j_tpu.common.updaters import Adam
    from deeplearning4j_tpu.common.weights import WeightInit
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder()
            .seed(21).updater(Adam(5e-2)).weight_init(WeightInit.XAVIER)
            .list()
            .layer(DenseLayer(n_in=6, n_out=16, activation="tanh"))
            .layer(OutputLayer(n_in=16, n_out=3, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(6))
            .build())
    return MultiLayerNetwork(conf).init()


def _run_training():
    """Global-view training on whatever global mesh exists: (a) DP sync
    (ParallelTrainer), then (b) DP x TP (ShardedParallelTrainer —
    params sharded over "model" ACROSS processes). Returns (losses
    covering both phases, this process's local-shard Evaluation as
    JSON — the distributed-evaluation recipe's transport payload)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from deeplearning4j_tpu.optimize.listeners import CollectScoresListener
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
    from deeplearning4j_tpu.parallel.tensor import ShardedParallelTrainer
    from deeplearning4j_tpu.parallel.trainer import ParallelTrainer

    devs = np.array(jax.devices())
    mesh = Mesh(devs, ("data",))
    model = _build_model()
    listener = CollectScoresListener()
    model.set_listeners(listener)
    rng = np.random.default_rng(0)
    B = 16
    x = rng.standard_normal((B, 6)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, B)]
    ParallelTrainer(model, mesh, mode="sync").fit(x, y, epochs=5,
                                                  batch_size=B)
    losses = [s for _, s in listener.scores]

    # DP x TP across the same global devices. "model" is the OUTERMOST
    # mesh axis: jax.devices() is process-major and make_mesh reshapes
    # row-major, so the model-axis pairs straddle the process boundary
    # and every TP activation gather crosses the distributed runtime
    # (innermost "model" would keep TP intra-process and prove nothing)
    n_dev = len(devs)
    tp_mesh = make_mesh(MeshSpec.of(model=2, data=max(n_dev // 2, 1)),
                        devices=devs.tolist())
    tp_model = _build_model()
    tp_listener = CollectScoresListener()
    tp_model.set_listeners(tp_listener)
    tp_trainer = ShardedParallelTrainer(tp_model, tp_mesh)
    tp_trainer.fit(x, y, epochs=2, batch_size=B)
    # second fit: model.params now holds TP-sharded GLOBAL arrays (not
    # host-gatherable from one process) — placement must pass them
    # through instead of np.asarray-ing them (regression: resumed/
    # multi-call training under multi-process TP)
    tp_trainer.fit(x, y, epochs=1, batch_size=B)

    # Threshold-encoded gradient sharing over the SAME global mesh
    # (parallel/gradient_sharing.py): the int8 all-reduce + residual/τ
    # shard_map program must compute the identical trajectory under 1
    # and N processes — the multihost proof of the compressed exchange
    # (its collectives ride the distributed runtime like the dense psum)
    thr_model = _build_model()
    thr_listener = CollectScoresListener()
    thr_model.set_listeners(thr_listener)
    ParallelTrainer(thr_model, mesh, mode="sync",
                    gradient_sharing="threshold").fit(x, y, epochs=3,
                                                      batch_size=B)
    thr_losses = [s for _, s in thr_listener.scores]

    # Distributed-evaluation recipe (what the mesh evaluate() guard
    # tells multi-process callers to do): each process scores ITS OWN
    # data shard on the host, the evaluators travel as JSON, and the
    # collector merges them. Here the "transport" is this process's
    # stdout; run_smoke merges and compares against the single-process
    # full-data evaluation.
    from deeplearning4j_tpu.eval import Evaluation

    pi, pc = jax.process_index(), jax.process_count()
    # array_split boundaries: uneven B/pc must not drop the remainder
    bounds = np.cumsum([0] + [len(a) for a in np.array_split(x, pc)])
    shard = slice(int(bounds[pi]), int(bounds[pi + 1]))
    local_ev = Evaluation()
    local_ev.eval(y[shard], np.asarray(model.output(x[shard])))
    return (losses + [s for _, s in tp_listener.scores], thr_losses,
            local_ev.to_json())


def _worker_main(coordinator: str, n: int, i: int):
    import jax

    jax.config.update("jax_platforms", "cpu")
    from deeplearning4j_tpu.parallel.multihost import initialize_multihost

    initialize_multihost(coordinator, n, i)
    assert jax.process_count() == n, jax.process_count()
    assert len(jax.devices()) == n * _LOCAL_DEVICES, len(jax.devices())
    losses, thr_losses, eval_json = _run_training()
    print("LOSSES " + json.dumps(losses), flush=True)
    print("THRLOSSES " + json.dumps(thr_losses), flush=True)
    print("EVALJSON " + eval_json, flush=True)


def _single_main():
    import jax

    jax.config.update("jax_platforms", "cpu")
    losses, thr_losses, eval_json = _run_training()
    print("LOSSES " + json.dumps(losses), flush=True)
    print("THRLOSSES " + json.dumps(thr_losses), flush=True)
    print("EVALJSON " + eval_json, flush=True)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn(args, n_local_devices):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n_local_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    return subprocess.Popen(
        [sys.executable, "-m", "deeplearning4j_tpu.parallel.multihost_smoke",
         *args],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))))


def _parse_tag(out: str, tag: str):
    for line in out.splitlines():
        if line.startswith(tag + " "):
            return line[len(tag) + 1:]
    return None


def _parse_losses(out: str):
    s = _parse_tag(out, "LOSSES")
    return None if s is None else json.loads(s)


def _parse_eval(out: str):
    return _parse_tag(out, "EVALJSON")


class _PortBindRace(RuntimeError):
    """The jax coordinator lost the race for its pre-probed port (a
    parallel CI job re-grabbed it between `_free_port` and bind)."""


_BIND_MARKERS = ("Address already in use", "address already in use",
                 "Failed to bind")


def run_smoke(n: int = 2, timeout: int = 420, *,
              bind_attempts: int = 3) -> dict:
    """Orchestrate: n distributed workers + 1 single-process reference,
    compare loss trajectories. Returns a report dict; raises on fail.

    The coordinator port is probed-then-bound, which is a race under
    parallel CI — a bind failure retries the whole worker cycle on a
    fresh port, `bind_attempts` times."""
    last: Exception = RuntimeError("unreachable")
    for attempt in range(max(1, int(bind_attempts))):
        try:
            return _run_smoke_once(n, timeout)
        except _PortBindRace as e:
            last = e
            import logging
            logging.getLogger(__name__).warning(
                "coordinator port bind race (attempt %d/%d): %s — "
                "retrying on a fresh port", attempt + 1, bind_attempts,
                str(e)[-200:])
    raise last


def _run_smoke_once(n: int, timeout: int) -> dict:
    port = _free_port()
    coord = f"127.0.0.1:{port}"
    procs = []
    try:
        workers = [_spawn(["--worker", str(i), "--n", str(n),
                           "--coordinator", coord], _LOCAL_DEVICES)
                   for i in range(n)]
        procs.extend(workers)
        single = _spawn(["--single"], n * _LOCAL_DEVICES)
        procs.append(single)

        results, thr_results, worker_evals = [], [], []
        for w in workers:
            out, err = w.communicate(timeout=timeout)
            if w.returncode != 0:
                if any(m in err for m in _BIND_MARKERS):
                    raise _PortBindRace(err[-400:])
                raise RuntimeError(
                    f"worker failed rc={w.returncode}: {err[-800:]}")
            results.append(_parse_losses(out))
            thr_results.append(json.loads(_parse_tag(out, "THRLOSSES")
                                          or "null"))
            worker_evals.append(_parse_eval(out))
        sout, serr = single.communicate(timeout=timeout)
        if single.returncode != 0:
            raise RuntimeError(f"single-proc run failed: {serr[-800:]}")
        ref = _parse_losses(sout)
        thr_ref = json.loads(_parse_tag(sout, "THRLOSSES") or "null")
        ref_eval = _parse_eval(sout)
    finally:
        # a dead worker leaves its peer blocked at the coordinator
        # barrier forever — never leak the siblings
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()

    if any(r is None for r in results) or ref is None:
        raise RuntimeError("missing LOSSES output")

    def check_match(worker_traj, ref_traj, what):
        for i, r in enumerate(worker_traj):
            if r is None or ref_traj is None or len(r) != len(ref_traj):
                raise RuntimeError(
                    f"worker {i} {what} trajectory length mismatch: "
                    f"{r} vs {ref_traj}")
            for a, b in zip(r, ref_traj):
                if abs(a - b) > 1e-4 * max(1.0, abs(b)):
                    raise RuntimeError(
                        f"worker {i} {what} loss diverged from single-"
                        f"process run: {r} vs {ref_traj}")

    check_match(results, ref, "dense")
    # the compressed exchange must be process-count invariant too
    check_match(thr_results, thr_ref, "threshold")
    # merge the per-process evaluators (the documented multi-process
    # evaluation recipe) and compare with the single-process full-data
    # evaluation — confusion matrices must be identical
    import numpy as np

    from deeplearning4j_tpu.eval import Evaluation

    if any(e is None for e in worker_evals) or ref_eval is None:
        raise RuntimeError("missing EVALJSON output")
    merged = Evaluation()
    for e in worker_evals:
        merged.merge(Evaluation.from_json(e))
    ref_ev = Evaluation.from_json(ref_eval)
    # the loss check above tolerates ~1e-4 cross-run drift (collective
    # reduction order), so an argmax near-tie may flip ONE sample's
    # predicted class between runs — require identical totals and allow
    # at most one flipped count in the confusion matrices
    diff = int(np.abs(merged.confusion.matrix
                      - ref_ev.confusion.matrix).sum())
    eval_match = merged.total == ref_ev.total and diff <= 2
    if not eval_match:
        raise RuntimeError(
            f"merged distributed evaluation != single-process "
            f"(L1 diff {diff}): {merged.confusion.matrix.tolist()} vs "
            f"{ref_ev.confusion.matrix.tolist()}")
    return {"n_processes": n, "losses": results[0], "single_process": ref,
            "threshold_losses": thr_results[0], "match": True,
            "threshold_match": True, "eval_merge_match": True}


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    args = {argv[i]: argv[i + 1] if i + 1 < len(argv) else None
            for i in range(len(argv)) if argv[i].startswith("--")}
    if "--worker" in args:
        _worker_main(args["--coordinator"], int(args["--n"]),
                     int(args["--worker"]))
    elif "--single" in args:
        _single_main()
    else:
        report = run_smoke(int(args.get("--n", 2) or 2))
        print(json.dumps(report))


if __name__ == "__main__":
    main()

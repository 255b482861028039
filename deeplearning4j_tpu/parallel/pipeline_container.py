"""Container-level pipeline parallelism: stage-partition a
MultiLayerNetwork over the "pipe" mesh axis.

No reference equivalent (SURVEY §2.13: pipeline parallelism ❌ — this
is the mesh-axis design the SPMD engine left open). The primitive
GPipe schedule lives in `parallel/pipeline.py` (ppermute ring +
lax.scan ticks); this module connects it to the PUBLIC container API
so a real model — not a hand-rolled closure — trains under PP:

- the network is split prolog | homogeneous run | epilog, where the
  run is the longest streak of consecutive layers with identical
  (layer type, param shapes) — the repeated transformer-block /
  stacked-MLP body where the FLOPs live. The run must divide evenly
  into mesh["pipe"] stages (`per = run/S` blocks per stage, applied by
  a `lax.scan` inside the stage).
- prolog/epilog (embedding / positional encoding / output loss) are
  computed replicated on every pipe device: same math everywhere, so
  parity with the single-device container is exact; their cost is the
  cheap gather/projection ends of the model.
- the training step keeps the MODEL's param tree (str(i)-keyed) as the
  optimization state: the loss stacks the run's params on the fly
  under jit, so gradients come back per-layer and the container's own
  `_apply_updates` (updaters, schedules, constraints) applies
  unchanged — numerical parity with `model.fit` is by construction,
  not by re-implementation.

Autodiff runs through the whole schedule (ppermute transposes to the
reverse permute), giving pipeline-parallel backprop from one
`jax.value_and_grad`.
"""

from __future__ import annotations

import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from deeplearning4j_tpu.datasets.iterator import as_iterator
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.listeners import ComposedListeners
from deeplearning4j_tpu.parallel.pipeline import pipeline_forward


from deeplearning4j_tpu.nd.donation import donate_argnums as _donate


def _layer_signature(layer, lparams):
    import json
    # full config equality, not just type + shapes: two layers with
    # identical param shapes but different activations/head counts must
    # not merge into one run (the stage executes every block through
    # the FIRST layer's forward)
    try:
        conf = json.dumps(layer.to_dict(), sort_keys=True, default=str)
    except Exception:
        conf = repr(layer)
    return (layer.layer_name, conf,
            tuple(sorted((pn, tuple(np.shape(a)))
                         for pn, a in lparams.items())))


def find_homogeneous_run(model) -> Tuple[int, int]:
    """[start, stop) of the longest streak of consecutive layers with
    identical type + param shapes (the pipelineable body). Layers
    without params (activations, dropout) break the streak — they
    would change the stage function."""
    best = (0, 0)
    i = 0
    n = len(model.layers)
    while i < n:
        sig = _layer_signature(model.layers[i], model.params.get(str(i), {}))
        j = i + 1
        while j < n and _layer_signature(
                model.layers[j], model.params.get(str(j), {})) == sig:
            j += 1
        if j - i > best[1] - best[0]:
            best = (i, j)
        i = j
    return best


class PipelineParallelTrainer:
    """GPipe training for a MultiLayerNetwork over `mesh[pipe_axis]`.

    `microbatches` is the GPipe M (bubble fraction = (S-1)/(M+S-1));
    the global batch must divide by it. Masks and TBPTT are not
    supported on this path (assert eagerly); dropout inside the
    pipelined run is driven by the same per-layer rng folding the
    sequential container uses, so loss parity holds whenever the model
    itself is deterministic (no dropout) and holds in distribution
    otherwise."""

    def __init__(self, model, mesh: Mesh, *, pipe_axis: str = "pipe",
                 data_axis: Optional[str] = None, microbatches: int = 4,
                 run: Optional[Tuple[int, int]] = None, stats=None):
        # stats: optional TrainingMasterStats — sync_step timing per
        # pipelined step (one device sync per step when enabled)
        self.stats = stats
        if not model._initialized:
            model.init()
        if not isinstance(model, MultiLayerNetwork):
            raise NotImplementedError(
                "PipelineParallelTrainer stages MultiLayerNetwork stacks; "
                "for a ComputationGraph, pipeline its repeated-block "
                "subgraph as a MultiLayerNetwork or use DP x TP "
                "(ShardedParallelTrainer)")
        self.model = model
        self.mesh = mesh
        self.pipe_axis = pipe_axis
        # DP composition: batch shards over `data_axis` (each data
        # shard streams its own microbatches through the pipe ring;
        # GSPMD sums the replicated-param gradients across shards)
        if data_axis is not None and data_axis not in mesh.shape:
            raise ValueError(
                f"data_axis {data_axis!r} is not a mesh axis "
                f"{tuple(mesh.shape)} — a silent fallback would leave "
                "the batch replicated over that axis and mis-scale "
                "gradients")
        self.data_axis = data_axis
        self.microbatches = int(microbatches)
        if self.microbatches < 1:
            raise ValueError(
                f"microbatches must be >= 1; got {microbatches}")
        S = int(mesh.shape[pipe_axis])
        self.n_stages = S
        r0, r1 = run if run is not None else find_homogeneous_run(model)
        if (r1 - r0) < S:
            raise ValueError(
                f"longest homogeneous layer run [{r0}, {r1}) has "
                f"{r1 - r0} blocks — fewer than {S} pipeline stages. "
                "Reduce the pipe axis or deepen the repeated body.")
        if (r1 - r0) % S:
            raise ValueError(
                f"homogeneous run of {r1 - r0} blocks does not divide "
                f"into {S} stages; choose S | run length")
        for i in range(r0 + 1, r1):
            if i in model.conf.input_preprocessors:
                raise ValueError(
                    f"input preprocessor at layer {i} sits inside the "
                    "pipelined run; preprocessors are only supported in "
                    "the prolog/epilog")
        for i in range(r0, r1):
            layer = model.layers[i]
            if getattr(layer, "dropout", None) or \
                    getattr(layer, "weight_noise", None):
                raise ValueError(
                    f"layer {i} ({layer.layer_name}) uses dropout/weight "
                    "noise inside the pipelined run — per-block rng "
                    "threading is not supported on this path; move the "
                    "stochastic layer out of the run or disable it")
            if model.net_state.get(str(i)) or \
                    layer.layer_name == "mixture_of_experts":
                raise ValueError(
                    f"layer {i} ({layer.layer_name}) is stateful (running "
                    "stats / aux losses) inside the pipelined run — the "
                    "stage function discards per-block state; keep "
                    "stateful layers in the prolog/epilog")
        self.run = (r0, r1)
        self._step = None

    # ------------------------------------------------------ batch shaping
    def _data_shards(self) -> int:
        return (1 if self.data_axis is None
                else int(self.mesh.shape[self.data_axis]))

    def _batch_multiple(self) -> int:
        """Every (micro)batch reshapes to [microbatches, shard, ...] —
        the batch must be a multiple of this."""
        return self.microbatches * self._data_shards()

    def _validate_batch(self, n: int, what: str):
        """Eager divisibility check with a clear error — a bad shape
        must fail HERE, not as a cryptic reshape error inside the
        GPipe schedule (and a ragged tail must never silently train on
        a misaligned microbatch grid)."""
        M, shards = self.microbatches, self._data_shards()
        if n % M:
            raise ValueError(
                f"{what} of {n} examples does not divide into "
                f"microbatches={M}; choose a batch size that is a "
                f"multiple of {self._batch_multiple()} (microbatches x "
                f"mesh['{self.data_axis}']), or drop the ragged tail")
        if (n // M) % shards:
            raise ValueError(
                f"{what} of {n} examples: per-microbatch size "
                f"{n // M} does not divide over the {shards}-way "
                f"'{self.data_axis}' mesh axis; choose a batch size "
                f"that is a multiple of {self._batch_multiple()} "
                f"(microbatches x mesh['{self.data_axis}'])")

    # ------------------------------------------------------------ loss
    def _pp_loss(self, params, state, x, y, rng):
        """The container's loss with the homogeneous run executed by the
        GPipe schedule: its forward core before the run, its output
        loss after. Returns (loss, new_state)."""
        model = self.model
        r0, r1 = self.run
        S, per = self.n_stages, (r1 - r0) // self.n_stages
        n = len(model.layers)

        # prolog [0, r0): the container's own forward core
        h, new_state, _, _, mask = model._forward_core(
            params, state, x, train=True, rng=rng, upto=r0)
        assert mask is None, "masks are not supported under PP"

        # pipelined run [r0, r1): stack per-layer params → [S, per, ...]
        template = model.layers[r0]
        run_params = [params[str(i)] for i in range(r0, r1)]
        stacked = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves).reshape(
                (S, per) + np.shape(leaves[0])), *run_params)

        def stage_fn(stage_params, h):
            # stage_params leaves [per, ...]: apply this stage's `per`
            # blocks sequentially via scan (rng=None — the constructor
            # rejects stochastic layers inside the run); the template's
            # remat_policy wraps the block body exactly like the
            # sequential container's scan path (nn/scan_stack.py)
            from deeplearning4j_tpu.nn import scan_stack

            def body(hh, p_one):
                hh, _ = template.forward(p_one, {}, hh, train=True,
                                         rng=None)
                return hh, None

            body = scan_stack.remat_wrap(
                body, scan_stack.effective_remat_policy(template),
                prevent_cse=False)
            h_out, _ = jax.lax.scan(body, h, stage_params)
            return h_out

        h = pipeline_forward(stage_fn, stacked, h, self.mesh,
                             pipe_axis=self.pipe_axis,
                             microbatches=self.microbatches,
                             data_axis=self.data_axis)

        # epilog [r1, n): remaining hidden layers, incl. weight noise
        # (the prolog gets it via `_forward_core`; without it here an
        # epilog DropConnect layer would silently train different math
        # than `model.fit`), then the container's own output loss
        from deeplearning4j_tpu.nn import scan_stack
        for i in range(r1, n - 1):
            layer = model.layers[i]
            if i in model.conf.input_preprocessors:
                h = model.conf.input_preprocessors[i].pre_process(h, None)
            lrng = None if rng is None else jax.random.fold_in(rng, i)
            lparams = layer.apply_weight_noise(
                params.get(str(i), {}), True,
                None if lrng is None else jax.random.fold_in(lrng, 0x5EED))
            # layer_forward applies the layer's remat_policy (the
            # containers own remat now — layers no longer self-wrap)
            h, st = scan_stack.layer_forward(
                layer, lparams, state.get(str(i), {}), h, train=True,
                rng=lrng)
            if st:
                new_state[str(i)] = st
        loss, _ = model._output_loss(params, state, h, None, y, rng, None,
                                     new_state, train=True)
        return loss, new_state

    # ------------------------------------------------------------ step
    def _build(self):
        from deeplearning4j_tpu.optimize.gradients import (
            apply_gradient_normalization)
        from deeplearning4j_tpu.monitor import diagnostics as diagx
        model = self.model
        gn = model.conf.gradient_normalization
        gn_t = model.conf.gradient_normalization_threshold
        diag = getattr(model, "_diag", None)

        def step(params, upd, state, it, x, y, rng):
            (loss, new_state), grads = jax.value_and_grad(
                lambda p: self._pp_loss(p, state, x, y, rng),
                has_aux=True)(params)
            grads = apply_gradient_normalization(grads, gn, gn_t)
            new_params, new_upd = model._apply_updates(params, grads, upd, it)
            # aux-only per-layer stats of the pipelined step (no
            # activation stats — interior stage activations live
            # inside the GPipe schedule)
            new_params, new_upd, new_state, dv = diagx.collect_and_gate(
                diag, "pipeline", params_old=params, params_new=new_params,
                upd_old=upd, upd_new=new_upd, state_old=state,
                state_new=new_state, grads=grads, loss=loss)
            return new_params, new_upd, new_state, loss, dv

        self._step = jax.jit(step, donate_argnums=_donate(0, 1))

    def evaluate(self, data, labels=None, *, batch_size: int = 32,
                 evaluation=None):
        """Evaluation through the SAME pipelined forward the trainer
        uses (prolog | GPipe run | epilog), so a stage-partitioned
        model never needs to materialize unsharded. Ragged tails pad
        to the microbatch multiple and slice after the forward."""
        from deeplearning4j_tpu.eval import Evaluation
        model = self.model
        if getattr(self, "_eval_forward", None) is None:
            r0, r1 = self.run
            n = len(model.layers)

            def fwd(params, state, x):
                h, _, _, _, _ = model._forward_core(
                    params, state, x, train=False, rng=None, upto=r0)
                S, per = self.n_stages, (r1 - r0) // self.n_stages
                run_params = [params[str(i)] for i in range(r0, r1)]
                stacked = jax.tree_util.tree_map(
                    lambda *leaves: jnp.stack(leaves).reshape(
                        (S, per) + np.shape(leaves[0])), *run_params)
                template = model.layers[r0]

                def stage_fn(stage_params, hh):
                    def body(h2, p_one):
                        h2, _ = template.forward(p_one, {}, h2,
                                                 train=False, rng=None)
                        return h2, None
                    out, _ = jax.lax.scan(body, hh, stage_params)
                    return out

                h = pipeline_forward(stage_fn, stacked, h, self.mesh,
                                     pipe_axis=self.pipe_axis,
                                     microbatches=self.microbatches,
                                     data_axis=self.data_axis)
                for i in range(r1, n):
                    if i in model.conf.input_preprocessors:
                        h = model.conf.input_preprocessors[i].pre_process(
                            h, None)
                    h, _ = model.layers[i].forward(
                        params.get(str(i), {}), state.get(str(i), {}),
                        h, train=False, rng=None)
                return h

            self._eval_forward = jax.jit(fwd)
        iterator = as_iterator(data, labels, batch_size=batch_size)
        ev = evaluation if evaluation is not None else Evaluation()
        # tails pad to the FULL microbatch grid — microbatches x the
        # data-axis shard count: padding only to `microbatches` would
        # leave a per-microbatch size that doesn't divide over the
        # data mesh axis and fail (or mis-shard) inside the schedule
        M = self._batch_multiple()
        for ds in iterator:
            x = np.asarray(ds.features)
            n_real = x.shape[0]
            pad = (-n_real) % M
            if pad:
                x = np.concatenate([x, np.repeat(x[-1:], pad, axis=0)])
            out = np.asarray(self._eval_forward(
                model.params, model.net_state, jnp.asarray(x)))[:n_real]
            ev.eval(np.asarray(ds.labels), out)
        return ev

    def fit(self, data, labels=None, *, epochs: int = 1,
            batch_size: int = 32):
        model = self.model
        # eager divisibility validation (the requested batch size AND
        # every actual batch — iterators can yield ragged tails)
        self._validate_batch(int(batch_size), "batch_size")
        if self._step is None:
            self._build()
        from deeplearning4j_tpu import monitor
        monitor.attach_master_stats(self.stats)
        iterator = as_iterator(data, labels, batch_size=batch_size)
        listeners = ComposedListeners(model.listeners
                                      + monitor.extra_listeners())
        rng_root = jax.random.PRNGKey(model.conf.seed + 1)
        params, upd, state = model.params, model.updater_state, model.net_state

        def live_state():
            # fault/ checkpointing: fit-local device trees (the model's
            # attributes are only written back when fit returns)
            return {"params": params, "net_state": state,
                    "updater_state": upd,
                    "trainer_meta": {"kind": "pipeline",
                                     "trainer": "pipeline",
                                     "n_stages": self.n_stages}}

        model._live_state_provider = live_state
        try:
            # epoch/fit listener events fire like the containers' fit
            # loops (checkpoint listeners drain their writer at fit end)
            listeners.on_fit_start(model)
            for _ in range(epochs):
                listeners.on_epoch_start(model, model.epoch_count)
                iterator.reset()
                for ds in iterator:
                    if ds.features_mask is not None or \
                            ds.labels_mask is not None:
                        raise ValueError("masks are not supported under PP")
                    self._validate_batch(ds.num_examples(), "fit batch")
                    rng = jax.random.fold_in(rng_root, model.iteration_count)
                    t0 = time.perf_counter() if self.stats is not None else 0.0
                    params, upd, new_state, loss, dv = self._step(
                        params, upd, state, model.iteration_count,
                        jnp.asarray(ds.features), jnp.asarray(ds.labels), rng)
                    state = {**state, **new_state}
                    if self.stats is not None:
                        jax.block_until_ready(loss)
                        self.stats.record("sync_step",
                                          time.perf_counter() - t0,
                                          iteration=model.iteration_count)
                        self.stats.next_round()
                    model.score_value = float(loss)
                    from deeplearning4j_tpu.monitor import (
                        diagnostics as diagx)
                    rows = diagx.process_if_due(model, dv, "pipeline",
                                                model.iteration_count)
                    listeners.iteration_done(model, model.iteration_count,
                                             model.epoch_count,
                                             model.score_value,
                                             batch_size=ds.num_examples(),
                                             diagnostics=rows[-1] if rows
                                             else None)
                    model.iteration_count += 1
                listeners.on_epoch_end(model, model.epoch_count)
                model.epoch_count += 1
            listeners.on_fit_end(model)
        finally:
            model._live_state_provider = None
        model.params, model.updater_state, model.net_state = params, upd, state
        return model

    def resume(self, directory, *, iterator=None):
        """Restore the model's full training state from the newest
        VALID checkpoint under `directory` (fault/ runtime). The GPipe
        step keeps the container's per-layer param tree as the
        optimization state, so a model-level restore is complete — a
        following `fit()` continues the interrupted run."""
        from deeplearning4j_tpu import fault
        model, _ = fault.resume(directory, model=self.model, trainer=self,
                                iterator=iterator)
        return model

"""What `MultiLayerNetwork` and `ComputationGraph` share: the fused
train step, the updater walk, the `fit` loop, TBPTT's chunk loop, the
AOT seams, evaluation and resume — written once.

The whole optimization step (forward → loss → autodiff backward →
gradient normalization → updater → param update → constraints) is ONE
jitted function; XLA fuses it end-to-end. A batch is a pytree: one
array each for the list container, tuples (one entry per graph input /
output) for the DAG container — the shared code only ever maps over its
leaves. A container supplies its configuration, `_init_trees`, its
forward walk, `_loss_fn`, `output`, and a few small answers:

- `_layer(lk)`: the layer owning a per-layer param entry (what
  `layer_for_key` asks once it has resolved a packed run to its template);
- `_keyed_layers()`: every (param key, layer) pair, in forward order;
- `_scan_runs(params)`: the loss path's scan runs (lists of keys);
- `_step_batch(ds, data_format)`: a DataSet / MultiDataSet as the
  step's `(x, y, fmask, lmask, n_examples)`; `_as_io(v)` packs bare
  arrays the same way;
- `_predict(ds, data_format)`: `output` on a DataSet's features.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.common.updaters import Sgd
from deeplearning4j_tpu.nd.donation import donate_argnums as _donate
from deeplearning4j_tpu.nd.dtype import DataTypePolicy, resolve_policy
from deeplearning4j_tpu.nn.conf.builder import BackpropType
from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrentLayer
from deeplearning4j_tpu.nn import scan_stack
from deeplearning4j_tpu.optimize.gradients import (
    apply_gradient_normalization,
    apply_max_norm_constraint,
)
from deeplearning4j_tpu.optimize.listeners import ComposedListeners, TrainingListener
from deeplearning4j_tpu.datasets.iterator import TimedDataSetIterator, as_iterator
from deeplearning4j_tpu import monitor

_leaves = jax.tree_util.tree_leaves


def _convert_features(x, data_format):
    if data_format in (None, "native"):
        return x
    if data_format.upper() == "NCHW":
        return jnp.transpose(jnp.asarray(x), (0, 2, 3, 1))
    if data_format.upper() in ("NCW", "NFT"):  # [B, F, T] → [B, T, F]
        return jnp.transpose(jnp.asarray(x), (0, 2, 1))
    raise ValueError(f"Unknown data_format {data_format}")


def _convert_labels(y, data_format):
    if y is None or data_format in (None, "native"):
        return y
    y = jnp.asarray(y)
    if data_format.upper() in ("NCW", "NFT") and y.ndim == 3:
        return jnp.transpose(y, (0, 2, 1))
    return y


def validate_param_widths(params):
    """Unresolved n_in produces zero-width weights that only explode at
    first forward — fail at init instead (reference LayerValidation
    role)."""
    for key, ps in params.items():
        for pn, arr in ps.items():
            if 0 in np.shape(arr):
                raise ValueError(
                    f"layer {key} param {pn} has shape {np.shape(arr)} — "
                    f"input width unresolved; set n_in on the layer or "
                    f"set_input_type() on the builder")


class TrainableNetwork:
    def __init__(self, conf, dtype_policy: DataTypePolicy = None,
                 diagnostics=None):
        self.conf = conf
        # DL4J_DTYPE_POLICY env > explicit arg > conf.dtype_policy >
        # process default (nd/dtype.py)
        self.dtype = resolve_policy(dtype_policy, conf)
        # in-graph model-internals diagnostics (monitor/diagnostics.py):
        # DL4J_DIAGNOSTICS env > explicit arg > conf.diagnostics > off
        self.diagnostics = monitor.resolve_diagnostics(diagnostics, conf)
        self._diag = (monitor.Diagnostics(self.diagnostics)
                      if self.diagnostics is not None else None)
        self._last_diagnostics = None
        self._last_group_dv = None
        self.params: Dict[str, Dict[str, jnp.ndarray]] = {}
        self.net_state: Dict[str, Dict[str, jnp.ndarray]] = {}
        self.updater_state: Dict[str, Dict[str, Any]] = {}
        self.iteration_count = 0
        self.epoch_count = 0
        self.listeners: List[TrainingListener] = []
        self.score_value: float = float("nan")
        self._rnn_carries: Dict[str, Any] = {}  # rnnTimeStep streaming state
        self._rnn_stream_pos = 0  # host-side stream-budget tracker
        self._stream_budget_cache = None
        self._jit_train_step = None
        self._jit_tbptt_step = None
        self._jit_multi_step = None
        self._jit_output = None
        self._jit_rnn_step = None
        self._solver = None
        self._ambient_seq_ctx = None
        self._uses_seq_parallel = any(
            getattr(l, "sequence_parallel", None)
            for _, l in self._keyed_layers())
        self._packed_runs_cache = None
        self._initialized = False

    def _sync_ambient_context(self):
        """Cached jitted steps bake in trace-time decisions — including
        which attention schedule the ambient `sequence_sharding` context
        selected. If the active (mesh, axis) differs from the one the
        cached programs were traced under, drop them so the next call
        re-traces; otherwise a step compiled outside the context would
        silently keep running local attention inside it (and vice
        versa). No-op for models with no sequence-parallel layers."""
        if not self._uses_seq_parallel:
            return
        from deeplearning4j_tpu.parallel.context import current_sequence_mesh
        ctx = current_sequence_mesh()
        if ctx == self._ambient_seq_ctx:
            return
        self._ambient_seq_ctx = ctx
        self._jit_train_step = None
        self._jit_tbptt_step = None
        self._jit_multi_step = None
        self._jit_output = None
        self._jit_rnn_step = None
        self._solver = None

    def init(self, seed: Optional[int] = None):
        seed = self.conf.seed if seed is None else seed
        (self.params, self.net_state, self.updater_state) = \
            self._init_trees(seed)
        validate_param_widths(self.params)
        self._initialized = True
        return self

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def add_listener(self, listener):
        self.listeners.append(listener)
        return self

    # ------------------------------------------------ the container's answers
    def _layer(self, lk: str):
        raise NotImplementedError

    def _keyed_layers(self):
        raise NotImplementedError

    def _scan_runs(self, params):
        raise NotImplementedError

    def _step_batch(self, ds, data_format=None):
        raise NotImplementedError

    def _as_io(self, v):
        return v

    single_io = True  # a batch is one features array and one labels array

    def _predict(self, ds, data_format=None):
        raise NotImplementedError

    def layer_for_key(self, lk: str):
        """The layer owning a grads/params entry: a ``stacked::`` run
        entry resolves to its first member, the run's template."""
        if scan_stack.is_run_key(lk):
            lk = scan_stack.run_members(lk)[0]
        return self._layer(lk)

    def _recurrent_layers(self):
        return [(k, l) for k, l in self._keyed_layers()
                if isinstance(l, BaseRecurrentLayer)]

    def local_loss(self, params, state, x, y, rng):
        """Train-mode loss of one unmasked (features, labels) pair —
        the body the data-parallel exchange programs differentiate
        (parallel/gradient_sharing.py)."""
        return self._loss_fn(params, state, x, y, rng, None, None,
                             train=True)

    # ---------------------------------------------------------- train step
    def _packed_runs(self, params):
        """Runs whose compute-dtype copy is stacked at the train-step
        boundary (nn/scan_stack.py): the loss-path scan runs (the
        output layer never packs) filtered to configs whose
        gradient-normalization / constraint semantics survive a stacked
        leading axis on the gradients."""
        runs = self._packed_runs_cache
        if runs is None:
            rwt = [(keys, self.layer_for_key(keys[0]))
                   for keys in self._scan_runs(params)]
            runs = scan_stack.packable_runs(self.conf, rwt)
            self._packed_runs_cache = runs
        return runs

    def _apply_updates(self, params, grads, upd_state, step):
        """The updater walk, over per-layer trees all three: each leaf
        of `params` / `upd_state` is read once and written once, where
        it lies (a donated leaf comes back in its own buffer). A packed
        run's gradients arrive as each layer's slice of the stacked
        gradient (`_grad_update`), a read that fuses into the leaf's
        update."""
        from deeplearning4j_tpu.tenancy import lora
        # a FROZEN attached adapter freezes the WHOLE base, not just
        # the wrapped matmul weights: biases, norms and embeddings hold
        # still too, so the published delta fully describes the tenant
        # and N tenants fine-tuned off one base stay composable. The
        # flag is derived from leaf types/aux (static under trace —
        # part of the treedef, so no stale-compile hazard).
        frozen_base = any(
            w.frozen for lv in params.values() for w in lv.values()
            if type(w).__name__ == "LoRAWeight")
        new_params, new_upd = {}, {}
        for lk, lgrads in grads.items():
            layer = self.layer_for_key(lk)
            updater = layer.updater or Sgd(1e-3)
            if frozen_base and not lora.contains_lora(params[lk]):
                # frozen-base training, no adapter in this entry:
                # nothing here may move
                new_params[lk] = params[lk]
                new_upd[lk] = upd_state[lk]
                continue
            lp, lu = {}, {}
            for pk, g in lgrads.items():
                p = params[lk][pk]
                if type(p).__name__ == "LoRAWeight":
                    # adapter leaf (tenancy/lora.py): B/A move through
                    # the updater; a frozen base keeps its object
                    # identity — zero copies, bit-identical base
                    lp[pk], lu[pk] = lora.apply_adapter_update(
                        updater, p, g, upd_state[lk][pk], step)
                    continue
                if frozen_base:
                    # plain leaf beside an adapted one (a Dense bias
                    # next to its wrapped W): frozen too
                    lp[pk] = p
                    lu[pk] = upd_state[lk][pk]
                    continue
                # bf16 grads (mixed policy) meet the fp32 master here:
                # upcast BEFORE the updater so m/v/param stay fp32
                g = g.astype(p.dtype)
                delta, new_s = updater.apply(g, upd_state[lk][pk], step)
                lp[pk] = p - delta.astype(p.dtype)
                lu[pk] = new_s
            new_params[lk] = layer.apply_constraints(lp)
            new_upd[lk] = lu
        if self.conf.max_norm is not None:
            new_params = apply_max_norm_constraint(new_params, self.conf.max_norm)
        return new_params, new_upd

    def _pack(self, params, tbptt=False):
        """What the loss differentiates: the COMPUTE-dtype copy of the
        per-layer tree, with each packable run (nn/scan_stack.py)
        stacked into ONE ``stacked::`` entry AFTER the cast, so the
        pass that converts a leaf writes it straight into its slot and
        forward scan and backward stay depth-independent. Nothing else
        is packed: masters and updater state keep their per-layer
        leaves through the whole program. The TBPTT step threads
        carries through the unrolled path and stacks nothing. Returns
        (compute tree, runs)."""
        runs = ([] if tbptt or not scan_stack.scan_enabled(self.conf)
                else self._packed_runs(params))
        return scan_stack.pack_tree(self.dtype.cast_params(params), runs), runs

    def _grad_update(self, params, upd, state, it, x, y, rng, fmask, lmask,
                     carries, tbptt=False):
        """Loss, gradients and the updater walk of one step over the
        per-layer trees. Returns (new_params, new_upd, new_state, loss,
        new_carries, dv)."""
        diag = self._diag
        want_acts = diag is not None and diag.config.activation_stats

        def lf(p):
            return self._loss_fn(p, state, x, y, rng, fmask, lmask,
                                 train=True, carries=carries,
                                 act_stats=want_acts)

        # differentiate wrt the packed COMPUTE-dtype tree (cast and
        # stacked outside value_and_grad): a run's gradients come out
        # stacked, and under mixed_bf16 they — and any data-parallel
        # all-reduce of them — are bf16; the updater walk upcasts each
        # layer's slice onto its fp32 master params/state
        compute, runs = self._pack(params, tbptt)
        (loss, aux), grads = jax.value_and_grad(lf, has_aux=True)(compute)
        if want_acts:
            new_state, new_carries, acts = aux
        else:
            (new_state, new_carries), acts = aux, None
        grads = apply_gradient_normalization(
            grads, self.conf.gradient_normalization,
            self.conf.gradient_normalization_threshold)
        # masters and updater state are never stacked or flattened: the
        # updater walk (and the diagnostics) take each layer's slice of
        # a run's stacked gradient
        grads = scan_stack.unpack_tree(grads, runs)
        new_params, new_upd = self._apply_updates(params, grads, upd, it)
        # aux outputs only: the update/param math above is untouched,
        # so the trajectory stays bit-identical to diagnostics-off
        # (except an explicit skip firing)
        new_params, new_upd, new_state, dv = \
            monitor.diagnostics.collect_and_gate(
                diag, "fit", params_old=params, params_new=new_params,
                upd_old=upd, upd_new=new_upd, state_old=state,
                state_new=new_state, grads=grads, loss=loss, acts=acts)
        return new_params, new_upd, new_state, loss, new_carries, dv

    def _make_train_step(self, tbptt: bool = False):
        def step_fn(params, upd_state, state, it, x, y, rng, fmask, lmask, carries=None):
            if tbptt and carries is not None:
                carries = jax.tree_util.tree_map(jax.lax.stop_gradient, carries)
            return self._grad_update(params, upd_state, state, it, x, y, rng,
                                     fmask, lmask, carries, tbptt)

        return jax.jit(step_fn, donate_argnums=_donate(0, 1, 2))

    def _multi_step_fn(self):
        """Unjitted k-fused-steps function (`lax.scan` over the step
        body). Exposed separately so `ParallelTrainer` can re-jit the
        SAME body with mesh shardings — one copy of the fused numerics.
        The scan carries the per-layer trees the program was handed;
        each step stacks its own compute-dtype copy (`_pack`), exactly
        as the per-step program does.

        The scan carry must keep a constant pytree structure, so state
        keys a train-mode forward emits that were absent from
        `init_state` (e.g. a MoE layer's popped-empty aux slot) are NOT
        carried across fused steps; the per-step path merges them into
        `net_state` outside jit, where growth is legal. Keys present at
        init (batchnorm running stats, ...) update normally."""
        def one(carry, inp):
            params, upd, state, it = carry
            x, y, rng = inp
            # per-step stats ride the fused scan's ys — stacked [k, K]
            # at program exit, ONE batched transfer per listener
            # cadence (the fused-dispatch contract)
            new_params, new_upd, new_state, loss, _, dv = self._grad_update(
                params, upd, state, it, x, y, rng, None, None, None)
            state = {k: new_state.get(k, v) for k, v in state.items()}
            return (new_params, new_upd, state, it + 1), (loss, dv)

        def multi(params, upd, state, it0, xs, ys, rngs):
            (params, upd, state, _), (losses, dvs) = jax.lax.scan(
                one, (params, upd, state, jnp.asarray(it0, jnp.int32)),
                (xs, ys, rngs))
            return params, upd, state, losses, dvs

        return multi

    def _make_multi_step(self):
        """k fused train steps in ONE device dispatch via `lax.scan`.

        Small models (LeNet-class) are dispatch-bound: a ~1ms TPU step
        costs ~10ms of Python/runtime per call. Scanning the step body
        over stacked minibatches amortizes that to one dispatch per k
        steps — the reference has no analogue because its loop overhead
        is native (`MultiLayerNetwork.java:1156` fit loop); ours is the
        idiomatic XLA fix. Numerics are identical to k single steps:
        same per-iteration RNG fold, same updater step counter, and the
        same body: the per-layer trees are donated, carried by the scan
        and returned in the buffers they arrived in.
        """
        return jax.jit(self._multi_step_fn(), donate_argnums=_donate(0, 1, 2))

    def _run_multi_step(self, xs, ys, it0):
        """Run k fused steps on stacked batches (every leaf [k, B,
        ...]). Returns per-step losses (device array)."""
        if self._jit_multi_step is None:
            self._jit_multi_step = self._make_multi_step()
        rng_root = jax.random.PRNGKey(self.conf.seed + 1)
        its = jnp.arange(it0, it0 + _leaves(xs)[0].shape[0])
        rngs = jax.vmap(lambda i: jax.random.fold_in(rng_root, i))(its)
        (self.params, self.updater_state, self.net_state, losses, dvs) = \
            self._jit_multi_step(self.params, self.updater_state,
                                 self.net_state, it0, xs, ys, rngs)
        # stacked per-step diag vectors ({} with diagnostics off) — read
        # by the fit loop at listener cadence, NOT here (no sync)
        self._last_group_dv = dvs
        return losses

    # ------------------------------------------------- AOT observability
    def _train_step_avals(self, x, y, steps: int):
        """Stacked input avals for the fused train-step: only shapes and
        dtypes are read, so callers can pass arrays OR ShapeDtypeStructs
        and no host memory is spent on the stacks."""
        def sds(a):
            return jax.ShapeDtypeStruct((steps,) + tuple(a.shape),
                                        jnp.dtype(a.dtype))
        key = jax.random.PRNGKey(0)
        rngs = jax.ShapeDtypeStruct((steps,) + tuple(key.shape), key.dtype)
        return (jax.tree_util.tree_map(sds, self._as_io(x)),
                jax.tree_util.tree_map(sds, self._as_io(y)), rngs)

    def lower_train_step(self, x, y, *, steps: int = 1, it0: int = 0):
        """AOT-lower the exact fused train-step that
        `fit(steps_per_execution=steps)` dispatches. Returns a
        `jax.stages.Lowered`: `.cost_analysis()` (per-program FLOPs /
        bytes accessed) runs on any host with no accelerator attached —
        the device-free seam `benchtools/hlo_cost.py` builds on — and
        `.compile()` yields the same executable the fit loop would
        build (bench.py compiles it once for cost analysis AND the
        timed windows, so the minutes-long ResNet program is never
        compiled twice). Call the compiled executable with a plain
        Python int for `it0`, matching this lowering's aval."""
        if not self._initialized:
            self.init()
        if self._jit_multi_step is None:
            self._jit_multi_step = self._make_multi_step()
        xs, ys, rngs = self._train_step_avals(x, y, steps)
        return self._jit_multi_step.lower(
            self.params, self.updater_state, self.net_state, it0,
            xs, ys, rngs)

    def train_step_jaxpr(self, x, y, *, steps: int = 1):
        """ClosedJaxpr of the same fused train-step (the per-op cost
        tables in `benchtools/hlo_cost.py` walk it primitive by
        primitive)."""
        if not self._initialized:
            self.init()
        xs, ys, rngs = self._train_step_avals(x, y, steps)
        return jax.make_jaxpr(self._multi_step_fn())(
            self.params, self.updater_state, self.net_state, 0,
            xs, ys, rngs)

    # ----------------------------------------------------------------- fit
    def _fit(self, iterator, *, epochs: int, steps_per_execution: int,
             data_format=None):
        """The fit loop over an iterator of DataSets / MultiDataSets.

        `steps_per_execution > 1` fuses that many minibatch steps into a
        single device dispatch (`lax.scan` over stacked batches) —
        numerics identical, Python overhead paid once per group. Falls
        back to per-step dispatch for TBPTT, line-search solvers, masked
        batches, and ragged tails."""
        if not self._initialized:
            self.init()
        self._sync_ambient_context()
        # iterator-side ETL attribution (feeds the etl_ms info key and,
        # when monitoring is on, fit/etl spans + the ETL histogram)
        iterator = TimedDataSetIterator(iterator)
        listeners = ComposedListeners(self.listeners
                                      + monitor.extra_listeners())
        rng_root = jax.random.PRNGKey(self.conf.seed + 1)
        tbptt = self.conf.backprop_type == BackpropType.TRUNCATED_BPTT
        solver = None
        if getattr(self.conf, "optimization_algo", "sgd") != "sgd":
            if tbptt:
                raise ValueError(
                    "optimization_algo=%r cannot be combined with truncated "
                    "BPTT: the line-search solvers optimize the full-sequence "
                    "loss and would ignore tbptt_fwd_length. Use SGD, or "
                    "standard backprop_type." % self.conf.optimization_algo)
            # line-search family (reference OptimizationAlgorithm enum):
            # each minibatch is optimized for max_iterations by the solver.
            # Cached on self so repeated fit() calls reuse the jitted loss.
            if self._solver is None:
                from deeplearning4j_tpu.optimize.solvers import Solver
                self._solver = Solver(self, self.conf.optimization_algo,
                                      max_iterations=self.conf.max_iterations)
            solver = self._solver
        if self._jit_train_step is None:
            self._jit_train_step = self._make_train_step(tbptt=False)
        if tbptt and self._jit_tbptt_step is None:
            self._jit_tbptt_step = self._make_train_step(tbptt=True)
        spe = max(1, int(steps_per_execution))
        fused_ok = spe > 1 and solver is None and not tbptt

        def fit_one(x, y, fmask, lmask, n_examples, etl_ms):
            rng = jax.random.fold_in(rng_root, self.iteration_count)
            dv = None
            # forward_backward covers the step's device dispatch (the
            # fused fwd+bwd+update program); the score readback + host
            # state merge + listener fan-out is the update span. With
            # monitoring off both spans are the shared no-op.
            with monitor.span("fit/forward_backward",
                              iteration=self.iteration_count):
                if solver is not None:
                    loss = solver.optimize(x, y, fmask, lmask)
                elif tbptt and any(a.ndim == 3 for a in _leaves(x)):
                    loss, dv = self._fit_tbptt(x, y, fmask, lmask, rng)
                else:
                    (self.params, self.updater_state, new_state, loss, _,
                     dv) = \
                        self._jit_train_step(self.params, self.updater_state,
                                             self.net_state, self.iteration_count,
                                             x, y, rng, fmask, lmask, None)
                    self.net_state = {**self.net_state, **new_state}
            with monitor.span("fit/update", iteration=self.iteration_count):
                self.score_value = float(loss)
                dstats = None
                if (self._diag is not None and dv
                        and self._diag.due(self.iteration_count)):
                    # ONE batched device→host transfer at cadence; the
                    # watchdog's warn/halt/count actions live here
                    dstats = self._diag.process(
                        self, dv, "fit", self.iteration_count)[-1]
                listeners.iteration_done(self, self.iteration_count, self.epoch_count,
                                         self.score_value,
                                         batch_size=n_examples,
                                         etl_ms=etl_ms,
                                         batch=(x, y, fmask, lmask),
                                         diagnostics=dstats)
            self.iteration_count += 1

        def flush(pending, etl_ms):
            if not pending:
                return
            if len(pending) == 1:
                fit_one(*pending[0], etl_ms)
                return
            with monitor.span("fit/forward_backward",
                              iteration=self.iteration_count,
                              fused_steps=len(pending)):
                xs, ys = jax.tree_util.tree_map(
                    lambda *a: jnp.stack(a), *[(p[0], p[1]) for p in pending])
                losses = np.asarray(self._run_multi_step(xs, ys,
                                                         self.iteration_count))
            with monitor.span("fit/update", fused_steps=len(pending)):
                group_stats = None
                dvs = self._last_group_dv
                if (self._diag is not None and dvs
                        and any(self._diag.due(self.iteration_count + j)
                                for j in range(len(pending)))):
                    # the fused group's stacked stats arrive in ONE
                    # batched transfer when any step in it is on-cadence
                    group_stats = self._diag.process(
                        self, dvs, "fit", self.iteration_count)
                for j, (x, y, fmask, lmask, n_examples) in enumerate(pending):
                    self.score_value = float(losses[j])
                    dstats = (group_stats[j] if group_stats is not None
                              and self._diag.due(self.iteration_count)
                              else None)
                    # mid-group callbacks see POST-group params with a
                    # mid-group iteration count; only the last callback
                    # is a state-consistent step boundary (checkpoint
                    # listeners key off this). Flush-time ETL is charged
                    # to the first fused iteration.
                    listeners.iteration_done(self, self.iteration_count,
                                             self.epoch_count, self.score_value,
                                             batch_size=n_examples,
                                             etl_ms=etl_ms if j == 0 else 0.0,
                                             batch=(x, y, fmask, lmask),
                                             step_boundary=(
                                                 j == len(pending) - 1),
                                             diagnostics=dstats)
                    self.iteration_count += 1

        def shapes(x, y):
            return [np.shape(a) for a in _leaves((x, y))]

        mon_on = monitor.is_enabled()
        listeners.on_fit_start(self)
        for _ in range(epochs):
            listeners.on_epoch_start(self, self.epoch_count)
            iterator.reset()
            pending = []
            for ds in iterator:
                etl_ms = iterator.last_etl_ms
                if mon_on:
                    t1 = time.perf_counter()
                    monitor.tracer().complete_between(
                        "fit/etl", t1 - etl_ms / 1e3, t1,
                        iteration=self.iteration_count)
                x, y, fmask, lmask, n_examples = self._step_batch(
                    ds, data_format)
                if not fused_ok or _leaves((fmask, lmask)):
                    flush(pending, 0.0)
                    pending = []
                    fit_one(x, y, fmask, lmask, n_examples, etl_ms)
                else:
                    if pending and shapes(x, y) != shapes(*pending[0][:2]):
                        flush(pending, 0.0)
                        pending = []
                    pending.append((x, y, fmask, lmask, n_examples))
                    if len(pending) == spe:
                        flush(pending, etl_ms)
                        pending = []
            flush(pending, 0.0)
            listeners.on_epoch_end(self, self.epoch_count)
            self.epoch_count += 1
        listeners.on_fit_end(self)
        return self

    def _fit_tbptt(self, x, y, fmask, lmask, rng):
        """Truncated BPTT: chunk every time axis, carry RNN state across
        chunks with stop_gradient (reference `doTruncatedBPTT`
        MultiLayerNetwork.java:1393)."""
        T = max(a.shape[1] for a in _leaves(x) if a.ndim == 3)
        L = self.conf.tbptt_fwd_length
        budget = self._stream_budget()
        if budget is not None and T > budget:
            raise ValueError(
                f"TBPTT over a {T}-step sequence exceeds the bounded "
                f"carry budget {budget} (min over transformer cache_len "
                f"/ positional max_len): chunks past the budget would "
                f"silently clamp into the KV cache. Shorten the "
                f"sequences or rebuild with cache_len/max_len >= {T}.")
        batch = _leaves(x)[0].shape[0]
        carries = {k: layer.init_carry(batch, self.dtype.compute_dtype)
                   for k, layer in self._recurrent_layers()}
        tmap = jax.tree_util.tree_map
        total_loss = 0.0
        nchunks = 0
        dv = None
        for s in range(0, T, L):
            # only rank-3 [B, T, F] time series are chunked (a 4D conv
            # input in a multi-input graph must pass through untouched)
            xc = tmap(lambda a: a[:, s:s + L] if a.ndim == 3 else a, x)
            yc = tmap(lambda a: a[:, s:s + L] if a.ndim == 3 else a, y)
            fm = tmap(lambda m: m[:, s:s + L], fmask)
            lm = tmap(lambda m: m[:, s:s + L] if m.ndim >= 2 else m, lmask)
            crng = jax.random.fold_in(rng, s)
            (self.params, self.updater_state, new_state, loss, carries,
             dv) = \
                self._jit_tbptt_step(self.params, self.updater_state, self.net_state,
                                     self.iteration_count, xc, yc, crng, fm, lm, carries)
            self.net_state = {**self.net_state, **new_state}
            total_loss += float(loss)
            nchunks += 1
        # diagnostics reflect the LAST chunk (one iteration spans many
        # chunks under TBPTT; the skip gate still fires per chunk)
        return total_loss / max(nchunks, 1), dv

    # ------------------------------------------------------------ scoring
    def score(self, dataset=None, training: bool = False):
        """Loss on a DataSet (or the last fit minibatch's score if None) —
        reference `score()` semantics."""
        if dataset is None:
            return self.score_value
        x, y, fmask, lmask, _ = self._step_batch(dataset)
        loss, _ = self._loss_fn(self.params, self.net_state, x, y, None,
                                fmask, lmask, train=training)
        return float(loss)

    def _evaluate_with(self, evaluator, iterator, data_format=None):
        """Shared evaluation loop — any evaluator type with
        .eval(labels, out, mask=) accumulates over the iterator
        (reference evaluate/evaluateROC/evaluateRegression overloads)."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        iterator = as_iterator(iterator, batch_size=128)
        iterator.reset()
        for ds in iterator:
            out = self._predict(ds, data_format)
            kw = {}
            meta = getattr(ds, "example_metadata", None)
            if meta is not None and isinstance(evaluator, Evaluation):
                kw["record_metadata"] = meta
            evaluator.eval(ds.labels, np.asarray(out),
                           mask=ds.labels_mask, **kw)
        return evaluator

    def evaluate(self, iterator, data_format=None, labels_list=None,
                 top_n: int = 1):
        """Reference `evaluate(iterator[, labelsList[, topN]])`
        :2794,:2892,:2944."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        return self._evaluate_with(
            Evaluation(labels_names=labels_list, top_n=top_n),
            iterator, data_format)

    def evaluate_roc(self, iterator, threshold_steps: int = 0,
                     data_format=None):
        """Binary ROC over the iterator (reference `evaluateROC` :2814)."""
        from deeplearning4j_tpu.eval.roc import ROC
        return self._evaluate_with(ROC(threshold_steps=threshold_steps),
                                   iterator, data_format)

    def evaluate_roc_multi_class(self, iterator, threshold_steps: int = 0,
                                 data_format=None):
        """One-vs-all ROC per class (reference `evaluateROCMultiClass`
        :2825)."""
        from deeplearning4j_tpu.eval.roc import ROCMultiClass
        return self._evaluate_with(
            ROCMultiClass(threshold_steps=threshold_steps), iterator,
            data_format)

    def evaluate_regression(self, iterator, data_format=None):
        from deeplearning4j_tpu.eval.regression import RegressionEvaluation
        return self._evaluate_with(RegressionEvaluation(), iterator,
                                   data_format)

    # ------------------------------------------------------ rnn streaming
    def rnn_clear_previous_state(self):
        self._rnn_carries = {}
        self._rnn_stream_pos = 0

    def _stream_budget(self):
        if self._stream_budget_cache is None:
            from deeplearning4j_tpu.nn.layers.transformer import (
                stream_budget)
            self._stream_budget_cache = (stream_budget(
                [l for _, l in self._keyed_layers()]),)
        return self._stream_budget_cache[0]

    def _check_stream_budget(self, new_tokens: int):
        """Bounded-carry guard: KV caches / positional tables clamp
        writes past their length, so streaming beyond the budget would
        silently corrupt outputs. Tracked host-side because the carry's
        device-side position cannot raise (same rule the zoo generate /
        beam_search paths enforce via `_check_cache_budget`)."""
        budget = self._stream_budget()
        pos = self._rnn_stream_pos
        if budget is not None and pos + new_tokens > budget:
            raise ValueError(
                f"rnn_time_step has streamed {pos} positions and this call "
                f"adds {new_tokens}, exceeding the stream budget {budget} "
                f"(min over transformer cache_len / positional max_len). "
                f"Call rnn_clear_previous_state() to start a new sequence, "
                f"or rebuild with a larger cache_len/max_len.")

    # -------------------------------------------------------- param access
    def param_table(self) -> Dict[str, jnp.ndarray]:
        """Flat {"0_W": array} view (reference `Model.paramTable`
        "0_W"-style keys)."""
        out = {}
        for lk, lp in self.params.items():
            for pk, arr in lp.items():
                out[f"{lk}_{pk}"] = arr
        return out

    def num_params(self) -> int:
        return sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(self.params))

    # ------------------------------------------------------------- resume
    @classmethod
    def resume(cls, directory):
        """Rebuild from the newest VALID full-state checkpoint under
        `directory` (fault/ runtime): params, updater state, running
        stats and counters all restored, so a follow-up `fit()`
        continues the interrupted run bit-exactly (the per-step rng key
        is derived from the restored iteration count). Corrupt newest
        checkpoints fall back to older ones with a logged warning."""
        from deeplearning4j_tpu import fault
        model, _ = fault.resume(directory)
        if not isinstance(model, cls):
            raise TypeError(
                f"checkpoint under {directory} holds a "
                f"{type(model).__name__}; use that container's resume()")
        return model

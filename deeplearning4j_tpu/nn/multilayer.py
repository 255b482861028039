"""MultiLayerNetwork — the sequential model container.

Reference: `nn/multilayer/MultiLayerNetwork.java` (3,156 LoC): init
flattens params (:576-625), fit loop (:1156-1264), backprop chain
(:1282-1360), TBPTT (:1393), inference `output` (:1866), streaming
`rnnTimeStep` (:2605-2673).

TPU-first redesign:
- params/state/updater-state are nested pytrees keyed by layer index
  ("0","1",…) and param name ("W","b",…) — the stable naming scheme the
  reference achieves with its flat-vector views (`paramTable`).
- the whole optimization step (forward → loss → autodiff backward →
  gradient normalization → updater → param update → constraints) is ONE
  jitted function; XLA fuses it end-to-end. No Solver/ConvexOptimizer
  object tree: `jax.value_and_grad` replaces the hand-written
  `backpropGradient` chain.
- TBPTT threads recurrent carries across sequence chunks with
  `stop_gradient` at chunk boundaries (`doTruncatedBPTT` semantics).
- dropout keys derive from a per-iteration PRNG key folded per layer.

The reference's `fit(DataSetIterator)` contract, score(), output(),
feedForward(), rnnTimeStep(), evaluate() surfaces are all here.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.common.updaters import Sgd
from deeplearning4j_tpu.nd.dtype import DataTypePolicy
from deeplearning4j_tpu.nn.conf.builder import MultiLayerConfiguration
from deeplearning4j_tpu.nn.layers.base import Layer
from deeplearning4j_tpu.nn.layers.feedforward import BaseOutputLayerMixin
from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrentLayer
from deeplearning4j_tpu.nn import scan_stack
from deeplearning4j_tpu.nn.trainable import (
    TrainableNetwork,
    _convert_features,
    _convert_labels,
)
from deeplearning4j_tpu.datasets.iterator import as_iterator


class MultiLayerNetwork(TrainableNetwork):
    def __init__(self, conf: MultiLayerConfiguration, dtype_policy: DataTypePolicy = None,
                 diagnostics=None):
        self.layers: List[Layer] = conf.layers
        super().__init__(conf, dtype_policy, diagnostics)
        # scan-over-layers segment plans (nn/scan_stack.py), keyed by
        # the forward's layer count; built lazily from traced shapes
        self._scan_plans: Dict[int, list] = {}
        out = self.layers[-1] if self.layers else None
        self._has_loss = out is None or isinstance(out, BaseOutputLayerMixin)

    # ------------------------------------------------------------------ init
    def _init_trees(self, seed: int):
        """Pure init: build (params, net_state, updater_state) without
        touching self — also usable under `jax.eval_shape` to get the
        tree SHAPES with zero allocation (sharded checkpointing)."""
        root = jax.random.PRNGKey(seed)
        pdt = self.dtype.param_dtype
        params, state, upd = {}, {}, {}
        for i, layer in enumerate(self.layers):
            key = jax.random.fold_in(root, i)
            p = layer.init_params(key, pdt)
            s = layer.init_state(pdt)
            if p:
                params[str(i)] = p
                updater = layer.updater or Sgd(1e-3)
                upd[str(i)] = {name: updater.init_state(arr) for name, arr in p.items()}
            if s:
                state[str(i)] = s
        return params, state, upd

    # ------------------------------------------------ the shared code's answers
    def _layer(self, lk: str):
        return self.layers[int(lk)]

    def _keyed_layers(self):
        return [(str(i), layer) for i, layer in enumerate(self.layers)]

    def _scan_runs(self, params):
        # plan over n-1: the output layer never packs
        plan = self._forward_plan(params, max(len(self.layers) - 1, 0))
        return [[str(i) for i in range(seg[1], seg[2])]
                for seg in plan if seg[0] == "scan"]

    def _step_batch(self, ds, data_format=None):
        x = _convert_features(ds.features, data_format)
        y = _convert_labels(ds.labels, data_format)
        fmask = None if ds.features_mask is None else jnp.asarray(ds.features_mask)
        lmask = None if ds.labels_mask is None else _convert_labels(ds.labels_mask, data_format)
        return x, y, fmask, lmask, int(np.shape(x)[0])

    def _predict(self, ds, data_format=None):
        return self.output(ds.features, data_format=data_format,
                           mask=None if ds.features_mask is None
                           else jnp.asarray(ds.features_mask))

    # --------------------------------------------------------------- forward
    def _forward_plan(self, params, n):
        """Scan-over-layers segment plan for the first `n` layers —
        ('layer', i) entries interleaved with ('scan', start, stop)
        maximal homogeneous runs. Cached per n (shapes are fixed per
        model); built from the traced params so it works identically
        under jit and AOT lowering."""
        plan = self._scan_plans.get(n)
        if plan is None:
            plan = scan_stack.build_layer_plan(
                self.layers, params, self.conf.input_preprocessors, n)
            self._scan_plans[n] = plan
        return plan

    def _forward_core(self, params, state, x, *, train, rng, mask=None,
                      carries=None, upto=None, collect=False,
                      stats_out=None):
        """Shared forward pass. Returns (h, new_state, new_carries,
        activations_if_collect, final_mask).

        Maximal runs of structurally identical layers execute as ONE
        `lax.scan` over their stacked params (nn/scan_stack.py) —
        program size and compile time stop scaling with depth. The
        carry-threading path (TBPTT / rnn_time_step / generate), the
        per-activation collector, and heterogeneous stacks stay on the
        unrolled loop; both paths apply each layer's `remat_policy`
        and produce identical numerics (same per-layer rng folds)."""
        # mixed precision: every param leaf computes in compute_dtype
        # (identity for the fp32 policy / an already-cast tree — the
        # train step casts OUTSIDE value_and_grad so grads are bf16)
        params = self.dtype.cast_params(params)
        x = jnp.asarray(x)
        if not (self.layers and scan_stack.consumes_token_ids(self.layers[0])):
            # token-id inputs pass uncast: a bf16 round corrupts ids
            # above 256 (the embedding gathers from float-carried ids)
            x = self.dtype.cast_compute(x)
        h = x
        new_state = {}
        new_carries = {}
        acts = []
        n = len(self.layers) if upto is None else upto

        def one_layer(i, h, mask, skip_pp=False, override_params=None):
            layer = self.layers[i]
            si = str(i)
            if not skip_pp and i in self.conf.input_preprocessors:
                pp = self.conf.input_preprocessors[i]
                h = pp.pre_process(h, mask)
                mask = pp.process_mask(mask)
            lrng = None if rng is None else jax.random.fold_in(rng, i)
            lparams = layer.apply_weight_noise(
                params.get(si, {}) if override_params is None
                else override_params, train,
                None if lrng is None else jax.random.fold_in(lrng, 0x5EED))
            lstate = state.get(si, {})
            if carries is not None and isinstance(layer, BaseRecurrentLayer):
                carry_in = carries.get(si)
                if carry_in is None:
                    carry_in = layer.init_carry(h.shape[0], h.dtype)
                h, st, carry_out = scan_stack.layer_forward_with_carry(
                    layer, lparams, lstate, h, carry_in, train=train,
                    rng=lrng, mask=mask)
                new_carries[si] = carry_out
            else:
                h, st = scan_stack.layer_forward(
                    layer, lparams, lstate, h, train=train, rng=lrng,
                    mask=mask)
            if st:
                new_state[si] = st
            mask = layer.forward_mask(mask, None)
            if collect:
                acts.append(h)
            if stats_out is not None:
                from deeplearning4j_tpu.monitor.diagnostics import (
                    activation_stats)
                stats_out[si] = activation_stats(h)
            return h, mask

        if (carries is None and not collect
                and scan_stack.scan_enabled(self.conf)):
            segments = self._forward_plan(params, n)
        else:
            segments = [("layer", i) for i in range(n)]
        for seg in segments:
            if seg[0] == "layer":
                h, mask = one_layer(seg[1], h, mask)
                continue
            start, stop = seg[1], seg[2]
            if start in self.conf.input_preprocessors:
                pp = self.conf.input_preprocessors[start]
                h = pp.pre_process(h, mask)
                mask = pp.process_mask(mask)
            template = self.layers[start]
            run_keys = [str(i) for i in range(start, stop)]
            packed = params.get(scan_stack.run_key(run_keys))
            if not scan_stack.mask_invariant(template, mask):
                # run layers transform the mask — replay unrolled (the
                # start preprocessor is already applied; the plan
                # guarantees none inside the run)
                plist = (scan_stack.unstack_entry(packed, stop - start)
                         if packed is not None else
                         [params[k] for k in run_keys])
                h, mask = one_layer(start, h, mask, skip_pp=True,
                                    override_params=plist[0])
                for i in range(start + 1, stop):
                    h, mask = one_layer(i, h, mask,
                                        override_params=plist[i - start])
                continue
            if packed is None:
                packed = scan_stack.stack_params(
                    [params[k] for k in run_keys])
            if stats_out is not None:
                h, run_stats = scan_stack.scan_forward(
                    template, packed, h, train=train, rng=rng,
                    fold_ids=range(start, stop), mask=mask,
                    collect_stats=True)
                # per-layer stats of the packed run via the scan ys —
                # keyed by the run entry, expanded to member layer keys
                # at the diagnostics boundary (never unpacked here)
                stats_out[scan_stack.run_key(run_keys)] = run_stats
            else:
                h = scan_stack.scan_forward(
                    template, packed, h, train=train, rng=rng,
                    fold_ids=range(start, stop), mask=mask)
        return h, new_state, new_carries, acts, mask

    def _loss_fn(self, params, state, x, y, rng, fmask, lmask, *, train,
                 carries=None, act_stats=False):
        """Full loss incl. regularization. Returns
        (loss, (new_state, new_carries)) — with ``act_stats=True`` (the
        diagnostics train step) the aux grows a third element: the
        per-layer activation-stats dict, which must leave through the
        value_and_grad aux channel (a side-effect dict would leak
        tracers)."""
        n = len(self.layers)
        stats_out = {} if act_stats else None
        h, new_state, new_carries, _, mask = self._forward_core(
            params, state, x, train=train, rng=rng, mask=fmask,
            carries=carries, upto=n - 1, stats_out=stats_out)
        return self._output_loss(params, state, h, mask, y, rng, lmask,
                                 new_state, new_carries, stats_out,
                                 train=train)

    def _output_loss(self, params, state, h, mask, y, rng, lmask, new_state,
                     new_carries=None, stats_out=None, *, train):
        """The loss's tail from the last hidden activation `h`: the
        output layer's preprocessor and loss, regularization and the
        auxiliary losses threaded through `new_state`. Shared with the
        pipeline trainer (parallel/pipeline_container.py), whose
        schedule produces `h` its own way."""
        n = len(self.layers)
        if (n - 1) in self.conf.input_preprocessors:
            pp = self.conf.input_preprocessors[n - 1]
            h = pp.pre_process(h, mask)
            mask = pp.process_mask(mask)
        out_layer = self.layers[-1]
        si = str(n - 1)
        lrng = None if rng is None else jax.random.fold_in(rng, n - 1)
        label_mask = lmask if lmask is not None else mask
        # losses / softmax statistics stay fp32 under a mixed policy:
        # the incoming activations, the labels AND the output layer's
        # params are upcast to output_dtype (grads still flow back in
        # compute_dtype through the cast transpose)
        h = self.dtype.cast_output(h)
        y = self.dtype.cast_output(jnp.asarray(y))
        out_params = self.dtype.cast_output_params(
            self.dtype.cast_params(params.get(si, {})))
        out_params = out_layer.apply_weight_noise(
            out_params, train,
            None if lrng is None else jax.random.fold_in(lrng, 0x5EED))
        loss = out_layer.compute_loss(out_params, state.get(si, {}), h, y,
                                      train=train, rng=lrng, mask=label_mask)
        reg = 0.0
        for i, layer in enumerate(self.layers):
            p = params.get(str(i))
            if p:
                reg = reg + layer.regularization_score(p)
        for k, p in params.items():
            if scan_stack.is_run_key(k):
                # stacked run entry: the template's l1/l2 sums over the
                # stacked array — identical to summing per layer
                template = self.layers[int(scan_stack.run_members(k)[0])]
                reg = reg + template.regularization_score(p)
        # auxiliary losses threaded through layer state (e.g. MoE load
        # balance) — consumed here, not persisted across steps
        for st in new_state.values():
            if "aux_loss" in st:
                reg = reg + st.pop("aux_loss")
        total = self.dtype.cast_output(loss) + reg
        if stats_out is not None:
            return total, (new_state, new_carries, stats_out)
        return total, (new_state, new_carries)

    # ----------------------------------------------------------------- fit
    def fit(self, data, labels=None, *, epochs: int = 1, batch_size: int = 32,
            data_format=None, shuffle: bool = True,
            steps_per_execution: int = 1):
        """Train. `data` may be a DataSetIterator, DataSet, list of
        DataSets, or a feature array (+ labels); the loop is
        `TrainableNetwork._fit`."""
        return self._fit(
            as_iterator(data, labels, batch_size=batch_size, shuffle=shuffle),
            epochs=epochs, steps_per_execution=steps_per_execution,
            data_format=data_format)

    # ------------------------------------------------------------- inference
    def _forward_output(self, params, state, x, mask=None):
        """Eval-mode forward of one features array to the final
        activation, pure (what the mesh trainers re-jit)."""
        return self._forward_core(params, state, x, train=False, rng=None,
                                  mask=mask)[0]

    def output(self, x, train: bool = False, data_format=None, mask=None):
        """Forward pass to the final activation (reference
        `MultiLayerNetwork.output` :1866)."""
        if not self._initialized:
            self.init()
        self._sync_ambient_context()
        x = _convert_features(x, data_format)
        if self._jit_output is None:
            def fwd(params, state, x, mask):
                # eval numerics stay fp32 under a mixed policy
                return self.dtype.cast_output(
                    self._forward_output(params, state, x, mask))
            self._jit_output = jax.jit(fwd)
        return self._jit_output(self.params, self.net_state, x, mask)

    def feed_forward(self, x, train: bool = False, data_format=None, mask=None):
        """All layer activations (reference `feedForward`)."""
        x = _convert_features(x, data_format)
        _, _, _, acts, _ = self._forward_core(self.params, self.net_state, x,
                                              train=train, rng=None, mask=mask,
                                              collect=True)
        return acts

    # ------------------------------------------------------ rnn streaming
    def rnn_time_step(self, x, data_format=None):
        """Streaming inference carrying RNN state across calls (reference
        `rnnTimeStep` :2605-2673). Accepts [B, F] (single step) or
        [B, T, F]; for token-id models (embedding first layer over a
        recurrent input) a rank-2 array is [B, T] ids — including
        [B, 1] single-step decode — and the KV-cache/positional carries
        stream exactly like LSTM state."""
        x = _convert_features(x, data_format)
        x = jnp.asarray(x)
        ids_input = (len(self.layers) > 0
                     and getattr(self.layers[0], "time_series_input",
                                 False))
        squeeze = x.ndim == 2 and not ids_input
        if squeeze:
            x = x[:, None, :]
        # time extent of this call: rank-2 (ids [B,T]) and rank-3
        # ([B,T,F]) carry a time axis at dim 1; a rank-4 conv frame
        # does not — it is ONE streamed position
        t_new = int(x.shape[1]) if x.ndim in (2, 3) else 1
        self._check_stream_budget(t_new)
        carries = dict(self._rnn_carries)
        for k, layer in self._recurrent_layers():
            if k not in carries:
                carries[k] = layer.init_carry(x.shape[0], self.dtype.compute_dtype)
        if self._jit_rnn_step is None:
            def rnn_fwd(params, state, x, carries):
                h, _, new_carries, _, _ = self._forward_core(
                    params, state, x, train=False, rng=None, carries=carries)
                return h, new_carries
            self._jit_rnn_step = jax.jit(rnn_fwd)
        h, new_carries = self._jit_rnn_step(self.params, self.net_state, x,
                                            carries)
        self._rnn_carries.update(new_carries)
        self._rnn_stream_pos += t_new
        return h[:, -1, :] if squeeze and h.ndim == 3 else h

    # -------------------------------------------------------- param access
    def set_param_table(self, table: Dict[str, Any]):
        for key, arr in table.items():
            lk, pk = key.split("_", 1)
            self.params[lk][pk] = jnp.asarray(arr)

    def copy(self) -> "MultiLayerNetwork":
        clone = MultiLayerNetwork(MultiLayerConfiguration.from_dict(self.conf.to_dict()),
                                 self.dtype, diagnostics=self.diagnostics)
        if self._initialized:
            # fresh buffers, not aliases: fit() donates its argument
            # arrays to XLA, which would delete a shared buffer out
            # from under whichever of original/clone trains second
            clone.params = jax.tree_util.tree_map(jnp.array, self.params)
            clone.net_state = jax.tree_util.tree_map(jnp.array, self.net_state)
            clone.updater_state = jax.tree_util.tree_map(
                jnp.array, self.updater_state)
            clone._initialized = True
        return clone

    # ------------------------------------------------------------ pretrain
    def pretrain(self, data, *, epochs: int = 1, batch_size: int = 32):
        """Greedy layerwise pretraining for AutoEncoder-style layers
        (reference `MultiLayerNetwork.pretrain` :1172 path)."""
        if not self._initialized:
            self.init()
        iterator = as_iterator(data, batch_size=batch_size)
        rng_root = jax.random.PRNGKey(self.conf.seed + 2)
        for i, layer in enumerate(self.layers):
            if not hasattr(layer, "pretrain_loss"):
                continue
            si = str(i)
            updater = layer.updater or Sgd(1e-3)

            @jax.jit
            def pt_step(lparams, upd_state, x, rng, it):
                def lf(p):
                    return layer.pretrain_loss(p, x, rng)
                loss, grads = jax.value_and_grad(lf)(lparams)
                new_p, new_u = {}, {}
                for pk, g in grads.items():
                    delta, ns = updater.apply(g, upd_state[pk], it)
                    new_p[pk] = lparams[pk] - delta
                    new_u[pk] = ns
                return new_p, new_u, loss

            lparams = self.params[si]
            upd_state = {pk: updater.init_state(v) for pk, v in lparams.items()}
            it = 0
            for _ in range(epochs):
                iterator.reset()
                for ds in iterator:
                    # featurize through the already-pretrained stack below
                    h, _, _, _, _ = self._forward_core(self.params, self.net_state,
                                                       jnp.asarray(ds.features),
                                                       train=False, rng=None, upto=i)
                    rng = jax.random.fold_in(rng_root, it * 997 + i)
                    lparams, upd_state, loss = pt_step(lparams, upd_state, h, rng, it)
                    it += 1
            self.params[si] = lparams
        return self

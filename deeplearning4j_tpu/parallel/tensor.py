"""Tensor parallelism — param sharding specs + sharded trainer.

No reference equivalent (SURVEY §2.13: the reference has no TP; its
README's "model parallelism" is device data-parallelism). TPU-native
TP is a *sharding annotation*, not an engine: weights get
`PartitionSpec`s over the "model" mesh axis and GSPMD/XLA inserts the
all-gathers/reduce-scatters. Semantics are unchanged (annotations never
change math) — only layout/communication differ, which is exactly why
this composes freely with the data axis.

Default policy (Megatron-style for MLPs): every ≥2-D param is sharded
on its LAST axis (the output-features axis for Dense "W" [in, out] and
conv HWIO "W"), 1-D params follow on their only axis, and the model's
FINAL output layer stays replicated so the loss computation does not
gather logits across the mesh boundary.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.datasets.iterator import as_iterator
from deeplearning4j_tpu.monitor import diagnostics as _diagmod
from deeplearning4j_tpu.optimize.listeners import ComposedListeners


from deeplearning4j_tpu.nd.donation import donate_argnums as _donate


def tp_param_specs(model, model_axis: str = "model",
                   shard_output_layer: bool = False,
                   axis_size: Optional[int] = None) -> Dict:
    """PartitionSpec tree matching `model.params` for BOTH containers.

    MultiLayerNetwork params are keyed by layer index; ComputationGraph
    params by node name (output detection switches accordingly). Every
    ≥2-D param shards its LAST axis — Dense "W" [in, out] and conv HWIO
    "W" [H, W, I, O] both put output features last, so one rule covers
    MLPs and conv stacks; 1-D params (biases, BN gamma/beta — per
    output channel) follow on their only axis. `axis_size` (pass the
    mesh's model-axis extent) gates sharding on divisibility: an axis
    the mesh does not divide evenly stays replicated rather than
    tripping GSPMD's uneven-partition restrictions.
    """
    if hasattr(model, "layers"):        # MultiLayerNetwork
        n_layers = len(model.layers)

        def is_output(lk):
            return int(lk) == n_layers - 1
    else:                                # ComputationGraph
        outputs = set(model.conf.network_outputs)

        def is_output(lk):
            return lk in outputs

    def replicate(lk, pn, arr):
        return is_output(lk) and not shard_output_layer

    return _last_axis_specs(model, model_axis, axis_size, replicate,
                            shard_1d=True)


def _last_axis_specs(model, axis, axis_size, replicate_pred, *,
                     shard_1d):
    """Shared spec builder: every param shards its LAST axis over
    `axis` unless `replicate_pred(lk, pn, arr)` says otherwise, the
    axis does not divide by `axis_size`, or it is a scalar. 1-D params
    follow only when `shard_1d`."""
    def divides(dim):
        return axis_size is None or (dim % axis_size == 0)

    specs: Dict[str, Dict] = {}
    for lk, lparams in model.params.items():
        lspec = {}
        for pn, arr in lparams.items():
            nd = np.ndim(arr)
            if (nd == 0 or replicate_pred(lk, pn, arr)
                    or not divides(np.shape(arr)[-1])
                    or (nd == 1 and not shard_1d)):
                lspec[pn] = P()
            else:
                lspec[pn] = P(*([None] * (nd - 1) + [axis]))
        specs[lk] = lspec
    return specs


def fsdp_param_specs(model, data_axis: str = "data", *,
                     axis_size: int,
                     min_shard_elems: int = 1024) -> Dict:
    """ZeRO-3 / FSDP as a sharding annotation: every large param
    shards over the SAME axis the batch shards over, so each device
    holds 1/N of the weights and optimizer state; GSPMD inserts the
    all-gather at use and reduce-scatters the gradients. No wrapper
    engine — the capability the torch ecosystem builds FSDP for is one
    PartitionSpec tree here (beyond-reference: SURVEY §2.13 leaves the
    mesh axes open for exactly this).

    `axis_size` is REQUIRED (pass the mesh's data-axis extent): the
    divisibility gate is what keeps a [*, n_classes] head from hitting
    GSPMD's uneven-partition errors at fit time. Params shard on their
    LAST axis when divisible; small params (< `min_shard_elems`)
    replicate — gathering a bias costs more than storing it."""
    def replicate(lk, pn, arr):
        return int(np.prod(np.shape(arr))) < min_shard_elems

    return _last_axis_specs(model, data_axis, int(axis_size), replicate,
                            shard_1d=True)


def moe_param_specs(model, expert_axis: str = "expert",
                    model_axis: Optional[str] = None) -> Dict:
    """Expert parallelism: MixtureOfExperts params get their leading
    expert axis sharded over `expert_axis`; other params replicated (or
    TP-sharded over `model_axis` when given). GSPMD inserts the
    dispatch/combine collectives."""
    specs: Dict[str, Dict] = {}
    for lk, lparams in model.params.items():
        layer = model.layers[int(lk)]
        lspec = {}
        is_moe = layer.layer_name == "mixture_of_experts"
        for pn, arr in lparams.items():
            if is_moe and pn.startswith(("We", "be")):
                lspec[pn] = P(*([expert_axis] + [None] * (np.ndim(arr) - 1)))
            else:
                lspec[pn] = P()
        specs[lk] = lspec
    if model_axis is not None:
        tp = tp_param_specs(model, model_axis)
        for lk in specs:
            for pn in specs[lk]:
                if specs[lk][pn] == P():
                    specs[lk][pn] = tp[lk][pn]
    return specs


class ShardedParallelTrainer:
    """DP x TP training: batch sharded over `data_axis`, params sharded
    by `tp_param_specs` over `model_axis`; XLA inserts all collectives
    (gradient psum over data, activation gathers over model)."""

    def __init__(self, model, mesh: Mesh, *, data_axis: str = "data",
                 model_axis: str = "model", param_specs: Optional[Dict] = None,
                 gradient_sharing: Optional[str] = None,
                 threshold_config=None, stats=None,
                 bucketed: Optional[bool] = None):
        self.model = model
        self.mesh = mesh
        # stats: optional TrainingMasterStats — per-phase round timing
        # (broadcast / sync_step), same opt-in sync cost as
        # ParallelTrainer's stats collection
        self.stats = stats
        self.data_axis = data_axis
        self.model_axis = model_axis
        if not model._initialized:
            model.init()
        if param_specs is None:
            ax = (int(mesh.shape[model_axis])
                  if model_axis in mesh.shape else None)
            param_specs = tp_param_specs(model, model_axis, axis_size=ax)
        self.param_specs = param_specs
        # gradient exchange over the DATA axis: dense fp32 (GSPMD psum)
        # or error-feedback threshold encoding — the data-axis exchange
        # goes manual (shard_map) while the model-axis TP collectives
        # stay GSPMD-inserted (`auto` axes). Resolution mirrors
        # ParallelTrainer: env > arg > conf > dense.
        from deeplearning4j_tpu.parallel import gradient_sharing as _gs
        self.gradient_sharing = _gs.resolve_mode(gradient_sharing,
                                                 model.conf)
        if self.gradient_sharing in _gs.RS_MODES:
            if _gs.env_mode() == self.gradient_sharing and (
                    gradient_sharing or "dense") not in _gs.RS_MODES \
                    and getattr(model.conf, "gradient_sharing",
                                "dense") not in _gs.RS_MODES:
                # global env A/B toggle: degrade where the ZeRO path
                # does not apply (params here may be TP/FSDP-sharded
                # over mesh axes GSPMD owns) — back to what the ARG/CONF
                # would have resolved without the env, NOT blanket dense
                # (an explicitly configured threshold exchange must
                # survive a fleet-wide rs A/B)
                for v in (gradient_sharing,
                          getattr(model.conf, "gradient_sharing", None)):
                    if v is not None:
                        self.gradient_sharing = v
                        break
                else:
                    self.gradient_sharing = "dense"
            else:
                raise NotImplementedError(
                    "dense_rs/threshold_rs shard the updater over the "
                    "data axis of a pure-DP mesh (ParallelTrainer); "
                    "under ShardedParallelTrainer the params are "
                    "GSPMD-sharded and FSDP-style sharding goes through "
                    "param_specs=fsdp_param_specs(...) instead")
        # bucketed (per-layer-run, overlapped) threshold exchange:
        # default ON, same resolution as ParallelTrainer
        self.bucketed = _gs.resolve_bucketed(bucketed)
        n_data = int(mesh.shape[data_axis]) if data_axis in mesh.shape else 1
        if self.gradient_sharing == "threshold":
            _gs.wire_dtype(n_data)      # replica-count ceiling check
        self.threshold_config = (threshold_config if threshold_config
                                 is not None
                                 else _gs.ThresholdConfig.from_conf(
                                     model.conf))
        self._thr_step = None
        self._thr_residual_r = None
        self._thr_tau = None
        # exact-resume per-replica updater stack restored by
        # _restore_fault_state (fault/), consumed by the next fit()
        self._resume_upd_r = None
        self._step = None
        if not model.single_io:
            raise NotImplementedError(
                "ShardedParallelTrainer supports single-input single-"
                "output graphs; train multi-io graphs via "
                "ParallelTrainer or model.fit")

    def _sharding(self, spec):
        return NamedSharding(self.mesh, spec)

    def _param_shardings(self):
        return jax.tree_util.tree_map(
            self._sharding, self.param_specs,
            is_leaf=lambda x: isinstance(x, P))

    def _build_shardings(self):
        if getattr(self, "_psh", None) is not None:
            return
        psh = self._param_shardings()
        # updater state mirrors the param tree one level down (per-param
        # dicts of updater slots) — replicate lookup by param name
        ush = {lk: {pn: jax.tree_util.tree_map(lambda _: psh[lk][pn], slots)
                    for pn, slots in lupd.items()}
               for lk, lupd in self.model.updater_state.items()}
        self._psh, self._ush = psh, ush
        self._repl = self._sharding(P())
        self._bsh = self._sharding(P(self.data_axis))

    def _build(self):
        model = self.model
        raw_step = model._make_train_step(tbptt=False)

        def step(params, upd, state, it, x, y, rng):
            return raw_step(params, upd, state, it, x, y, rng,
                            None, None, None)

        self._build_shardings()
        self._step = jax.jit(
            step,
            in_shardings=(self._psh, self._ush, self._repl, None,
                          self._bsh, self._bsh, None),
            out_shardings=(self._psh, self._ush, self._repl, None, None,
                           None),
            donate_argnums=_donate(0, 1, 2))

    # ------------------------------------------- threshold gradient sharing
    def _rep_sharding(self, leaf, spec):
        """Sharding for a per-replica (leading data-axis) stacked leaf:
        replica axis sharded over `data_axis`, the underlying TP spec
        preserved on the trailing dims when ranks line up (scalar-state
        leaves just shard the replica axis)."""
        dims = tuple(spec)
        if np.ndim(leaf) == len(dims):     # leaf given UNSTACKED
            return NamedSharding(self.mesh, P(self.data_axis, *dims))
        return NamedSharding(self.mesh, P(self.data_axis))

    def _replicate_per_worker(self, tree, spec_for):
        """Stack n_data copies on a new leading axis and shard it over
        the data axis (the per-replica residual / updater-state layout
        of the threshold exchange)."""
        from deeplearning4j_tpu.parallel.placement import gput
        n = int(self.mesh.shape[self.data_axis])

        def place(path_spec, a):
            a = np.asarray(a)
            stacked = np.broadcast_to(a[None], (n,) + a.shape)
            return gput(stacked, self._rep_sharding(a, path_spec))

        out = {}
        for lk, sub in tree.items():
            out[lk] = {}
            for pn, v in sub.items():
                spec = spec_for(lk, pn)
                out[lk][pn] = jax.tree_util.tree_map(
                    lambda a: place(spec, a), v)
        return out

    def _place_per_worker(self, stacked, spec_for):
        """Place an ALREADY-stacked per-replica host tree (leading
        replica axis) under the rep shardings — the restore-side
        counterpart of `_replicate_per_worker` (fault/ resume hands
        back per-replica state that must keep its drift, not be
        re-broadcast)."""
        from deeplearning4j_tpu.parallel.placement import gput

        def place(path_spec, a):
            a = np.asarray(a)
            return gput(a, self._rep_sharding(a[0] if a.ndim else a,
                                              path_spec))

        out = {}
        for lk, sub in stacked.items():
            out[lk] = {}
            for pn, v in sub.items():
                spec = spec_for(lk, pn)
                out[lk][pn] = jax.tree_util.tree_map(
                    lambda a: place(spec, a), v)
        return out

    # ---------------------------------------------------------- fault/resume
    def _restore_fault_state(self, arrays, meta):
        """fault.resume() hook: threshold residual + τ + per-replica
        updater stacks back under their DP x TP shardings, re-sharding
        the replica axis on an elastic replica-count change."""
        if meta.get("kind") != "threshold" or not arrays:
            return
        from deeplearning4j_tpu.fault import state as fs
        from deeplearning4j_tpu.parallel import gradient_sharing as gs
        self._build_shardings()
        n = (int(self.mesh.shape[self.data_axis])
             if self.data_axis in self.mesh.shape else 1)
        spec_for = lambda lk, pn: self.param_specs[lk][pn]
        res_r = arrays.get("residual_r")
        if res_r:
            self._thr_residual_r = self._place_per_worker(
                fs.reshard_replica_stack(res_r, n, kind="residual"),
                spec_for)
        tau = arrays.get("tau")
        if tau is not None:
            # scalar (PR-4 / single-barrier) or per-bucket tree
            # (bucketed) — restored as written, coerced at next fit
            self._thr_tau = gs.restore_tau(tau)
        upd_r = arrays.get("upd_r")
        if upd_r:
            self._resume_upd_r = self._place_per_worker(
                fs.reshard_replica_stack(upd_r, n, kind="state"), spec_for)

    def resume(self, directory, *, iterator=None):
        """Restore model + trainer state from the newest VALID
        checkpoint under `directory` (fault/ runtime). Returns the
        model; a following `fit()` continues the interrupted run."""
        from deeplearning4j_tpu import fault
        model, _ = fault.resume(directory, model=self.model, trainer=self,
                                iterator=iterator)
        return model

    def _build_threshold(self):
        """Threshold sync step for DP x TP: shard_map is MANUAL over the
        data axis only (the compressed integer all-reduce), while every
        other mesh axis stays `auto` — GSPMD keeps inserting the TP
        activation/weight collectives inside the body, so tensor
        parallelism composes with the compressed gradient exchange
        without hand-written model-axis collectives."""
        from deeplearning4j_tpu.parallel import gradient_sharing as gs

        mesh, axis = self.mesh, self.data_axis
        n = int(mesh.shape[axis])
        if self.bucketed and any(
                not jnp.issubdtype(jnp.result_type(l), jnp.floating)
                for l in jax.tree_util.tree_leaves(
                    self.model.updater_state)):
            # the bucketed VJP threads updater state through the
            # cotangent channel (float leaves only) — fail with the
            # escape hatch named instead of an obscure custom_vjp
            # cotangent TypeError at trace time
            raise ValueError(
                "bucketed threshold gradient sharing threads updater "
                "state through the VJP and requires float state leaves; "
                "this model's updater has non-float state — pass "
                "bucketed=False for the single-barrier program")
        maker = (gs.make_bucketed_step if self.bucketed
                 else gs.make_threshold_step)
        step = maker(
            self.model, axis, self.threshold_config, n_workers=n,
            diag=self.model._diag,
            **({"mode": "threshold"} if self.bucketed else {}))
        self._build_shardings()
        rep = P(axis)
        strip = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)
        expand = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)
        @partial(shard_map, mesh=mesh,
                 in_specs=(P(), rep, P(), None, rep, P(),
                           P(axis), P(axis), None),
                 out_specs=(P(), rep, P(), rep, P(), P(), P(), P()),
                 axis_names={axis}, check_vma=False)
        def thr_step(params, upd_r, state, it, res_r, tau, x, y, rng):
            params, upd, state, res, tau, loss, sp, dv = step(
                params, strip(upd_r), state, it, strip(res_r), tau,
                x, y, rng)
            return (params, expand(upd), state, expand(res), tau, loss,
                    sp, dv)

        self._thr_step = jax.jit(thr_step, donate_argnums=_donate(0, 1, 2, 4))

    def _threshold_state(self):
        from deeplearning4j_tpu.parallel import gradient_sharing as gs
        if self._thr_residual_r is None:
            zeros = gs.zeros_residual(self.model.params)
            self._thr_residual_r = self._replicate_per_worker(
                zeros, lambda lk, pn: self.param_specs[lk][pn])
        # τ form follows the step program: per-bucket tree (bucketed)
        # vs one scalar (single-barrier) — one coercion seam for both
        # trainers (path switches + cross-form checkpoint restores)
        self._thr_tau = gs.ensure_tau_form(
            self._thr_tau, self.bucketed, self.model.params,
            self.threshold_config)
        return self._thr_residual_r, self._thr_tau

    def evaluate(self, data, labels=None, *, batch_size: int = 32,
                 evaluation=None):
        """Evaluation with the SAME shardings training uses: params stay
        TP-sharded over `model_axis`, the batch shards over `data_axis`,
        XLA inserts the activation collectives. Ragged tails are zero-
        padded to the data-axis multiple and sliced after the forward —
        the model never materializes on one device (it may not fit)."""
        from deeplearning4j_tpu.eval import Evaluation
        from deeplearning4j_tpu.parallel.placement import gput, gput_tree
        from deeplearning4j_tpu.parallel.trainer import (
            _mesh_evaluate,
            _require_single_process,
        )

        _require_single_process("ShardedParallelTrainer.evaluate()")
        model = self.model
        self._build_shardings()
        if getattr(self, "_eval_forward", None) is None:
            self._eval_forward = jax.jit(
                model._forward_output,
                in_shardings=(self._psh, self._repl, self._bsh),
                out_shardings=self._bsh)
        params = gput_tree(model.params, self._psh)
        state = gput_tree(model.net_state, self._repl)
        iterator = as_iterator(data, labels, batch_size=batch_size)
        merged = evaluation if evaluation is not None else Evaluation()
        return _mesh_evaluate(
            model, iterator, merged, int(self.mesh.shape[self.data_axis]),
            lambda x: self._eval_forward(params, state, x),
            lambda f: gput(f, self._bsh))

    def fit(self, data, labels=None, *, epochs: int = 1, batch_size: int = 32):
        from deeplearning4j_tpu.parallel.placement import (
            gput, gput_tree, host_view_tree)

        model = self.model
        thr = self.gradient_sharing == "threshold"
        if thr and self._thr_step is None:
            self._build_threshold()
        if not thr and self._step is None:
            self._build()
        from deeplearning4j_tpu import monitor
        from deeplearning4j_tpu.parallel import gradient_sharing as gs
        monitor.attach_master_stats(self.stats)
        n_data = int(self.mesh.shape[self.data_axis])
        # multi-process aware placement: each process contributes only
        # its addressable shards of the TP-sharded param tree. Threshold
        # mode holds updater state PER-REPLICA (leading data axis — each
        # reference worker advances its own updater).
        def place_upd():
            if thr:
                # exact resume (fault/) hands back the drifted per-
                # replica stack; a cold start replicates the model view
                if self._resume_upd_r is not None:
                    u, self._resume_upd_r = self._resume_upd_r, None
                    return u
                return self._replicate_per_worker(
                    model.updater_state,
                    lambda lk, pn: self.param_specs[lk][pn])
            return gput_tree(model.updater_state, self._ush)
        if self.stats is not None:
            with self.stats.time_phase("broadcast"):
                params = gput_tree(model.params, self._psh)
                upd = place_upd()
                state = gput_tree(model.net_state, self._repl)
                jax.block_until_ready(params)
        else:
            params = gput_tree(model.params, self._psh)
            upd = place_upd()
            state = gput_tree(model.net_state, self._repl)
        if thr:
            res_r, tau = self._threshold_state()
            wire_b = gs.exchange_wire_bytes(model.params, "threshold",
                                            n_workers=n_data)
        dense_b = gs.exchange_wire_bytes(
            model.params, "dense", grad_dtype=model.dtype.compute_dtype)
        iterator = as_iterator(data, labels, batch_size=batch_size)
        listeners = ComposedListeners(model.listeners
                                      + monitor.extra_listeners())
        rng_root = jax.random.PRNGKey(model.conf.seed + 5)
        # per-step scalar readback serializes host on device; only pay
        # it when a listener/stats consumer will look at the score (same
        # gate as ParallelTrainer's sync path)
        eager_loss = bool(model.listeners) or self.stats is not None
        loss = None
        sp = None
        rep0_live = jax.jit(
            lambda t: jax.tree_util.tree_map(lambda a: a[0], t),
            out_shardings=self._ush) if thr else None

        def live_state():
            # fault/ checkpointing: fit-local device trees (the model's
            # attributes are stale until fit returns); threshold mode
            # adds the per-replica updater stack + residual/τ
            src = {"params": params, "net_state": state}
            if thr:
                src["updater_state"] = rep0_live(upd)
                src["trainer_arrays"] = {"upd_r": upd,
                                         "residual_r": res_r, "tau": tau}
                src["trainer_meta"] = {"kind": "threshold",
                                       "trainer": "sharded",
                                       "bucketed": self.bucketed,
                                       "n_workers": n_data}
            else:
                src["updater_state"] = upd
                src["trainer_meta"] = {"kind": "sync_dense",
                                       "trainer": "sharded",
                                       "n_workers": n_data}
            return src

        model._live_state_provider = live_state
        try:
            # epoch/fit listener events fire like the containers' fit
            # loops (checkpoint listeners drain their writer at fit end)
            listeners.on_fit_start(model)
            for _ in range(epochs):
                listeners.on_epoch_start(model, model.epoch_count)
                iterator.reset()
                for ds in iterator:
                    x = gput(ds.features, self._bsh)
                    y = gput(ds.labels, self._bsh)
                    rng = jax.random.fold_in(rng_root, model.iteration_count)
                    t0 = time.perf_counter() if self.stats is not None else 0.0
                    if thr:
                        params, upd, state, res_r, tau, loss, sp, dv = \
                            self._thr_step(params, upd, state,
                                           model.iteration_count, res_r, tau,
                                           x, y, rng)
                        gs.record_exchange("threshold", wire_b, dense_b, 1,
                                           trainer="sharded")
                    else:
                        params, upd, state, loss, _, dv = self._step(
                            params, upd, state, model.iteration_count, x, y,
                            rng)
                        gs.record_exchange("dense", dense_b, dense_b, 1,
                                           trainer="sharded")
                    if self.stats is not None:
                        jax.block_until_ready(loss)
                        self.stats.record("sync_step",
                                          time.perf_counter() - t0,
                                          iteration=model.iteration_count)
                        self.stats.next_round()
                    if eager_loss:
                        model.score_value = float(loss)
                    rows = _diagmod.process_if_due(
                        model, dv, "exchange" if thr else "fit",
                        model.iteration_count)
                    # non-eager: NaN = "score not read back this step" (the
                    # monitor listener's sentinel), never a stale score
                    listeners.iteration_done(model, model.iteration_count,
                                             model.epoch_count,
                                             model.score_value if eager_loss
                                             else float("nan"),
                                             batch_size=ds.num_examples(),
                                             diagnostics=rows[-1] if rows
                                             else None)
                    model.iteration_count += 1
                listeners.on_epoch_end(model, model.epoch_count)
                model.epoch_count += 1
            listeners.on_fit_end(model)
        finally:
            model._live_state_provider = None
        if loss is not None and not eager_loss:
            model.score_value = float(loss)
        if thr:
            self._thr_residual_r, self._thr_tau = res_r, tau
            if sp is not None:
                gs.record_threshold_stats(gs.tau_scalar(tau),
                                          float(np.asarray(sp)),
                                          trainer="sharded")
            # per-replica updater states drift (reference semantics);
            # the model keeps replica 0's view, sliced with the dense
            # updater shardings so the result is fetchable/reusable
            # under multi-process execution
            rep0 = jax.jit(
                lambda t: jax.tree_util.tree_map(lambda a: a[0], t),
                out_shardings=self._ush)
            upd = rep0(upd)
        # model-sharded leaves are not host-gatherable from one process
        # under multi-process execution; those stay as global arrays
        model.params = host_view_tree(params)
        model.updater_state = host_view_tree(upd)
        model.net_state = host_view_tree(state)
        return model

"""ParallelTrainer — multi-device training engine.

Reference equivalence (SURVEY.md §3.3, §3.4):
- sync mode ≙ `ParallelWrapper` gradient-sharing + `SharedTrainingMaster`:
  every step computes gradients on a data-sharded batch; because the
  loss is a mean over the global batch and params are replicated, XLA
  inserts a `psum` over the "data" axis — the ICI all-reduce that
  replaces `EncodedGradientsAccumulator`'s threshold-compressed UDP
  gossip (`EncodingHandler.java:136-178`). No compression needed at
  ICI bandwidth.
- averaging mode ≙ `ParallelWrapper` param-averaging /
  `ParameterAveragingTrainingMaster`: each replica holds its OWN params
  + updater state (leading replica axis sharded over "data") and runs
  `averaging_frequency` local steps with no cross-device traffic
  (`shard_map`), then params/updater state are `pmean`-averaged —
  exactly the reference's averaging round
  (`ParallelWrapper.java:327` `Nd4j.averageAndPropagate`, incl. updater
  state :339-366). Useful over DCN where local SGD beats per-step sync.

Both modes reuse the model's own loss/updater machinery — no separate
"trainer thread + model replica" objects; the mesh does the fan-out.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.common.updaters import Sgd
from deeplearning4j_tpu.datasets.iterator import as_iterator
from deeplearning4j_tpu.optimize.gradients import apply_gradient_normalization
from deeplearning4j_tpu.optimize.listeners import ComposedListeners
from deeplearning4j_tpu.parallel.mesh import device_mesh
from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.monitor import diagnostics as _diag


from deeplearning4j_tpu.nd.donation import donate_argnums as _donate


# shared with ShardedParallelTrainer — see parallel/placement.py
from deeplearning4j_tpu.parallel.placement import (  # noqa: E402
    gput as _gput,
    gput_tree as _gput_tree,
)


def _require_single_process(what="mesh evaluate()"):
    """The host-side `np.asarray` readback needs fully-addressable
    arrays. Called FIRST so multi-process callers fail before any
    compile or device transfer is paid."""
    if jax.process_count() > 1:
        raise NotImplementedError(
            f"{what} reads results back to one host and needs fully-"
            f"addressable arrays; under multi-process execution score "
            f"each process's local data shard on the host "
            f"(evaluator.eval(y, model.output(x)) per process) and "
            f"combine the evaluators with merge() — they all serialize "
            f"via to_json for the transport")


def _mesh_evaluate(model, iterator, merged, n_div, forward, put_x):
    """Shared mesh-evaluation loop (ParallelTrainer and
    ShardedParallelTrainer): every batch runs through the SHARDED
    forward; ragged tails are zero-padded up to the data-axis multiple
    and the padded rows sliced off before scoring — no example is
    skipped and no full-model host replica is ever materialized (a
    TP-sharded model may not even fit on one device)."""
    for ds in iterator:
        n = ds.num_examples()
        x = np.asarray(ds.features)
        if n % n_div != 0:
            pad = n_div - n % n_div
            x = np.concatenate(
                [x, np.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
        out = np.asarray(forward(put_x(x)))[:n]
        merged.eval(np.asarray(ds.labels), out)
    return merged


class ParallelTrainer:
    def __init__(self, model, mesh: Optional[Mesh] = None, *,
                 mode: str = "sync", averaging_frequency: int = 5,
                 average_updater_state: bool = True, data_axis: str = "data",
                 gradient_sharing: Optional[str] = None,
                 threshold_config=None, stats=None,
                 bucketed: Optional[bool] = None, rs_param_specs=None):
        if mode not in ("sync", "averaging"):
            raise ValueError(f"mode must be sync|averaging, got {mode}")
        # stats: optional TrainingMasterStats — per-phase round timing
        # (broadcast / local_fit / average / sync_step) at the cost of a
        # device sync per timed phase (reference stats semantics)
        self.stats = stats
        self.model = model
        self.mesh = mesh if mesh is not None else device_mesh()
        self.mode = mode
        self.averaging_frequency = max(1, averaging_frequency)
        self.average_updater_state = average_updater_state
        self.data_axis = data_axis
        self.n_workers = int(np.prod([self.mesh.shape[a] for a in [data_axis]]))
        # gradient exchange mode for sync training: dense fp32 exchange,
        # error-feedback threshold encoding (reference
        # SharedTrainingMaster semantics), or the ZeRO-style
        # reduce-scatter modes dense_rs/threshold_rs
        # (parallel/gradient_sharing.py). Resolution:
        # DL4J_GRADIENT_SHARING env > explicit arg > model conf's
        # gradient_sharing field > "dense".
        from deeplearning4j_tpu.parallel import gradient_sharing as _gs
        self.gradient_sharing = _gs.resolve_mode(gradient_sharing,
                                                 model.conf)
        if self.gradient_sharing != "dense" and mode != "sync":
            want = self.gradient_sharing
            if (_gs.env_mode() == want
                    and (gradient_sharing or "dense") != want
                    and getattr(model.conf, "gradient_sharing",
                                "dense") != want):
                # global env A/B toggle: degrade gracefully where the
                # compressed/sharded exchange does not apply (averaging
                # mode exchanges parameters, not gradients) — only an
                # EXPLICIT arg/conf request is a hard error
                self.gradient_sharing = "dense"
            else:
                raise ValueError(
                    f"gradient_sharing={want!r} restructures the per-step "
                    "gradient exchange and only applies to mode='sync'; "
                    "averaging mode exchanges parameters, not gradients")
        if self.gradient_sharing in ("threshold", "threshold_rs"):
            _gs.wire_dtype(self.n_workers)  # replica-count ceiling check
        if (self.gradient_sharing in _gs.RS_MODES
                and not _gs.rs_supported_gn(model.conf)):
            raise ValueError(
                "the dense_rs/threshold_rs modes run gradient "
                "normalization on reduced gradient SHARDS and support "
                "only elementwise modes (none / "
                "clip_elementwise_absolute_value); this configuration's "
                f"{model.conf.gradient_normalization!r} needs whole-layer "
                "norms — use dense/threshold instead")
        # bucketed (per-layer-run, overlapped) exchange: default ON —
        # each packed run / unpacked layer exchanges inside the backward
        # pass. DL4J_BUCKETED_EXCHANGE=0 or bucketed=False restores the
        # PR-4 single-barrier program (the rs modes are inherently
        # bucketed). docs/COMMS.md "Bucketed collectives".
        self.bucketed = _gs.resolve_bucketed(bucketed)
        # optional PartitionSpec tree (e.g. tensor.fsdp_param_specs
        # output) steering WHICH leaves the rs modes reduce-scatter —
        # the FSDP composition seam; default derives the same rule from
        # shapes at first fit
        self.rs_param_specs = rs_param_specs
        self._rs_plan_cache = None
        self.threshold_config = (threshold_config if threshold_config
                                 is not None
                                 else _gs.ThresholdConfig.from_conf(
                                     model.conf))
        self._thr_step = None
        self._thr_multi = None
        self._bkt_step = None         # bucketed step (any mode)
        self._bkt_multi = None
        self._thr_residual_r = None   # per-replica error-feedback residual
        self._thr_tau = None          # adaptive threshold: per-bucket
        #                               {layer_key: f32} tree (bucketed)
        #                               or device scalar (single-barrier)
        # exact-resume stacks restored by _restore_fault_state (fault/):
        # consumed by the next fit() instead of replicating the model's
        # host trees (per-replica updater/param state drifts — a
        # broadcast would erase the drift the checkpoint preserved)
        self._resume_upd_r = None
        self._resume_avg = None
        self._sync_step = None
        self._sync_multi = None
        self._local_step = None
        self._local_multi = None
        self._average_fn = None
        # the bucketed engine differentiates `model.local_loss`, one
        # features/labels pair; multi-io graphs keep the GSPMD
        # single-barrier dense program
        self._multi_io_graph = not model.single_io

    # ------------------------------------------------------------- sync mode
    def _build_sync_step(self):
        model = self.model
        mesh = self.mesh
        repl = NamedSharding(mesh, P())
        batch_sharded = NamedSharding(mesh, P(self.data_axis))

        raw_step = model._make_train_step(tbptt=False)

        def step(params, upd, state, it, x, y, rng):
            return raw_step(params, upd, state, it, x, y, rng, None, None, None)

        self._sync_step = jax.jit(
            step,
            in_shardings=(repl, repl, repl, None, batch_sharded, batch_sharded, None),
            out_shardings=(repl, repl, repl, None, None, None),
            donate_argnums=_donate(0, 1, 2),
        )

    def _build_sync_multi(self):
        """k fused sync steps in ONE dispatch — the model's own
        `_multi_step_fn` body (one copy of the fused numerics), re-jit
        with mesh shardings: batch stacks [k, B/d, ...] over the data
        axis, everything else replicated; XLA inserts the per-step psum
        exactly as in `_build_sync_step`."""
        mesh = self.mesh
        repl = NamedSharding(mesh, P())
        stack_sh = NamedSharding(mesh, P(None, self.data_axis))
        self._sync_multi = jax.jit(
            self.model._multi_step_fn(),
            in_shardings=(repl, repl, repl, None, stack_sh, stack_sh, None),
            out_shardings=(repl, repl, repl, None, None),
            donate_argnums=_donate(0, 1, 2),
        )

    # ------------------------------------------- threshold gradient sharing
    def _build_threshold_step(self):
        """Per-step threshold sync: the explicit-collective shard_map
        program from parallel/gradient_sharing.py — local grads on the
        batch shard, error-feedback threshold encode, integer all-reduce,
        decode, shared update. The per-replica residual enters/exits with
        a leading replica axis sharded over the data axis (the averaging
        mode's rep-spec idiom); ``stacked::`` run packing happens inside
        the step, so the residual the trainer holds stays per-layer."""
        from deeplearning4j_tpu.parallel import gradient_sharing as gs

        mesh, axis = self.mesh, self.data_axis
        step = gs.make_threshold_step(
            self.model, axis, self.threshold_config,
            n_workers=self.n_workers,
            diag=self.model._diag)
        rep = P(axis)
        strip = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)
        expand = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)

        @partial(shard_map, mesh=mesh,
                 in_specs=(P(), rep, P(), None, rep, P(),
                           P(axis), P(axis), None),
                 out_specs=(P(), rep, P(), rep, P(), P(), P(), P()),
                 check_vma=False)
        def thr_step(params, upd_r, state, it, res_r, tau, x, y, rng):
            params, upd, state, res, tau, loss, sp, dv = step(
                params, strip(upd_r), state, it, strip(res_r), tau,
                x, y, rng)
            return (params, expand(upd), state, expand(res), tau, loss,
                    sp, dv)

        self._thr_step = jax.jit(thr_step, donate_argnums=_donate(0, 1, 2, 4))

    def _build_threshold_multi(self):
        """k fused threshold sync steps in ONE dispatch: the scan lives
        inside shard_map and the residual + τ ride its carry next to the
        updater state (gradient_sharing.make_threshold_multi); packing
        of ``stacked::`` runs is paid once per program."""
        from deeplearning4j_tpu.parallel import gradient_sharing as gs

        mesh, axis = self.mesh, self.data_axis
        multi = gs.make_threshold_multi(
            self.model, axis, self.threshold_config,
            n_workers=self.n_workers,
            diag=self.model._diag)
        rep = P(axis)
        strip = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)
        expand = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)

        @partial(shard_map, mesh=mesh,
                 in_specs=(P(), rep, P(), None, rep, P(),
                           P(None, axis), P(None, axis), None),
                 out_specs=(P(), rep, P(), rep, P(), P(), P(), P()),
                 check_vma=False)
        def thr_multi(params, upd_r, state, it0, res_r, tau, xs, ys, rngs):
            params, upd, state, res, tau, losses, sps, dvs = multi(
                params, strip(upd_r), state, it0, strip(res_r), tau,
                xs, ys, rngs)
            return (params, expand(upd), state, expand(res), tau, losses,
                    sps, dvs)

        self._thr_multi = jax.jit(thr_multi,
                                  donate_argnums=_donate(0, 1, 2, 4))

    def _threshold_state(self, per_bucket: bool = False):
        """(residual_r, tau) device state — created lazily, persisted
        across fit() calls exactly like updater state (the reference's
        accumulator survives across training rounds). τ is a per-bucket
        {layer_key: scalar} tree on the bucketed paths and one scalar
        on the single-barrier path; switching paths between fits (or
        resuming a checkpoint written by the other one) coerces the
        form (scalar broadcast / bucket mean)."""
        from deeplearning4j_tpu.parallel import gradient_sharing as gs
        if self._thr_residual_r is None:
            self._thr_residual_r = self._replicate_tree(
                gs.zeros_residual(self.model.params))
        self._thr_tau = gs.ensure_tau_form(
            self._thr_tau, per_bucket, self.model.params,
            self.threshold_config)
        return self._thr_residual_r, self._thr_tau

    # ------------------------------------------ bucketed exchange (any mode)
    def _updater_state_floats(self) -> bool:
        """True when every updater-state leaf is floating — the
        precondition for threading updater state through the bucketed
        VJP's cotangent channel (all built-in updaters qualify)."""
        return all(jnp.issubdtype(jnp.result_type(l), jnp.floating)
                   for l in jax.tree_util.tree_leaves(
                       self.model.updater_state))

    def _rs_plan(self):
        """Which param leaves the `_rs` modes reduce-scatter — derived
        once from `rs_param_specs` (e.g. `tensor.fsdp_param_specs`
        output: the FSDP composition) or from shapes by the same
        rule."""
        from deeplearning4j_tpu.parallel import gradient_sharing as gs
        if self._rs_plan_cache is None:
            self._rs_plan_cache = gs.rs_shard_plan(
                self.model.params, self.n_workers,
                specs=self.rs_param_specs, data_axis=self.data_axis)
        return self._rs_plan_cache

    def _shard_rs_state(self, tree):
        """Cold-start ZeRO placement of the (full, per-layer) updater
        state: sharded leaves split along their LAST axis into one
        stacked shard per replica, replicated leaves broadcast — the
        leading replica axis is sharded over the data axis so each
        device physically holds 1/N of the sharded optimizer state."""
        plan = self._rs_plan()
        n = self.n_workers
        out = {}
        for lk, lupd in tree.items():
            out[lk] = {}
            for pk, slots in lupd.items():
                if plan[lk][pk]:
                    f = lambda a: np.stack(
                        np.split(np.asarray(a), n, axis=-1))
                else:
                    f = lambda a: np.broadcast_to(
                        np.asarray(a)[None], (n,) + np.shape(a)).copy()
                out[lk][pk] = jax.tree_util.tree_map(f, slots)
        return self._place_replica_stack(out)

    def _rs_full_state_fn(self):
        """jit that reassembles the full per-layer updater tree from
        the sharded stack (replicated out-sharding — multi-process
        fetchable): concatenate shards along the sharded axis,
        replica 0 for replicated leaves. The checkpoint/model view of
        ZeRO state is ALWAYS the full tree, so checkpoints are
        independent of the replica count that wrote them and elastic
        resume is plain re-slicing at the next fit."""
        plan = self._rs_plan()
        n = self.n_workers
        repl = NamedSharding(self.mesh, P())

        def full(upd_r):
            out = {}
            for lk, lupd in upd_r.items():
                out[lk] = {}
                for pk, slots in lupd.items():
                    if plan[lk][pk]:
                        f = lambda a: jnp.concatenate(
                            [a[i] for i in range(n)], axis=-1)
                    else:
                        f = lambda a: a[0]
                    out[lk][pk] = jax.tree_util.tree_map(f, slots)
            return out

        return jax.jit(full, out_shardings=repl)

    def _build_bucketed(self, mode: str, multi: bool):
        """Bucketed sync program (per-step or k-fused) for any exchange
        mode: the shard_map wrapper strips/expands the leading replica
        axis of the per-replica trees (threshold updater stacks, rs
        updater shards, the error-feedback residual) and leaves
        replicated trees alone."""
        from deeplearning4j_tpu.parallel import gradient_sharing as gs

        mesh, axis = self.mesh, self.data_axis
        rs_plan = self._rs_plan() if mode in gs.RS_MODES else None
        maker = gs.make_bucketed_multi if multi else gs.make_bucketed_step
        fn = maker(self.model, axis, self.threshold_config,
                   n_workers=self.n_workers, mode=mode,
                   rs_plan=rs_plan,
                   diag=self.model._diag)
        per_replica_upd = mode != "dense"
        has_thr = mode in ("threshold", "threshold_rs")
        rep = P(axis)
        upd_spec = rep if per_replica_upd else P()
        res_spec = rep if has_thr else P()
        batch_spec = P(None, axis) if multi else P(axis)
        strip = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)
        expand = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)

        @partial(shard_map, mesh=mesh,
                 in_specs=(P(), upd_spec, P(), None, res_spec, P(),
                           batch_spec, batch_spec, None),
                 out_specs=(P(), upd_spec, P(), res_spec, P(), P(), P(),
                            P()),
                 check_vma=False)
        def run(params, upd_r, state, it, res_r, tau, x, y, rng):
            u = strip(upd_r) if per_replica_upd else upd_r
            r = strip(res_r) if has_thr else res_r
            params, u, state, r, tau, loss, sp, dv = fn(
                params, u, state, it, r, tau, x, y, rng)
            return (params, expand(u) if per_replica_upd else u, state,
                    expand(r) if has_thr else r, tau, loss, sp, dv)

        donate = _donate(0, 1, 2, 4) if has_thr else _donate(0, 1, 2)
        return jax.jit(run, donate_argnums=donate)

    def _replicated_view(self, tree):
        """Gather a per-replica (data-axis-sharded) device tree into
        replicated form so every PROCESS can address the full stack —
        the multi-process capture path for checkpoints of residual/τ
        and per-replica updater stacks (a data-axis-sharded leaf is not
        fully addressable from any one host, and `flatten_arrays`
        rejects it). One all-gather per capture, at checkpoint cadence
        only; a no-op reshard under a single process."""
        if getattr(self, "_rep_view_fn", None) is None:
            repl = NamedSharding(self.mesh, P())
            self._rep_view_fn = jax.jit(lambda t: t, out_shardings=repl)
        return self._rep_view_fn(tree)

    def threshold_residual(self):
        """Host view of the per-replica error-feedback residual
        (per-LAYER keys — the ``stacked::`` packing exists only inside
        the step program), or None before the first threshold step."""
        if self._thr_residual_r is None:
            return None
        tree = self._thr_residual_r
        if jax.process_count() > 1:
            tree = self._replicated_view(tree)
        return jax.tree_util.tree_map(np.asarray, tree)

    # -------------------------------------------------------- averaging mode
    def _make_local_one_step(self):
        model = self.model
        gn = model.conf.gradient_normalization
        gn_t = model.conf.gradient_normalization_threshold

        def local_one_step(params, upd, state, it, x, y, rng):
            """One fully-local step on one replica's shard (no collectives)."""
            def lf(p):
                return model._loss_fn(p, state, x, y, rng, None, None, train=True)
            (loss, (new_state, _)), grads = jax.value_and_grad(lf, has_aux=True)(params)
            grads = apply_gradient_normalization(grads, gn, gn_t)
            new_params, new_upd = model._apply_updates(params, grads, upd, it)
            return new_params, new_upd, new_state, loss

        return local_one_step

    def _build_averaging(self):
        mesh = self.mesh
        axis = self.data_axis
        local_one_step = self._make_local_one_step()


        # per-replica params: leading axis of size n_workers, sharded over "data"
        rep_spec = P(axis)

        @partial(shard_map, mesh=mesh,
                 in_specs=(rep_spec, rep_spec, rep_spec, None, P(axis), P(axis), None),
                 out_specs=(rep_spec, rep_spec, rep_spec, P(axis)),
                 check_vma=False)
        def local_step(params_r, upd_r, state_r, it, x, y, rng):
            # strip the per-replica leading axis (size 1 inside the shard)
            params = jax.tree_util.tree_map(lambda a: a[0], params_r)
            upd = jax.tree_util.tree_map(lambda a: a[0], upd_r)
            state = jax.tree_util.tree_map(lambda a: a[0], state_r)
            axis_idx = jax.lax.axis_index(axis)
            rng = jax.random.fold_in(rng, axis_idx)
            params, upd, state, loss = local_one_step(params, upd, state, it, x, y, rng)
            expand = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)
            return expand(params), expand(upd), expand(state), loss[None]

        @partial(shard_map, mesh=mesh,
                 in_specs=(rep_spec,), out_specs=rep_spec, check_vma=False)
        def average(tree_r):
            tree = jax.tree_util.tree_map(lambda a: a[0], tree_r)
            avg = jax.tree_util.tree_map(lambda a: jax.lax.pmean(a, axis), tree)
            return jax.tree_util.tree_map(lambda a: a[None], avg)

        self._local_step = jax.jit(local_step, donate_argnums=_donate(0, 1, 2))
        self._average_fn = jax.jit(average, donate_argnums=_donate(0))

    def _build_averaging_multi(self):
        """k fused local-SGD steps in ONE dispatch: the scan lives
        INSIDE shard_map, and the pmean averaging round fires at its
        `averaging_frequency` cadence via `lax.cond` — numerics
        identical to the per-step path (same rng folds, same iteration
        counters, same averaging boundaries), dispatch paid once per
        group."""
        mesh = self.mesh
        axis = self.data_axis
        freq = self.averaging_frequency
        avg_upd = self.average_updater_state
        local_one_step = self._make_local_one_step()

        from jax import lax

        rep_spec = P(axis)

        @partial(shard_map, mesh=mesh,
                 in_specs=(rep_spec, rep_spec, rep_spec, None, None,
                           P(None, axis), P(None, axis), None),
                 out_specs=(rep_spec, rep_spec, rep_spec, P(None, axis)),
                 check_vma=False)
        def local_multi(params_r, upd_r, state_r, it0, since0, xs, ys, rngs):
            params = jax.tree_util.tree_map(lambda a: a[0], params_r)
            upd = jax.tree_util.tree_map(lambda a: a[0], upd_r)
            state = jax.tree_util.tree_map(lambda a: a[0], state_r)
            axis_idx = jax.lax.axis_index(axis)

            def avg(tree):
                return jax.tree_util.tree_map(
                    lambda a: jax.lax.pmean(a, axis), tree)

            def body(carry, inp):
                params, upd, state, it, since = carry
                x, y, rng = inp
                rng = jax.random.fold_in(rng, axis_idx)
                params, upd, state, loss = local_one_step(
                    params, upd, state, it, x, y, rng)
                do = since + 1 >= freq
                params = lax.cond(do, avg, lambda t: t, params)
                state = lax.cond(do, avg, lambda t: t, state)
                if avg_upd:
                    upd = lax.cond(do, avg, lambda t: t, upd)
                since = jnp.where(do, 0, since + 1)
                return (params, upd, state, it + 1, since), loss

            (params, upd, state, _, _), losses = lax.scan(
                body,
                (params, upd, state, jnp.asarray(it0, jnp.int32),
                 jnp.asarray(since0, jnp.int32)),
                (xs, ys, rngs))
            expand = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)
            return expand(params), expand(upd), expand(state), losses[:, None]

        self._local_multi = jax.jit(local_multi, donate_argnums=_donate(0, 1, 2))

    @staticmethod
    def _run_grouped(iterator, epochs, spe, divisible, run_single, drain,
                     model, listeners=None):
        """Shared epoch/grouping loop for both modes: accumulate up to
        `spe` same-shape batches, drain each FULL group through one
        fused dispatch; spe == 1 runs per-step. Partial groups (epoch
        tails, shape changes) go through run_single so only ONE fused
        shape [spe, ...] ever compiles — a distinct executable per tail
        length would cost minutes of XLA compile each on a real TPU.

        Epoch/fit listener events fire like the containers' own fit
        loops (epoch-cadence checkpointing and the end-of-fit
        durability drain depend on them)."""
        def flush(pending):
            if len(pending) == spe:
                drain(pending)
            else:
                for d in pending:
                    run_single(d)

        if listeners is not None:
            listeners.on_fit_start(model)
        for _ in range(epochs):
            if listeners is not None:
                listeners.on_epoch_start(model, model.epoch_count)
            iterator.reset()
            pending = []
            for ds in iterator:
                if not divisible(ds):
                    continue
                if spe == 1:
                    run_single(ds)
                    continue
                if pending and np.shape(ds.features) != np.shape(
                        pending[0].features):
                    flush(pending)   # shape change: close the group
                    pending = []
                pending.append(ds)
                if len(pending) >= spe:
                    drain(pending)
                    pending = []
            flush(pending)
            if listeners is not None:
                listeners.on_epoch_end(model, model.epoch_count)
            model.epoch_count += 1
        if listeners is not None:
            listeners.on_fit_end(model)

    def _replicate_tree(self, tree):
        """Stack n_workers copies along a new leading axis, shard over data."""
        n = self.n_workers
        stacked = jax.tree_util.tree_map(
            lambda a: np.broadcast_to(np.asarray(a)[None], (n,) + np.shape(a)),
            tree)
        sharding = NamedSharding(self.mesh, P(self.data_axis))
        return _gput_tree(stacked, sharding)

    def _unreplicate_tree(self, tree):
        return jax.tree_util.tree_map(lambda a: np.asarray(a[0]), tree)

    def _place_replica_stack(self, stacked):
        """Place an ALREADY-stacked per-replica host tree (leading
        replica axis of size n_workers) sharded over the data axis —
        the restore-side counterpart of `_replicate_tree`, which
        broadcasts one copy instead."""
        return _gput_tree(stacked, NamedSharding(self.mesh,
                                                 P(self.data_axis)))

    # ---------------------------------------------------------- fault/resume
    def _restore_fault_state(self, arrays, meta):
        """fault.resume() hook: restore gradient-sharing residual + τ,
        per-replica updater state and the averaging-mode stacks from a
        checkpoint — re-sharding the replica axis when the checkpoint
        was written at a different replica count (elastic resume)."""
        if not arrays and not meta:
            return
        from deeplearning4j_tpu.fault import state as fs
        kind = meta.get("kind")
        n = self.n_workers
        if kind in ("threshold", "threshold_rs"):
            res_r = arrays.get("residual_r")
            if res_r:
                res_r = fs.reshard_replica_stack(res_r, n, kind="residual")
                self._thr_residual_r = self._place_replica_stack(res_r)
            tau = arrays.get("tau")
            if tau is not None:
                # scalar (PR-4) or per-bucket tree, restored as written;
                # _threshold_state coerces at the next fit if the
                # trainer runs the other path
                from deeplearning4j_tpu.parallel import (
                    gradient_sharing as _gs)
                self._thr_tau = _gs.restore_tau(tau)
            upd_r = arrays.get("upd_r")
            if upd_r:
                # threshold_rs carries NO per-replica stack: its sharded
                # updater state round-trips through the model-level full
                # tree and re-slices at the next fit (elastic by
                # construction)
                upd_r = fs.reshard_replica_stack(upd_r, n, kind="state")
                self._resume_upd_r = self._place_replica_stack(upd_r)
        elif kind == "averaging":
            stacks = {}
            for k in ("params_r", "upd_r", "state_r"):
                t = arrays.get(k)
                stacks[k] = self._place_replica_stack(
                    fs.reshard_replica_stack(t, n, kind="state")) \
                    if t else {}
            stacks["since_avg"] = int(meta.get("since_avg", 0))
            self._resume_avg = stacks

    def resume(self, directory, *, iterator=None):
        """Restore model + trainer state from the newest VALID
        checkpoint under `directory` (fault/ runtime): params, layer
        state, per-replica updater stacks, threshold residual/τ or
        averaging-cadence phase, counters, and the iterator cursor when
        one is passed. Returns the model; a following `fit()` continues
        the interrupted run exactly (elastic: a changed mesh replica
        count re-shards the per-replica leaves)."""
        from deeplearning4j_tpu import fault
        model, _ = fault.resume(directory, model=self.model, trainer=self,
                                iterator=iterator)
        return model

    # -------------------------------------------------------------- evaluate
    def evaluate(self, data, labels=None, *, batch_size: int = 32,
                 evaluation=None):
        """Mesh-wide evaluation (reference: the Spark eval functions,
        `spark/impl/multilayer/scoring/` — workers score their shard,
        results merged via `Evaluation.merge`). Each batch's forward
        runs ONCE over the mesh with the batch sharded over the data
        axis; per-shard Evaluation objects are then merged, so the
        result is bit-identical to a single-device evaluation while the
        compute scales with the mesh."""
        from deeplearning4j_tpu.eval import Evaluation

        _require_single_process()
        model = self.model
        if not model._initialized:
            model.init()
        iterator = as_iterator(data, labels, batch_size=batch_size)
        repl = NamedSharding(self.mesh, P())
        batch_sh = NamedSharding(self.mesh, P(self.data_axis))
        params = _gput_tree(model.params, repl)
        state = _gput_tree(model.net_state, repl)

        if getattr(self, "_eval_forward", None) is None:
            self._eval_forward = jax.jit(
                model._forward_output, in_shardings=(repl, repl, batch_sh),
                out_shardings=batch_sh)

        merged = evaluation if evaluation is not None else Evaluation()
        # accumulating into `merged` directly keeps its top_n / labels /
        # threshold settings; `Evaluation.merge` remains the
        # cross-process combiner (masters / multihost)
        return _mesh_evaluate(
            model, iterator, merged, self.n_workers,
            lambda x: self._eval_forward(params, state, x),
            lambda f: _gput(f, batch_sh))

    def _fit_sync_threshold(self, iterator, listeners, rng_root, epochs,
                            steps_per_execution, divisible, check_trained):
        """Sync-mode fit with threshold-encoded gradient exchange
        (gradient_sharing="threshold"): same grouping/looping contract
        as the dense path, but each step's all-reduce moves the int8
        sign tensor instead of fp32 gradients, with the per-replica
        error-feedback residual and adaptive τ persisted across steps
        (and across fit() calls) like updater state."""
        from deeplearning4j_tpu.parallel import gradient_sharing as gs

        model = self.model
        if self._thr_step is None:
            self._build_threshold_step()
        spe = max(1, int(steps_per_execution))
        if spe > 1 and self._thr_multi is None:
            self._build_threshold_multi()
        repl = NamedSharding(self.mesh, P())

        # updater state is PER-REPLICA in threshold mode (each reference
        # worker advances its own updater on its local gradients) —
        # leading replica axis, same layout as the residual. An exact
        # resume (fault/) hands back the drifted per-replica stack; a
        # cold start replicates the model's view.
        def place():
            p = _gput_tree(model.params, repl)
            if self._resume_upd_r is not None:
                u, self._resume_upd_r = self._resume_upd_r, None
            else:
                u = self._replicate_tree(model.updater_state)
            return p, u, _gput_tree(model.net_state, repl)
        if self.stats is not None:
            with self.stats.time_phase("broadcast"):
                params, upd_r, state = place()
                jax.block_until_ready(params)
        else:
            params, upd_r, state = place()
        res_r, tau = self._threshold_state()
        batch_sh = NamedSharding(self.mesh, P(self.data_axis))
        stack_sh = NamedSharding(self.mesh, P(None, self.data_axis))
        eager_loss = bool(model.listeners) or self.stats is not None
        # comm accounting is host math on static shapes — every step is
        # counted with zero device syncs (docs/COMMS.md)
        wire_b = gs.exchange_wire_bytes(model.params, "threshold",
                                        n_workers=self.n_workers)
        dense_b = gs.exchange_wire_bytes(
            model.params, "dense", grad_dtype=model.dtype.compute_dtype)
        last_loss = None
        last_sparsity = None
        # replica-0 slice with a REPLICATED out-sharding (multi-process
        # fetchable) — the model-level updater view inside checkpoints
        rep0 = jax.jit(
            lambda t: jax.tree_util.tree_map(lambda a: a[0], t),
            out_shardings=repl)

        def live_state():
            # fault/ checkpointing: the fit's device-local trees are the
            # live training state (model attributes are stale until fit
            # returns); the per-replica updater stack and residual/τ
            # ride along for exact resume — gathered replicated so every
            # process can address them (multi-process elastic capture)
            return {"params": params, "net_state": state,
                    "updater_state": rep0(upd_r),
                    "trainer_arrays": {
                        "upd_r": self._replicated_view(upd_r),
                        "residual_r": self._replicated_view(res_r),
                        "tau": tau},
                    "trainer_meta": {"kind": "threshold",
                                     "trainer": "parallel",
                                     "n_workers": self.n_workers}}

        def run_single(ds):
            nonlocal params, upd_r, state, res_r, tau
            nonlocal last_loss, last_sparsity
            x = _gput(ds.features, batch_sh)
            y = _gput(ds.labels, batch_sh)
            rng = jax.random.fold_in(rng_root, model.iteration_count)
            t0 = time.perf_counter()
            params, upd_r, state, res_r, tau, loss, sp, dv = self._thr_step(
                params, upd_r, state, model.iteration_count, res_r, tau,
                x, y, rng)
            last_loss, last_sparsity = loss, sp
            gs.record_exchange("threshold", wire_b, dense_b, 1,
                               trainer="parallel")
            if eager_loss:
                model.score_value = float(loss)
                gs.record_threshold_stats(float(tau), float(sp),
                                          trainer="parallel")
            rows = _diag.process_if_due(model, dv, "exchange",
                                        model.iteration_count)
            if self.stats is not None:
                self.stats.record("sync_step", time.perf_counter() - t0,
                                  iteration=model.iteration_count)
                self.stats.next_round()
            listeners.iteration_done(model, model.iteration_count,
                                     model.epoch_count,
                                     model.score_value if eager_loss
                                     else float("nan"),
                                     batch_size=ds.num_examples(),
                                     diagnostics=rows[-1] if rows else None)
            model.iteration_count += 1

        def drain(pending):
            nonlocal params, upd_r, state, res_r, tau
            nonlocal last_loss, last_sparsity
            if not pending:
                return
            if len(pending) == 1:
                run_single(pending[0])
                return
            xs = _gput(np.stack([np.asarray(d.features) for d in pending]),
                       stack_sh)
            ys = _gput(np.stack([np.asarray(d.labels) for d in pending]),
                       stack_sh)
            it0 = model.iteration_count
            rngs = jax.vmap(lambda i: jax.random.fold_in(rng_root, i))(
                jnp.arange(it0, it0 + len(pending)))
            t0 = time.perf_counter()
            (params, upd_r, state, res_r, tau, losses, sps,
             dvs) = self._thr_multi(
                params, upd_r, state, it0, res_r, tau, xs, ys, rngs)
            last_loss, last_sparsity = losses, sps
            gs.record_exchange("threshold", wire_b, dense_b, len(pending),
                               trainer="parallel")
            lv = np.asarray(losses) if eager_loss else None
            if eager_loss:
                gs.record_threshold_stats(float(tau),
                                          float(np.asarray(sps)[-1]),
                                          trainer="parallel")
            rows = _diag.process_if_due(model, dvs, "exchange", it0,
                                        steps=len(pending))
            if self.stats is not None:
                self.stats.record("sync_step", time.perf_counter() - t0,
                                  iteration=it0, fused_steps=len(pending))
                self.stats.next_round()
            for j, d in enumerate(pending):
                if eager_loss:
                    model.score_value = float(lv[j])
                listeners.iteration_done(model, model.iteration_count,
                                         model.epoch_count,
                                         model.score_value if eager_loss
                                         else float("nan"),
                                         batch_size=d.num_examples(),
                                         step_boundary=(
                                             j == len(pending) - 1),
                                         diagnostics=(
                                             rows[j] if rows
                                             and model._diag.due(
                                                 model.iteration_count)
                                             else None))
                model.iteration_count += 1

        model._live_state_provider = live_state
        try:
            self._run_grouped(iterator, epochs, spe, divisible,
                              run_single, drain, model, listeners)
        finally:
            model._live_state_provider = None
        check_trained()
        self._thr_residual_r, self._thr_tau = res_r, tau
        if last_loss is not None and not eager_loss:
            lv = np.asarray(last_loss)
            model.score_value = float(lv[-1] if lv.ndim else lv)
        if last_sparsity is not None:
            sv = np.asarray(last_sparsity)
            gs.record_threshold_stats(float(np.asarray(tau)),
                                      float(sv[-1] if sv.ndim else sv),
                                      trainer="parallel")
        model.params = jax.tree_util.tree_map(np.asarray, params)
        model.net_state = jax.tree_util.tree_map(np.asarray, state)
        # per-replica updater states drift (each advanced on its own
        # shard, reference semantics); the model keeps replica 0's view.
        # The slice is taken with a REPLICATED out-sharding so the host
        # fetch is legal under multi-process execution (a bare a[0]
        # lands on replica 0's devices, which other processes cannot
        # read back)
        rep0 = jax.jit(
            lambda t: jax.tree_util.tree_map(lambda a: a[0], t),
            out_shardings=repl)
        model.updater_state = jax.tree_util.tree_map(np.asarray,
                                                     rep0(upd_r))
        return model

    def _fit_sync_bucketed(self, mode, iterator, listeners, rng_root,
                           epochs, steps_per_execution, divisible,
                           check_trained):
        """Sync-mode fit with the bucketed (overlapped) exchange: every
        ``stacked::`` packed run / unpacked layer exchanges inside the
        backward pass (dense pmean, threshold encode+int-psum, or the
        ZeRO reduce-scatter+all-gather of the `_rs` modes), per-bucket
        residual/τ persisted across steps and fit() calls like updater
        state. Same grouping/looping contract as the single-barrier
        paths."""
        from deeplearning4j_tpu.parallel import gradient_sharing as gs

        model = self.model
        per_replica_upd = mode != "dense"
        has_thr = mode in ("threshold", "threshold_rs")
        rs = mode in gs.RS_MODES
        if not self._updater_state_floats():
            # the updater advances INSIDE the VJP hooks — its state
            # threads the cotangent channel, which carries float leaves
            # only (every built-in updater qualifies; fit() already
            # degraded plain dense to the single-barrier program)
            raise ValueError(
                f"gradient_sharing={mode!r} threads updater state "
                "through the bucketed VJP and requires float state "
                "leaves, but this model's updater has non-float state. "
                "The rs modes are inherently bucketed (bucketed=False "
                "does not apply); use gradient_sharing='dense' or "
                "'threshold' with bucketed=False instead")
        if self._bkt_step is None:
            self._bkt_step = self._build_bucketed(mode, multi=False)
        spe = max(1, int(steps_per_execution))
        if spe > 1 and self._bkt_multi is None:
            self._bkt_multi = self._build_bucketed(mode, multi=True)
        repl = NamedSharding(self.mesh, P())

        def place_upd():
            if rs:
                return self._shard_rs_state(model.updater_state)
            if mode == "threshold":
                if self._resume_upd_r is not None:
                    u, self._resume_upd_r = self._resume_upd_r, None
                    return u
                return self._replicate_tree(model.updater_state)
            return _gput_tree(model.updater_state, repl)

        def place():
            return (_gput_tree(model.params, repl), place_upd(),
                    _gput_tree(model.net_state, repl))
        if self.stats is not None:
            with self.stats.time_phase("broadcast"):
                params, upd_r, state = place()
                jax.block_until_ready(params)
        else:
            params, upd_r, state = place()
        if has_thr:
            res_r, tau = self._threshold_state(per_bucket=True)
        else:
            res_r, tau = {}, {}
        batch_sh = NamedSharding(self.mesh, P(self.data_axis))
        stack_sh = NamedSharding(self.mesh, P(None, self.data_axis))
        eager_loss = bool(model.listeners) or self.stats is not None
        # comm accounting is host math on static shapes — every step is
        # counted with zero device syncs (docs/COMMS.md)
        wire_b = gs.exchange_wire_bytes(
            model.params, mode, n_workers=self.n_workers,
            rs_plan=self._rs_plan() if rs else None,
            grad_dtype=model.dtype.compute_dtype)
        dense_b = gs.exchange_wire_bytes(
            model.params, "dense", grad_dtype=model.dtype.compute_dtype)
        last_loss = None
        last_sparsity = None
        rep0 = jax.jit(
            lambda t: jax.tree_util.tree_map(lambda a: a[0], t),
            out_shardings=repl)
        rs_full = self._rs_full_state_fn() if rs else None

        def updater_view():
            # the model/checkpoint view of the live updater state:
            # replica 0 of the drifted per-replica stack (threshold),
            # the reassembled full tree (rs — checkpoints stay
            # replica-count independent), or the replicated tree itself
            if rs:
                return rs_full(upd_r)
            if mode == "threshold":
                return rep0(upd_r)
            return upd_r

        def live_state():
            # fault/ checkpointing: the fit's device-local trees are the
            # live training state (model attributes are stale until fit
            # returns); threshold-family modes add the per-bucket
            # residual/τ — and per-replica updater drift where it exists
            src = {"params": params, "net_state": state,
                   "updater_state": updater_view(),
                   "trainer_meta": {"kind": {"dense": "sync_dense",
                                             "threshold": "threshold",
                                             "dense_rs": "sync_dense_rs",
                                             "threshold_rs": "threshold_rs",
                                             }[mode],
                                    "trainer": "parallel",
                                    "bucketed": True,
                                    "n_workers": self.n_workers}}
            if has_thr:
                # per-replica stacks gathered replicated so every
                # process can address them (multi-process elastic
                # capture); τ is replicated by construction
                arrays = {"residual_r": self._replicated_view(res_r),
                          "tau": tau}
                if mode == "threshold":
                    arrays["upd_r"] = self._replicated_view(upd_r)
                src["trainer_arrays"] = arrays
            return src

        def record(steps):
            gs.record_exchange(mode, wire_b, dense_b, steps,
                               trainer="parallel")

        def run_single(ds):
            nonlocal params, upd_r, state, res_r, tau
            nonlocal last_loss, last_sparsity
            x = _gput(ds.features, batch_sh)
            y = _gput(ds.labels, batch_sh)
            rng = jax.random.fold_in(rng_root, model.iteration_count)
            t0 = time.perf_counter()
            params, upd_r, state, res_r, tau, loss, sp, dv = self._bkt_step(
                params, upd_r, state, model.iteration_count, res_r, tau,
                x, y, rng)
            last_loss, last_sparsity = loss, sp
            record(1)
            if eager_loss:
                model.score_value = float(loss)
                if has_thr:
                    gs.record_threshold_stats(gs.tau_scalar(tau),
                                              float(sp),
                                              trainer="parallel")
            rows = _diag.process_if_due(model, dv, "exchange",
                                        model.iteration_count)
            if self.stats is not None:
                self.stats.record("sync_step", time.perf_counter() - t0,
                                  iteration=model.iteration_count)
                self.stats.next_round()
            listeners.iteration_done(model, model.iteration_count,
                                     model.epoch_count,
                                     model.score_value if eager_loss
                                     else float("nan"),
                                     batch_size=ds.num_examples(),
                                     diagnostics=rows[-1] if rows else None)
            model.iteration_count += 1

        def drain(pending):
            nonlocal params, upd_r, state, res_r, tau
            nonlocal last_loss, last_sparsity
            if not pending:
                return
            if len(pending) == 1:
                run_single(pending[0])
                return
            xs = _gput(np.stack([np.asarray(d.features) for d in pending]),
                       stack_sh)
            ys = _gput(np.stack([np.asarray(d.labels) for d in pending]),
                       stack_sh)
            it0 = model.iteration_count
            rngs = jax.vmap(lambda i: jax.random.fold_in(rng_root, i))(
                jnp.arange(it0, it0 + len(pending)))
            t0 = time.perf_counter()
            (params, upd_r, state, res_r, tau, losses, sps,
             dvs) = self._bkt_multi(
                params, upd_r, state, it0, res_r, tau, xs, ys, rngs)
            last_loss, last_sparsity = losses, sps
            record(len(pending))
            lv = np.asarray(losses) if eager_loss else None
            if eager_loss and has_thr:
                gs.record_threshold_stats(gs.tau_scalar(tau),
                                          float(np.asarray(sps)[-1]),
                                          trainer="parallel")
            rows = _diag.process_if_due(model, dvs, "exchange", it0,
                                        steps=len(pending))
            if self.stats is not None:
                self.stats.record("sync_step", time.perf_counter() - t0,
                                  iteration=it0, fused_steps=len(pending))
                self.stats.next_round()
            for j, d in enumerate(pending):
                if eager_loss:
                    model.score_value = float(lv[j])
                listeners.iteration_done(model, model.iteration_count,
                                         model.epoch_count,
                                         model.score_value if eager_loss
                                         else float("nan"),
                                         batch_size=d.num_examples(),
                                         step_boundary=(
                                             j == len(pending) - 1),
                                         diagnostics=(
                                             rows[j] if rows
                                             and model._diag.due(
                                                 model.iteration_count)
                                             else None))
                model.iteration_count += 1

        model._live_state_provider = live_state
        try:
            self._run_grouped(iterator, epochs, spe, divisible,
                              run_single, drain, model, listeners)
        finally:
            model._live_state_provider = None
        check_trained()
        if has_thr:
            self._thr_residual_r, self._thr_tau = res_r, tau
        if last_loss is not None and not eager_loss:
            lv = np.asarray(last_loss)
            model.score_value = float(lv[-1] if lv.ndim else lv)
        if has_thr and last_sparsity is not None:
            sv = np.asarray(last_sparsity)
            gs.record_threshold_stats(gs.tau_scalar(tau),
                                      float(sv[-1] if sv.ndim else sv),
                                      trainer="parallel")
        model.params = jax.tree_util.tree_map(np.asarray, params)
        model.net_state = jax.tree_util.tree_map(np.asarray, state)
        model.updater_state = jax.tree_util.tree_map(np.asarray,
                                                     updater_view())
        return model

    # ------------------------------------------------------------------- fit
    def fit(self, data, labels=None, *, epochs: int = 1, batch_size: int = 32,
            steps_per_execution: int = 1):
        """Global-batch training over the mesh. `batch_size` is the GLOBAL
        batch; it must divide by the data-axis size.

        `steps_per_execution > 1` fuses that many steps into one
        `lax.scan` dispatch — numerics identical, host dispatch paid
        once per group. Both modes honor it (sync: scan over sharded
        batch stacks; averaging: the pmean round fires in-scan at its
        cadence); stats collection forces per-step execution in
        averaging mode because fused dispatch has no observable phase
        boundaries. The per-step loss device→host sync is also skipped
        when no listeners/stats need it, so small-model distributed
        training is not serialized on scalar readbacks."""
        model = self.model
        if not model._initialized:
            model.init()
        iterator = as_iterator(data, labels, batch_size=batch_size)
        # when the telemetry substrate is on, phase events flow onto the
        # global registry/tracer and the fit feeds /metrics like any
        # single-model fit (monitor.extra_listeners() is [] when off)
        monitor.attach_master_stats(self.stats)
        listeners = ComposedListeners(model.listeners
                                      + monitor.extra_listeners())
        rng_root = jax.random.PRNGKey(model.conf.seed + 3)

        n_div = self.n_workers
        batch_stats = {"trained": 0, "dropped": 0}

        def divisible(ds):
            # data-parallel shards need batch % devices == 0; ragged
            # TAILS are dropped (TF drop_remainder semantics) with a
            # warning — but a configuration where EVERY batch is
            # indivisible must fail loudly, not no-op (see fit() end)
            n = ds.num_examples()
            if n % n_div == 0:
                batch_stats["trained"] += 1
                return True
            batch_stats["dropped"] += 1
            if not getattr(self, "_warned_ragged", False):
                import logging
                logging.getLogger(__name__).warning(
                    "dropping ragged batch of %d examples (not divisible "
                    "by %d-way data parallelism); pad the dataset or pick "
                    "a divisible batch_size to train on every example",
                    n, n_div)
                self._warned_ragged = True
            return False

        def check_trained():
            if batch_stats["dropped"] and not batch_stats["trained"]:
                raise ValueError(
                    f"every batch was indivisible by the {n_div}-way data "
                    f"axis — fit() would be a silent no-op; use a "
                    f"batch_size divisible by {n_div}")

        if self.mode == "sync":
            from deeplearning4j_tpu.parallel import gradient_sharing as _gs
            gsmode = self.gradient_sharing
            if (gsmode == "dense" and self.bucketed
                    and not self._updater_state_floats()):
                # a custom updater with non-float state cannot thread
                # the bucketed VJP's cotangent channel — plain dense
                # silently keeps the single-barrier GSPMD program
                # (threshold/rs modes raise in _fit_sync_bucketed)
                gsmode = None
            if self._multi_io_graph and gsmode is not None:
                if gsmode == "dense":
                    # multi-input/-output graphs keep the GSPMD
                    # single-barrier program (the bucketed loss body
                    # packs exactly one features/labels pair)
                    gsmode = None
                else:
                    raise NotImplementedError(
                        f"gradient_sharing={gsmode!r} supports single-"
                        "input single-output models; train multi-io "
                        "graphs with gradient_sharing='dense' or via "
                        "model.fit")
            if gsmode is not None and (
                    gsmode in _gs.RS_MODES
                    or (self.bucketed and gsmode in ("dense",
                                                     "threshold"))):
                # default: bucketed per-layer-run exchange inside the
                # backward pass (the rs modes are inherently bucketed)
                return self._fit_sync_bucketed(
                    gsmode, iterator, listeners, rng_root, epochs,
                    steps_per_execution, divisible, check_trained)
            gsmode = self.gradient_sharing
            if gsmode == "threshold":
                # single-barrier PR-4 program (bucketed=False /
                # DL4J_BUCKETED_EXCHANGE=0)
                return self._fit_sync_threshold(
                    iterator, listeners, rng_root, epochs,
                    steps_per_execution, divisible, check_trained)

        if self.mode == "sync":
            if self._sync_step is None:
                self._build_sync_step()
            spe = max(1, int(steps_per_execution))
            if spe > 1 and self._sync_multi is None:
                self._build_sync_multi()
            repl = NamedSharding(self.mesh, P())
            if self.stats is not None:
                with self.stats.time_phase("broadcast"):
                    params = _gput_tree(model.params, repl)
                    upd = _gput_tree(model.updater_state, repl)
                    state = _gput_tree(model.net_state, repl)
                    jax.block_until_ready(params)
            else:
                params = _gput_tree(model.params, repl)
                upd = _gput_tree(model.updater_state, repl)
                state = _gput_tree(model.net_state, repl)
            batch_sh = NamedSharding(self.mesh, P(self.data_axis))
            stack_sh = NamedSharding(self.mesh, P(None, self.data_axis))
            # loss readback serializes host on device each step; only pay
            # it when someone (listener/stats consumer) will look at it
            eager_loss = bool(model.listeners) or self.stats is not None
            last_loss = None
            from deeplearning4j_tpu.parallel import gradient_sharing as gs
            # real wire dtype: the GSPMD all-reduce moves COMPUTE-dtype
            # grads (bf16 under mixed_bf16 — half the fp32 payload)
            dense_b = gs.exchange_wire_bytes(
                model.params, "dense", grad_dtype=model.dtype.compute_dtype)

            def live_state():
                # fault/ checkpointing: fit-local device trees (the
                # model's attributes are stale until fit returns)
                return {"params": params, "net_state": state,
                        "updater_state": upd,
                        "trainer_meta": {"kind": "sync_dense",
                                         "trainer": "parallel",
                                         "n_workers": self.n_workers}}

            def run_single(ds):
                nonlocal params, upd, state, last_loss
                x = _gput(ds.features, batch_sh)
                y = _gput(ds.labels, batch_sh)
                rng = jax.random.fold_in(rng_root, model.iteration_count)
                t0 = time.perf_counter()
                params, upd, state, loss, _, dv = self._sync_step(
                    params, upd, state, model.iteration_count, x, y, rng)
                gs.record_exchange("dense", dense_b, dense_b, 1,
                                   trainer="parallel")
                last_loss = loss
                if eager_loss:
                    model.score_value = float(loss)
                rows = _diag.process_if_due(model, dv, "fit",
                                            model.iteration_count)
                if self.stats is not None:
                    # float(loss) above already synced the step
                    self.stats.record("sync_step",
                                      time.perf_counter() - t0,
                                      iteration=model.iteration_count)
                    self.stats.next_round()
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

            def drain(pending):
                nonlocal params, upd, state, last_loss
                if not pending:
                    return
                if len(pending) == 1:
                    run_single(pending[0])
                    return
                xs = _gput(np.stack([np.asarray(d.features) for d in pending]),
                           stack_sh)
                ys = _gput(np.stack([np.asarray(d.labels) for d in pending]),
                           stack_sh)
                it0 = model.iteration_count
                rngs = jax.vmap(lambda i: jax.random.fold_in(rng_root, i))(
                    jnp.arange(it0, it0 + len(pending)))
                t0 = time.perf_counter()
                params, upd, state, losses, dvs = self._sync_multi(
                    params, upd, state, it0, xs, ys, rngs)
                gs.record_exchange("dense", dense_b, dense_b, len(pending),
                                   trainer="parallel")
                last_loss = losses
                lv = np.asarray(losses) if eager_loss else None
                rows = _diag.process_if_due(model, dvs, "fit", it0,
                                            steps=len(pending))
                if self.stats is not None:
                    self.stats.record("sync_step",
                                      time.perf_counter() - t0, iteration=it0,
                                      fused_steps=len(pending))
                    self.stats.next_round()
                for j, d in enumerate(pending):
                    if eager_loss:
                        model.score_value = float(lv[j])
                    listeners.iteration_done(model, model.iteration_count,
                                             model.epoch_count,
                                             model.score_value if eager_loss
                                             else float("nan"),
                                             batch_size=d.num_examples(),
                                             step_boundary=(
                                                 j == len(pending) - 1),
                                             diagnostics=(
                                                 rows[j] if rows
                                                 and model._diag.due(
                                                     model.iteration_count)
                                                 else None))
                    model.iteration_count += 1

            model._live_state_provider = live_state
            try:
                self._run_grouped(iterator, epochs, spe, divisible,
                                  run_single, drain, model, listeners)
            finally:
                model._live_state_provider = None
            check_trained()
            if last_loss is not None and not eager_loss:
                lv = np.asarray(last_loss)
                model.score_value = float(lv[-1] if lv.ndim else lv)
            model.params = jax.tree_util.tree_map(np.asarray, params)
            model.net_state = jax.tree_util.tree_map(np.asarray, state)
            model.updater_state = jax.tree_util.tree_map(np.asarray, upd)
            return model

        # averaging (local SGD) mode. `steps_per_execution > 1` drains
        # k-batch groups through ONE shard_map dispatch whose scan fires
        # the pmean round at the averaging_frequency cadence — numerics
        # identical to per-step. Per-phase stats need the per-step path
        # (fused dispatch has no observable phase boundaries), so stats
        # collection forces spe=1.
        if self._local_step is None:
            self._build_averaging()
        spe = max(1, int(steps_per_execution))
        if self.stats is not None:
            spe = 1
        if spe > 1 and self._local_multi is None:
            self._build_averaging_multi()
        # exact resume (fault/) hands back the drifted per-replica
        # stacks + the averaging-cadence phase; a cold start replicates
        def place():
            if self._resume_avg is not None:
                ra, self._resume_avg = self._resume_avg, None
                return (ra["params_r"], ra["upd_r"], ra["state_r"],
                        ra["since_avg"])
            return (self._replicate_tree(model.params),
                    self._replicate_tree(model.updater_state),
                    self._replicate_tree(model.net_state), 0)
        if self.stats is not None:
            with self.stats.time_phase("broadcast"):
                params_r, upd_r, state_r, since_avg = place()
                jax.block_until_ready(params_r)
        else:
            params_r, upd_r, state_r, since_avg = place()
        batch_sh = NamedSharding(self.mesh, P(self.data_axis))
        stack_sh = NamedSharding(self.mesh, P(None, self.data_axis))
        # same lazy-readback gate as sync mode: the per-step scalar sync
        # is only paid when a listener/stats consumer will look at it
        eager_loss = bool(model.listeners) or self.stats is not None
        last_losses = None
        repl = NamedSharding(self.mesh, P())
        rep0 = jax.jit(
            lambda t: jax.tree_util.tree_map(lambda a: a[0], t),
            out_shardings=repl)

        def live_state():
            # fault/ checkpointing: every replica's params/updater/state
            # drifted independently since the last pmean round — the
            # full stacks plus the cadence phase are the live state;
            # replica 0 stands in for the model-level view
            return {"params": rep0(params_r), "net_state": rep0(state_r),
                    "updater_state": rep0(upd_r),
                    "trainer_arrays": {
                        "params_r": self._replicated_view(params_r),
                        "upd_r": self._replicated_view(upd_r),
                        "state_r": self._replicated_view(state_r)},
                    "trainer_meta": {"kind": "averaging",
                                     "trainer": "parallel",
                                     "since_avg": int(since_avg),
                                     "n_workers": self.n_workers}}

        def run_single(ds):
            nonlocal params_r, upd_r, state_r, since_avg, last_losses
            x = _gput(ds.features, batch_sh)
            y = _gput(ds.labels, batch_sh)
            rng = jax.random.fold_in(rng_root, model.iteration_count)
            t0 = time.perf_counter()
            params_r, upd_r, state_r, losses = self._local_step(
                params_r, upd_r, state_r, model.iteration_count, x, y, rng)
            last_losses = losses
            if eager_loss:
                model.score_value = float(jnp.mean(losses))
            if self.stats is not None:
                self.stats.record("local_fit", time.perf_counter() - t0,
                                  iteration=model.iteration_count)
            since_avg += 1
            if since_avg >= self.averaging_frequency:
                t0 = time.perf_counter()
                params_r = self._average_fn(params_r)
                state_r = self._average_fn(state_r)
                if self.average_updater_state:
                    upd_r = self._average_fn(upd_r)
                if self.stats is not None:
                    jax.block_until_ready(params_r)
                    self.stats.record("average",
                                      time.perf_counter() - t0,
                                      round=self.stats.next_round())
                since_avg = 0
            listeners.iteration_done(model, model.iteration_count,
                                     model.epoch_count,
                                     model.score_value if eager_loss
                                     else float("nan"),
                                     batch_size=ds.num_examples())
            model.iteration_count += 1

        def drain(pending):
            nonlocal params_r, upd_r, state_r, since_avg, last_losses
            if not pending:
                return
            if len(pending) == 1:
                run_single(pending[0])
                return
            xs = _gput(np.stack([np.asarray(d.features) for d in pending]),
                       stack_sh)
            ys = _gput(np.stack([np.asarray(d.labels) for d in pending]),
                       stack_sh)
            it0 = model.iteration_count
            rngs = jax.vmap(lambda i: jax.random.fold_in(rng_root, i))(
                jnp.arange(it0, it0 + len(pending)))
            params_r, upd_r, state_r, losses = self._local_multi(
                params_r, upd_r, state_r, it0, since_avg, xs, ys, rngs)
            last_losses = losses[-1]
            # cadence advances deterministically (since_avg < freq is
            # invariant) — host mirror of the in-scan update, no sync
            since_avg = (since_avg + len(pending)) % self.averaging_frequency
            lv = np.asarray(losses) if eager_loss else None
            for j, d in enumerate(pending):
                if eager_loss:
                    model.score_value = float(lv[j].mean())
                listeners.iteration_done(model, model.iteration_count,
                                         model.epoch_count,
                                         model.score_value if eager_loss
                                         else float("nan"),
                                         batch_size=d.num_examples(),
                                         step_boundary=(
                                             j == len(pending) - 1))
                model.iteration_count += 1

        model._live_state_provider = live_state
        try:
            self._run_grouped(iterator, epochs, spe, divisible,
                              run_single, drain, model, listeners)
        finally:
            model._live_state_provider = None
        if since_avg:
            params_r = self._average_fn(params_r)
            state_r = self._average_fn(state_r)
            if self.average_updater_state:
                upd_r = self._average_fn(upd_r)
        if last_losses is not None and not eager_loss:
            model.score_value = float(jnp.mean(last_losses))
        check_trained()
        model.params = self._unreplicate_tree(params_r)
        model.net_state = self._unreplicate_tree(state_r)
        model.updater_state = self._unreplicate_tree(upd_r)
        return model

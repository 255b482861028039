"""Threshold-encoded gradient sharing: error-feedback compressed collectives.

Reference equivalence: the signature distributed-training feature of
`SharedTrainingMaster` — `Nd4j.getExecutioner().thresholdEncode`
(sign-magnitude quantization at threshold τ), residual accumulation
(`EncodedGradientsAccumulator` keeps what was not sent and re-adds it
next step), and `AdaptiveThresholdAlgorithm` (τ chases a target
sparsity band). Communication characterization (arXiv:1810.11112)
shows dense gradient exchange dominating scaled-out step time; the
TensorFlow system paper (arXiv:1605.08695) argues the exchange
schedule should be a first-class, tunable part of the program. Here it
is: a jittable encode/decode the trainers select with
``gradient_sharing="dense"|"threshold"`` (env A/B override
``DL4J_GRADIENT_SHARING``, mirroring ``DL4J_SCAN_LAYERS``).

XLA-friendly wire format: instead of the reference's sparse
index/value chunks (data-dependent shapes XLA cannot compile), the
encoded update is a **dense int8 tensor of {-1, 0, +1}** — the
all-reduce payload drops from 4 bytes/element (fp32) to 1 byte/element
(int8), a fixed 4x wire reduction, while the threshold controls
*fidelity* (what fraction of the accumulated update magnitude gets
through this step) rather than wire size. Summing N int8 sign tensors
is exact for N ≤ 127 replicas; larger data axes automatically widen to
int16 (2x reduction).

Numeric contract (error feedback / EF-SGD):

    u_r        = updater_r(grad_r)               (per-replica updater —
                                                  each reference worker
                                                  runs its own)
    acc_r      = u_r + residual_r                (per replica)
    enc_r      = sign(acc_r) * (|acc_r| >= τ)    (int8 on the wire)
    residual_r = acc_r - τ * enc_r               (nothing is lost)
    û          = τ * Σ_r enc_r / N               (the shared update every
                                                  replica applies)

What gets encoded is the post-updater UPDATE, exactly as in the
reference (`EncodingHandler` encodes the updater's output): τ then
lives on the learning-rate scale, and every update magnitude the
threshold suppresses stays in the replica-local residual and re-enters
the accumulator next step, so the *sum* of applied updates tracks the
sum of true updates — the property the convergence-parity tests in
tests/test_gradient_sharing.py enforce against dense training.

τ adaptation (reference `AdaptiveThresholdAlgorithm` semantics):
``sparsity`` here is the encoded fraction — the share of elements that
made it onto the wire this step, pmean'd over replicas. Above the
target band, τ is boosted (send less); below it, τ decays (send
more); always clamped to [min_threshold, max_threshold]. τ and the
residual ride the fused multi-step scan carry next to the updater
state, and pack/unpack across the ``stacked::`` run boundary exactly
like updater state does (nn/scan_stack.py).

Bucketed (overlapped) exchange — the default for sync trainers:
instead of one post-backward barrier, every ``stacked::`` packed run
and every unpacked layer is a **bucket** whose exchange is emitted by
a `jax.custom_vjp` hook the moment backward finishes that bucket's
VJP: the cotangent of bucket i's params is data-independent of the
backward compute of buckets i+1.. (layers earlier in forward order),
so XLA's scheduler can run collective i concurrently with the
remaining backward — the comm/compute overlap the CUDA-aware-MPI
characterization (arXiv:1810.11112) identifies as the scaling
headroom beyond compression. In threshold mode the per-bucket
residual and τ thread THROUGH the VJP via the hook's cotangent
channel (the bwd rule returns the advanced residual/τ/updater state
as the "gradients" of those inputs), preserving the error-feedback
identity enc·τ + res_new = update + res_old **per bucket**. Opt out
with ``DL4J_BUCKETED_EXCHANGE=0`` (or ``bucketed=False`` on the
trainers) for the PR-4 single-barrier program.

ZeRO-style sharded-updater modes ``dense_rs`` / ``threshold_rs``:
on the same bucket structure, gradients are **reduce-scattered** over
the data axis instead of all-reduced, each replica runs the updater
only on its gradient shard (updater state sharded over the data axis
— 1/N optimizer memory, the ZeRO partitioning), updates its param
shard, and the updated params are **all-gathered**. Which leaves
shard follows the same rule as `parallel.tensor.fsdp_param_specs`
(last axis, divisibility-gated, small leaves replicated) so the wire
layout composes with FSDP sharding annotations. ``dense_rs`` computes
the same sums as bucketed ``dense`` (reduce-scatter + all-gather is
the all-reduce, elementwise updater math is shard-oblivious) and
matches it bit for bit wherever the compiler has no rounding choice;
beyond that the two agree to fp32 rounding (XLA:CPU's FMA contraction
follows the updater's operand shape — test_gradient_sharing.py);
``threshold_rs`` threshold-encodes the RAW gradient (+ residual)
before the integer reduce-scatter — the updater runs post-decode on
the shard, so τ lives on the gradient scale there, unlike
``threshold`` where it lives on the update scale.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np

from deeplearning4j_tpu.nn import scan_stack

MODES = ("dense", "threshold", "dense_rs", "threshold_rs")
RS_MODES = ("dense_rs", "threshold_rs")

# env values that force each mode (mirrors DL4J_SCAN_LAYERS's spelling
# tolerance: 0/off/false disable the feature, i.e. force dense)
_ENV_VAR = "DL4J_GRADIENT_SHARING"
_ENV_DENSE = ("dense", "0", "off", "false", "no")
_ENV_THRESHOLD = ("threshold", "1", "on", "true", "yes")

# bucketed (per-layer-run, overlapped) exchange toggle: default ON;
# DL4J_BUCKETED_EXCHANGE=0 restores the PR-4 single-barrier program
_BUCKET_ENV_VAR = "DL4J_BUCKETED_EXCHANGE"


def resolve_bucketed(explicit: Optional[bool] = None) -> bool:
    """Bucketed-exchange resolution: the ``DL4J_BUCKETED_EXCHANGE``
    env override wins (A/B the overlap without touching code), then an
    explicit trainer argument, then the default True. Unknown env
    spellings raise (mirroring ``DL4J_GRADIENT_SHARING``) — a typo'd
    opt-out must not silently keep the bucketed program running."""
    env = os.environ.get(_BUCKET_ENV_VAR)
    if env is not None and env.strip():
        v = env.strip().lower()
        if v in ("0", "off", "false", "no"):
            return False
        if v in ("1", "on", "true", "yes"):
            return True
        raise ValueError(
            f"{_BUCKET_ENV_VAR}={env!r}: expected one of "
            f"('0', 'off', 'false', 'no', '1', 'on', 'true', 'yes')")
    if explicit is not None:
        return bool(explicit)
    return True


@dataclasses.dataclass(frozen=True)
class ThresholdConfig:
    """Knobs of the threshold encoder + adaptive-τ controller.

    Defaults follow the reference's AdaptiveThresholdAlgorithm shape:
    start at `initial_threshold`, keep the encoded fraction inside
    [sparsity_target_min, sparsity_target_max], step τ geometrically
    when outside the band."""

    initial_threshold: float = 1e-3
    sparsity_target_min: float = 1e-3   # sending less than this: τ decays
    sparsity_target_max: float = 1e-1   # sending more than this: τ boosts
    decay: float = 1.0 / 1.2            # τ multiplier below the band
    boost: float = 1.2                  # τ multiplier above the band
    min_threshold: float = 1e-8
    max_threshold: float = 1.0

    def __post_init__(self):
        if not (0.0 < self.sparsity_target_min
                <= self.sparsity_target_max <= 1.0):
            raise ValueError(
                f"sparsity target band must satisfy 0 < min <= max <= 1, "
                f"got [{self.sparsity_target_min}, "
                f"{self.sparsity_target_max}]")
        if not (0.0 < self.decay < 1.0 < self.boost):
            raise ValueError(
                f"need decay < 1 < boost, got decay={self.decay} "
                f"boost={self.boost}")
        if not (0.0 < self.min_threshold <= self.initial_threshold
                <= self.max_threshold):
            raise ValueError(
                f"need min_threshold <= initial_threshold <= "
                f"max_threshold, got {self.min_threshold} / "
                f"{self.initial_threshold} / {self.max_threshold}")

    @staticmethod
    def from_conf(conf) -> "ThresholdConfig":
        """Config-carried initial τ (`gradient_sharing_threshold`),
        controller defaults for the rest."""
        tau0 = getattr(conf, "gradient_sharing_threshold", None)
        if tau0 is None:
            return ThresholdConfig()
        return ThresholdConfig(initial_threshold=float(tau0))


def env_mode() -> Optional[str]:
    """The ``DL4J_GRADIENT_SHARING`` override if set (validated), else
    None. Exposed so trainers can tell an env-forced mode (a global A/B
    toggle that must degrade gracefully where it does not apply) from
    an explicit arg/conf choice (a hard error when invalid)."""
    env = os.environ.get(_ENV_VAR)
    if env is None or not env.strip():
        return None
    v = env.strip().lower()
    if v in _ENV_DENSE:
        return "dense"
    if v in _ENV_THRESHOLD:
        return "threshold"
    if v in RS_MODES:
        return v
    raise ValueError(
        f"{_ENV_VAR}={env!r}: expected one of "
        f"{_ENV_DENSE + _ENV_THRESHOLD + RS_MODES}")


def resolve_mode(explicit: Optional[str] = None, conf=None) -> str:
    """Gradient-sharing mode resolution: the ``DL4J_GRADIENT_SHARING``
    env override wins (benchmark A/B without touching code), then an
    explicit trainer argument, then the model configuration's
    ``gradient_sharing`` field, then "dense"."""
    forced = env_mode()
    if forced is not None:
        return forced
    for v in (explicit, getattr(conf, "gradient_sharing", None)):
        if v is not None:
            if v not in MODES:
                raise ValueError(
                    f"gradient_sharing must be one of {MODES}, got {v!r}")
            return v
    return "dense"


def wire_dtype(n_workers: int):
    """Narrowest integer type whose sum of n_workers sign values is
    exact. int8 up to 127 replicas (4x vs fp32), int16 beyond."""
    if n_workers <= 127:
        return jnp.int8
    if n_workers <= 32767:
        return jnp.int16
    raise ValueError(
        f"threshold gradient sharing supports data axes up to 32767 "
        f"replicas, got {n_workers}")


# ------------------------------------------------------------ encode/decode
def encode_leaf(acc, tau, wdtype):
    """One leaf of the threshold encoder: (wire tensor, residual,
    elements sent). `acc` is gradient + carried residual."""
    mask = jnp.abs(acc) >= tau.astype(acc.dtype)
    enc = jnp.where(mask, jnp.sign(acc), 0.0).astype(wdtype)
    residual = acc - enc.astype(acc.dtype) * tau.astype(acc.dtype)
    return enc, residual, jnp.sum(mask, dtype=jnp.float32)


def adapt_threshold(tau, sparsity, cfg: ThresholdConfig):
    """One controller step: boost τ above the target band (sending too
    much), decay it below (sending too little), clamp always."""
    tau = jnp.where(sparsity > cfg.sparsity_target_max, tau * cfg.boost,
                    jnp.where(sparsity < cfg.sparsity_target_min,
                              tau * cfg.decay, tau))
    return jnp.clip(tau, cfg.min_threshold, cfg.max_threshold)


def tree_elements(tree) -> float:
    """Static element count of a pytree (host math, trace-safe)."""
    return float(sum(int(np.prod(np.shape(l)))
                     for l in jax.tree_util.tree_leaves(tree)))


def threshold_exchange(grads, residual, tau, axis: str,
                       cfg: ThresholdConfig, *, n_workers: int):
    """The complete compressed collective: encode (with error
    feedback), all-reduce the integer wire tensors over `axis`, decode
    to the shared update, adapt τ from the globally-averaged encoded
    fraction.

    Returns (ĝ, new_residual, new_tau, sparsity). ĝ replaces
    pmean(grads) in the sync step; `sparsity` is the achieved encoded
    fraction (the compression-fidelity observable the reference's
    EncodingHandler logs)."""
    wdtype = wire_dtype(n_workers)
    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_r = treedef.flatten_up_to(residual)
    enc, new_res, sent = [], [], 0.0
    for g, r in zip(flat_g, flat_r):
        e, nr, s = encode_leaf(g + r.astype(g.dtype), tau, wdtype)
        enc.append(e)
        new_res.append(nr)
        sent = sent + s
    summed = [jax.lax.psum(e, axis) for e in enc]
    inv_n = 1.0 / float(n_workers)
    ghat = [s.astype(g.dtype) * (tau.astype(g.dtype) * g.dtype.type(inv_n))
            for s, g in zip(summed, flat_g)]
    total = tree_elements(grads)
    sparsity = jax.lax.pmean(sent, axis) / total
    new_tau = adapt_threshold(tau, sparsity, cfg)
    unflatten = treedef.unflatten
    return unflatten(ghat), unflatten(new_res), new_tau, sparsity


def dense_exchange(grads, axis: str):
    """The uncompressed baseline as an *explicit* collective —
    numerically what GSPMD inserts for the jit dense path (mean of
    per-replica gradients), made manual so its wire payload is
    measurable by the same jaxpr accounting as the threshold path."""
    return jax.tree_util.tree_map(
        lambda g: jax.lax.pmean(g, axis), grads)


def zeros_residual(params):
    """Fresh per-layer residual tree matching `params` (the same shape
    contract updater state follows — per-layer keys at the boundary,
    packed to ``stacked::`` entries only inside the program). Reads
    shapes/dtypes only, so global (non-fetchable) param leaves are
    fine."""
    return jax.tree_util.tree_map(
        lambda a: np.zeros(np.shape(a),
                           getattr(a, "dtype", None) or np.asarray(a).dtype),
        params)


# --------------------------------------------------------------- step bodies
def compute_updater_deltas(model, params, grads, upd_state, step):
    """Run every layer's OWN updater on its local gradients, returning
    the update tree (what the reference threshold-encodes —
    `SharedTrainingMaster` workers encode post-updater UPDATES, not raw
    gradients, which is what lets a fixed τ ≈ learning-rate scale work)
    plus the advanced per-replica updater state. Mirrors the layer/run
    dispatch of the containers' `_apply_updates` without applying."""
    from deeplearning4j_tpu.common.updaters import Sgd

    deltas, new_upd = {}, {}
    for lk, lgrads in grads.items():
        layer = model.layer_for_key(lk)
        updater = layer.updater or Sgd(1e-3)
        ld, lu = {}, {}
        for pk, g in lgrads.items():
            # mixed policy: grads arrive in compute dtype (bf16) —
            # upcast BEFORE the updater so the deltas the threshold
            # encoder consumes (and the EF identity) live in fp32
            g = g.astype(params[lk][pk].dtype)
            delta, new_s = updater.apply(g, upd_state[lk][pk], step)
            ld[pk] = delta.astype(params[lk][pk].dtype)
            lu[pk] = new_s
        deltas[lk] = ld
        new_upd[lk] = lu
    return deltas, new_upd


def apply_decoded_updates(model, params, dhat):
    """params minus the decoded shared update, then the shared
    post-update constraint pipeline (`_apply_constraints_tree` — one
    copy for the threshold and bucketed dense/rs paths)."""
    new_params = {lk: {pk: params[lk][pk] - d for pk, d in ld.items()}
                  for lk, ld in dhat.items()}
    return _apply_constraints_tree(model, new_params)


def _pmean_state(state, axis):
    """Keep layer state replicated across the data axis: float leaves
    (batchnorm running stats — per-shard batch statistics) are
    averaged, everything else (identical per-replica counters) passes
    through."""
    def avg(a):
        if jnp.issubdtype(jnp.result_type(a), jnp.floating):
            return jax.lax.pmean(a, axis)
        return a
    return jax.tree_util.tree_map(avg, state)


def _exchange_diag(model, diag, axis, *, params_old, upd_old, res_old,
                   tau_old, state_old, params_new, upd_new, res_new,
                   tau_new, state_new, loss):
    """Shared diagnostics tail of every exchange-step body: collect the
    POST-exCHANGE update/param stats (the decoded, applied updates —
    for the bucketed modes these are exactly what left the VJP-hook
    channel), fold the error-feedback residual into the finite flags
    (a non-finite gradient saturates the int encode but poisons the
    residual, so the flags must see it), and under the ``skip``
    watchdog discard the WHOLE step in-graph — params, updater state,
    residual, τ and layer state all keep their previous values, keeping
    the EF identity consistent. Flags are psum'd over the data axis so
    every replica gates identically.

    Returns (params, upd, residual, tau, state, dv)."""
    if diag is None:
        return params_new, upd_new, res_new, tau_new, state_new, {}
    from deeplearning4j_tpu.monitor.diagnostics import keep_finite
    dv, ok = diag.collect(
        "exchange", params_new=params_new, params_old=params_old,
        loss=loss, extra_finite=res_new if res_new else None,
        axis_name=axis)
    if diag.config.watchdog == "skip":
        params_new = keep_finite(ok, params_new, params_old)
        upd_new = keep_finite(ok, upd_new, upd_old)
        if res_new:
            res_new = keep_finite(ok, res_new, res_old)
        if isinstance(tau_new, dict):
            tau_new = jax.tree_util.tree_map(
                lambda n, o: jnp.where(ok, n, o), tau_new, tau_old)
        elif tau_new is not None and tau_old is not None:
            tau_new = jnp.where(ok, tau_new, tau_old)
        state_new = {k: (keep_finite(ok, v, state_old[k])
                         if k in state_old else v)
                     for k, v in state_new.items()}
    return params_new, upd_new, res_new, tau_new, state_new, dv


def make_threshold_core(model, axis: str, cfg: ThresholdConfig, *,
                        n_workers: int, diag=None):
    """Per-replica threshold sync-step body on ALREADY-PACKED trees
    (params/updater-state/residual may contain ``stacked::`` run
    entries — the encoder is elementwise, so a stacked leading axis
    changes nothing; the layer/run dispatch goes through
    `scan_stack.is_run_key` exactly like `_apply_updates`).

    Reference pipeline order (`SharedTrainingMaster` workers): local
    gradients → local gradient normalization → local UPDATER (per-
    replica state, like each worker's own updater) → threshold-encode
    the update with error feedback → integer all-reduce → every replica
    applies the same decoded mean update to its (replicated) params.
    Encoding updates rather than raw gradients is what makes a fixed
    τ ≈ learning-rate scale meaningful and keeps error feedback honest
    under adaptive updaters (Adam's normalization would otherwise wash
    out the residual's accumulated magnitude).

    Loss is the local-shard mean; the returned loss/state are pmean'd
    so every replica exits replicated."""
    from deeplearning4j_tpu.optimize.gradients import (
        apply_gradient_normalization,
    )

    gn = model.conf.gradient_normalization
    gn_t = model.conf.gradient_normalization_threshold
    local_loss = model.local_loss

    def core(params, upd, state, it, residual, tau, x, y, rng):
        rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
        # cast outside value_and_grad: bf16 grads under a mixed policy
        # (compute_updater_deltas upcasts before the EF encode)
        (loss, (new_state, _)), grads = jax.value_and_grad(
            lambda p: local_loss(p, state, x, y, rng),
            has_aux=True)(model.dtype.cast_params(params))
        grads = apply_gradient_normalization(grads, gn, gn_t)
        deltas, new_upd = compute_updater_deltas(model, params, grads,
                                                 upd, it)
        dhat, new_residual, new_tau, sparsity = threshold_exchange(
            deltas, residual, tau, axis, cfg, n_workers=n_workers)
        new_params = apply_decoded_updates(model, params, dhat)
        pstate = _pmean_state(new_state, axis)
        ploss = jax.lax.pmean(loss, axis)
        (new_params, new_upd, new_residual, new_tau, pstate, dv) = \
            _exchange_diag(
                model, diag, axis, params_old=params, upd_old=upd,
                res_old=residual, tau_old=tau, state_old=state,
                params_new=new_params, upd_new=new_upd,
                res_new=new_residual, tau_new=new_tau, state_new=pstate,
                loss=ploss)
        return (new_params, new_upd, pstate,
                new_residual, new_tau, ploss, sparsity, dv)

    return core


def make_threshold_step(model, axis: str, cfg: ThresholdConfig, *,
                        n_workers: int, diag=None):
    """One threshold sync step on per-layer (boundary) trees: packs
    ``stacked::`` runs for params, updater state AND residual at entry,
    unpacks at exit — the residual follows updater state through the
    pack boundary exactly (nn/scan_stack.py contract)."""
    core = make_threshold_core(model, axis, cfg, n_workers=n_workers,
                               diag=diag)

    def step(params, upd, state, it, residual, tau, x, y, rng):
        runs = (model._packed_runs(params)
                if scan_stack.scan_enabled(model.conf) else [])
        if runs:
            params = scan_stack.pack_tree(params, runs)
            upd = scan_stack.pack_tree(upd, runs)
            residual = scan_stack.pack_tree(residual, runs)
        params, upd, state, residual, tau, loss, sparsity, dv = core(
            params, upd, state, it, residual, tau, x, y, rng)
        if runs:
            params = scan_stack.unpack_tree(params, runs)
            upd = scan_stack.unpack_tree(upd, runs)
            residual = scan_stack.unpack_tree(residual, runs)
        return params, upd, state, residual, tau, loss, sparsity, dv

    return step


def make_threshold_multi(model, axis: str, cfg: ThresholdConfig, *,
                         n_workers: int, diag=None):
    """k fused threshold sync steps: ONE `lax.scan` whose carry is
    (params, updater state, layer state, iteration, residual, τ) — the
    residual and τ ride the carry next to the updater state, and the
    ``stacked::`` run packing happens once per PROGRAM, not per step.
    Per-step diag vectors ride the scan ys (one batched transfer per
    listener cadence).

    Scan-carry structure rule (same as the containers'
    `_multi_step_fn`): only state keys present at entry survive across
    fused steps."""
    core = make_threshold_core(model, axis, cfg, n_workers=n_workers,
                               diag=diag)

    def multi(params, upd, state, it0, residual, tau, xs, ys, rngs):
        runs = (model._packed_runs(params)
                if scan_stack.scan_enabled(model.conf) else [])
        if runs:
            params = scan_stack.pack_tree(params, runs)
            upd = scan_stack.pack_tree(upd, runs)
            residual = scan_stack.pack_tree(residual, runs)

        def body(carry, inp):
            params, upd, state, it, residual, tau = carry
            x, y, rng = inp
            (params, upd, new_state, residual, tau, loss, sparsity,
             dv) = core(
                params, upd, state, it, residual, tau, x, y, rng)
            state = {k: new_state.get(k, v) for k, v in state.items()}
            return ((params, upd, state, it + 1, residual, tau),
                    (loss, sparsity, dv))

        carry = (params, upd, state, jnp.asarray(it0, jnp.int32),
                 residual, jnp.asarray(tau, jnp.float32))
        ((params, upd, state, _, residual, tau),
         (losses, sparsities, dvs)) = \
            jax.lax.scan(body, carry, (xs, ys, rngs))
        if runs:
            params = scan_stack.unpack_tree(params, runs)
            upd = scan_stack.unpack_tree(upd, runs)
            residual = scan_stack.unpack_tree(residual, runs)
        return params, upd, state, residual, tau, losses, sparsities, dvs

    return multi


# ------------------------------------------- bucketed (overlapped) exchange
# Bucket = one top-level key of the packed gradient tree: a
# ``stacked::`` run or a single unpacked layer. Each bucket's exchange
# is a `jax.custom_vjp` hook on that bucket's params: backward produces
# the bucket's cotangent the moment its VJP completes, the hook's bwd
# rule emits the collective right there, and XLA schedules it against
# the backward compute still pending for earlier layers. State the
# exchange advances (per-replica updater state, error-feedback
# residual, the [τ, sparsity] control vector) enters the hook as extra
# primal inputs and exits through their cotangents — the only data
# path out of a VJP rule — so the error-feedback identity holds per
# bucket with no post-backward barrier.

def _ctrl(tau):
    """[τ, sparsity] control vector for one bucket (sparsity slot is
    an output: the bwd rule fills it with the achieved encoded
    fraction)."""
    return jnp.stack([jnp.asarray(tau, jnp.float32), jnp.float32(0.0)])


def _elementwise_gn(g, gn, gn_t):
    """The gradient-normalization subset the rs modes support: modes
    that factorize per ELEMENT (so clipping a reduced shard equals
    clipping the reduced full tensor). Norm-based modes need the whole
    layer and are rejected at trainer build time."""
    gn = getattr(gn, "value", gn) or "none"
    if gn == "clip_elementwise_absolute_value":
        return jnp.clip(g, -gn_t, gn_t)
    return g


def rs_supported_gn(conf) -> bool:
    """True when this configuration's gradient normalization factorizes
    per element (the `_rs` modes normalize reduced gradient SHARDS)."""
    gn = getattr(conf, "gradient_normalization", None)
    gn = getattr(gn, "value", gn) or "none"
    return gn in ("none", "clip_elementwise_absolute_value")


def rs_shard_plan(params, n_workers: int, *, specs=None,
                  data_axis: str = "data",
                  min_shard_elems: int = 1024) -> dict:
    """{layer_key: {param_name: bool}} — which leaves the `_rs` modes
    reduce-scatter on their LAST axis. With `specs` (a PartitionSpec
    tree, e.g. `parallel.tensor.fsdp_param_specs` output) a leaf shards
    iff its spec's last entry names `data_axis` — the composition seam
    with FSDP annotations. Without, the same rule fsdp_param_specs
    applies is derived from shapes: last axis divisible by n_workers,
    at least `min_shard_elems` elements."""
    plan = {}
    for lk, lparams in params.items():
        lplan = {}
        for pn, arr in lparams.items():
            if specs is not None:
                spec = specs[lk][pn]
                dims = tuple(spec)
                lplan[pn] = bool(dims and dims[-1] == data_axis)
            else:
                shape = np.shape(arr)
                lplan[pn] = bool(
                    shape and shape[-1] % n_workers == 0
                    and int(np.prod(shape)) >= min_shard_elems)
        plan[lk] = lplan
    return plan


def _plan_for(rs_plan: dict, lk: str) -> dict:
    """Bucket-key lookup into a per-layer rs plan: a ``stacked::`` run
    resolves to its first member (structural identity guarantees every
    member shares the plan)."""
    if scan_stack.is_run_key(lk):
        lk = scan_stack.run_members(lk)[0]
    return rs_plan[lk]




def _threshold_bucket_hook(model, lk: str, axis: str,
                           cfg: ThresholdConfig, n_workers: int,
                           gn, gn_t):
    """Threshold exchange for ONE bucket, emitted inside the backward
    pass. Primal: identity on the bucket's params. VJP: local gradient
    → gradient normalization (every GN mode factorizes per layer key,
    so per-bucket == whole-tree) → per-replica updater → error-feedback
    threshold encode at this bucket's τ → integer all-reduce → decode.
    The advanced updater state / residual / [τ', sparsity] leave
    through the cotangents of the matching primal inputs."""
    from deeplearning4j_tpu.common.updaters import Sgd
    from deeplearning4j_tpu.optimize.gradients import (
        apply_gradient_normalization,
    )

    layer = model.layer_for_key(lk)
    updater = layer.updater or Sgd(1e-3)
    policy = model.dtype

    @jax.custom_vjp
    def hook(p, u, r, c, it_f):
        # primal casts to compute dtype INSIDE the hook: forward runs
        # bf16 under a mixed policy while the saved p stays the fp32
        # master, and the incoming cotangent (the gradient) is bf16
        return policy.cast_params(p)

    def fwd(p, u, r, c, it_f):
        return policy.cast_params(p), (p, u, r, c, it_f)

    def bwd(saved, g):
        p, u, r, c, it_f = saved
        g = apply_gradient_normalization({lk: g}, gn, gn_t)[lk]
        deltas, new_u = {}, {}
        for pk, gg in g.items():
            # bf16 grad → fp32 BEFORE the updater/EF encode, so
            # enc·τ + res' = upd + res holds exactly in fp32
            d, s = updater.apply(gg.astype(p[pk].dtype), u[pk], it_f)
            deltas[pk] = d.astype(p[pk].dtype)
            new_u[pk] = s
        dhat, new_r, new_tau, sp = threshold_exchange(
            deltas, r, c[0], axis, cfg, n_workers=n_workers)
        new_r = jax.tree_util.tree_map(
            lambda nr, rr: nr.astype(rr.dtype), new_r, r)
        return (dhat, new_u, new_r, jnp.stack([new_tau, sp]),
                jnp.zeros_like(it_f))

    hook.defvjp(fwd, bwd)
    return hook


def _dense_bucket_hook(model, lk: str, axis: str,
                       n_workers: int, gn, gn_t, plan_b: dict, *,
                       full_gn: bool):
    """Dense / ZeRO exchange for ONE bucket, emitted inside the
    backward pass. Per leaf: all-reduce-mean (plan False) or
    reduce-scatter-mean over the data axis (plan True — each replica
    then holds only its gradient shard), gradient normalization, the
    updater on exactly what this replica holds (full tensor, or the
    shard with SHARDED updater state — 1/N optimizer memory), update
    the held params, all-gather updated shards. The cotangent of the
    bucket's params is the UPDATED params (constraints applied by the
    caller).

    ``dense`` is this hook with an all-False plan (`full_gn=True`:
    every GN mode factorizes per layer key, so per-bucket GN on the
    reduced full gradient equals whole-tree GN); ``dense_rs`` shards
    by plan with elementwise-only GN (build-time gated). Under
    elementwise GN the two run the SAME per-element op sequence —
    reduce-scatter + all-gather is the same sum as the all-reduce —
    so dense_rs agrees with bucketed dense to the compiler's rounding
    (bit for bit on the first step)."""
    from deeplearning4j_tpu.common.updaters import Sgd
    from deeplearning4j_tpu.optimize.gradients import (
        apply_gradient_normalization,
    )

    layer = model.layer_for_key(lk)
    updater = layer.updater or Sgd(1e-3)
    n = n_workers
    policy = model.dtype

    @jax.custom_vjp
    def hook(p, u, it_f):
        return policy.cast_params(p)

    def fwd(p, u, it_f):
        # saved p = the fp32 master; the hook OUTPUT (and therefore the
        # incoming cotangent) is compute dtype — under mixed_bf16 the
        # gradient collective below moves bf16 on the wire (half the
        # dense fp32 payload), upcast to fp32 only after the reduce
        return policy.cast_params(p), (p, u, it_f)

    def bwd(saved, g):
        p, u, it_f = saved
        idx = jax.lax.axis_index(axis)
        reduced = {}
        for pk, gg in g.items():
            if plan_b.get(pk):
                red = jax.lax.psum_scatter(
                    gg, axis, scatter_dimension=gg.ndim - 1, tiled=True) / n
            else:
                red = jax.lax.pmean(gg, axis)
            reduced[pk] = red.astype(p[pk].dtype)
        if full_gn:
            reduced = apply_gradient_normalization({lk: reduced},
                                                   gn, gn_t)[lk]
        else:
            reduced = {pk: _elementwise_gn(v, gn, gn_t)
                       for pk, v in reduced.items()}
        # fusion barrier: pin the reduce | updater | apply cluster
        # boundaries so the dense and dense_rs programs split into the
        # same elementwise updater clusters. It narrows, but under
        # jax 0.9 no longer closes, the gap: INSIDE a cluster XLA:CPU
        # still picks which product to contract into an FMA from the
        # operand shape (full leaf vs shard) — a <= 1-ulp difference
        # per step (tests/test_gradient_sharing.py states the
        # contract). Costs nothing material: the updater is a vanishing
        # share of step FLOPs and collective scheduling is unaffected.
        reduced = jax.lax.optimization_barrier(reduced)
        new_p, new_u = {}, {}
        for pk, gg in g.items():
            d, su = updater.apply(reduced[pk], u[pk], it_f)
            d = jax.lax.optimization_barrier(d)
            if plan_b.get(pk):
                s = gg.shape[-1] // n
                psh = jax.lax.dynamic_slice_in_dim(
                    p[pk], idx * s, s, axis=gg.ndim - 1)
                new_p[pk] = jax.lax.all_gather(
                    psh - d.astype(psh.dtype), axis,
                    axis=gg.ndim - 1, tiled=True)
            else:
                new_p[pk] = p[pk] - d.astype(p[pk].dtype)
            new_u[pk] = su
        return new_p, new_u, jnp.zeros_like(it_f)

    hook.defvjp(fwd, bwd)
    return hook


def _threshold_rs_bucket_hook(model, lk: str, axis: str,
                              cfg: ThresholdConfig, n_workers: int,
                              gn, gn_t, plan_b: dict, elems: float):
    """Compressed ZeRO exchange for ONE bucket: threshold-encode the
    RAW local gradient (+ error-feedback residual) to the integer wire
    format, reduce-scatter the int tensor, decode the gradient SHARD
    (τ·Σ/N), run the updater on the shard (sharded updater state),
    update the param shard, all-gather updated params. Unlike
    ``threshold``, the updater runs post-decode — so τ lives on the
    GRADIENT scale here, and the residual keeps un-sent gradient (not
    update) mass."""
    from deeplearning4j_tpu.common.updaters import Sgd

    layer = model.layer_for_key(lk)
    updater = layer.updater or Sgd(1e-3)
    n = n_workers
    wdtype = wire_dtype(n)
    inv_n = 1.0 / float(n)
    policy = model.dtype

    @jax.custom_vjp
    def hook(p, u, r, c, it_f):
        return policy.cast_params(p)

    def fwd(p, u, r, c, it_f):
        return policy.cast_params(p), (p, u, r, c, it_f)

    def bwd(saved, g):
        p, u, r, c, it_f = saved
        tau = c[0]
        idx = jax.lax.axis_index(axis)
        new_p, new_u, new_r = {}, {}, {}
        sent_total = jnp.float32(0.0)
        for pk, gg in g.items():
            # bf16 grad → fp32 residual dtype BEFORE the EF encode (a
            # bf16 accumulate would erase the carried residual mass)
            gg = gg.astype(r[pk].dtype)
            acc = gg + r[pk].astype(gg.dtype)
            enc, res_new, sent = encode_leaf(acc, tau, wdtype)
            sent_total = sent_total + sent
            new_r[pk] = res_new.astype(r[pk].dtype)
            scale = tau.astype(gg.dtype) * gg.dtype.type(inv_n)
            if plan_b.get(pk):
                wire = jax.lax.psum_scatter(
                    enc, axis, scatter_dimension=enc.ndim - 1, tiled=True)
                # GN on the REDUCED (decoded) shard — the same
                # post-reduce order dense_rs uses, which is the
                # contract the trainer's elementwise-GN gate states
                gsh = _elementwise_gn(wire.astype(gg.dtype) * scale,
                                      gn, gn_t)
                s = gg.shape[-1] // n
                psh = jax.lax.dynamic_slice_in_dim(
                    p[pk], idx * s, s, axis=gg.ndim - 1)
                d, su = updater.apply(gsh, u[pk], it_f)
                nps = psh - d.astype(psh.dtype)
                new_p[pk] = jax.lax.all_gather(
                    nps, axis, axis=gg.ndim - 1, tiled=True)
            else:
                ghat = _elementwise_gn(
                    jax.lax.psum(enc, axis).astype(gg.dtype) * scale,
                    gn, gn_t)
                d, su = updater.apply(ghat, u[pk], it_f)
                new_p[pk] = p[pk] - d.astype(p[pk].dtype)
            new_u[pk] = su
        sp = jax.lax.pmean(sent_total, axis) / elems
        new_tau = adapt_threshold(tau, sp, cfg)
        return (new_p, new_u, new_r, jnp.stack([new_tau, sp]),
                jnp.zeros_like(it_f))

    hook.defvjp(fwd, bwd)
    return hook


def _apply_constraints_tree(model, new_params):
    """The post-update constraint pipeline `_apply_updates` runs, for
    params the rs hooks already updated: per-layer constraints (never
    on packed runs — `packable_runs` guarantees it), then the global
    max-norm. Replicated math on replicated params."""
    from deeplearning4j_tpu.optimize.gradients import (
        apply_max_norm_constraint,
    )

    out = {}
    for lk, lp in new_params.items():
        layer = model.layer_for_key(lk)
        out[lk] = (lp if scan_stack.is_run_key(lk)
                   else layer.apply_constraints(lp))
    if model.conf.max_norm is not None:
        out = apply_max_norm_constraint(out, model.conf.max_norm)
    return out


def make_bucketed_core(model, axis: str, cfg: ThresholdConfig, *,
                       n_workers: int, mode: str,
                       rs_plan: Optional[dict] = None, diag=None):
    """Per-replica bucketed sync-step body on ALREADY-PACKED trees.
    Uniform signature across the four modes:

        core(params, upd, state, it, residual, tau, x, y, rng)
          -> (params, upd, state, residual, tau, loss, sparsity, dv)

    ``dv`` is the packed diagnostics vector (monitor/diagnostics.py;
    ``{}`` when diagnostics are off): per-layer POST-EXCHANGE
    update/param stats — the applied updates that came back through the
    VJP-hook channel — plus watchdog finite flags.

    `tau` is a PER-BUCKET dict of f32 scalars (empty for the dense
    modes, as is `residual`); `upd` is the per-replica updater view for
    ``threshold`` (each replica its own, PR-4 semantics), the SHARDED
    updater view for the `_rs` modes (ZeRO partitioning), and the
    single replicated tree for ``dense``. `sparsity` is the
    element-weighted mean encoded fraction over buckets (1.0 for
    dense modes — everything is sent)."""
    from deeplearning4j_tpu.optimize.gradients import (
        apply_gradient_normalization,
    )

    gn = model.conf.gradient_normalization
    gn_t = model.conf.gradient_normalization_threshold
    local_loss = model.local_loss

    def core(params, upd, state, it, residual, tau, x, y, rng):
        rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
        it_f = jnp.asarray(it, jnp.float32)

        if mode in ("dense", "dense_rs"):
            no_shard: dict = {}
            hooks = {lk: _dense_bucket_hook(
                model, lk, axis, n_workers, gn, gn_t,
                no_shard if mode == "dense" else _plan_for(rs_plan, lk),
                full_gn=mode == "dense") for lk in params}

            def lf(p, u):
                hp = {lk: hooks[lk](p[lk], u[lk], it_f) for lk in p}
                return local_loss(hp, state, x, y, rng)

            (loss, (new_state, _)), (upd_p, new_upd) = jax.value_and_grad(
                lf, argnums=(0, 1), has_aux=True)(params, upd)
            new_params = _apply_constraints_tree(model, upd_p)
            pstate = _pmean_state(new_state, axis)
            ploss = jax.lax.pmean(loss, axis)
            (new_params, new_upd, _, _, pstate, dv) = _exchange_diag(
                model, diag, axis, params_old=params, upd_old=upd,
                res_old=residual, tau_old=tau, state_old=state,
                params_new=new_params, upd_new=new_upd, res_new={},
                tau_new={}, state_new=pstate, loss=ploss)
            return (new_params, new_upd, pstate,
                    residual, tau, ploss, jnp.float32(1.0), dv)

        if mode == "threshold":
            hooks = {lk: _threshold_bucket_hook(
                model, lk, axis, cfg, n_workers, gn, gn_t)
                for lk in params}
            ctrl = {lk: _ctrl(tau[lk]) for lk in params}

            def lf(p, u, r, c):
                hp = {lk: hooks[lk](p[lk], u[lk], r[lk], c[lk], it_f)
                      for lk in p}
                return local_loss(hp, state, x, y, rng)

            (loss, (new_state, _)), (dhat, new_upd, new_res, new_ctrl) = \
                jax.value_and_grad(lf, argnums=(0, 1, 2, 3),
                                   has_aux=True)(params, upd, residual,
                                                 ctrl)
            new_params = apply_decoded_updates(model, params, dhat)

        elif mode == "threshold_rs":
            hooks = {lk: _threshold_rs_bucket_hook(
                model, lk, axis, cfg, n_workers, gn, gn_t,
                _plan_for(rs_plan, lk), tree_elements(params[lk]))
                for lk in params}
            ctrl = {lk: _ctrl(tau[lk]) for lk in params}

            def lf(p, u, r, c):
                hp = {lk: hooks[lk](p[lk], u[lk], r[lk], c[lk], it_f)
                      for lk in p}
                return local_loss(hp, state, x, y, rng)

            (loss, (new_state, _)), (upd_p, new_upd, new_res, new_ctrl) = \
                jax.value_and_grad(lf, argnums=(0, 1, 2, 3),
                                   has_aux=True)(params, upd, residual,
                                                 ctrl)
            new_params = _apply_constraints_tree(model, upd_p)

        else:
            raise ValueError(f"mode must be one of {MODES}, got {mode!r}")

        new_tau = {lk: new_ctrl[lk][0] for lk in new_ctrl}
        total = tree_elements(params)
        sparsity = sum(new_ctrl[lk][1] * tree_elements(params[lk])
                       for lk in new_ctrl) / total
        pstate = _pmean_state(new_state, axis)
        ploss = jax.lax.pmean(loss, axis)
        (new_params, new_upd, new_res, new_tau, pstate, dv) = \
            _exchange_diag(
                model, diag, axis, params_old=params, upd_old=upd,
                res_old=residual, tau_old=tau, state_old=state,
                params_new=new_params, upd_new=new_upd, res_new=new_res,
                tau_new=new_tau, state_new=pstate, loss=ploss)
        return (new_params, new_upd, pstate,
                new_res, new_tau, ploss, sparsity, dv)

    return core


def _pack_scalar_tree(tree, runs):
    """Per-layer scalar tree (per-bucket τ) packed to bucket keys: a
    run's bucket carries its FIRST member's scalar (unpack broadcasts
    it back, so all members of a run share τ by invariant)."""
    members = {k for keys in runs for k in keys}
    out = {k: v for k, v in tree.items() if k not in members}
    for keys in runs:
        out[scan_stack.run_key(keys)] = tree[keys[0]]
    return out


def _unpack_scalar_tree(tree, runs):
    out = {k: v for k, v in tree.items() if not scan_stack.is_run_key(k)}
    for keys in runs:
        v = tree[scan_stack.run_key(keys)]
        for k in keys:
            out[k] = v
    return out


def init_tau_tree(params, cfg: ThresholdConfig) -> dict:
    """Fresh per-bucket τ state with per-LAYER keys (the checkpoint
    contract: ``stacked::`` packing exists only inside the program)."""
    return {lk: np.float32(cfg.initial_threshold) for lk in params}


def coerce_tau(tau, layer_keys, cfg: Optional[ThresholdConfig] = None):
    """Checkpoint-form τ → per-layer tree: PR-4 checkpoints carry ONE
    scalar (broadcast to every layer), bucketed checkpoints a per-layer
    dict; a missing τ falls back to the config's initial value."""
    keys = list(layer_keys)
    if tau is None:
        cfg = cfg or ThresholdConfig()
        return {lk: np.float32(cfg.initial_threshold) for lk in keys}
    if isinstance(tau, dict):
        cfg = cfg or ThresholdConfig()
        return {lk: np.float32(tau[lk]) if lk in tau
                else np.float32(cfg.initial_threshold) for lk in keys}
    return {lk: np.float32(np.asarray(tau)) for lk in keys}


def ensure_tau_form(tau, per_bucket: bool, params,
                    cfg: ThresholdConfig):
    """The second half of the τ seam (`restore_tau` is the first):
    bring an existing τ state — or None — into the form the CURRENT
    step program needs: a per-bucket `{layer_key: scalar}` tree when
    `per_bucket`, one scalar otherwise. Cross-form inputs coerce
    (scalar broadcasts; a tree collapses to its bucket mean). One
    helper for both trainers so path switches and cross-form
    checkpoint restores can never diverge between them."""
    if tau is None:
        return (init_tau_tree(params, cfg) if per_bucket
                else jnp.float32(cfg.initial_threshold))
    if per_bucket and not isinstance(tau, dict):
        return coerce_tau(np.asarray(tau), params.keys(), cfg)
    if not per_bucket and isinstance(tau, dict):
        return jnp.float32(tau_scalar(tau))
    return tau


def restore_tau(tau):
    """Checkpoint-form τ → trainer state AS WRITTEN: a per-bucket
    {layer_key: scalar} tree (bucketed checkpoints) or one scalar
    (PR-4 single-barrier checkpoints). Coercion to the current path's
    form happens at the next fit (`coerce_tau` / `tau_scalar`); the
    single restore seam keeps both trainers' checkpoint handling from
    diverging."""
    if isinstance(tau, dict):
        return {lk: np.float32(np.asarray(v)) for lk, v in tau.items()}
    return jnp.float32(np.asarray(tau))


def tau_scalar(tau) -> float:
    """Observability scalar for a τ state of either form (scalar or
    per-layer tree): the mean over buckets. Tree leaves are stacked on
    device and fetched in ONE transfer — a per-leaf float() would cost
    one host round-trip per layer per step on the eager-listener
    path."""
    if isinstance(tau, dict):
        if not tau:
            return 0.0
        vals = np.asarray(jnp.stack([jnp.asarray(v)
                                     for v in tau.values()]))
        return float(vals.mean())
    return float(np.asarray(tau))


def make_bucketed_step(model, axis: str, cfg: ThresholdConfig, *,
                       n_workers: int, mode: str,
                       rs_plan: Optional[dict] = None, diag=None):
    """One bucketed sync step on per-layer (boundary) trees: packs
    ``stacked::`` runs for params, updater state, residual AND the
    per-bucket τ at entry, unpacks at exit. Signature matches
    `make_threshold_step` with τ as a per-layer scalar tree (empty
    dicts for residual/τ in the dense modes)."""
    core = make_bucketed_core(model, axis, cfg, n_workers=n_workers,
                              mode=mode, rs_plan=rs_plan, diag=diag)
    threshold_state = mode in ("threshold", "threshold_rs")

    def step(params, upd, state, it, residual, tau, x, y, rng):
        runs = (model._packed_runs(params)
                if scan_stack.scan_enabled(model.conf) else [])
        if runs:
            params = scan_stack.pack_tree(params, runs)
            upd = scan_stack.pack_tree(upd, runs)
            if threshold_state:
                residual = scan_stack.pack_tree(residual, runs)
                tau = _pack_scalar_tree(tau, runs)
        params, upd, state, residual, tau, loss, sparsity, dv = core(
            params, upd, state, it, residual, tau, x, y, rng)
        if runs:
            params = scan_stack.unpack_tree(params, runs)
            upd = scan_stack.unpack_tree(upd, runs)
            if threshold_state:
                residual = scan_stack.unpack_tree(residual, runs)
                tau = _unpack_scalar_tree(tau, runs)
        return params, upd, state, residual, tau, loss, sparsity, dv

    return step


def make_bucketed_multi(model, axis: str, cfg: ThresholdConfig, *,
                        n_workers: int, mode: str,
                        rs_plan: Optional[dict] = None, diag=None):
    """k fused bucketed sync steps: ONE `lax.scan` whose carry is
    (params, updater state, layer state, iteration, residual, τ-tree)
    — the per-bucket residual/τ ride the carry next to the updater
    state, and the ``stacked::`` packing happens once per PROGRAM.
    Per-step diag vectors ride the scan ys. Bit-identical to k per-step
    calls (same rng folds, same counters)."""
    core = make_bucketed_core(model, axis, cfg, n_workers=n_workers,
                              mode=mode, rs_plan=rs_plan, diag=diag)
    threshold_state = mode in ("threshold", "threshold_rs")

    def multi(params, upd, state, it0, residual, tau, xs, ys, rngs):
        runs = (model._packed_runs(params)
                if scan_stack.scan_enabled(model.conf) else [])
        if runs:
            params = scan_stack.pack_tree(params, runs)
            upd = scan_stack.pack_tree(upd, runs)
            if threshold_state:
                residual = scan_stack.pack_tree(residual, runs)
                tau = _pack_scalar_tree(tau, runs)
        tau = jax.tree_util.tree_map(
            lambda t: jnp.asarray(t, jnp.float32), tau)

        def body(carry, inp):
            params, upd, state, it, residual, tau = carry
            x, y, rng = inp
            (params, upd, new_state, residual, tau, loss,
             sparsity, dv) = core(params, upd, state, it, residual,
                                  tau, x, y, rng)
            state = {k: new_state.get(k, v) for k, v in state.items()}
            return ((params, upd, state, it + 1, residual, tau),
                    (loss, sparsity, dv))

        carry = (params, upd, state, jnp.asarray(it0, jnp.int32),
                 residual, tau)
        ((params, upd, state, _, residual, tau),
         (losses, sps, dvs)) = \
            jax.lax.scan(body, carry, (xs, ys, rngs))
        if runs:
            params = scan_stack.unpack_tree(params, runs)
            upd = scan_stack.unpack_tree(upd, runs)
            if threshold_state:
                residual = scan_stack.unpack_tree(residual, runs)
                tau = _unpack_scalar_tree(tau, runs)
        return params, upd, state, residual, tau, losses, sps, dvs

    return multi


def bucket_plan(model) -> list:
    """Ordered (bucket_key, [member layer keys]) list of the model's
    exchange buckets in FORWARD order — packed ``stacked::`` runs plus
    singleton layers. Reversed, this is the backward ISSUE order the
    comm-overlap accounting in benchtools/hlo_cost.py walks (the last
    layer's bucket exchanges first)."""
    params = model.params
    runs = (model._packed_runs(params)
            if scan_stack.scan_enabled(model.conf) else [])
    members = {k for keys in runs for k in keys}
    entries = []
    for keys in runs:
        entries.append((scan_stack.run_key(keys), list(keys)))
    for lk in params:
        if lk not in members:
            entries.append((lk, [lk]))

    if hasattr(model, "layers"):
        order = {str(i): i for i in range(len(model.layers))}
    else:
        order = {name: i for i, name in enumerate(model.conf.topo_order)}
    entries.sort(key=lambda e: min(order.get(m, 0) for m in e[1]))
    return entries


# ------------------------------------------------------ comm-bytes accounting
def exchange_wire_bytes(params, mode: str, *, n_workers: int = 2,
                        rs_plan: Optional[dict] = None,
                        grad_dtype=None) -> float:
    """Host-side accounting of one step's gradient-exchange payload
    per replica (collective operand bytes): gradients in their ACTUAL
    dtype for dense (`grad_dtype` — the policy's compute dtype; bf16
    under mixed_bf16 halves the dense wire), the integer wire tensors
    + the sent-count/loss scalars for threshold. The `_rs` modes count
    the gradient reduce-scatter operand (grad-dtype or the int wire
    tensor) plus the updated-param all-gather operand (one PARAM-dtype
    shard per replica — the fp32 master is what gets gathered).
    Static — no device work, so the trainers can count every step
    without a sync (the FLOP-accounting discipline applied to
    communication)."""
    def leaf_itemsize(l):
        # shape/dtype only — a leaf may be a multi-process global array
        # whose VALUE no single host can fetch (TP-sharded params after
        # a previous fit); never materialize it
        dt = getattr(l, "dtype", None)
        return jnp.dtype(dt if dt is not None else type(l)).itemsize

    grad_item_of = leaf_itemsize
    if grad_dtype is not None:
        gsize = jnp.dtype(grad_dtype).itemsize

        def grad_item_of(l):  # noqa: F811 — floating grads ride
            dt = getattr(l, "dtype", None)  # grad_dtype, ints as-is
            dt = jnp.dtype(dt if dt is not None else type(l))
            return gsize if jnp.issubdtype(dt, jnp.floating) else dt.itemsize

    if mode == "dense":
        return float(sum(
            int(np.prod(np.shape(l))) * grad_item_of(l)
            for l in jax.tree_util.tree_leaves(params)))
    if mode in RS_MODES:
        if rs_plan is None:
            rs_plan = rs_shard_plan(params, n_workers)
        wire_item = (jnp.dtype(wire_dtype(n_workers)).itemsize
                     if mode == "threshold_rs" else None)
        total = 8.0 if mode == "threshold_rs" else 0.0
        for lk, lparams in params.items():
            for pn, arr in lparams.items():
                e = float(int(np.prod(np.shape(arr))))
                grad_item = (wire_item if wire_item is not None
                             else grad_item_of(arr))
                total += e * grad_item
                if rs_plan[lk][pn]:
                    # updated-PARAM shard all-gather: master dtype
                    total += (e / n_workers) * leaf_itemsize(arr)
        return total
    itemsize = jnp.dtype(wire_dtype(n_workers)).itemsize
    # + sent-count pmean (f32) + loss pmean (f32)
    return tree_elements(params) * itemsize + 8.0


def record_exchange(mode: str, wire_bytes: float, dense_bytes: float,
                    steps: int = 1, *, trainer: str = "parallel"):
    """Trainer-side monitor counters: exchanged bytes + steps per mode,
    and the wire compression ratio gauge. No-op (and no device sync —
    all inputs are host floats) when monitoring is disabled."""
    from deeplearning4j_tpu import monitor
    if not monitor.is_enabled():
        return
    reg = monitor.registry()
    reg.counter("gradient_exchange_bytes_total",
                help="gradient all-reduce payload bytes per replica",
                mode=mode, trainer=trainer).inc(wire_bytes * steps)
    reg.counter("gradient_exchange_steps_total",
                help="sync steps per gradient-sharing mode",
                mode=mode, trainer=trainer).inc(steps)
    if wire_bytes > 0:
        reg.gauge("gradient_sharing_compression_ratio",
                  help="dense/wire bytes of the gradient exchange",
                  trainer=trainer).set(dense_bytes / wire_bytes)


def record_threshold_stats(tau: float, sparsity: float, *,
                           trainer: str = "parallel"):
    """Gauge the adaptive controller's observables (called with values
    already read back to host — never forces a sync itself)."""
    from deeplearning4j_tpu import monitor
    if not monitor.is_enabled():
        return
    reg = monitor.registry()
    reg.gauge("gradient_sharing_threshold",
              help="current adaptive threshold tau",
              trainer=trainer).set(float(tau))
    reg.gauge("gradient_sharing_sparsity",
              help="achieved encoded fraction of the last exchange",
              trainer=trainer).set(float(sparsity))


# ------------------------------------------------- AOT analysis seam (jaxpr)
def exchange_jaxpr(params, mode: str, n_workers: int, *,
                   axis: str = "data", cfg: Optional[ThresholdConfig] = None,
                   rs_plan: Optional[dict] = None, grad_dtype=None):
    """ClosedJaxpr of ONE gradient exchange (dense pmean vs threshold
    encode→int-psum→decode) over an **AbstractMesh** — traceable on a
    single-device host with no mesh at all, which is what lets
    `benchtools/hlo_cost.py` count dense-vs-threshold comm-bytes
    device-free. Gradient avals are taken from
    `params` (shapes; floating leaves take `grad_dtype` when given —
    the mixed policy's compute dtype, so the analyzed program carries
    the REAL bf16 wire)."""
    from functools import partial

    from jax.sharding import AbstractMesh, PartitionSpec as P


    cfg = cfg or ThresholdConfig()
    mesh = AbstractMesh((int(n_workers),), (axis,))
    # per-replica operands enter with a leading replica axis (the
    # rep-spec representation the trainers use for residuals)
    def leaf_dtype(a):
        # shape/dtype only — a leaf may be a non-fetchable global array
        # (TP-sharded params after a multi-process fit), and a host
        # round-trip per leaf would be waste even when legal
        dt = getattr(a, "dtype", None)
        if dt is None:
            dt = np.asarray(a).dtype
        return jnp.dtype(dt)

    def aval_r(a, dtype_override=None):
        dt = leaf_dtype(a)
        if dtype_override is not None and jnp.issubdtype(dt, jnp.floating):
            dt = jnp.dtype(dtype_override)
        return jax.ShapeDtypeStruct((int(n_workers),) + tuple(np.shape(a)),
                                    dt)
    # the grad-dtype override shapes the wire only where the wire IS
    # the gradient (dense / dense_rs); the threshold modes encode fp32
    # accumulators (post-upcast) to an int wire either way
    dense_like = mode in ("dense", "dense_rs")
    grads_r = jax.tree_util.tree_map(
        lambda a: aval_r(a, grad_dtype if dense_like else None), params)
    param_dtypes = jax.tree_util.tree_map(leaf_dtype, params)
    strip = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)
    expand = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)
    rep = P(axis)

    if mode == "dense":
        @partial(shard_map, mesh=mesh, in_specs=(rep,), out_specs=rep,
                 check_vma=False)
        def ex(g_r):
            return expand(dense_exchange(strip(g_r), axis))

        return jax.make_jaxpr(ex)(grads_r)

    if mode in RS_MODES:
        plan = rs_plan if rs_plan is not None else rs_shard_plan(
            params, n_workers)
        wdtype = wire_dtype(n_workers)
        inv_n = 1.0 / float(n_workers)

        @partial(shard_map, mesh=mesh, in_specs=(rep,), out_specs=rep,
                 check_vma=False)
        def ex(g_r):
            g = strip(g_r)
            tau = jnp.float32(cfg.initial_threshold)
            out = {}
            for lk, lgrads in g.items():
                lout = {}
                for pn, gg in lgrads.items():
                    if mode == "threshold_rs":
                        enc, _, _ = encode_leaf(gg, tau, wdtype)
                    else:
                        enc = gg
                    if plan[lk][pn]:
                        sh = jax.lax.psum_scatter(
                            enc, axis, scatter_dimension=enc.ndim - 1,
                            tiled=True)
                        nsh = (sh.astype(gg.dtype) * gg.dtype.type(inv_n)
                               ).astype(param_dtypes[lk][pn])
                        lout[pn] = jax.lax.all_gather(
                            nsh, axis, axis=nsh.ndim - 1, tiled=True)
                    else:
                        lout[pn] = (jax.lax.psum(enc, axis)
                                    .astype(gg.dtype)
                                    * gg.dtype.type(inv_n))
                out[lk] = lout
            return expand(out)

        return jax.make_jaxpr(ex)(grads_r)

    if mode != "threshold":
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")

    @partial(shard_map, mesh=mesh, in_specs=(rep, rep, P()),
             out_specs=(rep, rep, P(), P()), check_vma=False)
    def ex(g_r, r_r, tau):
        ghat, res, tau, sp = threshold_exchange(
            strip(g_r), strip(r_r), tau, axis, cfg, n_workers=n_workers)
        return expand(ghat), expand(res), tau, sp

    tau0 = jax.ShapeDtypeStruct((), jnp.float32)
    return jax.make_jaxpr(ex)(grads_r, grads_r, tau0)

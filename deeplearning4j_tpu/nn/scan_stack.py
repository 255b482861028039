"""Scan-over-layers compilation + generalized rematerialization.

Whole-program XLA compilation is the premise of the TPU port (Fischer &
Saba, arXiv:1810.09868), but a Python-unrolled layer loop makes the XLA
program — and therefore trace time, compile time, and code size — grow
linearly with depth. TensorFlow's deployment experience (Abadi et al.,
arXiv:1605.08695) is that a loop-ROLLED graph representation is what
keeps compile cost bounded at production depth. This module brings that
to both containers:

- `build_layer_plan` / `build_graph_plan` detect **maximal runs of
  structurally identical layers** (same class, same config dict, same
  param-table shapes/dtypes; no input preprocessor, persistent state,
  or carry threading inside the run),
- `scan_forward` drives such a run with ONE `jax.lax.scan` over the
  run's params stacked along a leading axis — the block body is traced
  and compiled once regardless of depth, and gradients flow back to the
  per-layer param tree through the stack op,
- `pack_tree` moves that stacking to the TRAIN-STEP boundary, for the
  COMPUTE-dtype copy alone: the containers cast the per-layer params
  and stack each run of the cast tree into one entry
  (``stacked::<keys>``) that the loss is differentiated against, so
  forward scan and backward stay depth-independent, gradients come out
  stacked, and no per-step stack/unstack equations sit between them.
  The float32 masters and the updater state are never stacked: the
  updater walk hands layer `i` slice `i` of a run's stacked gradient
  and updates each leaf where it lies (`Trainable._apply_updates`);
  `unpack_tree` slices a stacked tree back to per-layer keys (the
  diagnostics' view of a run's gradients, the gradient-sharing cores),
- `remat_wrap` / `effective_remat_policy` generalize rematerialization
  from the transformer-only `remat` flag into a per-layer
  ``remat_policy`` conf field (``none | full | dots_saveable`` via
  `jax.checkpoint`), applied by the containers in BOTH the scan body
  and the unrolled fallback.

Numerics contract: the scan body executes the run's first layer
(`template`) with each layer's own params and the SAME per-layer rng
fold indices the unrolled loop uses, so the scan path produces the same
loss and gradients as the unrolled path on identical inits (fp
reassociation aside). Layers opt out of stacking with the class
attribute ``stackable_params = False`` (e.g. MoE, whose forward emits
fresh state keys the scan carry cannot thread).

Opt-outs: ``scan_layers=False`` on the configuration, or the
``DL4J_SCAN_LAYERS=0`` environment override (benchmark A/B without
touching code).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# minimum run length worth rolling into a scan: a 2-layer "run" still
# compiles one body instead of two
MIN_RUN = 2

REMAT_POLICIES = ("none", "full", "dots_saveable")

WEIGHT_NOISE_FOLD = 0x5EED  # the containers' per-layer weight-noise fold


def validate_remat_policy(policy) -> Optional[str]:
    """Normalize/validate a remat_policy value (None and "none" are the
    same: no rematerialization)."""
    if policy is None:
        return None
    if policy not in REMAT_POLICIES:
        raise ValueError(
            f"remat_policy must be one of {REMAT_POLICIES} (or None); "
            f"got {policy!r}")
    return None if policy == "none" else policy


def effective_remat_policy(layer) -> Optional[str]:
    """The policy a container should apply for this layer: the explicit
    ``remat_policy`` field, else the legacy transformer ``remat`` bool
    mapped to "full"."""
    policy = validate_remat_policy(getattr(layer, "remat_policy", None))
    if policy is not None:
        return policy
    return "full" if getattr(layer, "remat", False) else None


def remat_wrap(fn, policy: Optional[str], *, prevent_cse: bool = True):
    """Wrap `fn` with `jax.checkpoint` per the policy. Callers pass
    ``prevent_cse=False`` for `lax.scan` bodies (the scan carry already
    prevents the CSE the flag guards against — the standard
    scan-over-layers remat idiom)."""
    policy = validate_remat_policy(policy)
    if policy is None:
        return fn
    if policy == "full":
        return jax.checkpoint(fn, prevent_cse=prevent_cse)
    return jax.checkpoint(fn, prevent_cse=prevent_cse,
                          policy=jax.checkpoint_policies.dots_saveable)


def layer_forward(layer, params, state, h, *, train, rng, mask=None):
    """`layer.forward` with the layer's remat policy applied (training
    only) — the unrolled-path counterpart of the scan body's wrap.
    The mask rides the closure (no gradients flow through it)."""
    policy = effective_remat_policy(layer) if train else None
    if policy is None:
        return layer.forward(params, state, h, train=train, rng=rng,
                             mask=mask)

    def body(p, s, hh, r):
        return layer.forward(p, s, hh, train=True, rng=r, mask=mask)

    return remat_wrap(body, policy)(params, state, h, rng)


def layer_forward_with_carry(layer, params, state, h, carry, *, train,
                             rng, mask=None):
    """`layer.forward_with_carry` with the layer's remat policy applied
    (training only) — the carry-threading (TBPTT) counterpart of
    `layer_forward`, so recurrent layers of ANY type honor
    `remat_policy`, not just transformers."""
    policy = effective_remat_policy(layer) if train else None
    if policy is None:
        return layer.forward_with_carry(params, state, h, carry,
                                        train=train, rng=rng, mask=mask)

    def body(p, s, hh, c, r):
        return layer.forward_with_carry(p, s, hh, c, train=True, rng=r,
                                        mask=mask)

    return remat_wrap(body, policy)(params, state, h, carry, rng)


# ----------------------------------------------------------- run detection
def scan_enabled(conf) -> bool:
    """Config-level toggle with environment override (DL4J_SCAN_LAYERS=0
    disables globally — benchmark A/B without code changes)."""
    env = os.environ.get("DL4J_SCAN_LAYERS")
    if env is not None and env.strip().lower() in ("0", "false", "off", "no"):
        return False
    return bool(getattr(conf, "scan_layers", True))


def consumes_token_ids(layer) -> bool:
    """True when this layer treats its input as token IDS (embedding
    gathers), unwrapping frozen/transfer-learning wrappers — the guard
    the mixed-precision input cast consults: a bf16 round corrupts
    float-carried ids above 256. Ids carried as INT arrays are always
    safe (non-floating inputs are never cast)."""
    inner = getattr(layer, "layer", None)
    if inner is not None and getattr(layer, "layer_name", "") == "frozen":
        return consumes_token_ids(inner)
    return getattr(layer, "layer_name", "") == "embedding"


def layer_signature(layer, lparams) -> Tuple:
    """Structural identity of a layer instance: full config equality
    (not just class — two blocks with different head counts must not
    merge) plus param-table shapes/dtypes."""
    try:
        conf = json.dumps(layer.to_dict(), sort_keys=True, default=str)
    except Exception:  # noqa: BLE001 — unserializable config: never merge
        conf = f"id:{id(layer)}"
    shapes = tuple(sorted(
        (pn, tuple(np.shape(a)), str(getattr(a, "dtype", "?")))
        for pn, a in lparams.items()))
    return (type(layer).__name__, conf, shapes)


def stackable(layer, lparams) -> bool:
    """Can this layer participate in a stacked-params scan run? The
    stackable-params contract: has params, no persistent state
    (`init_state` empty — running stats can't thread a constant-
    structure scan carry), and does not opt out via
    ``stackable_params = False`` (layers whose forward emits fresh
    state keys, e.g. MoE aux losses)."""
    if not getattr(layer, "stackable_params", True):
        return False
    if not lparams:
        return False
    try:
        if layer.init_state(jnp.float32):
            return False
    except Exception:  # noqa: BLE001 — exotic init_state: stay unrolled
        return False
    return True


def build_layer_plan(layers: Sequence, params: Dict[str, dict],
                     preprocessors: Dict[int, Any], n: int,
                     min_run: int = MIN_RUN) -> List[Tuple]:
    """Segment plan for a sequential stack: ``('layer', i)`` entries
    interleaved with ``('scan', start, stop)`` maximal homogeneous
    runs. An input preprocessor at the run START is fine (it applies
    before the run); one INSIDE a run breaks it."""
    segments: List[Tuple] = []
    i = 0
    while i < n:
        layer = layers[i]
        lp = params.get(str(i), {})
        if not stackable(layer, lp):
            segments.append(("layer", i))
            i += 1
            continue
        sig = layer_signature(layer, lp)
        j = i + 1
        while (j < n and j not in preprocessors
               and stackable(layers[j], params.get(str(j), {}))
               and layer_signature(layers[j], params.get(str(j), {})) == sig):
            j += 1
        if j - i >= min_run:
            segments.append(("scan", i, j))
        else:
            segments.extend(("layer", t) for t in range(i, j))
        i = j
    return segments


def build_graph_plan(conf, params: Dict[str, dict], output_layer_names,
                     min_run: int = MIN_RUN) -> Tuple[Dict[str, List[str]],
                                                      set]:
    """Chain detection for the DAG container: maximal single-consumer
    chains of structurally identical layer nodes in topo order.
    Returns ``(chains, members)`` where ``chains`` maps each chain-head
    node name to the ordered member list and ``members`` is the set of
    non-head members the walk must skip."""
    consumers: Dict[str, List[str]] = {n: [] for n in conf.nodes}
    for name, node in conf.nodes.items():
        for src in node.inputs:
            consumers[src].append(name)
    outputs = set(conf.network_outputs)
    out_names = set(output_layer_names)

    def chainable(node):
        return (node.kind == "layer" and node.preprocessor is None
                and node.name not in out_names
                and stackable(node.layer, params.get(node.name, {})))

    chains: Dict[str, List[str]] = {}
    members: set = set()
    for name in conf.topo_order:
        if name in members or name in chains:
            continue
        node = conf.nodes[name]
        if not chainable(node):
            continue
        sig = layer_signature(node.layer, params.get(name, {}))
        chain = [name]
        cur = node
        while True:
            outs = consumers[cur.name]
            # a network output is consumed externally too — can't be an
            # interior chain link
            if len(outs) != 1 or cur.name in outputs:
                break
            nxt = conf.nodes[outs[0]]
            if nxt.inputs != [cur.name] or not chainable(nxt):
                break
            if layer_signature(nxt.layer,
                               params.get(nxt.name, {})) != sig:
                break
            chain.append(nxt.name)
            cur = nxt
        if len(chain) >= min_run:
            chains[name] = chain
            members.update(chain[1:])
    return chains, members


# ------------------------------------------------------------ scan forward
def mask_invariant(layer, mask) -> bool:
    """True when the run's layers propagate the mask unchanged (the
    base `forward_mask` returns the identical object) — the condition
    for closing the mask over the scan body."""
    if mask is None:
        return True
    try:
        return layer.forward_mask(mask, None) is mask
    except Exception:  # noqa: BLE001
        return False


def stack_params(run_params: Sequence[dict]):
    """Stack a run's per-layer param dicts along a new leading axis."""
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *run_params)


def unstack_entry(stacked, n: int) -> List[dict]:
    """Per-layer param dicts out of a stacked run entry (inverse of
    `stack_params`)."""
    return [jax.tree_util.tree_map(lambda a, j=j: a[j], stacked)
            for j in range(n)]


def scan_forward(template, stacked, h, *, train: bool, rng,
                 fold_ids: Sequence[int], mask=None,
                 collect_stats: bool = False):
    """Run a homogeneous layer run as one `lax.scan` over its stacked
    params (leading axis = layer position).

    `fold_ids` are the SAME per-layer rng fold indices the unrolled
    loop uses (`jax.random.fold_in(rng, i)`), so dropout/weight-noise
    draws are bit-identical to the unrolled path. The template's remat
    policy wraps the scan body (`prevent_cse=False` — the scan idiom),
    so activation memory stays O(one block) + O(depth * residual).

    ``collect_stats=True`` (the in-graph diagnostics seam —
    monitor/diagnostics.py) emits each scanned layer's activation
    mean/std/dead-fraction through the scan ys and returns
    ``(h, stats)`` with ``stats`` shaped ``[run_length, 3]`` — the
    per-layer view of a packed run WITHOUT unpacking it."""
    policy = effective_remat_policy(template) if train else None

    def out(hh):
        if not collect_stats:
            return None
        from deeplearning4j_tpu.monitor.diagnostics import activation_stats
        return activation_stats(hh)

    if rng is not None:
        keys = jnp.stack([jax.random.fold_in(rng, i) for i in fold_ids])

        def body(hh, sl):
            p, lrng = sl
            lp = template.apply_weight_noise(
                p, train, jax.random.fold_in(lrng, WEIGHT_NOISE_FOLD))
            hh, _ = template.forward(lp, {}, hh, train=train, rng=lrng,
                                     mask=mask)
            return hh, out(hh)

        xs = (stacked, keys)
    else:

        def body(hh, p):
            hh, _ = template.forward(p, {}, hh, train=train, rng=None,
                                     mask=mask)
            return hh, out(hh)

        xs = stacked
    body = remat_wrap(body, policy, prevent_cse=False)
    h, ys = jax.lax.scan(body, h, xs)
    return (h, ys) if collect_stats else h


# -------------------------------------------------- boundary pack/unpack
# The fused train step differentiates a tree whose homogeneous runs are
# each ONE stacked entry instead of per-layer keys: the compute-dtype
# copy, stacked after the cast. Forward and backward operate on the
# stacked representation; the masters and the updater state keep their
# per-layer leaves, and the updater walk reads a run's stacked gradient
# a slice a layer. The gradient-sharing cores (parallel/
# gradient_sharing.py) still pack and unpack whole trees round their
# exchange programs.

RUN_PREFIX = "stacked::"

# gradient-normalization modes that are elementwise (or no-ops) and
# therefore see identical numbers through a stacked leading axis; the
# per-layer-norm modes must not be applied to a packed tree
SAFE_PACK_GN = ("none", "clip_elementwise_absolute_value")


def run_key(keys: Sequence[str]) -> str:
    return RUN_PREFIX + ",".join(keys)


def is_run_key(key: str) -> bool:
    return isinstance(key, str) and key.startswith(RUN_PREFIX)


def run_members(key: str) -> List[str]:
    return key[len(RUN_PREFIX):].split(",")


def packable_runs(conf, runs_with_templates) -> List[List[str]]:
    """Filter runs eligible for boundary packing. Per-layer-norm
    gradient normalization, the global max-norm constraint, and
    per-layer constraints all compute norms whose semantics a stacked
    leading axis would change — those configs keep the per-layer
    update path (the forward still scans)."""
    gn = getattr(conf, "gradient_normalization", None)
    gn = getattr(gn, "value", gn) or "none"
    if gn not in SAFE_PACK_GN or getattr(conf, "max_norm", None) is not None:
        return []
    return [list(keys) for keys, template in runs_with_templates
            if not template.constraints]


def pack_tree(tree: Dict[str, Any], runs: Sequence[Sequence[str]]):
    """Replace each run's per-layer entries with one stacked entry
    keyed ``stacked::<member,member,...>``."""
    members = {k for keys in runs for k in keys}
    out = {k: v for k, v in tree.items() if k not in members}
    for keys in runs:
        out[run_key(keys)] = stack_params([tree[k] for k in keys])
    return out


def unpack_tree(tree: Dict[str, Any], runs: Sequence[Sequence[str]]):
    """Inverse of `pack_tree`: split stacked run entries back into the
    per-layer tree the container owns."""
    out = {k: v for k, v in tree.items() if not is_run_key(k)}
    for keys in runs:
        stacked = tree[run_key(keys)]
        for j, k in enumerate(keys):
            out[k] = jax.tree_util.tree_map(lambda a, j=j: a[j], stacked)
    return out

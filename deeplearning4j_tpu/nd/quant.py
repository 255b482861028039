"""Int8 weight-only inference quantization.

Autoregressive decode is memory-bandwidth-bound: every emitted token
re-reads the full weight set from HBM while the matmuls themselves are
skinny (arXiv:2606.15870 frames per-chip bandwidth as the serving
ceiling across TPU generations; the TensorFlow system paper treats
quantized inference as a deployment-tier concern the framework owns).
Storing the transformer's matmul weights as int8 cuts the bytes moved
per decoded token ~4x without touching the training path.

Scheme — per-output-channel symmetric int8:

    scale[c] = max(|W[:, c]|) / 127          (fp32, one per out channel)
    q[:, c]  = round(W[:, c] / scale[c])     (int8, clipped to [-127,127])

Dequantization happens INSIDE the matmul, after the int8 read:

    y = (x @ q.astype(compute_dtype)) * scale

which is exact because a per-output-channel scale commutes with the
contraction — the jitted decode/prefill programs read int8 from HBM,
upcast in registers, and compute in the policy's compute dtype. The
quantized weight rides the params tree as a `QuantizedTensor` pytree
node (two leaves: `q` int8, `scale` fp32), so jit/donation/tree_map
plumbing see ordinary arrays and the layer matmul seams
(`MultiHeadAttention._project`, `DenseLayer.pre_output`, the
transformer FF) dispatch on the leaf type at trace time — zero
overhead for plain fp weights.

What quantizes: matmul weights the layer declares via
`Layer.quantizable_weights()` — attention qkv/out projections, the
transformer FF pair, dense/output heads (tied or not), and the
embedding table (its gather reads ONE int8 row and scales after the
read — exact, and tied heads share it with the output matmul). What
does NOT: biases and LayerNorm gain/shift (tiny, numerically
load-bearing).

Parity contract (docs/SERVING.md): greedy int8 decode must agree
top-1 with fp decode over full generations on the zoo LM, with
bounded logit error — test-enforced, and the serving ledger proves
the weight-HBM-byte reduction on the real decode program.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

INT8_MAX = 127


class QuantizedTensor:
    """A per-output-channel symmetric int8 weight: `q` int8 with the
    original shape, `scale` fp32 broadcastable over the last axis.
    Registered as a pytree node, so params trees holding it flow
    through jit/tree_map/donation unchanged."""

    __slots__ = ("q", "scale")

    def __init__(self, q, scale):
        self.q = q
        self.scale = scale

    # array-ish surface (shape checks, aval-byte accounting)
    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    @property
    def dtype(self):
        return self.q.dtype

    def __repr__(self):
        return (f"QuantizedTensor(shape={tuple(self.shape)}, "
                f"q={self.q.dtype}, scale={self.scale.dtype})")


def _qt_flatten(t):
    return (t.q, t.scale), None


def _qt_unflatten(aux, children):
    return QuantizedTensor(*children)


jax.tree_util.register_pytree_node(QuantizedTensor, _qt_flatten,
                                   _qt_unflatten)


def quantize(w, *, axis: int = -1) -> QuantizedTensor:
    """Per-output-channel symmetric int8 quantization of a matmul
    weight. `axis` is the OUTPUT-channel axis (last, for the
    framework's `[n_in, n_out]` convention) — the one axis whose scale
    commutes with the contraction."""
    w = jnp.asarray(w)
    if w.ndim < 2:
        raise ValueError(
            f"quantize() wants a matmul weight (ndim >= 2); got shape "
            f"{tuple(w.shape)} — biases/gains stay floating")
    axis = axis % w.ndim
    reduce_axes = tuple(i for i in range(w.ndim) if i != axis)
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=reduce_axes,
                   keepdims=True)
    # an all-zero channel must not divide by zero; its q rounds to 0
    # either way, so any positive scale is exact
    scale = jnp.where(amax > 0, amax, 1.0) / INT8_MAX
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale),
                 -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return QuantizedTensor(q, scale.astype(jnp.float32))


def dequantize(t: QuantizedTensor, dtype=jnp.float32):
    """Materialize the fp weight (tests / debugging; the matmul seam
    never calls this — it scales AFTER the contraction)."""
    return (t.q.astype(jnp.float32) * t.scale).astype(dtype)


# weight-wrapper extension point: other pytree weight wrappers (the
# LoRA adapter node in tenancy/lora.py) register their own matmul here
# at import time, so every layer seam picks them up without this leaf
# module importing anyone. Dispatch still happens at trace time; plain
# fp weights never reach the loop.
_MATMUL_EXTENSIONS: list = []


def register_matmul_extension(cls, fn):
    """Register `fn(x, w)` for weight leaves of type `cls` in the
    `matmul` seam. Last registration of a class wins (idempotent under
    module reload)."""
    global _MATMUL_EXTENSIONS
    _MATMUL_EXTENSIONS = [(c, f) for c, f in _MATMUL_EXTENSIONS
                          if c is not cls]
    _MATMUL_EXTENSIONS.append((cls, fn))


def matmul(x, w):
    """`x @ w` with dequantize-inside-matmul when `w` is quantized —
    the ONE seam every quantizable layer matmul routes through. The
    isinstance dispatch happens at trace time: plain fp weights take
    the literal `x @ w` path, so training programs are untouched."""
    if isinstance(w, QuantizedTensor):
        y = x @ w.q.astype(x.dtype)
        # scale is [1, ..., n_out] (keepdims) — broadcasts over the
        # result's trailing output-channel axis exactly
        return y * w.scale.astype(x.dtype)
    if _MATMUL_EXTENSIONS:
        for cls, fn in _MATMUL_EXTENSIONS:
            if isinstance(w, cls):
                return fn(x, w)
    return x @ w


def quantized_weight_keys(net) -> dict:
    """{layer_key: [param_key, ...]} of every weight the net's layers
    declare quantizable (`Layer.quantizable_weights()`)."""
    out = {}
    for i, layer in enumerate(net.layers):
        keys = [k for k in layer.quantizable_weights()
                if k in net.params.get(str(i), {})]
        if keys:
            out[str(i)] = keys
    return out


def quantize_net_params(net, mode: str = "int8"):
    """A quantized COPY of `net.params`: every declared matmul weight
    becomes a `QuantizedTensor`, everything else is shared by
    reference. The result is what the serving/generation programs take
    as their params argument — `net.params` itself (training master)
    is never touched."""
    if mode != "int8":
        raise ValueError(
            f"unknown quantization mode {mode!r}; supported: 'int8'")
    plan = quantized_weight_keys(net)
    out = {}
    for lk, lparams in net.params.items():
        qkeys = plan.get(lk, ())
        out[lk] = {pk: (quantize(v) if pk in qkeys else v)
                   for pk, v in lparams.items()}
    return out


# one program for all the leaves of a tree, cached by the policy and
# the leaves' shapes: a tree reassigned with the same shapes is cast by
# the program the first one compiled
@functools.partial(jax.jit, static_argnums=0)
def _cast_leaves(policy, leaves):
    return policy.cast_params(leaves)


def compute_copy(policy, tree):
    """`tree` as a serving program computes on it. Under a mixed policy
    (float32 masters, bfloat16 compute) a copy whose floating leaves
    are in the compute dtype, made by ONE jitted call over the leaves
    that differ; a leaf already there (an int8 `q`, a tenant's shared
    base) is the same object in the copy, so nothing is held twice.
    Not mixed (`float32`, `bf16_params`): `tree` itself. The value a
    program reads is the one its own `cast_params` would have made of
    the master, in every step; on the copy that call traces nothing."""
    if not policy.is_mixed:
        return tree
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    at = [i for i, x in enumerate(leaves) if policy.casts(x)]
    if not at:
        return tree
    for i, x in zip(at, _cast_leaves(policy, [leaves[i] for i in at])):
        leaves[i] = x
    return jax.tree_util.tree_unflatten(treedef, leaves)


def serving_tree(net, quantize_mode: Optional[str]):
    """-> (the params tree a serving/generation program should read,
    its bytes on the device). The tree is `net.params` in the net's
    compute dtype (`compute_copy`: the masters themselves unless the
    policy is mixed), with the declared matmul weights quantized first
    under `quantize_mode`. One pass per net per mode, re-used by
    prefill, decode, and admission programs alike. The cache is keyed
    on the IDENTITY of `net.params`: every fit()/restore/hot swap
    reassigns that tree, which drops the copies made of the old one —
    a fine-tuned net must never silently serve pre-training weights —
    and the next call makes them anew. `net.params` is never touched."""
    cache = net.__dict__.get("_serving_params_cache")
    if cache is None or cache["source"] is not net.params:
        cache = net.__dict__["_serving_params_cache"] = {
            "source": net.params, "trees": {}}
    trees = cache["trees"]
    if quantize_mode not in trees:
        tree = (net.params if quantize_mode is None
                else quantize_net_params(net, quantize_mode))
        tree = compute_copy(net.dtype, tree)
        trees[quantize_mode] = (tree, weight_bytes(tree))
    return trees[quantize_mode]


def serving_params(net, quantize_mode: Optional[str]):
    """The tree of `serving_tree`: what every dispatch resolves (a dict
    lookup once made)."""
    return serving_tree(net, quantize_mode)[0]


def weight_bytes(params_tree) -> int:
    """HBM bytes of every weight leaf in a params tree (QuantizedTensor
    counts q + scale) — the ledger's weight-byte evidence input."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(params_tree):
        total += int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
    return total

"""LoRA-style low-rank adapters over a frozen shared base.

The publish unit of the multi-tenant fleet is an adapter DELTA, not a
model: each tenant fine-tunes `W_eff = W + (alpha/r) * B @ A` with the
base `W` frozen, then ships only `{B, A}` (kilobytes against a model
of megabytes). N tenants then serve from ONE in-memory copy of the
base params — composition happens inside the matmul, never by
materializing `W_eff`:

    x @ W_eff = x @ W + ((x @ B) @ A) * (alpha/r)

so the low-rank factors ride the dispatch as two skinny matmuls and
the base weight stays shared by reference (and may itself be an int8
`QuantizedTensor` — the recursion through `nd.quant.matmul` makes
int8-base + fp-adapter compose for free).

The `LoRAWeight` pytree node wraps a weight leaf the layer declared
via `Layer.adapter_weights()` (the `quantizable_weights()` mirror —
same matmul seams). jit/tree_map/donation see ordinary leaves; the
layer code never changes. `frozen` rides the node as STATIC aux data:
the matmul stops gradients at the base read, so a `fit()` on an
adapted net differentiates only the adapter leaves and the base stays
bit-identical (`nn/multilayer._apply_updates` keeps the base leaf's
object identity — no `-0.0` churn, no per-tenant base copy).

Init follows the LoRA convention: `A ~ N(0, 1/r)` and `B = 0`, so a
freshly attached adapter is an EXACT no-op (x @ B is zeros) — the
adapter-on/off parity tests pin that down.

Honest limits: adapted layers must not carry l1/l2 regularization or
norm constraints (both would touch the wrapped node as if it were an
array — and l1/l2 would push nonzero gradient into a frozen base);
`attach_adapter` refuses them. Embedding tables don't participate
(gather path, no matmul seam).
"""

from __future__ import annotations

import io
import json
import zipfile
from pathlib import Path
from typing import Dict, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nd import quant

ADAPTER_FORMAT_VERSION = 1


class LoRAWeight:
    """A matmul weight with a low-rank delta: children `(base, B, A)`
    — `base` is the shared (possibly int8-quantized) weight, `B`
    `[n_in, r]`, `A` `[r, n_out]` — and static aux `(scale, frozen)`
    with `scale = alpha / r`."""

    __slots__ = ("base", "B", "A", "scale", "frozen")

    def __init__(self, base, B, A, scale: float, frozen: bool = True):
        self.base = base
        self.B = B
        self.A = A
        self.scale = float(scale)
        self.frozen = bool(frozen)

    # array-ish surface (shape checks, width validation)
    @property
    def shape(self):
        return self.base.shape

    @property
    def ndim(self):
        return self.base.ndim

    @property
    def dtype(self):
        return self.base.dtype

    def __repr__(self):
        return (f"LoRAWeight(shape={tuple(self.shape)}, "
                f"r={self.B.shape[-1]}, scale={self.scale}, "
                f"frozen={self.frozen})")


def _lw_flatten(w):
    return (w.base, w.B, w.A), (w.scale, w.frozen)


def _lw_unflatten(aux, children):
    base, B, A = children
    scale, frozen = aux
    return LoRAWeight(base, B, A, scale, frozen)


jax.tree_util.register_pytree_node(LoRAWeight, _lw_flatten, _lw_unflatten)


def _lora_matmul(x, w: LoRAWeight):
    """`x @ W_eff` without materializing `W_eff`: base matmul (through
    `quant.matmul`, so an int8 base dequantizes inside as usual) plus
    the rank-r bottleneck. `stop_gradient` on a frozen base makes its
    cotangent exactly zero — the updater never moves it."""
    base = w.base
    if w.frozen:
        base = jax.tree_util.tree_map(jax.lax.stop_gradient, base)
    y = quant.matmul(x, base)
    delta = (x @ w.B.astype(x.dtype)) @ w.A.astype(x.dtype)
    return y + delta * jnp.asarray(w.scale, x.dtype)


quant.register_matmul_extension(LoRAWeight, _lora_matmul)


# ------------------------------------------------------------ tree helpers
def adapter_weight_keys(net) -> Dict[str, list]:
    """{layer_key: [param_key, ...]} of every weight the net's layers
    declare adapter-eligible (`Layer.adapter_weights()`)."""
    out = {}
    for lk, layer in net._keyed_layers():
        keys = [k for k in layer.adapter_weights()
                if k in net.params.get(lk, {})]
        if keys:
            out[lk] = keys
    return out


def contains_lora(tree) -> bool:
    """True if any node in `tree` is a LoRAWeight (checked on the
    container structure, so it works on traced trees too)."""
    if isinstance(tree, LoRAWeight):
        return True
    if isinstance(tree, dict):
        return any(contains_lora(v) for v in tree.values())
    if isinstance(tree, (list, tuple)):
        return any(contains_lora(v) for v in tree)
    return False


def _leaf_shape(w):
    # a quantized base reports its original weight shape
    return tuple(w.shape)


def init_adapter(net, *, rank: int, seed: int = 0) -> dict:
    """A fresh adapter tree `{lk: {pk: {"B", "A"}}}` for every
    adapter-eligible weight: `B` zeros `[n_in, r]`, `A` gaussian
    `N(0, 1/r)` `[r, n_out]` — the composed delta starts exactly 0."""
    if rank < 1:
        raise ValueError(f"adapter rank must be >= 1; got {rank}")
    plan = adapter_weight_keys(net)
    index = {lk: i for i, (lk, _) in enumerate(net._keyed_layers())}
    root = jax.random.PRNGKey(seed)
    out: dict = {}
    for lk, keys in plan.items():
        lp = {}
        for j, pk in enumerate(sorted(keys)):
            w = net.params[lk][pk]
            n_in, n_out = _leaf_shape(w)[-2], _leaf_shape(w)[-1]
            key = jax.random.fold_in(jax.random.fold_in(root, index[lk]), j)
            lp[pk] = {
                "B": jnp.zeros((n_in, rank), jnp.float32),
                "A": (jax.random.normal(key, (rank, n_out), jnp.float32)
                      / float(rank)),
            }
        out[lk] = lp
    return out


def _check_layer_adaptable(layer, lk):
    if layer.l1 or layer.l2:
        raise ValueError(
            f"layer {lk}: l1/l2 regularization on an adapted layer "
            f"would touch the wrapped LoRAWeight node (and push "
            f"gradient into a frozen base) — set l1=l2=0 on adapted "
            f"layers")
    if layer.constraints:
        raise ValueError(
            f"layer {lk}: norm constraints are not supported on "
            f"adapted layers (they rescale the raw param leaf, which "
            f"is now a LoRAWeight node)")


def attach_adapter(net, adapter: dict, *, rank: int, alpha: float,
                   frozen: bool = True):
    """Wrap the net's adapter-eligible weights as `LoRAWeight` nodes
    (training-side composition). Reassigns `net.params` — a NEW tree
    object, so the `quant.serving_params` identity cache invalidates,
    exactly like fit()/restore — and patches `updater_state` so the
    adapter leaves get fresh optimizer slots ({"B": ..., "A": ...}
    dicts; a frozen base keeps no slot — it will never move).
    Base leaves are shared BY REFERENCE: attaching N adapters to one
    base allocates only the B/A factors."""
    scale = float(alpha) / float(rank)
    new_params = {lk: dict(lv) for lk, lv in net.params.items()}
    new_upd = {lk: dict(lv) for lk, lv in net.updater_state.items()}
    from deeplearning4j_tpu.common.updaters import Sgd
    for lk, lv in adapter.items():
        layer = net.layer_for_key(lk)
        _check_layer_adaptable(layer, lk)
        updater = layer.updater or Sgd(1e-3)
        for pk, ba in lv.items():
            w = new_params[lk][pk]
            if isinstance(w, LoRAWeight):
                raise ValueError(
                    f"layer {lk} param {pk} already carries an "
                    f"adapter — strip_adapter() first")
            B, A = jnp.asarray(ba["B"]), jnp.asarray(ba["A"])
            if (B.shape[0], A.shape[1]) != (_leaf_shape(w)[-2],
                                            _leaf_shape(w)[-1]):
                raise ValueError(
                    f"layer {lk} param {pk}: adapter factors "
                    f"{B.shape}x{A.shape} don't fit weight "
                    f"{tuple(w.shape)}")
            new_params[lk][pk] = LoRAWeight(w, B, A, scale, frozen)
            slots = {"B": updater.init_state(B),
                     "A": updater.init_state(A)}
            if not frozen:
                slots["base"] = updater.init_state(w)
            new_upd.setdefault(lk, {})[pk] = slots
    net.params = new_params
    net.updater_state = new_upd
    return net


def extract_adapter(net) -> dict:
    """The adapter tree `{lk: {pk: {"B", "A"}}}` currently attached —
    the publish unit (`ModelRegistry.publish_adapter`)."""
    out: dict = {}
    for lk, lv in net.params.items():
        for pk, w in lv.items():
            if isinstance(w, LoRAWeight):
                out.setdefault(lk, {})[pk] = {"B": w.B, "A": w.A}
    return out


def strip_adapter(net) -> dict:
    """Detach: restore plain base leaves (same objects that went in)
    and return the adapter tree. Reassigns `net.params` (identity
    invalidation) and drops the adapter optimizer slots."""
    adapter: dict = {}
    new_params = {lk: dict(lv) for lk, lv in net.params.items()}
    new_upd = {lk: dict(lv) for lk, lv in net.updater_state.items()}
    from deeplearning4j_tpu.common.updaters import Sgd
    for lk, lv in list(new_params.items()):
        for pk, w in list(lv.items()):
            if isinstance(w, LoRAWeight):
                adapter.setdefault(lk, {})[pk] = {"B": w.B, "A": w.A}
                lv[pk] = w.base
                layer = net.layer_for_key(lk)
                updater = layer.updater or Sgd(1e-3)
                new_upd[lk][pk] = updater.init_state(w.base) \
                    if not isinstance(w.base, quant.QuantizedTensor) \
                    else new_upd[lk].get(pk)
    net.params = new_params
    net.updater_state = new_upd
    return adapter


def compose_params(base_params: dict, adapter: dict, *, rank: int,
                   alpha: float) -> dict:
    """Serving-side composition: a params tree whose adapted leaves
    are `LoRAWeight(base, B, A)` nodes SHARING the base leaves by
    reference (the base may already be the int8-quantized serving
    copy). Non-adapted leaves are shared verbatim — composing a tenant
    view allocates nothing but the tree spine."""
    scale = float(alpha) / float(rank)
    out = {}
    for lk, lv in base_params.items():
        lav = adapter.get(lk, {})
        out[lk] = {pk: (LoRAWeight(w, jnp.asarray(lav[pk]["B"]),
                                   jnp.asarray(lav[pk]["A"]), scale, True)
                        if pk in lav else w)
                   for pk, w in lv.items()}
    return out


def apply_adapter_update(updater, p: LoRAWeight, g, slots: dict, step):
    """One optimizer step on a LoRAWeight leaf (the
    `_apply_updates` branch): B/A move through the layer's updater;
    a frozen base keeps its OBJECT IDENTITY (not `base - 0.0`), so
    the shared-base memory claim and bit-identity both hold."""
    dB, sB = updater.apply(g.B.astype(p.B.dtype), slots["B"], step)
    dA, sA = updater.apply(g.A.astype(p.A.dtype), slots["A"], step)
    new_B = p.B - dB.astype(p.B.dtype)
    new_A = p.A - dA.astype(p.A.dtype)
    new_slots = dict(slots, B=sB, A=sA)
    if p.frozen or "base" not in slots:
        base = p.base
    else:
        db, sb = updater.apply(g.base.astype(p.base.dtype),
                               slots["base"], step)
        base = p.base - db.astype(p.base.dtype)
        new_slots["base"] = sb
    return LoRAWeight(base, new_B, new_A, p.scale, p.frozen), new_slots


def adapter_bytes(adapter: dict) -> int:
    """Bytes of the adapter tree — the <5%-of-full-zip evidence input."""
    return quant.weight_bytes(adapter)


# ------------------------------------------------------------------ serde
from deeplearning4j_tpu.fault.state import checksum_array as _crc


def save_adapter(path: Union[str, Path, io.IOBase], adapter: dict, *,
                 meta: Optional[dict] = None):
    """Adapter artifact: a zip holding `adapter.npz` ("lk::pk__B"
    keys) + `meta.json` (format version, rank/alpha/base_version from
    `meta`, per-array crc32) — the ModelSerializer container idiom at
    adapter scale."""
    flat = {}
    for lk, lv in adapter.items():
        for pk, ba in lv.items():
            flat[f"{lk}::{pk}__B"] = np.asarray(ba["B"])
            flat[f"{lk}::{pk}__A"] = np.asarray(ba["A"])
    checksums = {k: _crc(arr) for k, arr in flat.items()}
    m = dict(meta or {})
    m.setdefault("format_version", ADAPTER_FORMAT_VERSION)
    m["array_checksums"] = checksums
    buf = io.BytesIO()
    np.savez(buf, **flat)
    if hasattr(path, "write"):
        zf_target = path
    else:
        zf_target = str(path)
    with zipfile.ZipFile(zf_target, "w", zipfile.ZIP_DEFLATED) as zf:
        zf.writestr("adapter.npz", buf.getvalue())
        zf.writestr("meta.json", json.dumps(m, indent=2))


def load_adapter(path: Union[str, Path, io.IOBase]):
    """-> (adapter_tree, meta). Verifies per-array crc32 when the
    artifact carries checksums; raises ValueError on corruption."""
    src = path if hasattr(path, "read") else str(path)
    with zipfile.ZipFile(src, "r") as zf:
        meta = json.loads(zf.read("meta.json"))
        with zf.open("adapter.npz") as f:
            data = np.load(io.BytesIO(f.read()))
            flat = {k: data[k] for k in data.files}
    expected = meta.get("array_checksums") or {}
    bad = [k for k, arr in flat.items()
           if k in expected and _crc(arr) != expected[k]]
    if bad:
        raise ValueError(
            f"adapter artifact failed checksum verification: {bad[:5]}")
    out: dict = {}
    for key, arr in flat.items():
        lp, slot = key.rsplit("__", 1)
        lk, pk = lp.split("::", 1)
        out.setdefault(lk, {}).setdefault(pk, {})[slot] = jnp.asarray(arr)
    return out, meta

"""Shared arithmetic of the plain references: matrix products at a stated
precision, seeds, per-leaf norms.  Imports nothing of the program.

`mm(a, b, mode)` is every matrix product a reference makes.  Modes:

- ``f32``: float32 operands, ``Precision.HIGHEST`` (on a TPU a float32
  product otherwise runs in lower precision).  This is the reference.
- ``bf16``: operands rounded to bfloat16, float32 accumulation: what the
  configurations state (`mixed_bf16`).  Used by tests only.
- ``fp8``: operands rounded to 4 exponent and 3 mantissa bits (e4m3)
  under one scale per tensor,
  float32 accumulation, in the forward AND both backward products.  This
  is the control: the nearest precision below bfloat16, the step that
  would tempt a later PR.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
MODES = ("f32", "bf16", "fp8")


def seed_words(seed: int) -> np.ndarray:
    """`--seed` (any whole number to a little over 2**31) as two uint32
    words, so that it can be a traced argument of a jitted call."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must not be negative, got {seed}")
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


def key_of(words):
    k = jax.random.PRNGKey(0)
    return jax.random.fold_in(jax.random.fold_in(k, words[0]), words[1])


def _round(x, mode):
    """Round float32 values to the precision of `mode`, staying float32.
    `lax.reduce_precision`, not a cast and back: XLA on the TPU removes
    such a pair of casts (it allows itself excess precision), and the
    control would then read as the reference (seen on the chip, PR 26)."""
    if mode == "f32":
        return x
    if mode == "bf16":
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    if mode == "fp8":
        # e4m3 under one scale per tensor: the largest magnitude sits at
        # 224, inside the format's range (its largest finite is 240)
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 224.0
        return jax.lax.reduce_precision(x / s, exponent_bits=4,
                                        mantissa_bits=3) * s
    raise ValueError(f"unknown precision mode {mode!r}; known: {MODES}")


def _mm_raw(a, b, mode):
    return jnp.matmul(_round(a, mode), _round(b, mode), precision=HI)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def mm(a, b, mode="f32"):
    """a [..., M, K] @ b [..., K, N] or b [K, N]."""
    return _mm_raw(a, b, mode)


def _mm_fwd(a, b, mode):
    return _mm_raw(a, b, mode), (a, b)


def _mm_bwd(mode, res, g):
    a, b = res
    da = _mm_raw(g, jnp.swapaxes(b, -1, -2), mode)
    if b.ndim == 2 and a.ndim > 2:
        a2 = a.reshape(-1, a.shape[-1])
        g2 = g.reshape(-1, g.shape[-1])
        db = _mm_raw(a2.T, g2, mode)
    else:
        db = _mm_raw(jnp.swapaxes(a, -1, -2), g, mode)
    return da, db


mm.defvjp(_mm_fwd, _mm_bwd)


def leaf_norms(tree, stacked=()):
    """{leaf name: 2-norm}.  A leaf under one of the `stacked` top-level
    keys holds one layer per leading index and gives one norm per layer
    (`blocks.wq[3]`), so that "the worst leaf" is a layer's leaf."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
        name = ".".join(str(k) for k in keys)
        x = leaf.astype(jnp.float32)
        if keys and keys[0] in stacked:
            n = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
            out[name] = n
        else:
            out[name] = jnp.sqrt(jnp.sum(x * x))
    return out


def flatten_norms(norms) -> dict:
    """Device dict from `leaf_norms` -> {name or name[i]: float} on the host."""
    flat = {}
    for name, v in jax.device_get(norms).items():
        v = np.asarray(v)
        if v.ndim == 0:
            flat[name] = float(v)
        else:
            for i, x in enumerate(v):
                flat[f"{name}[{i}]"] = float(x)
    return flat


def adam(p, g, m, v, step, lr, b1=0.9, b2=0.999, eps=1e-8):
    """Adam as Kingma & Ba state it, bias-corrected, step counted from 0."""
    t = step + 1.0
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    upd = lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
    return p - upd, m, v


def free(*trees):
    """Delete device buffers now, not when Python gets round to it."""
    for t in trees:
        for leaf in jax.tree_util.tree_leaves(t):
            if hasattr(leaf, "delete") and not leaf.is_deleted():
                leaf.delete()


@jax.jit
def cosine_gap(a, b):
    """1 - cosine between two trees of the same structure, as one vector."""
    f32 = lambda t: [x.astype(jnp.float32)  # noqa: E731
                     for x in jax.tree_util.tree_leaves(t)]
    la, lb = f32(a), f32(b)
    dot = sum(jnp.vdot(x, y) for x, y in zip(la, lb))
    na = sum(jnp.vdot(x, x) for x in la)
    nb = sum(jnp.vdot(y, y) for y in lb)
    return 1.0 - dot / jnp.sqrt(na * nb)

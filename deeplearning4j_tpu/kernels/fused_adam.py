"""Fused Adam over a whole ``stacked::`` packed run — Pallas kernel.

The optimizer sweep is the elementwise tail of the train step: per
leaf, the jnp Adam path reads m, v, param, grad and writes m', v',
param' as separate XLA ops — for a packed scan stack that is a pile of
small bandwidth-bound kernels. This kernel consumes the ENTIRE run in
one pass: every leaf of the packed param/grad/m/v trees is raveled and
concatenated into one [rows, 128] lane-aligned buffer, and a single
grid sweep read-modify-writes param/m/v together — one kernel launch
per run instead of ~6 XLA ops per leaf.

Operand-assembly cost, and the pre-flattened state layout: params and
grads MUST be raveled+concatenated per step (the model needs params in
layer layout; autodiff emits grads in layer layout), but m/v belong to
the optimizer alone — so the containers keep a packed run's m/v in the
kernel's lane-aligned ``[rows, 128]`` layout BETWEEN steps
(`flatten_opt_state` at the scan_stack pack boundary, inverse at
unpack). Inside a fused multi-step program the flat m/v ride the
`lax.scan` carry untouched: the per-micro-step concat/ravel/slice
relayout of the optimizer state disappears entirely, halving the
assembly traffic around the kernel. The conversion is an exact
relayout (pad lanes stay zero under the Adam recurrence because the
padded grads are zero), so the flat and per-leaf STATE forms of the
kernel are bit-identical — test-enforced. Checkpoints are unaffected: the
flat form exists only between pack/unpack inside the jitted step
programs, and the state the containers persist stays per-layer-keyed
(the fault-runtime contract).

Numerics follow `common.updaters.Adam.apply` + the containers'
``param - upd`` application operation for operation: the bias
corrections ``1 − βᵢᵗ`` and the (possibly scheduled) learning rate are
computed OUTSIDE the kernel with the exact jnp expressions the updater
uses and enter as scalar operands, and the in-kernel expression tree
mirrors `Adam.apply` term for term. What the two paths can promise each
other is agreement to the compiler's rounding of ``a*b + c``: whether a
multiply-add is contracted to one FMA (one rounding) or not (two) is
the backend's choice per program — XLA:CPU contracts a DIFFERENT
product in the kernel body than in the per-leaf path, and an
`optimization_barrier` does not stop it (nor can Mosaic lower one) — so
m and v agree to one rounding of their larger addend
(2 eps x (|b*m| + |(1-b)*g|) per element) and the parameters to 2 ulps
(test-enforced in interpret mode), not bit for bit. Mixed precision:
gradients are upcast to the param (master) dtype before the kernel,
exactly like the jnp path — m/v/param stay an fp32 master.

Interpret mode on CPU (parity tests), compiled on TPU; dispatch is
gated by `kernels_enabled()` (DL4J_PALLAS_KERNELS) in the containers'
`_apply_updates`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from deeplearning4j_tpu.common.updaters import Adam, _lr
from deeplearning4j_tpu.kernels.flash_attention import (
    _ceil_to,
    _resolve_interpret,
)

KERNEL_NAME = "dl4tpu_fused_adam"

_LANES = 128
_SUBLANES = 8

# marker key of the pre-flattened optimizer-state form: the packed
# run's m/v as single lane-aligned [rows, 128] buffers instead of
# per-param-key dicts (kept between steps; see module docstring)
FLAT_KEY = "__fused_flat__"


def fused_adam_eligible(updater) -> bool:
    """Packed-run fast-path gate: exactly the Adam rule (subclasses
    like Nadam change the update math) and kernels enabled."""
    from deeplearning4j_tpu.kernels import kernels_enabled
    return type(updater) is Adam and kernels_enabled()


def is_flat_state(state) -> bool:
    return isinstance(state, dict) and FLAT_KEY in state


def _adam_kernel(p_ref, g_ref, m_ref, v_ref, lr_ref, bc1_ref, bc2_ref,
                 p_out, m_out, v_out, *, beta1: float, beta2: float,
                 eps: float):
    # the expression tree of `Adam.apply`, term for term
    g = g_ref[...]
    m = beta1 * m_ref[...] + (1 - beta1) * g
    v = beta2 * v_ref[...] + (1 - beta2) * g * g
    mhat = m / bc1_ref[0, 0]
    vhat = v / bc2_ref[0, 0]
    upd = lr_ref[0, 0] * mhat / (jnp.sqrt(vhat) + eps)
    p_out[...] = p_ref[...] - upd
    m_out[...] = m
    v_out[...] = v


def _unflatten(flat, keys, shapes, sizes):
    out, off = {}, 0
    for k, shape, n in zip(keys, shapes, sizes):
        out[k] = flat[off:off + n].reshape(shape)
        off += n
    return out


def _layout(n: int, block_rows: int = 512):
    """The kernel's lane-aligned padded layout for `n` elements:
    (npad, padded rows, block rows). Shared by the per-step assembly
    AND the persistent pre-flattened state so both agree bit-for-bit
    on where every element lives."""
    npad = _ceil_to(max(n, 1), _LANES * _SUBLANES)
    rows = npad // _LANES
    br = min(block_rows, _ceil_to(rows, _SUBLANES))
    rowsp = _ceil_to(rows, br)
    if rowsp * _LANES != npad:
        npad = rowsp * _LANES
    return npad, rowsp, br


def _to2d(a, n, npad, rowsp):
    if npad != n:
        a = jnp.pad(a, (0, npad - n))
    return a.reshape(rowsp, _LANES)


def flatten_opt_state(params, state, *, block_rows: int = 512):
    """Per-leaf {key: {m, v}} -> the pre-flattened form: m/v each ONE
    lane-aligned [rows, 128] buffer in the kernel's exact layout (pad
    lanes zero — they stay zero under the Adam recurrence because the
    per-step grads are padded with zeros). Identity when already
    flat."""
    if is_flat_state(state):
        return state
    keys = sorted(params)
    sizes = [int(np.prod(np.shape(params[k]))) for k in keys]
    n = sum(sizes)
    npad, rowsp, _ = _layout(n, block_rows)
    m = jnp.concatenate([state[k]["m"].reshape(-1) for k in keys])
    v = jnp.concatenate([state[k]["v"].reshape(-1) for k in keys])
    return {FLAT_KEY: {"m": _to2d(m, n, npad, rowsp),
                       "v": _to2d(v, n, npad, rowsp)}}


def unflatten_opt_state(params, state, *, block_rows: int = 512):
    """Inverse relayout: flat [rows, 128] m/v back to the per-leaf
    {key: {m, v}} dicts the containers persist (checkpoints stay
    per-layer-keyed — the fault-runtime contract). Identity when
    already per-leaf."""
    if not is_flat_state(state):
        return state
    keys = sorted(params)
    shapes = [np.shape(params[k]) for k in keys]
    sizes = [int(np.prod(s)) for s in shapes]
    n = sum(sizes)
    m = state[FLAT_KEY]["m"].reshape(-1)[:n]
    v = state[FLAT_KEY]["v"].reshape(-1)[:n]
    new_m = _unflatten(m, keys, shapes, sizes)
    new_v = _unflatten(v, keys, shapes, sizes)
    return {k: {"m": new_m[k], "v": new_v[k]} for k in keys}


def flatten_run_states(params, state, run_keys):
    """Pre-flatten the eligible packed runs' optimizer state (called
    right after `scan_stack.pack_tree` at the step/program boundary —
    inside a fused multi-step program the flat m/v then ride the scan
    carry with NO per-micro-step relayout)."""
    if not run_keys:
        return state
    out = dict(state)
    for rk in run_keys:
        out[rk] = flatten_opt_state(params[rk], state[rk])
    return out


def unflatten_run_states(params, state, run_keys):
    """Inverse of `flatten_run_states` (called right before
    `scan_stack.unpack_tree`)."""
    if not run_keys:
        return state
    out = dict(state)
    for rk in run_keys:
        out[rk] = unflatten_opt_state(params[rk], state[rk])
    return out


def pack_run_trees(params, upd_state, runs, fused_runs):
    """The containers' step/program entry boundary in ONE place:
    `scan_stack.pack_tree` on params AND updater state, then the
    fused-eligible runs' m/v flattened into the kernel layout. The
    ordering contract — flatten AFTER pack, over the PACKED params —
    lives here so the four container call sites cannot drift."""
    from deeplearning4j_tpu.nn import scan_stack
    params = scan_stack.pack_tree(params, runs)
    upd_state = scan_stack.pack_tree(upd_state, runs)
    return params, flatten_run_states(params, upd_state, fused_runs)


def unpack_run_trees(params, upd_state, runs, fused_runs):
    """Inverse boundary: unflatten BEFORE unpack, over the
    still-packed params."""
    from deeplearning4j_tpu.nn import scan_stack
    upd_state = unflatten_run_states(params, upd_state, fused_runs)
    return (scan_stack.unpack_tree(params, runs),
            scan_stack.unpack_tree(upd_state, runs))


def adam_update_packed(updater: Adam, params, grads, state, step, *,
                       block_rows: int = 512,
                       interpret: bool | None = None):
    """One fused-kernel Adam update of a packed run entry. Returns
    (new_params, new_updater_state) shaped like the inputs — drop-in
    for the per-leaf loop in the containers' `_apply_updates`. `state`
    may be per-leaf {key: {m, v}} or the pre-flattened form
    (`flatten_opt_state`); the output keeps the input's form, so the
    flat m/v ride a fused program's scan carry without any per-step
    concat/ravel/slice."""
    interpret = _resolve_interpret(interpret)
    flat_in = is_flat_state(state)
    keys = sorted(params)
    shapes = [np.shape(params[k]) for k in keys]
    sizes = [int(np.prod(s)) for s in shapes]
    n = sum(sizes)
    npad, rowsp, br = _layout(n, block_rows)
    dt = params[keys[0]].dtype
    p = jnp.concatenate([params[k].reshape(-1) for k in keys])
    g = jnp.concatenate([grads[k].reshape(-1).astype(dt) for k in keys])
    p2 = _to2d(p, n, npad, rowsp)
    g2 = _to2d(g, n, npad, rowsp)
    if flat_in:
        m2, v2 = state[FLAT_KEY]["m"], state[FLAT_KEY]["v"]
        if m2.shape != (rowsp, _LANES):
            raise ValueError(
                f"pre-flattened m/v layout {m2.shape} does not match "
                f"the run's kernel layout {(rowsp, _LANES)}")
    else:
        m = jnp.concatenate([state[k]["m"].reshape(-1) for k in keys])
        v = jnp.concatenate([state[k]["v"].reshape(-1) for k in keys])
        m2 = _to2d(m, n, npad, rowsp)
        v2 = _to2d(v, n, npad, rowsp)
    # the EXACT scalar expressions Adam.apply evaluates — dividing by
    # the same scalars keeps the kernel bit-comparable to the jnp path
    t = jnp.asarray(step, jnp.float32) + 1.0
    bc1 = jnp.asarray(1 - updater.beta1 ** t, jnp.float32).reshape(1, 1)
    bc2 = jnp.asarray(1 - updater.beta2 ** t, jnp.float32).reshape(1, 1)
    lr = jnp.asarray(_lr(updater.learning_rate, step),
                     jnp.float32).reshape(1, 1)

    row_blk = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    scal_blk = pl.BlockSpec((1, 1), lambda i: (0, 0))
    p_new, m_new, v_new = pl.pallas_call(
        functools.partial(_adam_kernel, beta1=float(updater.beta1),
                          beta2=float(updater.beta2),
                          eps=float(updater.epsilon)),
        grid=(rowsp // br,),
        in_specs=[row_blk] * 4 + [scal_blk] * 3,
        out_specs=[row_blk] * 3,
        out_shape=[jax.ShapeDtypeStruct((rowsp, _LANES), dt)] * 3,
        interpret=interpret,
        name=KERNEL_NAME,
    )(p2, g2, m2, v2, lr, bc1, bc2)

    new_params = _unflatten(p_new.reshape(-1)[:n], keys, shapes, sizes)
    if flat_in:
        return new_params, {FLAT_KEY: {"m": m_new, "v": v_new}}
    m_new, v_new = (a.reshape(-1)[:n] for a in (m_new, v_new))
    new_m = _unflatten(m_new, keys, shapes, sizes)
    new_v = _unflatten(v_new, keys, shapes, sizes)
    new_state = {k: {"m": new_m[k], "v": new_v[k]} for k in keys}
    return new_params, new_state

"""Fused LayerNorm (+ residual) — Pallas TPU kernels.

LayerNorm is the canonical bandwidth-bound op of the transformer step
(PROFILE_aot per-op tables: ~1 FLOP/byte — pure VPU work that XLA
schedules as several HBM round trips when the surrounding residual adds
don't fuse). These kernels compute the fp32 row statistics AND apply
gamma/beta in a single HBM pass; `residual_layer_norm` additionally
folds the preceding residual add (``s = x + h; y = LN(s)`` — the
pre-LN transformer block's exact pattern) so the [B, T, D] sum is
never written out separately.

Design (same conventions as `flash_attention.py`):
- rows (all leading dims flattened) are blocked on the grid's only
  dimension; the feature axis D rides whole inside each block (block
  trailing dim == array dim satisfies Mosaic's layout rules, and D is
  at most a few thousand for the models here — well inside VMEM);
- statistics are computed in fp32 regardless of the activation dtype
  (the mixed_bf16 policy's "norm statistics stay fp32" rule —
  `nn/layers/normalization.layer_norm_reference` is the parity
  contract), outputs return in the input dtype;
- forward emits (y, mean, rstd); backward is the standard analytic
  LayerNorm gradient evaluated with jnp ops from the saved statistics
  (a handful of fused elementwise/reduce ops — XLA handles those well;
  the HBM win lives in the forward's fusion);
- interpret mode on CPU (how the tests validate parity), compiled by
  Mosaic elsewhere; `kernels_enabled()` gates dispatch
  (DL4J_PALLAS_KERNELS).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.kernels.flash_attention import (
    _ceil_to,
    _resolve_interpret,
)

KERNEL_NAMES = ("dl4tpu_layer_norm", "dl4tpu_residual_layer_norm")

_LN_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel",))


def _ln_kernel(x_ref, g_ref, b_ref, y_ref, mean_ref, rstd_ref, *,
               eps: float):
    xf = x_ref[...].astype(jnp.float32)                    # [BR, D]
    mean = jnp.mean(xf, axis=1, keepdims=True)             # [BR, 1]
    var = jnp.mean((xf - mean) ** 2, axis=1, keepdims=True)
    rstd = 1.0 / jnp.sqrt(var + eps)
    norm = ((xf - mean) * rstd).astype(y_ref.dtype)
    y_ref[...] = norm * g_ref[...] + b_ref[...]
    mean_ref[...] = mean
    rstd_ref[...] = rstd


def _residual_ln_kernel(x_ref, h_ref, g_ref, b_ref, s_ref, y_ref,
                        mean_ref, rstd_ref, *, eps: float):
    s = x_ref[...] + h_ref[...]                            # [BR, D]
    s_ref[...] = s
    xf = s.astype(jnp.float32)
    mean = jnp.mean(xf, axis=1, keepdims=True)
    var = jnp.mean((xf - mean) ** 2, axis=1, keepdims=True)
    rstd = 1.0 / jnp.sqrt(var + eps)
    norm = ((xf - mean) * rstd).astype(y_ref.dtype)
    y_ref[...] = norm * g_ref[...] + b_ref[...]
    mean_ref[...] = mean
    rstd_ref[...] = rstd


def _row_geometry(R: int, block_rows: int):
    br = min(block_rows, _ceil_to(max(R, 1), 8))
    Rp = _ceil_to(max(R, 1), br)
    return br, Rp


def _ln_call(kernel, name, ins, R, D, dtype, br, Rp, interpret,
             n_dense_out):
    """Shared pallas_call driver: `n_dense_out` [Rp, D] outputs followed
    by the mean/rstd [Rp, 1] statistics."""
    row_blk = pl.BlockSpec((br, D), lambda i: (i, 0))
    vec_blk = pl.BlockSpec((1, D), lambda i: (0, 0))
    stat_blk = pl.BlockSpec((br, 1), lambda i: (i, 0))
    n_in_rows = len(ins) - 2          # trailing two are gamma/beta
    return pl.pallas_call(
        kernel,
        grid=(Rp // br,),
        in_specs=[row_blk] * n_in_rows + [vec_blk, vec_blk],
        out_specs=[row_blk] * n_dense_out + [stat_blk, stat_blk],
        out_shape=(
            [jax.ShapeDtypeStruct((Rp, D), dtype)] * n_dense_out
            + [jax.ShapeDtypeStruct((Rp, 1), jnp.float32)] * 2),
        compiler_params=_LN_PARAMS,
        interpret=interpret,
        name=name,
    )(*ins)


def _prep_rows(x, br_target):
    shape = x.shape
    D = shape[-1]
    R = 1
    for s in shape[:-1]:
        R *= int(s)
    x2 = x.reshape(R, D)
    br, Rp = _row_geometry(R, br_target)
    if Rp != R:
        x2 = jnp.pad(x2, [(0, Rp - R), (0, 0)])
    return x2, R, Rp, br, D, shape


def _ln_bwd_math(gy, gamma, x32, mean, rstd, out_dtype):
    """Analytic LayerNorm backward from saved fp32 statistics:
    dx = rstd·(ĝ − mean(ĝ) − x̂·mean(ĝ·x̂)) with ĝ = gy·gamma, plus the
    affine grads dγ = Σ gy·x̂ and dβ = Σ gy (reduced in fp32)."""
    xhat = (x32 - mean) * rstd                              # [R, D] f32
    g32 = gy.astype(jnp.float32) * gamma.astype(jnp.float32)
    gmean = jnp.mean(g32, axis=-1, keepdims=True)
    gxmean = jnp.mean(g32 * xhat, axis=-1, keepdims=True)
    dx = (rstd * (g32 - gmean - xhat * gxmean)).astype(out_dtype)
    dgamma = jnp.sum(gy.astype(jnp.float32) * xhat, axis=0)
    dbeta = jnp.sum(gy.astype(jnp.float32), axis=0)
    return dx, dgamma, dbeta


# ----------------------------------------------------------- layer_norm
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def layer_norm(x, gamma, beta, eps: float = 1e-5, block_rows: int = 256,
               interpret: bool | None = None):
    """[..., D] → [..., D]: one-pass fused LayerNorm. Row statistics in
    fp32, output in x.dtype — parity contract:
    `nn.layers.normalization.layer_norm_reference`."""
    y, _, _ = _ln_forward(x, gamma, beta, eps, block_rows, interpret)
    return y


def _ln_forward(x, gamma, beta, eps, block_rows, interpret):
    interpret = _resolve_interpret(interpret)
    x2, R, Rp, br, D, shape = _prep_rows(x, block_rows)
    g2 = gamma.reshape(1, D)
    b2 = beta.reshape(1, D)
    y, mean, rstd = _ln_call(
        functools.partial(_ln_kernel, eps=float(eps)), KERNEL_NAMES[0],
        (x2, g2, b2), R, D, x.dtype, br, Rp, interpret, n_dense_out=1)
    return y[:R].reshape(shape), mean[:R], rstd[:R]


def _ln_fwd(x, gamma, beta, eps, block_rows, interpret):
    y, mean, rstd = _ln_forward(x, gamma, beta, eps, block_rows,
                                interpret)
    return y, (x, gamma, mean, rstd)


def _ln_bwd(eps, block_rows, interpret, res, gy):
    x, gamma, mean, rstd = res
    D = x.shape[-1]
    x32 = x.reshape(-1, D).astype(jnp.float32)
    gy2 = gy.reshape(-1, D)
    dx, dgamma, dbeta = _ln_bwd_math(gy2, gamma, x32, mean, rstd,
                                     x.dtype)
    return (dx.reshape(x.shape), dgamma.astype(gamma.dtype),
            dbeta.astype(gamma.dtype))


layer_norm.defvjp(_ln_fwd, _ln_bwd)


# -------------------------------------------------- residual_layer_norm
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def residual_layer_norm(x, h, gamma, beta, eps: float = 1e-5,
                        block_rows: int = 256,
                        interpret: bool | None = None):
    """Fused ``s = x + h; y = LayerNorm(s)`` → (s, y) — the pre-LN
    transformer block's residual-into-norm pattern in ONE HBM pass (the
    residual sum never round-trips before the statistics read it)."""
    s, y, _, _ = _res_ln_forward(x, h, gamma, beta, eps, block_rows,
                                 interpret)
    return s, y


def _res_ln_forward(x, h, gamma, beta, eps, block_rows, interpret):
    interpret = _resolve_interpret(interpret)
    x2, R, Rp, br, D, shape = _prep_rows(x, block_rows)
    h2, _, _, _, _, _ = _prep_rows(h, block_rows)
    g2 = gamma.reshape(1, D)
    b2 = beta.reshape(1, D)
    s, y, mean, rstd = _ln_call(
        functools.partial(_residual_ln_kernel, eps=float(eps)),
        KERNEL_NAMES[1], (x2, h2, g2, b2), R, D, x.dtype, br, Rp, interpret,
        n_dense_out=2)
    return s[:R].reshape(shape), y[:R].reshape(shape), mean[:R], rstd[:R]


def _res_ln_fwd(x, h, gamma, beta, eps, block_rows, interpret):
    s, y, mean, rstd = _res_ln_forward(x, h, gamma, beta, eps,
                                       block_rows, interpret)
    return (s, y), (s, gamma, mean, rstd)


def _res_ln_bwd(eps, block_rows, interpret, res, g):
    gs, gy = g
    s, gamma, mean, rstd = res
    D = s.shape[-1]
    s32 = s.reshape(-1, D).astype(jnp.float32)
    gy2 = gy.reshape(-1, D)
    dln, dgamma, dbeta = _ln_bwd_math(gy2, gamma, s32, mean, rstd,
                                      s.dtype)
    ds = gs + dln.reshape(s.shape)
    # d(x + h)/dx == d(x + h)/dh — both residual legs get ds
    return (ds, ds, dgamma.astype(gamma.dtype),
            dbeta.astype(gamma.dtype))


residual_layer_norm.defvjp(_res_ln_fwd, _res_ln_bwd)

"""The selective state-space recurrence of a prefill — Pallas TPU kernel.

A Mamba mixer (`nn/layers/statespace.py`) carries, a channel, a state of
`N` columns through time:

    h_t = exp(Delta_t * A) * h_{t-1} + (Delta_t * x_t) * B_t
    y_t = sum_n h_t[n] * C_t[n] + D * x_t

with `x_t`, `Delta_t` a value a channel, `B_t`, `C_t` a value a state
column (shared by the channels), `A` `[N, C]` and `D` `[C]` constants.
Nothing here is a matrix product: it is `N x C` multiply-adds and as many
exponentials a position on the vector unit, each step waiting for the one
before.  Written as XLA's associative scan it moves `[T, N, C]` float32
through memory several times; written as a `lax.scan` over time it is T
dependent steps of a few microseconds each.  This kernel keeps `h` in
VMEM across time:

- the state is `[N, C]`: the `N` state columns on sublanes (16: two
  float32 tiles), the channels on the lanes; one program owns a TILE of
  channels (`channels`, 512 by default) of one row of the wave and walks
  its time axis `steps` (128) positions a grid step, the innermost,
  sequential grid axis, `h` staying in a VMEM scratch between them;
- `B` and `C` come transposed, `[K, N, T]`: time on the lanes, so that a
  position's column `[N, 1]` is a static lane of the block and is
  broadcast over the channels;
- each row's length rides as a scalar-prefetch operand: past it `Delta`
  is taken as 0, which leaves `h` as it was (`exp(0) = 1`, nothing
  added), so the state returned is the one after the row's LAST REAL
  position whatever the bucket it was padded to; a block of positions
  wholly past the length is not computed (its `y` is zeros) and its
  inputs are not fetched again.

Interpret mode on the CPU (the parity tests), Mosaic on the chip.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.kernels.flash_attention import _resolve_interpret

KERNEL_NAME = "dl4tpu_selective_scan"

_STEPS = 128        # positions a grid step: one lane tile of B^T and C^T
_CHANNELS = 512     # channels a program: h is [16, 512] float32, 8 vregs


def unsupported_reason(x_shape, n_state: int,
                       channels: int = _CHANNELS) -> Optional[str]:
    """Why the kernel cannot tile `x [K, T, C]` with `n_state` state
    columns (None: it can)."""
    if len(x_shape) != 3:
        return f"x rank {len(x_shape)} is not [K, T, C]"
    _, T, C = x_shape
    if T % _STEPS:
        return f"{T} positions are not a multiple of {_STEPS}"
    if C % 128:
        return f"{C} channels are not a multiple of the 128 lanes"
    if n_state % 8:
        return f"{n_state} state columns are not a multiple of 8 sublanes"
    return None


def _tile(C: int, channels: int) -> int:
    """The widest tile of whole lane tiles, at most `channels`, that
    divides C."""
    t = min(channels, C) // 128 * 128
    while C % t:
        t -= 128
    return t


def _scan_kernel(lens_ref, x_ref, dl_ref, bt_ref, ct_ref, a_ref, d_ref,
                 h0_ref, y_ref, h_ref, h_scr, *, steps: int):
    """One (row, channel tile, block of positions): x_ref/dl_ref/y_ref
    [steps, tc]; bt_ref/ct_ref [N, steps]; a_ref [N, tc]; d_ref [1, tc];
    h0_ref/h_ref [N, tc]; h_scr [N, tc] lives across the blocks."""
    ti = pl.program_id(2)
    length = lens_ref[pl.program_id(0)]
    t0 = ti * steps

    @pl.when(ti == 0)
    def _first():
        h_scr[...] = h0_ref[...]

    @pl.when(t0 < length)
    def _run():
        a = a_ref[...]
        d = d_ref[...]
        bt = bt_ref[...]
        ct = ct_ref[...]
        h = h_scr[...]
        for j in range(steps):
            dl = jnp.where(t0 + j < length, dl_ref[j:j + 1, :], 0.0)
            xv = x_ref[j:j + 1, :]
            h = jnp.exp(dl * a) * h + (dl * xv) * bt[:, j:j + 1]
            y_ref[j:j + 1, :] = (jnp.sum(h * ct[:, j:j + 1], axis=0,
                                         keepdims=True) + d * xv)
        h_scr[...] = h

    @pl.when(t0 >= length)
    def _past():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(ti == pl.num_programs(2) - 1)
    def _last():
        h_ref[...] = h_scr[...]


@functools.partial(jax.jit, static_argnames=("channels", "interpret"))
def selective_scan(x, delta, a, b, c, d, h0, lengths, *,
                   channels: int = _CHANNELS,
                   interpret: bool | None = None):
    """The recurrence over a wave of right-padded rows.

    x, delta [K, T, C] (the mixer's convolved input and its step, taken
    in float32); a [N, C] float32, the NEGATIVE decay rates; b, c
    [K, T, N]; d [C]; h0 [K, N, C] float32; lengths [K] int32: row k's
    positions `lengths[k] ..` are padding.  Returns (y [K, T, C]
    float32, zeros in blocks wholly past a row's length; h [K, N, C]
    float32 after each row's last real position)."""
    K, T, C = x.shape
    N = a.shape[0]
    reason = unsupported_reason(x.shape, N, channels)
    if reason is not None:
        raise ValueError(f"{KERNEL_NAME}: {reason}")
    interpret = _resolve_interpret(interpret)
    f32 = jnp.float32
    tc = _tile(C, channels)
    steps = _STEPS

    def last_block(k, lens):
        # a block past the row's length repeats the index of the last
        # one that holds a real position: its inputs are not read again
        return jnp.maximum(lens[k] - 1, 0) // steps

    rows = pl.BlockSpec(
        (pl.squeezed, steps, tc),
        lambda k, ci, ti, lens: (k, jnp.minimum(ti, last_block(k, lens)), ci))
    cols = pl.BlockSpec(
        (pl.squeezed, N, steps),
        lambda k, ci, ti, lens: (k, 0, jnp.minimum(ti, last_block(k, lens))))
    state = pl.BlockSpec((pl.squeezed, N, tc),
                         lambda k, ci, ti, lens: (k, 0, ci))
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, steps=steps),
        out_shape=(jax.ShapeDtypeStruct((K, T, C), f32),
                   jax.ShapeDtypeStruct((K, N, C), f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(K, C // tc, T // steps),
            in_specs=[rows, rows, cols, cols,
                      pl.BlockSpec((N, tc), lambda k, ci, ti, lens: (0, ci)),
                      pl.BlockSpec((1, tc), lambda k, ci, ti, lens: (0, ci)),
                      state],
            out_specs=(pl.BlockSpec((pl.squeezed, steps, tc),
                                    lambda k, ci, ti, lens: (k, ti, ci)),
                       state),
            scratch_shapes=[pltpu.VMEM((N, tc), f32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(lengths.astype(jnp.int32), x.astype(f32), delta.astype(f32),
      jnp.swapaxes(b, 1, 2).astype(f32), jnp.swapaxes(c, 1, 2).astype(f32),
      a.astype(f32), d.astype(f32).reshape(1, C), h0.astype(f32))
    return y, h


def selective_scan_reference(x, delta, a, b, c, d, h0, lengths):
    """The same recurrence as a plain `lax.scan` over time: the CPU path
    of the layer and what the kernel is tested against.  Same arguments
    and results as `selective_scan` (y is computed at every position)."""
    f32 = jnp.float32
    T = x.shape[1]
    keep = jnp.arange(T)[None, :] < lengths[:, None]              # [K, T]
    delta = jnp.where(keep[..., None], delta.astype(f32), 0.0)
    x = x.astype(f32)

    def step(h, t):
        dl, xv, bv, cv = t            # [K, C], [K, C], [K, N], [K, N]
        h = jnp.exp(dl[:, None, :] * a) * h \
            + (dl * xv)[:, None, :] * bv[:, :, None]
        return h, jnp.sum(h * cv[:, :, None], axis=1) + d * xv

    tm = lambda v: jnp.swapaxes(v, 0, 1)  # noqa: E731 - time-major
    h, y = jax.lax.scan(step, h0.astype(f32),
                        (tm(delta), tm(x), tm(b.astype(f32)),
                         tm(c.astype(f32))))
    return tm(y), h

"""The held experts' grouped SwiGLU over a few rows — Pallas TPU kernel.

A routed expert layer (`nn/layers/moe.py::held_experts_swiglu`) sorts its
(token, expert) rows by expert and computes, for each group of rows,

    y = down_e((silu(rows @ gate_e) * (rows @ up_e)).astype(rows.dtype))

XLA's grouped product (`jax.lax.ragged_dot`, three calls) does this for
any number of rows.  A decode dispatch has few: some 8 rows over 32
experts a layer, so the product is bound by the experts' weight bytes and
nothing else.  This kernel reads each touched expert's three matrices
once, and an expert with no rows not at all:

- the whole rows array `[M, D]` sits in VMEM, and the grid walks
  (expert, F-tile): `E x F / tf` steps a call, whatever the rows;
- the groups' offsets ride as scalar prefetch.  The weight index maps of
  an expert with no rows point at the block already resident (the last
  one of the touched expert before it, or the first one of the first
  touched expert), so no DMA is issued, and its step computes nothing
  (`pl.when`): the clamp the flash kernels use for skipped tiles;
- inside a touched step the expert's rows are read in tiles of
  `ROW_TILE` (bfloat16's sublane tile) at aligned offsets: a tile that
  holds rows of a neighbouring group is multiplied whole and its other
  rows masked out when they are added; rows past the last group (idle
  slots, absent experts) lie in no group and are never added;
- gate and up go into the MXU in the input dtype with float32
  accumulation, the product `silu(a) * b` is cast to the input dtype
  before the down product (as the XLA path rounds it), and the down
  product accumulates over F-tiles in a float32 VMEM scratch that is
  written out once, in the input dtype.

`grouped_swiglu_xla` is the same computation as three `ragged_dot` calls:
the path for many rows, the kernel's backward pass (`jax.vjp` of it) and
what the kernel is tested against.  Interpret mode on the CPU (the
parity tests), Mosaic on the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.kernels.flash_attention import _ceil_to, \
    _resolve_interpret

KERNEL_NAME = "dl4tpu_grouped_swiglu"

ROW_TILE = 16

# The most rows (`N x k`) a layer hands the kernel: every decode program
# of the expert cells (512, 256, 256) and waves of up to 256 tokens at 4
# a token.  On a v5e the kernel takes 2.3-2.9 times less than
# `ragged_dot` at 1,024 rows, and 2,048 rows of 4,096 columns would fill
# the core's VMEM (`scripts/moe_gmm_cost.py`).
MAX_ROWS = 1024

# The most bytes one grid step's three weight blocks may hold (each is
# double-buffered): the F-tile is the largest multiple of 128 that
# divides F within it.
_STEP_WEIGHT_BYTES = 12 << 20


def f_tile(d: int, f: int, itemsize: int) -> int:
    """The F-tile for experts of width `f` over `d`: the largest
    multiple of 128 dividing `f` whose three `[d, tile]` blocks fit
    `_STEP_WEIGHT_BYTES`; `f` whole where it is no multiple of 128."""
    if f % 128:
        return f
    fits = [t for t in range(128, f + 1, 128)
            if f % t == 0 and 3 * d * t * itemsize <= _STEP_WEIGHT_BYTES]
    return max(fits) if fits else 128


def vmem_bytes(m: int, d: int, tf: int, itemsize: int) -> int:
    """VMEM a call holds: the rows and the result, double-buffered, the
    float32 accumulator, and one step's three weight blocks,
    double-buffered."""
    return 4 * m * d * itemsize + 4 * m * d + 2 * 3 * d * tf * itemsize


def group_blocks(sizes, nf: int):
    """sizes [E] -> (starts [E+1]: each group's first row and the end of
    the last; blk [E]: the weight block, `expert * nf + F-tile`, that an
    expert with no rows leaves resident: the last F-tile of the touched
    expert before it, else the first of the first touched expert)."""
    sizes = sizes.astype(jnp.int32)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(sizes, dtype=jnp.int32)])
    idx = jnp.arange(sizes.shape[0], dtype=jnp.int32)
    touched = sizes > 0
    before = jax.lax.cummax(jnp.where(touched, idx, -1))
    first = jnp.argmax(touched).astype(jnp.int32)
    return starts, jnp.where(before >= 0, before * nf + nf - 1, first * nf)


def weight_block(e, j, starts, blk, *, nf: int):
    """(expert, F-tile) whose weights grid step (e, j) reads: its own
    where expert e has rows, else the block already resident (no
    DMA)."""
    rows = starts[e + 1] > starts[e]
    return jnp.where(rows, e, blk[e] // nf), jnp.where(rows, j, blk[e] % nf)


def _grouped_kernel(starts_ref, blk_ref, rows_ref, wg_ref, wu_ref, wd_ref,
                    out_ref, acc_ref, *, n_experts, nf):
    e, j = pl.program_id(0), pl.program_id(1)
    s, end = starts_ref[e], starts_ref[e + 1]

    @pl.when((e == 0) & (j == 0))
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    @pl.when(end > s)
    def _():
        def tile(t, carry):
            r0 = pl.multiple_of(t * ROW_TILE, ROW_TILE)
            x = rows_ref[pl.ds(r0, ROW_TILE), :]
            a = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
            b = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
            m = (jax.nn.silu(a) * b).astype(x.dtype)
            y = jnp.dot(m, wd_ref[...], preferred_element_type=jnp.float32)
            r = r0 + jax.lax.broadcasted_iota(jnp.int32, (ROW_TILE, 1), 0)
            acc_ref[pl.ds(r0, ROW_TILE), :] += jnp.where(
                (r >= s) & (r < end), y, 0.0)
            return carry

        jax.lax.fori_loop(s // ROW_TILE, (end - 1) // ROW_TILE + 1, tile, 0)

    @pl.when((e == n_experts - 1) & (j == nf - 1))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _grouped_call(rows, sizes, w_gate, w_up, w_down, *, interpret):
    M, D = rows.shape
    E, _, F = w_gate.shape
    Mp = _ceil_to(M, ROW_TILE)
    tf = f_tile(D, F, w_gate.dtype.itemsize)
    nf = F // tf
    starts, blk = group_blocks(sizes, nf)

    def gate_up(e, j, *p):
        x, t = weight_block(e, j, *p, nf=nf)
        return x, 0, t

    def down(e, j, *p):
        x, t = weight_block(e, j, *p, nf=nf)
        return x, t, 0

    whole = pl.BlockSpec((Mp, D), lambda e, j, *_: (0, 0))
    out = pl.pallas_call(
        functools.partial(_grouped_kernel, n_experts=E, nf=nf),
        out_shape=jax.ShapeDtypeStruct((Mp, D), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(E, nf),
            in_specs=[
                whole,
                pl.BlockSpec((pl.squeezed, D, tf), gate_up),
                pl.BlockSpec((pl.squeezed, D, tf), gate_up),
                pl.BlockSpec((pl.squeezed, tf, D), down),
            ],
            out_specs=whole,
            scratch_shapes=[pltpu.VMEM((Mp, D), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_bytes(Mp, D, tf, rows.dtype.itemsize)
            + (8 << 20)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(starts, blk, jnp.pad(rows, ((0, Mp - M), (0, 0))) if Mp > M else rows,
      w_gate, w_up, w_down)
    return out[:M] if Mp > M else out


def grouped_swiglu_xla(rows, sizes, w_gate, w_up, w_down):
    """rows [M, D] sorted by expert, sizes [E] the rows of each group
    (rows past their sum belong to none), w_gate, w_up [E, D, F], w_down
    [E, F, D] -> [M, D] in rows.dtype: XLA's grouped products."""
    a = jax.lax.ragged_dot(rows, w_gate, sizes)
    b = jax.lax.ragged_dot(rows, w_up, sizes)
    return jax.lax.ragged_dot((jax.nn.silu(a) * b).astype(rows.dtype),
                              w_down, sizes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _grouped(rows, sizes, w_gate, w_up, w_down, interpret):
    return _grouped_call(rows, sizes, w_gate, w_up, w_down,
                         interpret=interpret)


def _grouped_fwd(rows, sizes, w_gate, w_up, w_down, interpret):
    return (_grouped_call(rows, sizes, w_gate, w_up, w_down,
                          interpret=interpret),
            (rows, sizes, w_gate, w_up, w_down))


def _grouped_bwd(interpret, res, g):
    rows, sizes, *weights = res
    _, vjp = jax.vjp(lambda r, wg, wu, wd: grouped_swiglu_xla(
        r, sizes, wg, wu, wd), rows, *weights)
    d_rows, d_gate, d_up, d_down = vjp(g)
    return d_rows, None, d_gate, d_up, d_down


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_swiglu(rows, sizes, w_gate, w_up, w_down, *, interpret=None):
    """`grouped_swiglu_xla`'s result from the kernel: same arguments,
    the same rows of the result for rows that lie in a group (the others
    are zeros).  Its gradient is `grouped_swiglu_xla`'s."""
    return _grouped(rows, sizes, w_gate, w_up, w_down,
                    _resolve_interpret(interpret))

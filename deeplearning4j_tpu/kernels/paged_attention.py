"""Length-bounded paged decode attention — Pallas TPU kernel.

The single-token decode step of the serving engine
(`MultiHeadAttention.forward_with_paged_cache`) used to gather every
slot's whole block table out of the pool — a `[S, budget, H, Dh]` view
of K and one of V per layer per step, whatever the slots held — and
score all of it under a mask. This kernel attends over the pool IN
PLACE: the block table and each slot's length ride as scalar-prefetch
operands, and a slot's program DMAs only the pages up to its own
length from the pool in HBM into VMEM. A slot of length 0 (idle, or
finished inside a fused chunk) reads nothing and returns zeros.

Design:
- pool pages are `[block_len, H*Dh]` slabs (`serving/paged.py` keeps
  the pools `[n_blocks, block_len, H*Dh]`): all heads of a page are one
  contiguous, lane-dense tile run, so a page is ONE DMA and nothing in
  the kernel slices the lane axis per head;
- grid = one program per slot; inside, a `fori_loop` over page groups
  (`_GROUP_POSITIONS` positions each) whose trip count is the slot's
  own `ceil(n_pages / pages_per_group)` — data, never a shape. Groups
  are double-buffered: group g+1's page DMAs are in flight while group
  g is scored. Pages of the last group past the slot's length are not
  read; their V rows are zeroed in VMEM instead (K rows need nothing:
  their scores are masked);
- heads without lane slicing: the query row `[1, H*Dh]` is spread to a
  block-diagonal `[H, H*Dh]` (row h keeps head h's Dh lanes), so
  `Q_bd @ K^T` is every head's scores in one MXU call and
  `P @ V` `[H, H*Dh]` holds head h's output in row h's own Dh lanes —
  the diagonal blocks, summed over rows, are the `[1, H*Dh]` output
  row the `Wo` projection wants. The off-diagonal products are wasted
  MXU work on a step that is bound by bytes, not FLOPs;
- online softmax across groups in fp32 (running max / denominator /
  accumulator in VMEM scratch), K·Q and P·V on the MXU with fp32
  accumulation, P cast to the pool dtype for the second product (the
  flash kernels' convention).

Interpret mode on the CPU (the parity tests), Mosaic on the chip;
`MultiHeadAttention` selects it through `kernels_enabled()` and
`unsupported_reason` (shapes the kernel cannot tile take the gather
path).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.kernels.flash_attention import (
    _ceil_to,
    _resolve_interpret,
)

KERNEL_NAME = "dl4tpu_paged_decode"

_NEG_INF = -1e30
# positions one page group holds: 128 keys fill the MXU's output lanes,
# and at block_len 16 a group is 8 page DMAs per pool (256 KB of bf16 K
# at H*Dh = 1024; K + V double-buffered = 1 MB of VMEM)
_GROUP_POSITIONS = 128


def _sublane_tile(dtype) -> int:
    """Rows of one (sublane, lane) tile: 8 for 4-byte, 16 for 2-byte,
    32 for 1-byte elements."""
    return 32 // np.dtype(dtype).itemsize


def unsupported_reason(pool_shape, dtype, n_heads: int) -> Optional[str]:
    """Why the kernel cannot tile a pool of this shape/dtype (None: it
    can). A page must be whole tiles: `block_len` a multiple of the
    dtype's sublane tile, `H*Dh` a multiple of the 128 lanes."""
    if len(pool_shape) != 3:
        return f"pool rank {len(pool_shape)} is not [n_blocks, bl, H*Dh]"
    _, bl, hd = pool_shape
    if hd % n_heads:
        return f"H*Dh {hd} is not a multiple of n_heads {n_heads}"
    if hd % 128:
        return f"H*Dh {hd} is not a multiple of the 128 lanes"
    if bl % _sublane_tile(dtype):
        return (f"block_len {bl} is not a multiple of the "
                f"{_sublane_tile(dtype)}-row sublane tile of "
                f"{np.dtype(dtype).name}")
    return None


def _paged_decode_kernel(tables_ref, lens_ref, q_ref, k_hbm, v_hbm, o_ref,
                         kbuf, vbuf, sems, m_scr, l_scr, acc_scr, *,
                         bl: int, pages: int, head_dim: int,
                         scale: float):
    """One slot: q_ref/o_ref [1, H*Dh]; k_hbm/v_hbm the whole pools
    [n_blocks, bl, H*Dh] in HBM; kbuf/vbuf [2, pages*bl, H*Dh]."""
    s = pl.program_id(0)
    length = lens_ref[s]
    n_pages = (length + (bl - 1)) // bl
    n_groups = (n_pages + (pages - 1)) // pages
    T = pages * bl
    Hp, HD = acc_scr.shape

    def page_copies(group, buf, j):
        page = tables_ref[s, group * pages + j]
        rows = pl.ds(j * bl, bl)
        return (pltpu.make_async_copy(k_hbm.at[page], kbuf.at[buf, rows],
                                      sems.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[page], vbuf.at[buf, rows],
                                      sems.at[1, buf]))

    def start_group(group, buf):
        for j in range(pages):
            held = group * pages + j < n_pages

            @pl.when(held)
            def _read():
                for c in page_copies(group, buf, j):
                    c.start()

            @pl.when(jnp.logical_not(held))
            def _blank():
                # never read: whatever VMEM held here (stale pages,
                # or NaN bit patterns at start-up) must not reach
                # 0-weight * V
                vbuf[buf, pl.ds(j * bl, bl), :] = jnp.zeros(
                    (bl, HD), vbuf.dtype)

    def wait_group(group, buf):
        for j in range(pages):
            @pl.when(group * pages + j < n_pages)
            def _wait():
                for c in page_copies(group, buf, j):
                    c.wait()

    row = jax.lax.broadcasted_iota(jnp.int32, (Hp, HD), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Hp, HD), 1)
    diag = jnp.logical_and(col >= row * head_dim,
                           col < (row + 1) * head_dim)
    q_bd = jnp.where(diag, q_ref[...].astype(jnp.float32),
                     0.0).astype(kbuf.dtype)              # [Hp, HD]

    m_scr[...] = jnp.full_like(m_scr[...], _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr[...])
    acc_scr[...] = jnp.zeros_like(acc_scr[...])

    @pl.when(n_groups > 0)
    def _prime():
        start_group(0, 0)

    def group_step(g, carry):
        buf = jax.lax.rem(g, 2)

        @pl.when(g + 1 < n_groups)
        def _prefetch():
            start_group(g + 1, 1 - buf)

        wait_group(g, buf)
        k = kbuf[buf]                                     # [T, HD]
        v = vbuf[buf]
        sc = jax.lax.dot_general(
            q_bd, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [Hp, T]
        k_pos = g * T + jax.lax.broadcasted_iota(jnp.int32, (Hp, T), 1)
        sc = jnp.where(k_pos < length, sc, _NEG_INF)
        m = m_scr[...]                                    # [Hp, 1]
        m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
        p = jnp.exp(sc - m_new)
        corr = jnp.exp(m - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, n_groups, group_step, 0)

    l = l_scr[...]
    inv = 1.0 / jnp.where(l == 0.0, 1.0, l)               # length 0 -> 0
    o = jnp.sum(jnp.where(diag, acc_scr[...] * inv, 0.0), axis=0,
                keepdims=True)                            # [1, HD]
    o_ref[...] = o.astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, block_table, lengths, *,
                           n_heads: int,
                           interpret: bool | None = None):
    """Single-token attention of every slot over the pages it holds.

    q [S, 1, H*Dh] (the new token's projected query, heads side by
    side); k_pool, v_pool [n_blocks, bl, H*Dh]; block_table
    [S, max_blocks] int32 (slot-local block index -> pool block id);
    lengths [S] int32: the positions slot s attends over, `0 ..
    lengths[s]-1` — 0 for a slot that is not decoding, which then reads
    no page and returns zeros. Returns [S, 1, H*Dh] in q.dtype.

    Reads `sum(ceil(lengths / bl))` pages of K and of V, nothing past a
    slot's length. Parity contract: the gather +
    `MultiHeadAttention._attend_cached` path, to a tolerance (the
    online softmax sums in another order, in fp32)."""
    reason = unsupported_reason(k_pool.shape, k_pool.dtype, n_heads)
    if reason is not None:
        raise ValueError(f"{KERNEL_NAME}: {reason}")
    interpret = _resolve_interpret(interpret)
    S, _, HD = q.shape
    bl = k_pool.shape[1]
    head_dim = HD // n_heads
    max_blocks = block_table.shape[1]
    pages = max(1, min(_GROUP_POSITIONS // bl, max_blocks))
    Hp = _ceil_to(n_heads, _sublane_tile(k_pool.dtype))
    T = pages * bl
    row = pl.BlockSpec((pl.squeezed, 1, HD), lambda s, tables, lens:
                       (s, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_paged_decode_kernel, bl=bl, pages=pages,
                          head_dim=head_dim,
                          scale=1.0 / float(np.sqrt(head_dim))),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[row, pool, pool],
            out_specs=row,
            scratch_shapes=[
                pltpu.VMEM((2, T, HD), k_pool.dtype),     # K groups
                pltpu.VMEM((2, T, HD), v_pool.dtype),     # V groups
                pltpu.SemaphoreType.DMA((2, 2)),          # (K|V, buffer)
                pltpu.VMEM((Hp, 1), jnp.float32),         # running max m
                pltpu.VMEM((Hp, 1), jnp.float32),         # running denom l
                pltpu.VMEM((Hp, HD), jnp.float32),        # accumulator
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32), q,
      k_pool, v_pool)

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

Grouped queries and a window (`nn/layers/parallel.py`): with fewer key
heads than query heads (`n_kv_heads`) the pool's pages are `Hkv*Dh`
wide, the query arrives `[Hkv, G, Dh]` and key head h's lane run
`[T, Dh]` is scored against its own G query rows (`[G, Dh] @ [Dh, T]`,
no block diagonal: the G rows fill the MXU's sublanes instead).  With
`starts` a slot attends over positions `starts[s] .. lengths[s]-1`
alone: pages wholly before `starts[s]` are never read, and the block
table is a RING, logical page b at column `b % max_blocks`, so a window
layer's table holds `ceil(window / bl) + 1` columns whatever the slot's
length.  A key head narrower than the 128 lanes (`Dh` 64: LFM2) cannot
be sliced out of its page: the query then arrives `[G, Hkv*Dh]` (row g
holds query head `h*G + g` in key head h's lanes), is spread to a
block-diagonal `[G*Hkv, Hkv*Dh]` (row `g*Hkv + h` keeps key head h's
lanes), scored against the whole page in one product, and the diagonal
blocks of `P @ V` summed over the key heads give `[G, Hkv*Dh]`: the
block-diagonal trick of the plain variant, G rows at a time.  All three
are static variants of the one kernel: without them the program is what
it was.

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
# the grouped variant's: a page of 8 key heads x 128 is a quarter of a
# 32-head page, and groups of 512 read 78-85% of the v5e's bandwidth at
# 4-9k positions where groups of 128 read 48-50 (chip, PR 33)
_GROUPED_GROUP_POSITIONS = 512


def _sublane_tile(dtype) -> int:
    """Rows of one (sublane, lane) tile: 8 for 4-byte, 16 for 2-byte,
    32 for 1-byte elements."""
    return 32 // np.dtype(dtype).itemsize


def unsupported_reason(pool_shape, dtype, n_heads: int,
                       n_kv_heads: Optional[int] = None) -> Optional[str]:
    """Why the kernel cannot tile a pool of this shape/dtype (None: it
    can). A page must be whole tiles: `block_len` a multiple of the
    dtype's sublane tile, `H*Dh` a multiple of the 128 lanes (`H` the
    key heads a page holds: `n_kv_heads`, by default `n_heads`).  With
    fewer key heads than query heads each key head's lanes are sliced
    out of the page where `Dh` is whole lane tiles; a narrower key head
    takes the block-diagonal form, whose `[G, Hkv, Dh]` blocks need
    `Hkv` a multiple of the 8 float32 sublanes."""
    if len(pool_shape) != 3:
        return f"pool rank {len(pool_shape)} is not [n_blocks, bl, H*Dh]"
    _, bl, hd = pool_shape
    n_kv = n_heads if n_kv_heads is None else n_kv_heads
    if n_heads % n_kv:
        return f"n_heads {n_heads} is not a multiple of n_kv_heads {n_kv}"
    if hd % n_kv:
        return f"H*Dh {hd} is not a multiple of n_heads {n_kv}"
    if n_kv != n_heads and (hd // n_kv) % 128 and n_kv % 8:
        return (f"head_dim {hd // n_kv} of a grouped-query pool is not a "
                f"multiple of the 128 lanes, and its {n_kv} key heads are "
                f"not a multiple of the 8 sublanes")
    if hd % 128:
        return f"H*Dh {hd} is not a multiple of the 128 lanes"
    if bl % _sublane_tile(dtype):
        return (f"block_len {bl} is not a multiple of the "
                f"{_sublane_tile(dtype)}-row sublane tile of "
                f"{np.dtype(dtype).name}")
    return None


def _paged_decode_kernel(*refs, bl: int, pages: int, head_dim: int,
                         scale: float, grouped: bool, windowed: bool,
                         ring: int, narrow: bool = False):
    """One slot: q_ref/o_ref [1, H*Dh] (grouped: [Hkv, G, Dh]; narrow
    grouped: [G, Hkv*Dh]);
    k_hbm/v_hbm the whole pools [n_blocks, bl, H*Dh] in HBM; kbuf/vbuf
    [2, pages*bl, H*Dh].  `windowed`: a third scalar operand, each
    slot's first position, and a table of `ring` columns read as a
    ring."""
    if windowed:
        tables_ref, lens_ref, starts_ref, *refs = refs
    else:
        tables_ref, lens_ref, *refs = refs
    (q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, m_scr, l_scr,
     acc_scr) = refs
    s = pl.program_id(0)
    length = lens_ref[s]
    n_pages = (length + (bl - 1)) // bl
    T = pages * bl
    HD = kbuf.shape[-1]
    if windowed:
        start = starts_ref[s]
        first = start // bl            # the first logical page read
        n_groups = jnp.maximum(n_pages - first + (pages - 1), 0) // pages
    else:
        start, first = 0, 0
        n_groups = (n_pages + (pages - 1)) // pages

    def logical_page(group, j):
        n = group * pages + j
        return first + n if windowed else n

    def page_copies(group, buf, j):
        logical = logical_page(group, j)
        page = tables_ref[s, logical % ring if windowed else logical]
        rows = pl.ds(j * bl, bl)
        return (pltpu.make_async_copy(k_hbm.at[page], kbuf.at[buf, rows],
                                      sems.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[page], vbuf.at[buf, rows],
                                      sems.at[1, buf]))

    def start_group(group, buf):
        for j in range(pages):
            held = logical_page(group, j) < n_pages

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
            @pl.when(logical_page(group, j) < n_pages)
            def _wait():
                for c in page_copies(group, buf, j):
                    c.wait()

    def masked(sc, g):
        """Scores [rows, T] of group g under the slot's own length (and
        first position)."""
        k_pos = g * T + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        if windowed:
            k_pos = first * bl + k_pos
            return jnp.where(jnp.logical_and(k_pos < length, k_pos >= start),
                             sc, _NEG_INF)
        return jnp.where(k_pos < length, sc, _NEG_INF)

    def online(sc, v, m_ref, l_ref, acc_ref):
        """One online-softmax update of (m, l, acc) by scores sc
        [rows, T] and values v [T, width]."""
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
        p = jnp.exp(sc - m_new)
        corr = jnp.exp(m - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    nt = (((1,), (1,)), ((), ()))
    if narrow:
        G = q_ref.shape[0]
        n_kv = HD // head_dim
        # [G, Hkv, HD]: block (g, h) keeps key head h's lanes
        h_of = jax.lax.broadcasted_iota(jnp.int32, (G, n_kv, HD), 1)
        col = jax.lax.broadcasted_iota(jnp.int32, (G, n_kv, HD), 2)
        diag = jnp.logical_and(col >= h_of * head_dim,
                               col < (h_of + 1) * head_dim)
        q_bd = jnp.where(diag, q_ref[...].astype(jnp.float32)[:, None, :],
                         0.0).reshape(G * n_kv, HD).astype(kbuf.dtype)

        def attend(g, k, v):
            sc = jax.lax.dot_general(
                q_bd, k, nt, preferred_element_type=jnp.float32) * scale
            online(masked(sc, g), v, m_scr, l_scr, acc_scr)   # [G*Hkv, T]
    elif grouped:
        n_kv = q_ref.shape[0]
        q = q_ref[...].astype(kbuf.dtype)                 # [Hkv, G, Dh]

        def attend(g, k, v):
            for h in range(n_kv):
                lanes = slice(h * head_dim, (h + 1) * head_dim)
                sc = jax.lax.dot_general(
                    q[h], k[:, lanes], nt,
                    preferred_element_type=jnp.float32) * scale   # [G, T]
                online(masked(sc, g), v[:, lanes], m_scr.at[h],
                       l_scr.at[h], acc_scr.at[h])
    else:
        Hp = acc_scr.shape[0]
        row = jax.lax.broadcasted_iota(jnp.int32, (Hp, HD), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (Hp, HD), 1)
        diag = jnp.logical_and(col >= row * head_dim,
                               col < (row + 1) * head_dim)
        q_bd = jnp.where(diag, q_ref[...].astype(jnp.float32),
                         0.0).astype(kbuf.dtype)          # [Hp, HD]

        def attend(g, k, v):
            sc = jax.lax.dot_general(
                q_bd, k, nt, preferred_element_type=jnp.float32) * scale
            online(masked(sc, g), v, m_scr, l_scr, acc_scr)   # [Hp, T]

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
        attend(g, kbuf[buf], vbuf[buf])                   # [T, HD] each
        return carry

    jax.lax.fori_loop(0, n_groups, group_step, 0)

    l = l_scr[...]
    inv = 1.0 / jnp.where(l == 0.0, 1.0, l)               # length 0 -> 0
    if narrow:
        o = jnp.sum(jnp.where(diag, (acc_scr[...] * inv).reshape(
            G, n_kv, HD), 0.0), axis=1)                   # [G, HD]
        o_ref[...] = o.astype(o_ref.dtype)
    elif grouped:
        o_ref[...] = (acc_scr[...] * inv).astype(o_ref.dtype)
    else:
        o = jnp.sum(jnp.where(diag, acc_scr[...] * inv, 0.0), axis=0,
                    keepdims=True)                        # [1, HD]
        o_ref[...] = o.astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, block_table, lengths, *,
                           n_heads: int,
                           n_kv_heads: Optional[int] = None,
                           starts=None,
                           group_positions: Optional[int] = None,
                           interpret: bool | None = None):
    """Single-token attention of every slot over the pages it holds.

    q [S, 1, H*Dh] (the new token's projected query, heads side by
    side); k_pool, v_pool [n_blocks, bl, Hkv*Dh] (`Hkv = n_kv_heads`,
    by default H: query head i reads key head `i // (H / Hkv)`);
    block_table [S, max_blocks] int32 (slot-local block index -> pool
    block id); lengths [S] int32: the positions slot s attends over,
    `0 .. lengths[s]-1` — 0 for a slot that is not decoding, which then
    reads no page and returns zeros. `starts` [S] int32 (None: 0):
    positions before `starts[s]` are not attended and pages wholly
    before it not read; the table is then a ring, logical page b at
    column `b % max_blocks`. `group_positions` (None: the variant's own
    constant) is there for the tests, which cross group edges at small
    sizes, and the microbenchmark. Returns [S, 1, H*Dh] in q.dtype.

    Reads `sum(ceil(lengths / bl) - starts // bl)` pages of K and of
    V, nothing past a slot's length. Parity contract: the gather +
    `MultiHeadAttention._attend_cached` path, to a tolerance (the
    online softmax sums in another order, in fp32)."""
    n_kv = n_heads if n_kv_heads is None else int(n_kv_heads)
    reason = unsupported_reason(k_pool.shape, k_pool.dtype, n_heads, n_kv)
    if reason is not None:
        raise ValueError(f"{KERNEL_NAME}: {reason}")
    interpret = _resolve_interpret(interpret)
    S = q.shape[0]
    bl, HD = k_pool.shape[1:]
    head_dim = HD // n_kv
    grouped, windowed = n_kv != n_heads, starts is not None
    narrow = grouped and head_dim % 128 != 0
    max_blocks = block_table.shape[1]
    group = group_positions or (_GROUPED_GROUP_POSITIONS if grouped
                                else _GROUP_POSITIONS)
    pages = max(1, min(group // bl, max_blocks))
    T = pages * bl
    if narrow:
        G = n_heads // n_kv
        # row g: query head h*G + g in key head h's lanes
        q_in = q.reshape(S, n_kv, G, head_dim).transpose(0, 2, 1, 3).reshape(
            S, G, HD)
        row = pl.BlockSpec((pl.squeezed, G, HD), lambda s, *_: (s, 0, 0))
        state = [pltpu.VMEM((n_heads, 1), jnp.float32),      # running max
                 pltpu.VMEM((n_heads, 1), jnp.float32),      # running denom
                 pltpu.VMEM((n_heads, HD), jnp.float32)]     # accumulator
    elif grouped:
        G = n_heads // n_kv
        q_in = q.reshape(S, n_kv, G, head_dim)
        row = pl.BlockSpec((pl.squeezed, n_kv, G, head_dim),
                           lambda s, *_: (s, 0, 0, 0))
        state = [pltpu.VMEM((n_kv, G, 1), jnp.float32),       # running max
                 pltpu.VMEM((n_kv, G, 1), jnp.float32),       # running denom
                 pltpu.VMEM((n_kv, G, head_dim), jnp.float32)]  # accumulator
    else:
        Hp = _ceil_to(n_heads, _sublane_tile(k_pool.dtype))
        q_in = q
        row = pl.BlockSpec((pl.squeezed, 1, HD), lambda s, *_: (s, 0, 0))
        state = [pltpu.VMEM((Hp, 1), jnp.float32),        # running max m
                 pltpu.VMEM((Hp, 1), jnp.float32),        # running denom l
                 pltpu.VMEM((Hp, HD), jnp.float32)]       # accumulator
    pool = pl.BlockSpec(memory_space=pl.ANY)
    scalars = [block_table.astype(jnp.int32), lengths.astype(jnp.int32)]
    if windowed:
        scalars.append(starts.astype(jnp.int32))
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, bl=bl, pages=pages,
                          head_dim=head_dim,
                          scale=1.0 / float(np.sqrt(head_dim)),
                          grouped=grouped, windowed=windowed,
                          ring=max_blocks, narrow=narrow),
        out_shape=jax.ShapeDtypeStruct(q_in.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(S,),
            in_specs=[row, pool, pool],
            out_specs=row,
            scratch_shapes=[
                pltpu.VMEM((2, T, HD), k_pool.dtype),     # K groups
                pltpu.VMEM((2, T, HD), v_pool.dtype),     # V groups
                pltpu.SemaphoreType.DMA((2, 2)),          # (K|V, buffer)
                *state,
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(*scalars, q_in, k_pool, v_pool)
    if narrow:
        out = out.reshape(S, G, n_kv, head_dim).transpose(0, 2, 1, 3)
    return out.reshape(q.shape)

"""Paged decode attention over a LATENT pool — Pallas TPU kernel.

The single-token decode step of a latent-attention block
(`nn/layers/latent.py::LatentAttentionBlock.paged_step`) in the absorbed
form: every head's query has been carried into the latent space, so all
heads score against ONE cached row a position (the `kv_lora_rank`-wide
latent beside the rotated key), and the probabilities weigh that same
latent.  Keys and values are one array: a page is read once and used
twice.

On the pattern of `paged_attention.py` (`dl4tpu_paged_decode`): the block
table and each slot's length ride as scalar-prefetch operands, one
program a slot DMAs only the pages up to its own length from the pool in
HBM into VMEM, page groups are double-buffered, the softmax runs online
in float32 across groups.  A slot of length 0 reads nothing and returns
zeros.

- the pool is `[n_blocks, block_len, W]`, `W` the cache row padded to
  whole 128-lane tiles (576 -> 640 at the published widths); the query
  is `[H, W]`, zero in the padding lanes, so `Q @ page^T` is every
  head's scores in one MXU call, no lane sliced;
- `P @ page` is `[H, W]`: its first `latent` lanes are the output, the
  rest (the rotated key's lanes, weighted) is computed and dropped:
  an eighth of the second product, on a step bound by bytes;
- intensity: H x (W + W) x 2 FLOPs over W x 2 bytes a position, 128
  FLOP/B at 64 heads against the v5e's ridge of 240.

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
from deeplearning4j_tpu.kernels.paged_attention import _sublane_tile

KERNEL_NAME = "dl4tpu_mla_paged_decode"

_NEG_INF = -1e30
# positions one page group holds: at block_len 64 a group is 8 page
# DMAs of 80 KB (W = 640, bf16); double-buffered 1.3 MB of VMEM
_GROUP_POSITIONS = 512


def unsupported_reason(pool_shape, dtype, n_heads: int,
                       latent: int) -> Optional[str]:
    """Why the kernel cannot tile a pool of this shape/dtype (None: it
    can)."""
    if len(pool_shape) != 3:
        return f"pool rank {len(pool_shape)} is not [n_blocks, bl, W]"
    _, bl, w = pool_shape
    if w % 128:
        return f"row width {w} is not a multiple of the 128 lanes"
    if latent % 128:
        return f"latent width {latent} is not a multiple of the 128 lanes"
    if bl % _sublane_tile(dtype):
        return (f"block_len {bl} is not a multiple of the "
                f"{_sublane_tile(dtype)}-row sublane tile")
    if n_heads % 8:
        return f"n_heads {n_heads} is not a multiple of 8 sublanes"
    return None


def _mla_decode_kernel(tables_ref, lens_ref, q_ref, pool_hbm, o_ref,
                       buf, sems, m_scr, l_scr, acc_scr, *,
                       bl: int, pages: int, scale: float):
    """One slot: q_ref/o_ref [H, W]; pool_hbm [n_blocks, bl, W] in HBM;
    buf [2, pages*bl, W]."""
    s = pl.program_id(0)
    length = lens_ref[s]
    n_pages = (length + (bl - 1)) // bl
    n_groups = (n_pages + (pages - 1)) // pages
    T = pages * bl
    H, W = acc_scr.shape

    def page_copy(group, slot, j):
        page = tables_ref[s, group * pages + j]
        return pltpu.make_async_copy(
            pool_hbm.at[page], buf.at[slot, pl.ds(j * bl, bl)],
            sems.at[slot])

    def start_group(group, slot):
        for j in range(pages):
            held = group * pages + j < n_pages

            @pl.when(held)
            def _read():
                page_copy(group, slot, j).start()

            @pl.when(jnp.logical_not(held))
            def _blank():
                # never read: stale VMEM must not reach 0-weight * row
                buf[slot, pl.ds(j * bl, bl), :] = jnp.zeros(
                    (bl, W), buf.dtype)

    def wait_group(group, slot):
        for j in range(pages):
            @pl.when(group * pages + j < n_pages)
            def _wait():
                page_copy(group, slot, j).wait()

    q = q_ref[...].astype(buf.dtype)                        # [H, W]
    m_scr[...] = jnp.full_like(m_scr[...], _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr[...])
    acc_scr[...] = jnp.zeros_like(acc_scr[...])

    @pl.when(n_groups > 0)
    def _prime():
        start_group(0, 0)

    def group_step(g, carry):
        slot = jax.lax.rem(g, 2)

        @pl.when(g + 1 < n_groups)
        def _prefetch():
            start_group(g + 1, 1 - slot)

        wait_group(g, slot)
        rows = buf[slot]                                    # [T, W]
        sc = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [H, T]
        k_pos = g * T + jax.lax.broadcasted_iota(jnp.int32, (H, T), 1)
        sc = jnp.where(k_pos < length, sc, _NEG_INF)
        m = m_scr[...]                                      # [H, 1]
        m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
        p = jnp.exp(sc - m_new)
        corr = jnp.exp(m - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p.astype(rows.dtype), rows, preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, n_groups, group_step, 0)

    l = l_scr[...]
    inv = 1.0 / jnp.where(l == 0.0, 1.0, l)                 # length 0 -> 0
    o_ref[...] = (acc_scr[...] * inv).astype(o_ref.dtype)


def mla_paged_decode_attention(q, pool, block_table, lengths, *,
                               latent: int, scale: float,
                               interpret: bool | None = None):
    """Absorbed single-token latent attention of every slot over the
    pages it holds.

    q [S, H, C]: each head's query in the cache row's own space (the
    absorbed `q_nope W_K[h]` beside the rotated `q_pe`; C <= W);
    pool [n_blocks, bl, W]; block_table [S, max_blocks] int32; lengths
    [S] int32: slot s attends over positions `0 .. lengths[s]-1`, none
    where 0.  Returns [S, H, latent] in q.dtype: the probabilities times
    the cached latent (the caller applies `W_V` and `W_o`).

    Reads `sum(ceil(lengths / bl))` pages, each once."""
    S, H, C = q.shape
    bl, W = pool.shape[1], pool.shape[2]
    reason = unsupported_reason(pool.shape, pool.dtype, H, latent)
    if reason is not None:
        raise ValueError(f"{KERNEL_NAME}: {reason}")
    interpret = _resolve_interpret(interpret)
    if C < W:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, W - C)))
    max_blocks = block_table.shape[1]
    pages = max(1, min(_GROUP_POSITIONS // bl, max_blocks))
    T = pages * bl
    row = pl.BlockSpec((pl.squeezed, H, W), lambda s, tables, lens:
                       (s, 0, 0))
    out = pl.pallas_call(
        functools.partial(_mla_decode_kernel, bl=bl, pages=pages,
                          scale=float(scale)),
        out_shape=jax.ShapeDtypeStruct((S, H, W), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[row, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row,
            scratch_shapes=[
                pltpu.VMEM((2, T, W), pool.dtype),        # page groups
                pltpu.SemaphoreType.DMA((2,)),            # one a buffer
                pltpu.VMEM((H, 1), jnp.float32),          # running max m
                pltpu.VMEM((H, 1), jnp.float32),          # running denom l
                pltpu.VMEM((H, W), jnp.float32),          # accumulator
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32), q, pool)
    return out[..., :latent]

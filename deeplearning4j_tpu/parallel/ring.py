"""Ring attention — sequence/context parallelism over a mesh axis.

No reference equivalent (the 2017 codebase scales sequences with TBPTT
only, SURVEY §5); this is new-design territory the TPU rebuild treats
as first-class: the sequence axis is sharded across devices, K/V blocks
rotate around the ICI ring via `ppermute`, and each device accumulates
its queries' attention with the numerically-stable online-softmax
(flash-attention style) running max/denominator. Math is EXACTLY
standard attention; wall-clock is one ring rotation (P-1 ppermutes)
with compute/communication overlap left to XLA.

Use inside `shard_map` over a mesh with a "seq" axis, or through
`sequence_parallel_attention` which wraps the shard_map for full
[B, T, H, D] arrays.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   use_flash: bool = False):
    """Per-shard blocks: q, k, v [B, T_local, H, Dh] (this device's
    sequence chunk). Returns o [B, T_local, H, Dh].

    Must run inside shard_map/pmap with `axis_name` bound.

    `use_flash=True` folds each rotated K/V block through the streaming
    Pallas carry kernel (`kernels.flash_attention.flash_attention_carry`)
    instead of the XLA einsum path: the local [T_local, T_local] score
    tile never materializes in HBM, compounding the sequence-parallel
    memory win with the flash one. Chunk visibility (fully visible /
    diagonal / fully masked) is dispatched by `lax.switch` on the
    rotated block's origin, so the kernels stay static.
    """
    if use_flash:
        return _ring_attention_flash(q, k, v, axis_name, causal)

    P_ = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    B, Tl, H, Dh = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(Dh, q.dtype))

    q_pos = idx * Tl + jnp.arange(Tl)                      # global positions

    def attend(acc, k_blk, v_blk, step):
        """Fold one K/V block into the online-softmax accumulator."""
        m, l, o = acc
        # the block currently held originated on device (idx + step) % P
        src = (idx + step) % P_
        k_pos = src * Tl + jnp.arange(Tl)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk) * scale
        if causal:
            ok = k_pos[None, :] <= q_pos[:, None]          # [Tq, Tk]
            scores = jnp.where(ok[None, None], scores, -jnp.inf)
        blk_max = jnp.max(scores, axis=-1)                 # [B,H,Tq]
        m_new = jnp.maximum(m, blk_max)
        # guard -inf rows (no valid key yet): exp(-inf - -inf) → use where
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(jnp.where(jnp.isfinite(scores),
                              scores - m_safe[..., None], -jnp.inf))
        p = jnp.where(jnp.isfinite(scores), p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, v_blk)
        return (m_new, l_new, o_new)

    perm = _ring_perm(P_)

    def block(carry, step):
        k_blk, v_blk, acc = carry
        acc = attend(acc, k_blk, v_blk, step)
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return (k_next, v_next, acc), None

    m0 = jnp.full((B, H, Tl), -jnp.inf, q.dtype)
    l0 = jnp.zeros((B, H, Tl), q.dtype)
    o0 = jnp.zeros((B, H, Tl, Dh), q.dtype)
    # P-1 (attend, rotate) steps, then fold the final block with no
    # trailing rotate — exactly P-1 ppermute rounds
    (k_f, v_f, acc), _ = lax.scan(block, (k, v, (m0, l0, o0)),
                                  jnp.arange(P_ - 1))
    m, l, o = attend(acc, k_f, v_f, P_ - 1)
    o = o / jnp.clip(l[..., None], 1e-20, None)
    return jnp.transpose(o, (0, 2, 1, 3))                  # [B,Tl,H,Dh]


def _ring_perm(P_):
    return [(j, (j - 1) % P_) for j in range(P_)]  # i receives from i+1


def _ring_case(idx, src):
    """0: src > idx (future chunk, fully masked), 1: diagonal,
    2: src < idx (past chunk, fully visible)."""
    return jnp.where(src < idx, 2, jnp.where(src == idx, 1, 0))


def _ring_flash_fwd_impl(q, k, v, axis_name, causal):
    """Flash-kernel ring body: same rotation schedule as the XLA path,
    but each fold goes through `flash_attention_carry` (O(block) VMEM,
    no [Tl, Tl] HBM tile). Returns (o [B,Tl,H,Dh], lse [B,H,Tl])."""
    from deeplearning4j_tpu.kernels.flash_attention import (
        _NEG_INF, flash_attention_carry,
    )

    P_ = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    B, Tl, H, Dh = q.shape
    out_dtype = q.dtype

    def fold_visible(carry, kb, vb):
        m, l, acc = carry
        return flash_attention_carry(q, kb, vb, m, l, acc, diag=False)

    def fold_diag(carry, kb, vb):
        m, l, acc = carry
        return flash_attention_carry(q, kb, vb, m, l, acc, diag=True)

    def fold_masked(carry, kb, vb):
        return carry

    def attend(carry, k_blk, v_blk, step):
        if not causal:
            return fold_visible(carry, k_blk, v_blk)
        src = (idx + step) % P_
        return lax.switch(_ring_case(idx, src),
                          (fold_masked, fold_diag, fold_visible),
                          carry, k_blk, v_blk)

    perm = _ring_perm(P_)

    def block(carry_kv, step):
        k_blk, v_blk, acc = carry_kv
        acc = attend(acc, k_blk, v_blk, step)
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return (k_next, v_next, acc), None

    m0 = jnp.full((B, H, Tl), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Tl), jnp.float32)
    acc0 = jnp.zeros((B, H, Tl, Dh), jnp.float32)
    (k_f, v_f, carry), _ = lax.scan(block, (k, v, (m0, l0, acc0)),
                                    jnp.arange(P_ - 1))
    m, l, acc = attend(carry, k_f, v_f, P_ - 1)
    l_safe = jnp.clip(l, 1e-20, None)
    o = acc / l_safe[..., None]
    lse = m + jnp.log(l_safe)
    return jnp.transpose(o, (0, 2, 1, 3)).astype(out_dtype), lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ring_attention_flash(q, k, v, axis_name, causal):
    """Differentiable flash ring attention (per-shard, inside
    shard_map). The backward runs a SECOND ring: each rotating K/V
    chunk carries its own dK/dV accumulator, fed by the chunked flash
    backward kernels, and lands home after the final rotation — so the
    [Tl, Tl] tile never materializes in either direction and training
    memory stays O(block) per device."""
    o, _ = _ring_flash_fwd_impl(q, k, v, axis_name, causal)
    return o


def _ring_flash_fwd(q, k, v, axis_name, causal):
    o, lse = _ring_flash_fwd_impl(q, k, v, axis_name, causal)
    return o, (q, k, v, o, lse)


def _ring_flash_bwd(axis_name, causal, res, g):
    from deeplearning4j_tpu.kernels.flash_attention import (
        _bwd_dkv_chunk, _bwd_dq_chunk, attention_delta,
    )

    q, k, v, o, lse = res
    P_ = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    delta = attention_delta(g, o)                    # [B, H, Tl] fp32

    def contrib_for(chunk_causal):
        def f(kb, vb):
            dq_c = _bwd_dq_chunk(q, kb, vb, g, lse, delta,
                                 causal=chunk_causal, block_q=512,
                                 block_k=1024, interpret=None)
            dk_c, dv_c = _bwd_dkv_chunk(q, kb, vb, g, lse, delta,
                                        causal=chunk_causal, block_q=512,
                                        block_k=1024, interpret=None)
            return (dq_c.astype(jnp.float32), dk_c.astype(jnp.float32),
                    dv_c.astype(jnp.float32))
        return f

    def contrib_masked(kb, vb):
        return (jnp.zeros(q.shape, jnp.float32),
                jnp.zeros(kb.shape, jnp.float32),
                jnp.zeros(vb.shape, jnp.float32))

    def contrib(k_blk, v_blk, step):
        if not causal:
            return contrib_for(False)(k_blk, v_blk)
        src = (idx + step) % P_
        return lax.switch(_ring_case(idx, src),
                          (contrib_masked, contrib_for(True),
                           contrib_for(False)),
                          k_blk, v_blk)

    perm = _ring_perm(P_)

    def block(carry, step):
        k_blk, v_blk, dk_a, dv_a, dq_a = carry
        dq_c, dk_c, dv_c = contrib(k_blk, v_blk, step)
        dq_a = dq_a + dq_c
        dk_a = dk_a + dk_c
        dv_a = dv_a + dv_c
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        # the chunk's grad accumulator travels WITH the chunk
        dk_a = lax.ppermute(dk_a, axis_name, perm)
        dv_a = lax.ppermute(dv_a, axis_name, perm)
        return (k_blk, v_blk, dk_a, dv_a, dq_a), None

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    (k_f, v_f, dk_a, dv_a, dq_a), _ = lax.scan(
        block, (k, v, dk0, dv0, dq0), jnp.arange(P_ - 1))
    # final fold (no trailing K/V rotate), then ONE more accumulator
    # rotation: the block held now originated at idx-1, so a single
    # ppermute lands every chunk's dK/dV back on its origin device
    dq_c, dk_c, dv_c = contrib(k_f, v_f, P_ - 1)
    dq_a = dq_a + dq_c
    dk_a = lax.ppermute(dk_a + dk_c, axis_name, perm)
    dv_a = lax.ppermute(dv_a + dv_c, axis_name, perm)
    return (dq_a.astype(q.dtype), dk_a.astype(k.dtype),
            dv_a.astype(v.dtype))


_ring_attention_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def sequence_parallel_attention(q, k, v, mesh: Mesh, *,
                                seq_axis: str = "seq",
                                causal: bool = False,
                                use_flash: bool = False):
    """Full arrays [B, T, H, Dh] → ring attention with T sharded over
    `seq_axis` of `mesh`."""
    spec = P(None, seq_axis)

    @partial(shard_map, mesh=mesh,
             in_specs=(spec, spec, spec), out_specs=spec,
             check_vma=False)
    def run(ql, kl, vl):
        return ring_attention(ql, kl, vl, seq_axis, causal=causal,
                              use_flash=use_flash)

    return run(q, k, v)


def reference_attention(q, k, v, causal: bool = False):
    """Single-device ground truth for parity tests."""
    Dh = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(Dh, q.dtype))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        T = q.shape[1]
        scores = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None],
                           scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)

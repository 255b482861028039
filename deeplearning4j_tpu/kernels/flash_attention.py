"""Flash attention — Pallas TPU kernels (forward AND backward).

Plays the role the cuDNN fused kernels play in the reference
(`deeplearning4j-cuda`, SURVEY §2.2): a hand-scheduled fast path behind
the same layer API, with the pure-XLA implementation as the reference
path for parity tests (the `ValidateCudnnLSTM` pattern).

Design (streaming flash blocking — VMEM use independent of T):
- every kernel's grid carries the inner loop as its MINOR dimension
  (forward/dQ: (B, H, q-blocks, k-blocks); dK/dV: (B, H, k-blocks,
  q-blocks)), so Pallas streams each operand tile HBM→VMEM per step
  instead of staging whole [T, D] arrays — the per-program VMEM
  footprint is O(block), which is what lets sequence lengths run past
  the point where whole-row staging (or XLA's [T, T] softmax
  materialization) blows the 16 MB VMEM / HBM budget.
- running state (online-softmax m, l and the output/grad accumulators)
  lives in VMEM scratch that persists across minor-dim steps:
  initialized at step 0, finalized into the output block on the last
  step (Mosaic iterates the minor dim sequentially, revisiting the
  same output block).
- the q-time and k-time axes pad INDEPENDENTLY (to a bq / bk multiple
  respectively — they are separate buffers), with in-kernel position
  masks zeroing padded keys; padded query rows are sliced off outside.
- causal masking skips fully-masked tiles with `pl.when` (no FLOPs,
  just the DMA), and masks the diagonal tiles elementwise.
- backward is the standard two-kernel flash recompute — probabilities
  are rebuilt blockwise from (q, k, lse), so the [T, T] attention
  matrix never materializes in HBM in either direction:
    dQ kernel: dQ += dS @ K with dS = P ∘ (dO·Vᵀ − Δ),
      Δ = rowsum(dO ∘ O) precomputed by XLA (tiny fused reduce);
    dK/dV kernel: dV += Pᵀ·dO and dK += dSᵀ·Q.
- all matmuls hit the MXU in fp32 accumulation; exp/mask on the VPU.
- lse/Δ ride along as [B, H, T, 1] so their blocks satisfy Mosaic's
  (sublane, lane) block-shape rules.
- chunk ("carry") variants thread the online-softmax state and emit
  per-chunk gradient contributions, which is what lets ring attention
  (`parallel/ring.py`) run BOTH directions through these kernels —
  sequence parallelism and flash memory behavior compose.

Runs in Pallas interpret mode on CPU (how the tests validate parity —
both forward values and gradients against the XLA reference);
compiled by Mosaic everywhere else. Every `pallas_call` carries a
stable `name` (`KERNEL_NAMES`) so a lowered program or a device trace
can be searched for it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

_FWD_NAME, _BWD_DQ_NAME, _BWD_DKV_NAME = KERNEL_NAMES = (
    "dl4tpu_flash_fwd", "dl4tpu_flash_bwd_dq", "dl4tpu_flash_bwd_dkv")

# batch/head/major-block grid dims are embarrassingly parallel; only the
# minor accumulation dim must run sequentially (the scratch carries
# state across it). Telling Mosaic this unlocks cross-step pipelining.
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _resolve_interpret(interpret):
    """None → the Pallas interpreter on the CPU backend (there is no
    Mosaic there; it is how the parity tests run), compiled by Mosaic on
    every other backend — where a kernel the compiler refuses raises.
    One definition so the primal and both vjp halves can never
    disagree."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


def _ceil_to(n, b):
    return -(-n // b) * b


# ---------------------------------------------------------------- forward
def _flash_fwd_kernel(q_ref, k_ref, v_ref, m_in_ref, l_in_ref, acc_in_ref,
                      *refs, block_q: int, block_k: int, k_len: int,
                      causal: bool, scale: float, n_k: int, carry: bool,
                      finalize: bool):
    """One (batch, head, q-block, k-block) step; k is the minor dim.

    `carry=False`: state starts fresh (m=-inf, l=0, acc=0) and the
    m/l/acc in refs are unused dummies. `carry=True`: state seeds from
    the in refs (the chunked ring fold). `finalize` selects the output
    refs: normalized o + lse, or the raw (m, l, acc) state."""
    if finalize:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        m_out_ref, l_out_ref, acc_out_ref, m_scr, l_scr, acc_scr = refs
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        if carry:
            m_scr[...] = m_in_ref[...]
            l_scr[...] = l_in_ref[...]
            acc_scr[...] = acc_in_ref[...]
        else:
            m_scr[...] = jnp.full_like(m_scr[...], _NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr[...])
            acc_scr[...] = jnp.zeros_like(acc_scr[...])

    # causal: skip tiles entirely above the diagonal (q_pos < k_pos for
    # every element) — DMA still happens, matmuls don't
    run = (kj * block_k <= (qi + 1) * block_q - 1) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[...].astype(jnp.float32) * scale       # [BQ, D]
        k = k_ref[...].astype(jnp.float32)               # [BK, D]
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = k_pos < k_len          # mask the padded tail block
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            valid = jnp.logical_and(valid, k_pos <= q_pos)
        s = jnp.where(valid, s, _NEG_INF)
        m = m_scr[...]                                   # [BQ, 1]
        l = l_scr[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        m_scr[...] = m_new
        l_scr[...] = l * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(p, v)

    @pl.when(kj == n_k - 1)
    def _fin():
        if finalize:
            l_safe = jnp.clip(l_scr[...], 1e-20, None)
            o_ref[...] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
            lse_ref[...] = m_scr[...] + jnp.log(l_safe)
        else:
            m_out_ref[...] = m_scr[...]
            l_out_ref[...] = l_scr[...]
            acc_out_ref[...] = acc_scr[...]


def _fwd_pallas_call(q, k, v, state, *, block_q, block_k, causal,
                     interpret, finalize):
    """Shared driver for the finalizing forward and the carry fold.
    q [B, Tq, H, D]; k, v [B, Tk, H, D]; state None or (m, l, acc) with
    m/l [B, H, Tq] fp32 and acc [B, H, Tq, D] fp32 (unnormalized)."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = 1.0 / float(np.sqrt(D))
    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
    Tqp = _ceil_to(Tq, bq)
    Tkp = _ceil_to(Tk, bk)
    carry = state is not None
    if carry:
        m, l, acc = state
        m = m[..., None].astype(jnp.float32)
        l = l[..., None].astype(jnp.float32)
        acc = acc.astype(jnp.float32)
    else:
        # dummies (never read): zero-size would change specs, so reuse
        # tiny broadcasts of the right logical shape
        m = jnp.zeros((B, H, Tq, 1), jnp.float32)
        l = jnp.zeros((B, H, Tq, 1), jnp.float32)
        acc = jnp.zeros((B, H, Tq, D), jnp.float32)
    if Tqp != Tq:
        q = jnp.pad(q, [(0, 0), (0, Tqp - Tq), (0, 0), (0, 0)])
        m = jnp.pad(m, [(0, 0), (0, 0), (0, Tqp - Tq), (0, 0)],
                    constant_values=_NEG_INF if carry else 0.0)
        l = jnp.pad(l, [(0, 0), (0, 0), (0, Tqp - Tq), (0, 0)])
        acc = jnp.pad(acc, [(0, 0), (0, 0), (0, Tqp - Tq), (0, 0)])
    if Tkp != Tk:
        pad = [(0, 0), (0, Tkp - Tk), (0, 0), (0, 0)]
        k, v = (jnp.pad(a, pad) for a in (k, v))
    qt, kt, vt = (jnp.transpose(a, (0, 2, 1, 3)) for a in (q, k, v))
    n_q, n_k = Tqp // bq, Tkp // bk

    q_blk = pl.BlockSpec((pl.squeezed, pl.squeezed, bq, D),
                         lambda b, h, i, j: (b, h, i, 0))
    k_blk = pl.BlockSpec((pl.squeezed, pl.squeezed, bk, D),
                         lambda b, h, i, j: (b, h, j, 0))
    # trailing singleton: Mosaic wants the block's last two dims
    # divisible by (8, 128) or equal to the array's — [bq, 1]
    # qualifies, a rank-1 [bq] block does not
    row_q = pl.BlockSpec((pl.squeezed, pl.squeezed, bq, 1),
                         lambda b, h, i, j: (b, h, i, 0))

    outs = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, block_q=bq, block_k=bk,
                          k_len=Tk, causal=causal, scale=scale, n_k=n_k,
                          carry=carry, finalize=finalize),
        grid=(B, H, n_q, n_k),
        in_specs=[q_blk, k_blk, k_blk, row_q, row_q, q_blk],
        out_specs=([q_blk, row_q] if finalize
                   else [row_q, row_q, q_blk]),
        out_shape=(
            [jax.ShapeDtypeStruct((B, H, Tqp, D), q.dtype),
             jax.ShapeDtypeStruct((B, H, Tqp, 1), jnp.float32)]
            if finalize else
            [jax.ShapeDtypeStruct((B, H, Tqp, 1), jnp.float32),
             jax.ShapeDtypeStruct((B, H, Tqp, 1), jnp.float32),
             jax.ShapeDtypeStruct((B, H, Tqp, D), jnp.float32)]),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max m
            pltpu.VMEM((bq, 1), jnp.float32),    # running denom l
            pltpu.VMEM((bq, D), jnp.float32),    # output accumulator
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=_FWD_NAME,
    )(qt, kt, vt, m, l, acc)
    if finalize:
        out, lse = outs
        return (jnp.transpose(out, (0, 2, 1, 3))[:, :Tq],
                lse[:, :, :Tq, 0])
    m_new, l_new, acc_new = outs
    return (m_new[:, :, :Tq, 0], l_new[:, :, :Tq, 0], acc_new[:, :, :Tq])


# The _fwd_pallas_call kernel reads the dummy state refs only when
# carry=True, but passing the full-size dummies costs nothing (XLA DCEs
# zero-filled constants into the program); keeping ONE kernel avoids a
# second Mosaic lowering to maintain.


def _flash_forward(q, k, v, *, block_q: int, block_k: int, causal: bool,
                   interpret: bool):
    """Returns (out [B, T, H, D], lse [B, H, T])."""
    return _fwd_pallas_call(q, k, v, None, block_q=block_q,
                            block_k=block_k, causal=causal,
                            interpret=interpret, finalize=True)


def flash_attention_carry(q, k, v, m, l, acc, *, diag: bool,
                          block_q: int = 512, block_k: int = 1024,
                          interpret: bool | None = None):
    """Fold one K/V chunk into a running online-softmax state.

    q [B, Tq, H, D]; k, v [B, Tk, H, D]; m, l [B, H, Tq] fp32 (running
    max / denominator, init m=-1e30, l=0); acc [B, H, Tq, D] fp32 (the
    UNNORMALIZED output accumulator). Returns updated (m, l, acc); the
    caller divides acc by l after the last chunk. `diag=True` applies
    same-chunk causal masking (local positions directly comparable);
    fully-visible chunks pass diag=False; fully-masked chunks should
    not be folded at all. This is the ring-attention building block
    (`parallel/ring.py` `use_flash`)."""
    interpret = _resolve_interpret(interpret)
    return _fwd_pallas_call(q, k, v, (m, l, acc), block_q=block_q,
                            block_k=block_k, causal=diag,
                            interpret=interpret, finalize=False)


# --------------------------------------------------------------- backward
def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_scr, *, block_q: int, block_k: int,
                         k_len: int, causal: bool, scale: float,
                         n_k: int):
    """One (batch, head, q-block, k-block) step:
    dQ = scale · Σ_kb dS @ K."""
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr[...])

    run = (kj * block_k <= (qi + 1) * block_q - 1) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[...].astype(jnp.float32)               # [BQ, D]
        do = do_ref[...].astype(jnp.float32)
        lse = lse_ref[...]                               # [BQ, 1]
        delta = delta_ref[...]                           # [BQ, 1]
        k = k_ref[...].astype(jnp.float32)               # [BK, D]
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = k_pos < k_len
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            valid = jnp.logical_and(valid, k_pos <= q_pos)
        s = jnp.where(valid, s, _NEG_INF)
        p = jnp.exp(s - lse)                             # [BQ, BK]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta)
        dq_scr[...] = dq_scr[...] + jax.lax.dot(ds, k)

    @pl.when(kj == n_k - 1)
    def _fin():
        dq_ref[...] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, block_q: int,
                          block_k: int, q_len: int, causal: bool,
                          scale: float, n_q: int):
    """One (batch, head, k-block, q-block) step (q is the minor dim):
    dV = Σ_qb Pᵀ·dO, dK = scale · Σ_qb dSᵀ·Q. Padded-KEY rows produce
    garbage that the caller slices off, so only q-padding is masked."""
    kj = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr[...])
        dv_scr[...] = jnp.zeros_like(dv_scr[...])

    # causal: skip q tiles entirely BEFORE this k tile's diagonal
    run = ((qi + 1) * block_q - 1 >= kj * block_k) if causal else True

    @pl.when(run)
    def _step():
        k = k_ref[...].astype(jnp.float32)               # [BK, D]
        v = v_ref[...].astype(jnp.float32)
        q = q_ref[...].astype(jnp.float32)               # [BQ, D]
        do = do_ref[...].astype(jnp.float32)
        lse = lse_ref[...]                               # [BQ, 1]
        delta = delta_ref[...]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        valid = q_pos < q_len
        if causal:
            k_pos = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            valid = jnp.logical_and(valid, k_pos <= q_pos)
        s = jnp.where(valid, s, _NEG_INF)
        p = jnp.exp(s - lse)                             # [BQ, BK]
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())))             # pᵀ·do [BK, D]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta)
        dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())))             # dsᵀ·q [BK, D]

    @pl.when(qi == n_q - 1)
    def _fin():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_prep(q, k, do, lse, delta, block_q, block_k):
    """Independent q/k-time padding + [..., 1] lifting shared by the
    two backward drivers. Returns padded operands and block geometry."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
    Tqp = _ceil_to(Tq, bq)
    Tkp = _ceil_to(Tk, bk)
    if Tqp != Tq:
        padq = [(0, 0), (0, Tqp - Tq), (0, 0), (0, 0)]
        q = jnp.pad(q, padq)
        do = jnp.pad(do, padq)
        lse = jnp.pad(lse, [(0, 0), (0, 0), (0, Tqp - Tq)])
        delta = jnp.pad(delta, [(0, 0), (0, 0), (0, Tqp - Tq)])
    return q, do, lse[..., None], delta[..., None], bq, bk, Tqp, Tkp


def _bwd_dq_chunk(q, k, v, do, lse, delta, *, causal, block_q, block_k,
                  interpret):
    """dQ contribution of one K/V chunk. q/do [B, Tq, H, D];
    k/v [B, Tk, H, D]; lse/delta [B, H, Tq] fp32. Returns [B,Tq,H,D]."""
    interpret = _resolve_interpret(interpret)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = 1.0 / float(np.sqrt(D))
    q, do, lse4, delta4, bq, bk, Tqp, Tkp = _bwd_prep(
        q, k, do, lse, delta, block_q, block_k)
    if Tkp != Tk:
        pad = [(0, 0), (0, Tkp - Tk), (0, 0), (0, 0)]
        k, v = (jnp.pad(a, pad) for a in (k, v))
    qt, kt, vt, dot = (jnp.transpose(a, (0, 2, 1, 3))
                       for a in (q, k, v, do))
    n_q, n_k = Tqp // bq, Tkp // bk
    q_blk = pl.BlockSpec((pl.squeezed, pl.squeezed, bq, D),
                         lambda b, h, i, j: (b, h, i, 0))
    k_blk = pl.BlockSpec((pl.squeezed, pl.squeezed, bk, D),
                         lambda b, h, i, j: (b, h, j, 0))
    row_q = pl.BlockSpec((pl.squeezed, pl.squeezed, bq, 1),
                         lambda b, h, i, j: (b, h, i, 0))
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_q=bq, block_k=bk,
                          k_len=Tk, causal=causal, scale=scale, n_k=n_k),
        grid=(B, H, n_q, n_k),
        in_specs=[q_blk, k_blk, k_blk, q_blk, row_q, row_q],
        out_specs=q_blk,
        out_shape=jax.ShapeDtypeStruct((B, H, Tqp, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=_BWD_DQ_NAME,
    )(qt, kt, vt, dot, lse4, delta4)
    return jnp.transpose(dq, (0, 2, 1, 3))[:, :Tq]


def _bwd_dkv_chunk(q, k, v, do, lse, delta, *, causal, block_q, block_k,
                   interpret):
    """(dK, dV) contribution of all of q/do against one K/V chunk.
    Shapes as `_bwd_dq_chunk`; returns ([B,Tk,H,D], [B,Tk,H,D])."""
    interpret = _resolve_interpret(interpret)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = 1.0 / float(np.sqrt(D))
    q, do, lse4, delta4, bq, bk, Tqp, Tkp = _bwd_prep(
        q, k, do, lse, delta, block_q, block_k)
    if Tkp != Tk:
        pad = [(0, 0), (0, Tkp - Tk), (0, 0), (0, 0)]
        k, v = (jnp.pad(a, pad) for a in (k, v))
    qt, kt, vt, dot = (jnp.transpose(a, (0, 2, 1, 3))
                       for a in (q, k, v, do))
    n_q, n_k = Tqp // bq, Tkp // bk
    # k-major grid: k/v (and dk/dv outputs) blocked by grid dim 2,
    # q/do/lse/Δ streamed by the minor dim 3
    kv_blk = pl.BlockSpec((pl.squeezed, pl.squeezed, bk, D),
                          lambda b, h, i, j: (b, h, i, 0))
    q_stream = pl.BlockSpec((pl.squeezed, pl.squeezed, bq, D),
                            lambda b, h, i, j: (b, h, j, 0))
    row_stream = pl.BlockSpec((pl.squeezed, pl.squeezed, bq, 1),
                              lambda b, h, i, j: (b, h, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, block_q=bq, block_k=bk,
                          q_len=Tq, causal=causal, scale=scale, n_q=n_q),
        grid=(B, H, n_k, n_q),
        in_specs=[q_stream, kv_blk, kv_blk, q_stream,
                  row_stream, row_stream],
        out_specs=[kv_blk, kv_blk],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tkp, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, Tkp, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=_BWD_DKV_NAME,
    )(qt, kt, vt, dot, lse4, delta4)
    untr = lambda a: jnp.transpose(a, (0, 2, 1, 3))[:, :Tk]  # noqa: E731
    return untr(dk), untr(dv)


def attention_delta(g, o):
    """Δ_i = Σ_d dO_id · O_id — the per-row correction every flash
    backward kernel needs; tiny elementwise reduce that XLA fuses."""
    return jnp.einsum("bthd,bthd->bht", g.astype(jnp.float32),
                      o.astype(jnp.float32))


def _flash_backward(q, k, v, o, lse, g, *, block_q: int, block_k: int,
                    causal: bool, interpret: bool):
    delta = attention_delta(g, o)
    dq = _bwd_dq_chunk(q, k, v, g, lse, delta, causal=causal,
                       block_q=block_q, block_k=block_k,
                       interpret=interpret)
    dk, dv = _bwd_dkv_chunk(q, k, v, g, lse, delta, causal=causal,
                            block_q=block_q, block_k=block_k,
                            interpret=interpret)
    return dq, dk, dv


def _xla_attention(q, k, v, causal):
    D = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(D, q.dtype))
    if causal:
        T = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s,
                      _NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = False, block_q: int = 512,
                    block_k: int = 1024, interpret: bool | None = None):
    """[B, T, H, D] x3 → [B, T, H, D]. Pallas forward AND backward (the
    flash two-kernel recompute — no [T, T] materialization either way,
    and O(block) VMEM so long sequences stream). `interpret=None`
    auto-selects (`_resolve_interpret`).

    Default blocks (512, 1024) are the measured v5e sweet spot: larger
    tiles amortize the per-step DMA/loop overhead while the fp32
    [BQ, BK] score tile still fits VMEM (measured fwd+bwd at D=64:
    2.15x over the XLA path at T=2048, 3.3x at T=8192; 128-square
    blocks ran 3.5x slower than this). `min(block, T)` keeps short
    sequences valid."""
    interpret = _resolve_interpret(interpret)
    out, _ = _flash_forward(q, k, v, block_q=block_q, block_k=block_k,
                            causal=causal, interpret=interpret)
    return out


# Below this sequence length the compiled path takes XLA's fused
# backward instead of the Pallas kernels: at small T the [T, T]
# re-materialization is cheap and XLA's single fused program beats the
# two-kernel launch + recompute overhead (measured v5e crossover:
# T=512 XLA 2.6 ms vs Pallas 5.0 ms/iter, T=1024 Pallas 6.5 vs XLA
# 8.8 — the cuDNN-helper pattern of activating only for favorable
# configs). Interpret mode always runs the Pallas kernels so the CPU
# parity suite exercises them at every size.
_PALLAS_BWD_MIN_T = 1024


def _fwd(q, k, v, causal, block_q, block_k, interpret):
    interpret = _resolve_interpret(interpret)
    out, lse = _flash_forward(q, k, v, block_q=block_q, block_k=block_k,
                              causal=causal, interpret=interpret)
    return out, (q, k, v, out, lse)


def _bwd(causal, block_q, block_k, interpret, res, g):
    interpret = _resolve_interpret(interpret)
    q, k, v, o, lse = res
    if not interpret and q.shape[1] < _PALLAS_BWD_MIN_T:
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _xla_attention(q_, k_, v_, causal), q, k, v)
        return vjp(g)
    return _flash_backward(q, k, v, o, lse, g, block_q=block_q,
                           block_k=block_k, causal=causal,
                           interpret=interpret)


flash_attention.defvjp(_fwd, _bwd)

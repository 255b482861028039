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
- causal attention computes only what the algorithm needs: a tile
  wholly above the diagonal is skipped with `pl.when`, and its operand
  index is clamped to the last (first, for dK/dV) tile its row needs,
  so the skipped step re-reads the resident block and issues no DMA; a
  tile wholly below the diagonal runs with no mask. Causal tiles are
  square (`_tiles`), so the one tile of a row that crosses the diagonal
  is the diagonal one; the backward kernels cut it into static
  sub-blocks, those above the diagonal never computed and only those on
  it masked, and the forward masks it whole (`_run_tile`).
- backward is the standard two-kernel flash recompute — probabilities
  are rebuilt blockwise from (q, k, lse), so the [T, T] attention
  matrix never materializes in HBM in either direction:
    dQ kernel: dQ += dS @ K with dS = P ∘ (dO·Vᵀ − Δ),
      Δ = rowsum(dO ∘ O) precomputed by XLA (tiny fused reduce);
    dK/dV kernel: dV += Pᵀ·dO and dK += dSᵀ·Q.
- every product takes its operands in the dtype the kernel is given
  (P and dS cast to it) and accumulates in fp32: bfloat16 in feeds the
  MXU at full rate, float32 in multiplies in float32. m, l, lse, Δ, the
  exponentials and every accumulator are fp32; exp/mask on the VPU.
  The softmax scale is folded into q where 1/√D is a power of two
  (exact), and applied to the fp32 scores otherwise.
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


# Causal tiles are square and large: a grid step costs the v5e some
# 0.4-0.8 us whatever it computes, so at T = 1,024 the 512-square tiles
# that let a whole tile above the diagonal be skipped run slower than
# (512, 1024) ones, and 1024-square tiles run fastest at T = 1,024 and
# 8,192 (`scripts/flash_cost.py`, PERF.md section 5). The backward
# kernels cut the work above the diagonal INSIDE the diagonal tile:
# `_DIAGONAL_SPLIT` sub-blocks a side, of which those above the diagonal
# are never computed (`_run_tile`); cut four ways dQ ran 5-20% and dK/dV
# 3-12% faster than uncut, and cut two ways dQ 2-7% slower than four.
# The forward runs its diagonal tile whole: each sub-block row updates
# the running max, denominator and accumulator once more, and cut two
# ways it ran 6-40% SLOWER than whole.
_CAUSAL_TILE = 1024
_DIAGONAL_SPLIT = 4


def _tiles(Tq: int, Tk: int, block_q, block_k, causal: bool):
    """(bq, bk), the tile sides of a call over Tq queries and Tk keys.
    A block left None follows causality: causal `_CAUSAL_TILE`, else
    (512, 1024), since every non-causal tile is computed and large ones
    amortize the per-step overhead. Each side is clamped to its length.
    Causal tiles are square, the smaller of the two sides, so the one
    tile of a row that crosses the diagonal is the diagonal one and its
    sub-blocks are static; that needs Tq == Tk (self-attention, or a
    ring's diagonal chunk), and with it a padded key lies after every
    real query."""
    if causal:
        if Tq != Tk:
            raise ValueError(f"causal flash attention needs as many "
                             f"queries as keys, got {Tq} and {Tk}")
        b = min(_CAUSAL_TILE if block_q is None else block_q,
                _CAUSAL_TILE if block_k is None else block_k, Tq)
        return b, b
    return (min(512 if block_q is None else block_q, Tq),
            min(1024 if block_k is None else block_k, Tk))


def _sub_block(b: int, split: int) -> int:
    """Side of the sub-blocks the diagonal tile of side b is cut into:
    b / split where that is a whole number of bfloat16 sublane tiles
    (16 rows), else b (the whole tile, masked)."""
    sub = b // split
    return sub if sub * split == b and sub % 16 == 0 else b


def _scale_split(D: int):
    """(factor folded into q, factor applied to the fp32 scores). 1/√D
    goes into q where it is a power of two — exact in any float dtype —
    and onto the fp32 scores otherwise, so no operand is rounded anew."""
    scale = 1.0 / float(np.sqrt(D))
    if np.frexp(scale)[0] == 0.5:
        return scale, 1.0
    return 1.0, scale


def _dot_nt(a, b):
    """a [M, D] · b [N, D]ᵀ in the operands' dtype, fp32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    """a [M, N]ᵀ · b [M, D] in the operands' dtype, fp32 accumulation."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot(a, b):
    """a [M, N] · b [N, D] in the operands' dtype, fp32 accumulation."""
    return jax.lax.dot(a, b, preferred_element_type=jnp.float32)


def _masked(s, q_start, k_start, *, causal, q_len=None, k_len=None):
    """The scores of one tile with every entry the algorithm drops set
    to -inf: keys after the query (causal) and padded positions (a
    `*_len` given only where the padded length differs from it)."""
    iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32, s.shape)
    q_pos = q_start + iota(0)
    k_pos = k_start + iota(1)
    keep = [k_pos <= q_pos] if causal else []
    if q_len is not None:
        keep.append(q_pos < q_len)
    if k_len is not None:
        keep.append(k_pos < k_len)
    return jnp.where(functools.reduce(jnp.logical_and, keep), s, _NEG_INF)


def _run_tile(step, *, causal, qi, kj, edge, bq, bk, split):
    """Trace one grid step's work, query tile qi against key tile kj,
    as `step(masked, r0, r1, c0, c1)` calls, each over the tile's query
    rows [r0, r1) and key rows [c0, c1), masked or not.

    Causal (square tiles): nothing above the diagonal (kj > qi), no
    mask below it, and the diagonal tile cut `split` ways a side
    (`_sub_block`): the sub-blocks above the diagonal are never
    computed, those below it run unmasked, and only those on it are
    masked (split 1: the whole tile, masked). No padding test is
    needed off them: a padded key lies after every real query, and a
    padded query row reads zeros (q, dO, lse and Δ are zero-padded), so
    it adds exactly nothing to dK and dV and its own outputs are sliced
    off. Non-causal: a tile is masked where it holds padding (`edge`,
    None where the padded length is the true one)."""
    def whole(masked):
        step(masked, 0, bq, 0, bk)

    if causal:
        pl.when(kj < qi)(lambda: whole(False))

        @pl.when(kj == qi)
        def _diagonal():
            sub = _sub_block(bq, split)
            for r in range(0, bq, sub):
                if r:
                    step(False, r, r + sub, 0, r)
                step(True, r, r + sub, r, r + sub)
    elif edge is None:
        whole(False)
    else:
        pl.when(edge)(lambda: whole(True))
        pl.when(jnp.logical_not(edge))(lambda: whole(False))


def _k_index_map(causal):
    """Index map of the key-side blocks of a q-major grid (forward, dQ).
    Causal: a step past the diagonal tile repeats its index, so the
    skipped step issues no copy."""
    if not causal:
        return lambda b, h, i, j: (b, h, j, 0)
    return lambda b, h, i, j: (b, h, jnp.minimum(j, i), 0)


def _q_index_map(causal):
    """Index map of the query-side blocks of the k-major dK/dV grid.
    Causal: a step before the diagonal tile repeats its index, so the
    skipped steps issue one copy, the one the first real step needs."""
    if not causal:
        return lambda b, h, i, j: (b, h, j, 0)
    return lambda b, h, i, j: (b, h, jnp.maximum(j, i), 0)


# ---------------------------------------------------------------- forward
def _flash_fwd_kernel(q_ref, k_ref, v_ref, m_in_ref, l_in_ref, acc_in_ref,
                      *refs, block_q: int, block_k: int, k_len, causal: bool,
                      q_scale: float, s_scale: float, n_k: int, carry: bool,
                      finalize: bool):
    """One (batch, head, q-block, k-block) step; k is the minor dim.

    `carry=False`: state starts fresh (m=-inf, l=0, acc=0) and the
    m/l/acc in refs are unused dummies. `carry=True`: state seeds from
    the in refs (the chunked ring fold). `finalize` selects the output
    refs: normalized o + lse, or the raw (m, l, acc) state. `k_len` is
    None where the keys are not padded."""
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

    def _step(masked, r0, r1, c0, c1):
        rows, cols = pl.ds(r0, r1 - r0), pl.ds(c0, c1 - c0)
        q = q_ref[rows, :]                               # [R, D]
        if q_scale != 1.0:
            q = q * jnp.asarray(q_scale, q.dtype)
        v = v_ref[cols, :]                               # [C, D]
        s = _dot_nt(q, k_ref[cols, :])                   # [R, C] fp32
        if s_scale != 1.0:
            s = s * s_scale
        if masked:
            s = _masked(s, qi * block_q + r0, kj * block_k + c0,
                        causal=causal, k_len=k_len)
        m = m_scr[rows, :]                               # [R, 1]
        l = l_scr[rows, :]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        m_scr[rows, :] = m_new
        l_scr[rows, :] = l * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[rows, :] = (acc_scr[rows, :] * corr
                            + _dot(p.astype(v.dtype), v))

    _run_tile(_step, causal=causal, qi=qi, kj=kj,
              edge=None if k_len is None else kj == n_k - 1,
              bq=block_q, bk=block_k, split=1)

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
    q_scale, s_scale = _scale_split(D)
    bq, bk = _tiles(Tq, Tk, block_q, block_k, causal)
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
                         _k_index_map(causal))
    # trailing singleton: Mosaic wants the block's last two dims
    # divisible by (8, 128) or equal to the array's — [bq, 1]
    # qualifies, a rank-1 [bq] block does not
    row_q = pl.BlockSpec((pl.squeezed, pl.squeezed, bq, 1),
                         lambda b, h, i, j: (b, h, i, 0))

    outs = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, block_q=bq, block_k=bk,
                          k_len=None if Tkp == Tk else Tk, causal=causal,
                          q_scale=q_scale, s_scale=s_scale, n_k=n_k,
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


def _flash_forward(q, k, v, *, block_q, block_k, causal: bool,
                   interpret: bool):
    """Returns (out [B, T, H, D], lse [B, H, T])."""
    return _fwd_pallas_call(q, k, v, None, block_q=block_q,
                            block_k=block_k, causal=causal,
                            interpret=interpret, finalize=True)


def flash_attention_carry(q, k, v, m, l, acc, *, diag: bool,
                          block_q=None, block_k=None,
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
                         k_len, causal: bool, q_scale: float,
                         s_scale: float, n_k: int):
    """One (batch, head, q-block, k-block) step:
    dQ = scale · Σ_kb dS @ K."""
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr[...])

    def _step(masked, r0, r1, c0, c1):
        rows, cols = pl.ds(r0, r1 - r0), pl.ds(c0, c1 - c0)
        q = q_ref[rows, :]                               # [R, D]
        if q_scale != 1.0:
            q = q * jnp.asarray(q_scale, q.dtype)
        k = k_ref[cols, :]                               # [C, D]
        s = _dot_nt(q, k)                                # [R, C] fp32
        if s_scale != 1.0:
            s = s * s_scale
        if masked:
            s = _masked(s, qi * block_q + r0, kj * block_k + c0,
                        causal=causal, k_len=k_len)
        p = jnp.exp(s - lse_ref[rows, :])                # lse [R, 1]
        dp = _dot_nt(do_ref[rows, :], v_ref[cols, :])
        ds = p * (dp - delta_ref[rows, :])               # Δ [R, 1]
        dq_scr[rows, :] = dq_scr[rows, :] + _dot(ds.astype(k.dtype), k)

    _run_tile(_step, causal=causal, qi=qi, kj=kj,
              edge=None if k_len is None else kj == n_k - 1,
              bq=block_q, bk=block_k, split=_DIAGONAL_SPLIT)

    @pl.when(kj == n_k - 1)
    def _fin():
        dq_ref[...] = (dq_scr[...] * (q_scale * s_scale)).astype(
            dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, block_q: int,
                          block_k: int, q_len, causal: bool,
                          q_scale: float, s_scale: float, n_q: int):
    """One (batch, head, k-block, q-block) step (q is the minor dim):
    dV = Σ_qb Pᵀ·dO, dK = scale · Σ_qb dSᵀ·Q. Padded-KEY rows produce
    garbage that the caller slices off, so only q-padding is masked
    (`q_len`, None where the queries are not padded)."""
    kj = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr[...])
        dv_scr[...] = jnp.zeros_like(dv_scr[...])

    def _step(masked, r0, r1, c0, c1):
        rows, cols = pl.ds(r0, r1 - r0), pl.ds(c0, c1 - c0)
        q = q_ref[rows, :]                               # [R, D]
        if q_scale != 1.0:       # dsᵀ·(q·scale): the scale is in dK too
            q = q * jnp.asarray(q_scale, q.dtype)
        do = do_ref[rows, :]
        s = _dot_nt(q, k_ref[cols, :])                   # [R, C] fp32
        if s_scale != 1.0:
            s = s * s_scale
        if masked:
            s = _masked(s, qi * block_q + r0, kj * block_k + c0,
                        causal=causal, q_len=q_len)
        p = jnp.exp(s - lse_ref[rows, :])                # lse [R, 1]
        dv_scr[cols, :] = dv_scr[cols, :] + _dot_tn(p.astype(do.dtype), do)
        dp = _dot_nt(do, v_ref[cols, :])
        ds = p * (dp - delta_ref[rows, :])               # Δ [R, 1]
        dk_scr[cols, :] = dk_scr[cols, :] + _dot_tn(ds.astype(q.dtype), q)

    _run_tile(_step, causal=causal, qi=qi, kj=kj,
              edge=None if q_len is None else qi == n_q - 1,
              bq=block_q, bk=block_k, split=_DIAGONAL_SPLIT)

    @pl.when(qi == n_q - 1)
    def _fin():
        dk_ref[...] = (dk_scr[...] * s_scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_prep(q, k, v, do, lse, delta, block_q, block_k, causal):
    """Independent q/k-time padding, the [B, H, T, D] transposes and the
    [..., 1] lifting shared by the two backward drivers. Returns the
    kernel operands (q, k, v, do, lse, Δ) and the block geometry."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    bq, bk = _tiles(Tq, Tk, block_q, block_k, causal)
    Tqp = _ceil_to(Tq, bq)
    Tkp = _ceil_to(Tk, bk)
    if Tqp != Tq:
        padq = [(0, 0), (0, Tqp - Tq), (0, 0), (0, 0)]
        q = jnp.pad(q, padq)
        do = jnp.pad(do, padq)
        lse = jnp.pad(lse, [(0, 0), (0, 0), (0, Tqp - Tq)])
        delta = jnp.pad(delta, [(0, 0), (0, 0), (0, Tqp - Tq)])
    if Tkp != Tk:
        padk = [(0, 0), (0, Tkp - Tk), (0, 0), (0, 0)]
        k, v = (jnp.pad(a, padk) for a in (k, v))
    ops = tuple(jnp.transpose(a, (0, 2, 1, 3)) for a in (q, k, v, do))
    return (ops + (lse[..., None], delta[..., None]),
            dict(bq=bq, bk=bk, n_q=Tqp // bq, n_k=Tkp // bk,
                 q_len=None if Tqp == Tq else Tq,
                 k_len=None if Tkp == Tk else Tk))


def _bwd_dq_chunk(q, k, v, do, lse, delta, *, causal, block_q, block_k,
                  interpret):
    """dQ contribution of one K/V chunk. q/do [B, Tq, H, D];
    k/v [B, Tk, H, D]; lse/delta [B, H, Tq] fp32. Returns [B,Tq,H,D]."""
    interpret = _resolve_interpret(interpret)
    B, Tq, H, D = q.shape
    q_scale, s_scale = _scale_split(D)
    ops, g = _bwd_prep(q, k, v, do, lse, delta, block_q, block_k, causal)
    bq, bk, n_q, n_k = g["bq"], g["bk"], g["n_q"], g["n_k"]
    q_blk = pl.BlockSpec((pl.squeezed, pl.squeezed, bq, D),
                         lambda b, h, i, j: (b, h, i, 0))
    k_blk = pl.BlockSpec((pl.squeezed, pl.squeezed, bk, D),
                         _k_index_map(causal))
    row_q = pl.BlockSpec((pl.squeezed, pl.squeezed, bq, 1),
                         lambda b, h, i, j: (b, h, i, 0))
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_q=bq, block_k=bk,
                          k_len=g["k_len"], causal=causal, q_scale=q_scale,
                          s_scale=s_scale, n_k=n_k),
        grid=(B, H, n_q, n_k),
        in_specs=[q_blk, k_blk, k_blk, q_blk, row_q, row_q],
        out_specs=q_blk,
        out_shape=jax.ShapeDtypeStruct((B, H, n_q * bq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=_BWD_DQ_NAME,
    )(*ops)
    return jnp.transpose(dq, (0, 2, 1, 3))[:, :Tq]


def _bwd_dkv_chunk(q, k, v, do, lse, delta, *, causal, block_q, block_k,
                   interpret):
    """(dK, dV) contribution of all of q/do against one K/V chunk.
    Shapes as `_bwd_dq_chunk`; returns ([B,Tk,H,D], [B,Tk,H,D])."""
    interpret = _resolve_interpret(interpret)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    q_scale, s_scale = _scale_split(D)
    ops, g = _bwd_prep(q, k, v, do, lse, delta, block_q, block_k, causal)
    bq, bk, n_q, n_k = g["bq"], g["bk"], g["n_q"], g["n_k"]
    # k-major grid: k/v (and dk/dv outputs) blocked by grid dim 2,
    # q/do/lse/Δ streamed by the minor dim 3
    kv_blk = pl.BlockSpec((pl.squeezed, pl.squeezed, bk, D),
                          lambda b, h, i, j: (b, h, i, 0))
    q_index = _q_index_map(causal)
    q_stream = pl.BlockSpec((pl.squeezed, pl.squeezed, bq, D), q_index)
    row_stream = pl.BlockSpec((pl.squeezed, pl.squeezed, bq, 1), q_index)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, block_q=bq, block_k=bk,
                          q_len=g["q_len"], causal=causal, q_scale=q_scale,
                          s_scale=s_scale, n_q=n_q),
        grid=(B, H, n_k, n_q),
        in_specs=[q_stream, kv_blk, kv_blk, q_stream,
                  row_stream, row_stream],
        out_specs=[kv_blk, kv_blk],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, n_k * bk, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, n_k * bk, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=_BWD_DKV_NAME,
    )(*ops)
    untr = lambda a: jnp.transpose(a, (0, 2, 1, 3))[:, :Tk]  # noqa: E731
    return untr(dk), untr(dv)


def attention_delta(g, o):
    """Δ_i = Σ_d dO_id · O_id — the per-row correction every flash
    backward kernel needs; tiny elementwise reduce that XLA fuses."""
    return jnp.einsum("bthd,bthd->bht", g.astype(jnp.float32),
                      o.astype(jnp.float32))


def _flash_backward(q, k, v, o, lse, g, *, block_q, block_k,
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
def flash_attention(q, k, v, causal: bool = False, block_q=None,
                    block_k=None, interpret: bool | None = None):
    """[B, T, H, D] x3 → [B, T, H, D]. Pallas forward AND backward (the
    flash two-kernel recompute — no [T, T] materialization either way,
    and O(block) VMEM so long sequences stream). `interpret=None`
    auto-selects (`_resolve_interpret`).

    Tiles follow causality (`_tiles`): causal, square, 1024 where the
    caller leaves a block None, the diagonal tile whole in the forward
    and cut `_DIAGONAL_SPLIT` ways a side in the backward; non-causal,
    (512, 1024), since every tile is computed. Measured on a v5e, the
    kernels alone, bfloat16 causal, forward and backward (dQ + dK/dV):
    at [4, 1024, 16, 64] 0.420 and 0.940 ms against 0.503 and 1.114 at
    (512, 1024) tiles and XLA's 0.657 and 1.980; at [1, 8192, 16, 64]
    3.113 and 7.080 ms against 3.956 and 8.935, and XLA's 9.152 and
    29.009. `min(block, T)` keeps short sequences valid."""
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

"""A parallel decoder block (Cohere's Command family): ONE LayerNorm
without bias a layer, read by grouped-query attention and by a routed
expert layer alike, both added to the residual:

    h = LN(x);  y = x + Attn(h) + MoE(h)

Attention has fewer key heads than query heads (query head i reads key
head `i // (H / Hkv)`), no bias and no query or key norm.  A layer is of
one of two kinds, which a model interleaves:

- a WINDOW layer (`window` positions, `rotary`): queries and keys are
  rotated GPT-J style (pairs (2i, 2i+1) of a head turned by
  `position * theta^(-2i/Dh)`, output in the same interleaved places)
  and query t sees keys j with `0 <= t - j < window`;
- a FULL layer (`window` None, no rotation, no position of any kind):
  plain causal attention.

The expert layer is `nn/layers/moe.py`'s share of a routed layer (a
sigmoid router over `n_routed` outputs with no bias and no scaling,
gates normalised over the chosen; this layer HOLDS experts `held_first
.. held_first + held_count - 1`) plus `n_shared` shared experts whose
outputs are averaged.

The layer implements the serving engine's paged protocol
(docs/SERVING.md) with a (K, V) pair of pool arrays `[n_blocks,
block_len, Hkv*Dh]`.  A window layer declares `paged_window`: the
engine grants it a RING of `ceil(window / block_len) + 1` blocks a slot
from a pool of its own, logical block b at table column `b % ring`, so
it never holds more than the window's positions whatever the slot's
length; a full layer's table has a column for every block of the budget
and is read the same way (`b % max_blocks == b`).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import moe
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer
from deeplearning4j_tpu.nn.layers.latent import LMHead
from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrentLayer


def layer_norm_gain(x, gain, eps):
    """`(x - mean) * rsqrt(var + eps) * gain` over the last axis, a gain
    and no bias, the statistics in float32, in x.dtype."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mu
    y = xc * jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def rotate_interleaved(x, positions, theta: float):
    """x [..., H, Dh] at `positions` (x's axes before the head axis):
    pairs (2i, 2i+1) turned by `position * theta^(-2i/Dh)`, each pair
    back in its own two columns (GPT-J's rotation, `rope_gptj`)."""
    dh = x.shape[-1]
    f = jnp.asarray(theta ** (-np.arange(0, dh, 2, dtype=np.float64) / dh),
                    jnp.float32)
    ang = positions.astype(jnp.float32)[..., None, None] * f
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    a, b = xf[..., 0::2], xf[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(x.shape).astype(x.dtype)


def rotate_half(x, positions, theta: float):
    """x [..., H, Dh] at `positions` (x's axes before the head axis):
    columns i and i + Dh/2 turned by `position * theta^(-2i/Dh)`, each
    back in its own place (the rotate-half rotation of GPT-NeoX and its
    descendants)."""
    dh = x.shape[-1]
    f = jnp.asarray(theta ** (-np.arange(0, dh, 2, dtype=np.float64) / dh),
                    jnp.float32)
    ang = positions.astype(jnp.float32)[..., None, None] * f
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    a, b = xf[..., :dh // 2], xf[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


def keep_mask(q_pos, k_pos, window=None):
    """Which keys a query sees: causal, and inside the window."""
    keep = (k_pos >= 0) & (k_pos <= q_pos)
    if window is not None:
        keep = keep & (q_pos - k_pos < window)
    return keep


def grouped_queries(q, n_kv_heads: int, head_dim: int):
    """q [B, T, H, Dh] -> [B, T, Hkv, G, Dh]: query head i beside the
    others that read key head `i // G`."""
    return q.reshape(q.shape[:2] + (n_kv_heads, -1, head_dim))


def attend_blocks(q, k, v, wo, *, n_kv_heads: int, head_dim: int,
                  window=None, query_block: int = 128,
                  key_block: int = 4096):
    """q [B, T, H, Dh], k and v [B, T, Hkv*Dh] of whole sequences at
    positions 0..T-1 -> the attention output [B, T, D].  A block of
    queries at a time against the keys it can see (a window layer:
    the band alone, so the work grows as T x window), keys in chunks
    of `key_block`: one softmax over all of them (one maximum, one
    sum, float32), no product wider than a chunk."""
    B, T = q.shape[:2]
    q = grouped_queries(q, n_kv_heads, head_dim)
    k = k.reshape(B, T, n_kv_heads, head_dim)
    v = v.reshape(B, T, n_kv_heads, head_dim)
    scale = head_dim ** -0.5
    qb, kb = min(T, query_block), key_block
    out = []
    for q0 in range(0, T, qb):
        q1 = min(T, q0 + qb)
        lo = 0 if window is None else max(0, q0 - window + 1)
        # the span in equal chunks, none wider than `key_block`
        n_chunks = -(-(q1 - lo) // kb)
        step = -(-(q1 - lo) // n_chunks)
        parts = []
        for k0 in range(lo, q1, step):
            k1 = min(q1, k0 + step)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q[:, q0:q1], k[:, k0:k1],
                           preferred_element_type=jnp.float32)
            keep = keep_mask(jnp.arange(q0, q1)[:, None],
                             jnp.arange(k0, k1)[None, :], window)
            parts.append((k0, k1, jnp.where(keep, s * scale, -jnp.inf)))
        # a query sees its own key, so the maximum over the chunks
        # is finite on every row
        m = parts[0][2].max(-1, keepdims=True)
        for _, _, s in parts[1:]:
            m = jnp.maximum(m, s.max(-1, keepdims=True))
        l, o = 0.0, 0.0
        for k0, k1, s in parts:
            e = jnp.exp(s - m)
            l = l + e.sum(-1, keepdims=True)
            o = o + jnp.einsum("bhgqk,bkhd->bqhgd", e.astype(v.dtype),
                               v[:, k0:k1],
                               preferred_element_type=jnp.float32)
        out.append((o / jnp.transpose(l, (0, 3, 1, 2, 4))).astype(v.dtype))
    o = jnp.concatenate(out, 1).reshape(B, T, -1)
    return jnp.matmul(o, wo)


def attend_cached(q, k_rows, v_rows, q_pos, k_pos, wo, *, n_kv_heads: int,
                  head_dim: int, window=None):
    """q [S, K, H, Dh] at `q_pos` [S, K] against cache rows
    [S, L, Hkv*Dh] which hold positions `k_pos` [S, K, L] (as each
    query sees them; negative: nothing): the plain core the kernel
    is tested against."""
    S, L = k_rows.shape[:2]
    k = k_rows.reshape(S, L, n_kv_heads, head_dim)
    v = v_rows.reshape(S, L, n_kv_heads, head_dim)
    s = jnp.einsum("skhgd,slhd->shgkl",
                   grouped_queries(q, n_kv_heads, head_dim),
                   k.astype(q.dtype), preferred_element_type=jnp.float32)
    keep = keep_mask(q_pos[:, :, None], k_pos, window)[:, None, None]
    p = jax.nn.softmax(jnp.where(keep, s * head_dim ** -0.5, -jnp.inf),
                       axis=-1)
    o = jnp.einsum("shgkl,slhd->skhgd", p.astype(q.dtype),
                   v.astype(q.dtype))
    return jnp.matmul(o.reshape(o.shape[:2] + (-1,)), wo)


def write_rows(pools, rows, block_table, positions, live):
    """Scatter rows (K, V) [S, K, W] at `positions` [S, K] through
    the table read as a ring; lanes that are not `live` land in the
    garbage block."""
    bl = pools[0].shape[1]
    idx = (positions // bl) % block_table.shape[1]
    blk = jnp.take_along_axis(block_table, idx, axis=1)
    if live is not None:
        blk = jnp.where(live, blk, 0)
    return tuple(pool.at[blk, positions % bl].set(r.astype(pool.dtype))
                 for pool, r in zip(pools, rows))


def ring_view(pools, block_table, positions):
    """-> (K rows, V rows [S, ring*bl, W], k_pos [S, K, ring*bl]):
    the slot's pages gathered in table order, and the position each
    row holds as the query at `positions` [S, K] sees it: table
    column r holds the newest logical block `b = r (mod ring)` not
    past the query's own, negative where there is none yet."""
    ring, bl = block_table.shape[1], pools[0].shape[1]
    views = tuple(p[block_table].reshape(block_table.shape[0], ring * bl,
                                         p.shape[-1]) for p in pools)
    at = positions[..., None] // bl                       # [S, K, 1]
    b = at - (at - jnp.arange(ring)) % ring               # [S, K, ring]
    k_pos = (b[..., None] * bl + jnp.arange(bl)).reshape(
        positions.shape + (ring * bl,))
    return views[0], views[1], k_pos


def gqa_in_place(arrays, n_heads: int, n_kv_heads: int) -> bool:
    """Can the single-token step attend over the (K, V) pools in place
    (`dl4tpu_paged_decode`)?  The kernels' shared switch and the pool's
    shape decide; a refusal is warned of once."""
    from deeplearning4j_tpu import kernels
    from deeplearning4j_tpu.kernels import paged_attention
    from deeplearning4j_tpu.nn.layers.attention import (
        _warn_paged_fallback)
    if not kernels.kernels_enabled():
        return False
    reason = paged_attention.unsupported_reason(
        arrays[0].shape, arrays[0].dtype, n_heads, n_kv_heads)
    if reason is not None:
        _warn_paged_fallback(reason)
        return False
    return True


def gqa_paged_attend(q, arrays, block_table, pos, live, wo, *, n_heads: int,
                     n_kv_heads: int, head_dim: int, window, in_place: bool,
                     dtype):
    """The single-token step's attention once the token's K and V rows
    are in their page: q [S, 1, H, Dh] at `pos` [S] over the pages the
    slot holds (`dl4tpu_paged_decode`, in place, from the window's first
    position) or over a gathered view -> [S, 1, D] in `dtype`."""
    if in_place:
        from deeplearning4j_tpu.kernels.paged_attention import (
            paged_decode_attention)
        lengths = pos + 1
        if live is not None:
            lengths = jnp.where(live, lengths, 0)
        starts = (None if window is None
                  else jnp.maximum(lengths - window, 0))
        o = paged_decode_attention(
            q.reshape(q.shape[:2] + (-1,)), arrays[0], arrays[1],
            block_table, lengths, n_heads=n_heads,
            n_kv_heads=n_kv_heads, starts=starts)
        return jnp.matmul(o.astype(dtype), wo)
    positions = pos[:, None]
    k_rows, v_rows, k_pos = ring_view(arrays, block_table, positions)
    return attend_cached(q, k_rows, v_rows, positions, k_pos, wo,
                         n_kv_heads=n_kv_heads, head_dim=head_dim,
                         window=window)


@register_layer
@dataclasses.dataclass(eq=False)
class GainLayerNorm(Layer):
    """LayerNorm with a learned gain and no bias over the last axis."""

    layer_name = "gain_layer_norm"

    n_out: int = 0
    eps: float = 1e-5

    def __post_init__(self):
        if self.activation is None:
            self.activation = "identity"
        super().__post_init__()

    def set_n_in(self, input_type, override=True):
        if override or not self.n_out:
            self.n_out = input_type.size

    def init_params(self, rng, dtype=jnp.float32):
        return {"gamma": jnp.ones((self.n_out,), dtype)}

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        return layer_norm_gain(x, params["gamma"], self.eps), state


@register_layer
@dataclasses.dataclass(eq=False)
class TiedLMHead(LMHead):
    """The vocabulary projection of a model whose head is its embedding:
    `logits = x W^T * logit_scale` with `W [V, D]` laid out as the
    embedding's table (float32 logits, as `LMHead`).  The container has
    no tie between two layers' parameters: whoever installs the weights
    gives this leaf the embedding's values."""

    layer_name = "tied_lm_head"

    logit_scale: float = 1.0

    def init_params(self, rng, dtype=jnp.float32):
        return {"W": (0.02 * jax.random.normal(
            rng, (self.n_out, self.n_in), jnp.float32)).astype(dtype)}

    def pre_output(self, params, x):
        logits = jax.lax.dot_general(
            x, params["W"].astype(x.dtype),
            (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return logits if self.logit_scale == 1.0 \
            else logits * self.logit_scale


@register_layer
@dataclasses.dataclass(eq=False)
class ParallelAttentionMoEBlock(BaseRecurrentLayer):
    """x + GQA(LN(x)) + MoE(LN(x)) over [B, T, D]; see the module's
    docstring for the two kinds of layer and the expert layer."""

    layer_name = "parallel_attention_moe_block"
    stackable_params = False      # window and full layers differ in program
    paged_cache = True            # the serving engine's paged protocol
    paged_stream_limit = None     # no table, no length of its own
    # (K, V) pages of heads, but the handoff wire carries one block list
    # a slot and this net may hold two: the engine refuses (two pools)
    paged_handoff_heads = None

    n_in: int = 0
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 16
    window: Optional[int] = None
    rotary: bool = False
    rope_theta: float = 10000.0
    eps: float = 1e-5
    ffn_hidden: int = 0
    n_routed: int = 0
    experts_per_token: int = 0
    held_first: int = 0
    held_count: int = 0
    n_shared: int = 1
    init_std: float = 0.02
    # length of the monolithic cache of `generate()` / `rnn_time_step`
    # (static shapes); the paged path takes its budget from the server
    cache_len: int = 512
    # queries scored at once against their keys in `forward` and
    # `forward_prefill`: 128 heads x 128 queries x 4,096 keys of float32
    # scores are 0.27 GB a chunk of keys
    query_block: int = 128
    # keys scored at once: over 4,096 keys XLA's attention falls off a
    # cliff on the v5e (PR 29), so longer rows are taken in chunks
    key_block: int = 4096

    def __post_init__(self):
        if self.activation is None:
            self.activation = "identity"
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"n_heads {self.n_heads} is not a multiple of "
                             f"n_kv_heads {self.n_kv_heads}")
        if self.window is not None and self.window < 1:
            raise ValueError(f"window must be >= 1; got {self.window}")
        super().__post_init__()

    # ----------------------------------------------------------- shapes
    @property
    def stream_limit(self):
        return self.cache_len

    @property
    def paged_window(self) -> Optional[int]:
        """Positions back from a query this layer ever reads (None:
        all): what the engine sizes the layer's ring of blocks from."""
        return self.window

    @property
    def kv_width(self) -> int:
        return self.n_kv_heads * self.head_dim

    def set_n_in(self, input_type, override=True):
        if override or not self.n_in:
            self.n_in = input_type.size

    def get_output_type(self, input_type):
        return InputType.recurrent(self.n_in,
                                   getattr(input_type, "timesteps", None))

    def init_params(self, rng, dtype=jnp.float32):
        D, F, E = self.n_in, self.ffn_hidden, self.held_count
        Q, KV = self.n_heads * self.head_dim, self.kv_width
        S = self.n_shared * F
        ks = jax.random.split(rng, 11)

        def n(k, *shape):
            return (self.init_std * jax.random.normal(k, shape, jnp.float32)
                    ).astype(dtype)

        return {"norm": jnp.ones((D,), dtype),
                "wq": n(ks[0], D, Q), "wk": n(ks[1], D, KV),
                "wv": n(ks[2], D, KV), "wo": n(ks[3], Q, D),
                "router": n(ks[4], D, self.n_routed),
                "e_gate": n(ks[5], E, D, F), "e_up": n(ks[6], E, D, F),
                "e_down": n(ks[7], E, F, D),
                "s_gate": n(ks[8], D, S), "s_up": n(ks[9], D, S),
                "s_down": n(ks[10], S, D)}

    # ------------------------------------------------------------ pieces
    def _qkv(self, params, h, positions):
        """h [..., D] at `positions` [...] -> (q [..., H, Dh], k and v
        [..., Hkv*Dh]: the rows the cache holds, keys already rotated)."""
        lead = h.shape[:-1]
        q = jnp.matmul(h, params["wq"]).reshape(
            lead + (self.n_heads, self.head_dim))
        k = jnp.matmul(h, params["wk"])
        v = jnp.matmul(h, params["wv"])
        if self.rotary:
            q = rotate_interleaved(q, positions, self.rope_theta)
            k = rotate_interleaved(
                k.reshape(lead + (self.n_kv_heads, self.head_dim)),
                positions, self.rope_theta).reshape(k.shape)
        return q, k, v

    def _attn(self) -> dict:
        return dict(n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
                    window=self.window)

    def _experts(self, params, h, valid=None, stats=None):
        """h [B, T, D] (the layer's one norm) -> the held experts' part
        of the routed sum plus the shared experts' average.  `valid`
        [B, T] marks the tokens that are real (None: all): the others
        are routed to no expert.  `stats`, a dict, gets this layer's
        routed rows added."""
        B, T, D = h.shape
        flat = h.reshape(B * T, D)
        chosen, gates = moe.sigmoid_topk_route(
            flat, params["router"], jnp.zeros((self.n_routed,), jnp.float32),
            self.experts_per_token, 1.0)
        y = moe.held_experts_swiglu(
            flat, chosen, gates, params["e_gate"], params["e_up"],
            params["e_down"], first=self.held_first,
            valid=None if valid is None else valid.reshape(-1), stats=stats)
        shared = moe.shared_experts_mean(h, params["s_gate"], params["s_up"],
                                         params["s_down"], self.n_shared)
        return y.reshape(B, T, D) + shared

    # ------------------------------------------------------- full forward
    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        if mask is not None:
            raise ValueError("ParallelAttentionMoEBlock is causal and takes "
                             "no padding mask: pad on the right")
        y, _ = self.forward_prefill(params, x, None)
        return y, state

    def forward_prefill(self, params, x, lengths, *, stats=None):
        """Whole right-padded prompts x [B, T, D] of `lengths` [B] (None:
        all T real) -> (y, (K rows, V rows)): the `[B, T, Hkv*Dh]` rows
        the layer's pages are cut from."""
        pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        h = layer_norm_gain(x, params["norm"], self.eps)
        q, k, v = self._qkv(params, h, pos)
        a = attend_blocks(q, k, v, params["wo"], query_block=self.query_block,
                          key_block=self.key_block, **self._attn())
        valid = None if lengths is None else pos < lengths[:, None]
        return x + a + self._experts(params, h, valid, stats), (k, v)

    # ---------------------------------------------- monolithic cache path
    def init_carry(self, batch, dtype=jnp.float32):
        shape = (batch, self.cache_len, self.kv_width)
        return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                jnp.zeros((), jnp.int32))

    def forward_with_carry(self, params, state, x, carry, *, train=False,
                           rng=None, mask=None):
        """Streaming step of `generate()` / `rnn_time_step`: the new
        tokens' rows enter `[B, cache_len, Hkv*Dh]` caches at the carry's
        position (a window layer keeps them all and masks)."""
        if mask is not None:
            raise ValueError("ParallelAttentionMoEBlock cannot stream with "
                             "a padding mask")
        k_cache, v_cache, pos = carry
        B, T, _ = x.shape
        positions = jnp.broadcast_to(pos + jnp.arange(T), (B, T))
        h = layer_norm_gain(x, params["norm"], self.eps)
        q, k, v = self._qkv(params, h, positions)
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), pos, 1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), pos, 1)
        k_pos = jnp.broadcast_to(jnp.arange(self.cache_len),
                                 (B, T, self.cache_len))
        a = attend_cached(q, k_cache, v_cache, positions, k_pos, params["wo"],
                          **self._attn())
        return (x + a + self._experts(params, h), {},
                (k_cache, v_cache, pos + T))

    # ------------------------------------------------------ paged protocol
    def paged_pool_arrays(self, n_blocks, block_len, dtype):
        """(K pool, V pool), `[n_blocks, block_len, Hkv*Dh]` each."""
        shape = (n_blocks, block_len, self.kv_width)
        return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))

    def carry_pages(self, carry):
        return (carry[0], carry[1])

    def paged_in_place(self, arrays) -> bool:
        return gqa_in_place(arrays, self.n_heads, self.n_kv_heads)

    def paged_step(self, params, x, arrays, block_table, pos, live=None, *,
                   stats=None):
        """One new token a slot: x [S, 1, D], `pos` [S] each slot's own
        position, `live` [S] the slots that are decoding.  The token's K
        and V rows enter their page, attention runs over the pages the
        slot holds (`dl4tpu_paged_decode`, in place, from the window's
        first position) or over a gathered view.  -> (y, arrays')."""
        positions = pos[:, None]
        h = layer_norm_gain(x, params["norm"], self.eps)
        q, k, v = self._qkv(params, h, positions)
        arrays = write_rows(arrays, (k, v), block_table, positions,
                            None if live is None else live[:, None])
        a = gqa_paged_attend(
            q, arrays, block_table, pos, live, params["wo"],
            n_heads=self.n_heads, in_place=self.paged_in_place(arrays),
            dtype=h.dtype, **self._attn())
        valid = None if live is None else live[:, None]
        return x + a + self._experts(params, h, valid, stats), arrays

    def paged_step_multi(self, params, x, arrays, block_table, pos, n_valid,
                         *, stats=None):
        """K consecutive tokens a slot at `pos .. pos+K-1`, the first
        `n_valid` of them real (the score program of speculation):
        gather + the plain core.  In a window layer's ring the K writes
        may not reach back into a block that the first of the K queries
        still reads: K <= block_len + 1 (the engine checks)."""
        K = x.shape[1]
        j = jnp.arange(K)[None, :]
        positions = pos[:, None] + j
        live = j < n_valid[:, None]
        h = layer_norm_gain(x, params["norm"], self.eps)
        q, k, v = self._qkv(params, h, positions)
        arrays = write_rows(arrays, (k, v), block_table, positions, live)
        k_rows, v_rows, k_pos = ring_view(arrays, block_table, positions)
        a = attend_cached(q, k_rows, v_rows, positions, k_pos, params["wo"],
                          **self._attn())
        return x + a + self._experts(params, h, live, stats), arrays

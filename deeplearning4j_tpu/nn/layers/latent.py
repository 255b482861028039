"""A 2024-style decoder block: pre-RMSNorm, no biases, latent attention
(MLA) with YaRN rotary positions, and a SwiGLU feed-forward that is
either dense or a routed expert layer of which this chip holds a share.

Latent attention (DeepSeek-V2): keys and values of all heads are
expanded from one `kv_lora_rank`-wide latent a token, and one rotated
`qk_rope_head_dim`-wide key is shared by all heads.  The cache holds
just those `kv_lora_rank + qk_rope_head_dim` values a token a layer
(the latent after its norm, the key after its rotation).  Two forms of
the same attention:

- expanded (`forward`, `forward_prefill`): `k_nope`, `v` are computed
  from the latent for every position and attention is ordinary
  multi-head attention with 192-wide keys and 128-wide values, scored
  a block of queries at a time (plain XLA; the repo's flash kernel takes
  one head width for keys and values);
- absorbed (the cached paths): the query is carried into the latent
  space (`q_abs = q_nope W_K[h]`), scored against the cached latent,
  the probabilities weigh the latent itself and `W_V[h]` is applied to
  the result: all heads read one cache row, nothing is expanded.

The layer implements the serving engine's paged protocol
(docs/SERVING.md) with ONE pool array a layer, `[n_blocks, block_len,
W]`, `W` the cache row padded to whole 128-lane tiles.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import moe
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer
from deeplearning4j_tpu.nn.layers.recurrent import (BaseRecurrentLayer,
                                                    RnnOutputLayer)

LANES = 128


def rms_norm(x, gain, eps):
    """`x / rms(x) * gain` with the statistics in float32, in x.dtype."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def swiglu(h, w_gate, w_up, w_down):
    a = jnp.matmul(h, w_gate)
    return jnp.matmul(jax.nn.silu(a) * jnp.matmul(h, w_up), w_down)


def yarn_inv_freq(dim: int, theta: float, scaling: Optional[dict]):
    """Rotary frequencies `theta^(-2i/dim)`, under `deepseek_yarn`
    scaling kept where a dimension turns more than `beta_fast` times
    over the original positions, divided by `factor` where it turns
    fewer than `beta_slow` times, a linear ramp between."""
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return f
    orig = scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return f / scaling["factor"] * ramp + f * (1.0 - ramp)


def yarn_mscale(scaling: Optional[dict], which: str) -> float:
    if not scaling or scaling["factor"] <= 1:
        return 1.0
    return 0.1 * scaling[which] * math.log(scaling["factor"]) + 1.0


@register_layer
@dataclasses.dataclass(eq=False)
class RMSNormLayer(Layer):
    """RMSNorm with a learned gain over the last axis."""

    layer_name = "rms_norm"

    n_out: int = 0
    eps: float = 1e-6

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
        return rms_norm(x, params["gamma"], self.eps), state


@register_layer
@dataclasses.dataclass(eq=False)
class LMHead(RnnOutputLayer):
    """Untied, unbiased vocabulary projection with float32 logits
    whatever the parameters' dtype (bfloat16 logits over 65,536 ids tie
    by the thousand), softmax over them."""

    layer_name = "lm_head"

    def __post_init__(self):
        self.has_bias = False
        super().__post_init__()

    def pre_output(self, params, x):
        return jnp.matmul(x, params["W"].astype(x.dtype),
                          preferred_element_type=jnp.float32)


@register_layer
@dataclasses.dataclass(eq=False)
class LatentAttentionBlock(BaseRecurrentLayer):
    """x + MLA(RMSNorm(x)), then h + FFN(RMSNorm(h)) over [B, T, D].

    `ffn`: "dense" (SwiGLU of width `ffn_hidden`) or "experts": a
    sigmoid router over `n_routed` outputs with a selection bias,
    `experts_per_token` chosen, gates normalised over the chosen and
    scaled by `routed_scaling`; this layer HOLDS the experts
    `held_first .. held_first + held_count - 1` (width `ffn_hidden`
    each) and computes the tokens routed to them
    (`moe.held_experts_swiglu`), plus one shared expert."""

    layer_name = "latent_attention_block"
    stackable_params = False      # dense and expert layers differ in tree
    paged_cache = True            # the serving engine's paged protocol
    paged_stream_limit = None     # rotary: no table, no length of its own

    n_in: int = 0
    n_heads: int = 8
    kv_lora_rank: int = 64
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    rope_theta: float = 10000.0
    rope_scaling: Any = None       # the public config's group, or None
    eps: float = 1e-6
    ffn: str = "dense"
    ffn_hidden: int = 0
    n_routed: int = 0
    experts_per_token: int = 0
    held_first: int = 0
    held_count: int = 0
    routed_scaling: float = 1.0
    router_bias_std: float = 0.0
    init_std: float = 0.02
    # length of the monolithic cache of `generate()` / `rnn_time_step`
    # (static shapes); the paged path takes its budget from the server
    cache_len: int = 512
    # queries scored at once against their keys in the expanded form: at
    # 8,192 positions a block of 256 is 0.5 GB of float32 scores (512
    # would be 1 GB more of temporaries beside 9 GB of weights)
    query_block: int = 256
    # keys scored at once: over 4,096 keys XLA's attention falls off a
    # cliff on the v5e (470 ms a layer at 8,192 positions against 15.8
    # at 4,096: my chip runs, PR 29), so longer rows are taken in chunks
    key_block: int = 4096

    def __post_init__(self):
        if self.activation is None:
            self.activation = "identity"
        if self.ffn not in ("dense", "experts"):
            raise ValueError(f"ffn must be 'dense' or 'experts'; got "
                             f"{self.ffn!r}")
        super().__post_init__()

    # ----------------------------------------------------------- shapes
    @property
    def stream_limit(self):
        return self.cache_len

    @property
    def row_width(self) -> int:
        """Values cached a token: the latent and the rotated key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_width(self) -> int:
        return -(-self.row_width // LANES) * LANES

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_scaling, "mscale_all_dim")
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    def set_n_in(self, input_type, override=True):
        if override or not self.n_in:
            self.n_in = input_type.size

    def get_output_type(self, input_type):
        return InputType.recurrent(self.n_in,
                                   getattr(input_type, "timesteps", None))

    def init_params(self, rng, dtype=jnp.float32):
        D, H, R = self.n_in, self.n_heads, self.kv_lora_rank
        dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim)
        F = self.ffn_hidden
        ks = jax.random.split(rng, 12)

        def n(k, *shape):
            return (self.init_std * jax.random.normal(k, shape, jnp.float32)
                    ).astype(dtype)

        p = {"attn_norm": jnp.ones((D,), dtype),
             "wq": n(ks[0], D, H * (dn + dr)),
             "q_norm": jnp.ones((dn + dr,), dtype),
             "wkv_a": n(ks[1], D, R + dr),
             "kv_norm": jnp.ones((R,), dtype),
             "wkv_b": n(ks[2], R, H * (dn + dv)),
             "wo": n(ks[3], H * dv, D),
             "ffn_norm": jnp.ones((D,), dtype)}
        if self.ffn == "dense":
            p.update(w_gate=n(ks[4], D, F), w_up=n(ks[5], D, F),
                     w_down=n(ks[6], F, D))
        else:
            E = self.held_count
            p.update(router=n(ks[4], D, self.n_routed),
                     router_bias=self.router_bias_std * jax.random.normal(
                         ks[5], (self.n_routed,), jnp.float32),
                     e_gate=n(ks[6], E, D, F), e_up=n(ks[7], E, D, F),
                     e_down=n(ks[8], E, F, D),
                     s_gate=n(ks[9], D, F), s_up=n(ks[10], D, F),
                     s_down=n(ks[11], F, D))
        return p

    # ------------------------------------------------------------ pieces
    def _rotate(self, x, positions):
        """x [..., dr] at `positions` (x's leading axes, or those less a
        head axis): pairs (2i, 2i+1) turned by `position * f_i`, output
        de-interleaved, as DeepSeek-V2's `apply_rotary_pos_emb`."""
        f = jnp.asarray(yarn_inv_freq(self.qk_rope_head_dim, self.rope_theta,
                                      self.rope_scaling), jnp.float32)
        ang = positions.astype(jnp.float32)[..., None] * f
        scale = (yarn_mscale(self.rope_scaling, "mscale")
                 / yarn_mscale(self.rope_scaling, "mscale_all_dim"))
        cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
        if cos.ndim < x.ndim:                      # a head axis in x
            cos, sin = cos[..., None, :], sin[..., None, :]
        xf = x.astype(jnp.float32)
        a, b = xf[..., 0::2], xf[..., 1::2]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                               -1).astype(x.dtype)

    def _queries(self, params, h, positions):
        """h [..., D] -> (q_nope [..., H, dn], q_pe [..., H, dr])."""
        dn, dr = self.qk_nope_head_dim, self.qk_rope_head_dim
        q = jnp.matmul(h, params["wq"]).reshape(
            h.shape[:-1] + (self.n_heads, dn + dr))
        q = rms_norm(q, params["q_norm"], self.eps)
        return q[..., :dn], self._rotate(q[..., dn:], positions)

    def _cache_rows(self, params, h, positions):
        """h [..., D] -> [..., row_width]: what the cache holds a token,
        the latent after its norm beside the rotated key."""
        R = self.kv_lora_rank
        kv = jnp.matmul(h, params["wkv_a"])
        return jnp.concatenate(
            [rms_norm(kv[..., :R], params["kv_norm"], self.eps),
             self._rotate(kv[..., R:], positions)], -1)

    def _pad_rows(self, rows, dtype):
        pad = self.pool_width - self.row_width
        return jnp.pad(rows.astype(dtype),
                       [(0, 0)] * (rows.ndim - 1) + [(0, pad)])

    def _wkv_b(self, params):
        w = params["wkv_b"].reshape(self.kv_lora_rank, self.n_heads, -1)
        return w[..., :self.qk_nope_head_dim], w[..., self.qk_nope_head_dim:]

    def _attend_expanded(self, params, h, positions):
        """h [B, T, D] -> (attention output [B, T, D], cache rows
        [B, T, row_width]); causal over T, a block of queries at a time
        against the keys up to its end."""
        B, T, _ = h.shape
        H, R = self.n_heads, self.kv_lora_rank
        dn, dv = self.qk_nope_head_dim, self.v_head_dim
        q_nope, q_pe = self._queries(params, h, positions)
        rows = self._cache_rows(params, h, positions)
        kvb = jnp.matmul(rows[..., :R], params["wkv_b"]).reshape(
            B, T, H, dn + dv)
        q = jnp.concatenate([q_nope, q_pe], -1)
        k = jnp.concatenate(
            [kvb[..., :dn],
             jnp.broadcast_to(rows[:, :, None, R:], (B, T, H, q_pe.shape[-1]))],
            -1)
        v = kvb[..., dn:]
        out = []
        qb, kb = min(T, self.query_block), self.key_block
        scale = self.softmax_scale
        for q0 in range(0, T, qb):
            q1 = min(T, q0 + qb)
            # keys in chunks of at most `key_block`: the softmax is taken
            # over all of them (one maximum, one sum, float32), but no
            # product or reduction is wider than a chunk
            parts = []
            for k0 in range(0, q1, kb):
                k1 = min(q1, k0 + kb)
                s = jnp.einsum("bqhd,bkhd->bhqk", q[:, q0:q1], k[:, k0:k1],
                               preferred_element_type=jnp.float32)
                keep = jnp.arange(k0, k1)[None, :] \
                    <= jnp.arange(q0, q1)[:, None]
                parts.append((k0, k1, jnp.where(keep, s * scale, -jnp.inf)))
            m = parts[0][2].max(-1, keepdims=True)    # key 0 is never masked
            for _, _, s in parts[1:]:
                m = jnp.maximum(m, s.max(-1, keepdims=True))
            l, o = 0.0, 0.0
            for k0, k1, s in parts:
                e = jnp.exp(s - m)
                l = l + e.sum(-1, keepdims=True)
                o = o + jnp.einsum("bhqk,bkhd->bqhd", e.astype(v.dtype),
                                   v[:, k0:k1],
                                   preferred_element_type=jnp.float32)
            out.append((o / jnp.swapaxes(l, 1, 2)).astype(v.dtype))
        o = jnp.concatenate(out, 1).reshape(B, T, H * dv)
        return jnp.matmul(o, params["wo"]), rows

    def _attend_absorbed(self, params, h, positions, cache):
        """h [S, K, D] at `positions` [S, K] against a cache view
        [S, L, >= row_width] whose index is the position: every cache
        row past a query's position is masked."""
        R = self.kv_lora_rank
        q, w_v = self._absorbed_queries(params, h, positions)
        cache = cache.astype(h.dtype)
        s = jnp.einsum("skhc,slc->shkl", q, cache[..., :self.row_width],
                       preferred_element_type=jnp.float32)
        keep = jnp.arange(cache.shape[1])[None, None, :] \
            <= positions[:, :, None]
        p = jax.nn.softmax(jnp.where(keep[:, None], s * self.softmax_scale,
                                     -jnp.inf), axis=-1)
        o_lat = jnp.einsum("shkl,slr->skhr", p.astype(h.dtype),
                           cache[..., :R])
        return self._project_out(params, o_lat, w_v)

    def _absorbed_queries(self, params, h, positions):
        """-> (q [S, K, H, row_width]: each head's query carried into the
        cache row's own space, `q_nope W_K[h]` beside the rotated `q_pe`;
        W_V [R, H, dv] for `_project_out`)."""
        w_k, w_v = self._wkv_b(params)
        q_nope, q_pe = self._queries(params, h, positions)
        return jnp.concatenate(
            [jnp.einsum("skhd,rhd->skhr", q_nope, w_k), q_pe], -1), w_v

    def _project_out(self, params, o_lat, w_v):
        """o_lat [S, K, H, R] (probabilities times the latent) -> the
        attention output [S, K, D]."""
        o = jnp.einsum("skhr,rhd->skhd", o_lat, w_v)
        return jnp.matmul(o.reshape(o.shape[:2] + (-1,)), params["wo"])

    def _feed_forward(self, params, x, valid=None, stats=None):
        """x [B, T, D] -> x + FFN(RMSNorm(x)).  `valid` [B, T] marks the
        tokens that are real (None: all): the others are routed to no
        expert.  `stats`, a dict, gets this layer's routed rows added."""
        h = rms_norm(x, params["ffn_norm"], self.eps)
        if self.ffn == "dense":
            return x + swiglu(h, params["w_gate"], params["w_up"],
                              params["w_down"])
        B, T, D = h.shape
        flat = h.reshape(B * T, D)
        chosen, gates = moe.sigmoid_topk_route(
            flat, params["router"], params["router_bias"],
            self.experts_per_token, self.routed_scaling)
        y, sizes = moe.held_experts_swiglu(
            flat, chosen, gates, params["e_gate"], params["e_up"],
            params["e_down"], first=self.held_first,
            valid=None if valid is None else valid.reshape(-1))
        moe.record_load(stats, sizes)
        shared = swiglu(h, params["s_gate"], params["s_up"], params["s_down"])
        return x + y.reshape(B, T, D) + shared

    # ------------------------------------------------------- full forward
    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        if mask is not None:
            raise ValueError("LatentAttentionBlock is causal and takes no "
                             "padding mask: pad on the right")
        pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        a, _ = self._attend_expanded(
            params, rms_norm(x, params["attn_norm"], self.eps), pos)
        return self._feed_forward(params, x + a), state

    # ---------------------------------------------- monolithic cache path
    def init_carry(self, batch, dtype=jnp.float32):
        return (jnp.zeros((batch, self.cache_len, self.row_width), dtype),
                jnp.zeros((), jnp.int32))

    def forward_with_carry(self, params, state, x, carry, *, train=False,
                           rng=None, mask=None):
        """Streaming step of `generate()` / `rnn_time_step`: the new
        tokens' rows enter a `[B, cache_len, row_width]` cache at the
        carry's position and attention runs in the absorbed form."""
        if mask is not None:
            raise ValueError("LatentAttentionBlock cannot stream with a "
                             "padding mask")
        cache, pos = carry
        B, T, _ = x.shape
        positions = jnp.broadcast_to(pos + jnp.arange(T), (B, T))
        h = rms_norm(x, params["attn_norm"], self.eps)
        cache = jax.lax.dynamic_update_slice_in_dim(
            cache, self._cache_rows(params, h, positions).astype(cache.dtype),
            pos, 1)
        a = self._attend_absorbed(params, h, positions, cache)
        return self._feed_forward(params, x + a), {}, (cache, pos + T)

    # ------------------------------------------------------ paged protocol
    def paged_pool_arrays(self, n_blocks, block_len, dtype):
        return (jnp.zeros((n_blocks, block_len, self.pool_width), dtype),)

    def carry_pages(self, carry):
        return (carry[0],)

    def paged_in_place(self, arrays) -> bool:
        from deeplearning4j_tpu import kernels
        from deeplearning4j_tpu.kernels import mla_paged_attention
        from deeplearning4j_tpu.nn.layers.attention import (
            _warn_paged_fallback)
        if not kernels.kernels_enabled():
            return False
        reason = mla_paged_attention.unsupported_reason(
            arrays[0].shape, arrays[0].dtype, self.n_heads,
            self.kv_lora_rank)
        if reason is not None:
            _warn_paged_fallback(reason)
            return False
        return True

    def _write_rows(self, pool, rows, block_table, positions, live):
        """Scatter rows [S, K, W] at `positions` [S, K] through the block
        table; lanes that are not `live` land in the garbage block."""
        bl = pool.shape[1]
        idx = jnp.minimum(positions // bl, block_table.shape[1] - 1)
        blk = jnp.take_along_axis(block_table, idx, axis=1)
        if live is not None:
            blk = jnp.where(live, blk, 0)
        return pool.at[blk, positions % bl].set(rows)

    def paged_step(self, params, x, arrays, block_table, pos, live=None, *,
                   stats=None):
        """One new token a slot: x [S, 1, D], `pos` [S] each slot's own
        position, `live` [S] the slots that are decoding.  The token's
        row enters its page, attention runs in the absorbed form over
        the pages the slot holds (`dl4tpu_mla_paged_decode`, in place) or
        over a gathered view.  -> (y, arrays')."""
        (pool,) = arrays
        positions = pos[:, None]
        h = rms_norm(x, params["attn_norm"], self.eps)
        rows = self._pad_rows(self._cache_rows(params, h, positions),
                              pool.dtype)
        pool = self._write_rows(pool, rows, block_table, positions,
                                None if live is None else live[:, None])
        if self.paged_in_place((pool,)):
            from deeplearning4j_tpu.kernels.mla_paged_attention import (
                mla_paged_decode_attention)
            q, w_v = self._absorbed_queries(params, h, positions)
            lengths = pos + 1
            if live is not None:
                lengths = jnp.where(live, lengths, 0)
            o_lat = mla_paged_decode_attention(
                q[:, 0], pool, block_table, lengths,
                latent=self.kv_lora_rank, scale=self.softmax_scale)
            a = self._project_out(params, o_lat[:, None].astype(h.dtype),
                                  w_v)
        else:
            a = self._attend_absorbed(params, h, positions,
                                      self._paged_view(pool, block_table))
        valid = None if live is None else live[:, None]
        return self._feed_forward(params, x + a, valid, stats), (pool,)

    def paged_step_multi(self, params, x, arrays, block_table, pos, n_valid,
                         *, stats=None):
        """K consecutive tokens a slot at `pos .. pos+K-1`, the first
        `n_valid` of them real (the score program of speculation and of
        shared-prefix suffixes): gather + absorbed attention."""
        (pool,) = arrays
        K = x.shape[1]
        j = jnp.arange(K)[None, :]
        positions = pos[:, None] + j
        live = j < n_valid[:, None]
        h = rms_norm(x, params["attn_norm"], self.eps)
        rows = self._pad_rows(self._cache_rows(params, h, positions),
                              pool.dtype)
        pool = self._write_rows(pool, rows, block_table, positions, live)
        a = self._attend_absorbed(params, h, positions,
                                  self._paged_view(pool, block_table))
        return self._feed_forward(params, x + a, live, stats), (pool,)

    def forward_prefill(self, params, x, lengths, *, stats=None):
        """Whole right-padded prompts x [B, T, D] of `lengths` [B]:
        expanded attention, and the rows their pages are cut from.
        -> (y, ([B, T, W],))."""
        pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        a, rows = self._attend_expanded(
            params, rms_norm(x, params["attn_norm"], self.eps), pos)
        valid = pos < lengths[:, None]
        return (self._feed_forward(params, x + a, valid, stats),
                (self._pad_rows(rows, x.dtype),))

    @staticmethod
    def _paged_view(pool, block_table):
        """[S, max_blocks * block_len, W]: position p of slot s at index
        p, as the monolithic cache has it."""
        seq = pool[block_table]
        return seq.reshape(seq.shape[0], -1, seq.shape[-1])

"""A hybrid decoder block (AI21's Jamba family, Liquid AI's LFM2 family):
pre-RMSNorm, no biases in the linear maps, a MIXER that is a selective
state-space layer (Mamba-1), a gated short convolution or grouped-query
attention, then a SwiGLU FFN, dense or of routed experts:

    h = x + Mixer(RMSNorm(x));  y = h + FFN(RMSNorm(h))

The Mamba mixer, for token t of a sequence (`C = d_inner` channels, `N`
state columns, `R = dt_rank`, a convolution over the last `K` inputs):

    [x_t, z_t] = W_in u_t                                  D -> 2C
    x_t  <- silu(b_c + sum_k w_c[k] * x_{t-K+1+k})         depthwise, causal
    [dt_t, B_t, C_t] = W_x x_t                             C -> R + N + N
    dt_t, B_t, C_t <- RMSNorm of each (learned gains)
    Delta_t = softplus(W_dt dt_t + b_dt)                   R -> C
    h_t = exp(Delta_t * A) * h_{t-1} + (Delta_t * x_t) B_t   A = -exp(A_log)
    y_t = h_t C_t + D * x_t
    out_t = W_out (y_t * silu(z_t))                        C -> D

`Delta`, `A`, `h` and the recurrence are float32; the products run in
the compute dtype.  The state `h` is `[N, C]` a sequence (the state
columns on sublanes, the channels on the lanes) and the convolution
remembers the last `K - 1` inputs: a FIXED size whatever the sequence's
length, where an attention layer keeps a row a position.

The gated short convolution (LFM2's `Lfm2ShortConv`, `K = d_conv` taps,
no bias, D channels):

    [B_t, C_t, x_t] = W_in u_t                             D -> 3D
    v_t = B_t * x_t
    c_t = sum_k w[k] * v_{t-K+1+k}                         depthwise, causal
    out_t = W_out (C_t * c_t)                              D -> D

Its state is the last `K - 1` rows of `v`, in the compute dtype.

The attention mixer has no position of any kind unless `rope_theta` is
set (then queries and keys are rotated rotate-half style, on every
column of a head); `qk_norm` puts an RMSNorm with a learned gain on each
query head and each key head before the rotation.  The FFN is a dense
SwiGLU of width `ffn_hidden`, or, where `n_routed` is set, that many
routed SwiGLU experts of width `ffn_hidden`, all held: a sigmoid router
in float32, the `experts_per_token` largest of score + `expert_bias`
chosen (the bias chooses and does not weigh), gates the chosen scores
over their sum (+ `route_eps`), times `routed_scaling`
(`nn/layers/moe.py`).

Three ways through the same arithmetic (`_mix`, `_conv`):

- the monolithic carry of `generate()` / `rnn_time_step`
  (`init_carry` / `forward_with_carry`);
- the serving engine's prefill (`forward_prefill`): whole right-padded
  prompts from a zero state; positions past a row's length do not move
  the state (`Delta` is 0 there) and the convolution's tail is taken at
  the row's last real token, so the state a padded prompt leaves is the
  unpadded prompt's.  The recurrence is `dl4tpu_selective_scan` on a TPU
  (`kernels/selective_scan.py`) and a `lax.scan` over time elsewhere;
  padded positions are routed to no expert;
- the engine's decode step: a Mamba or convolution layer DECLARES a
  per-slot state (`slot_state_arrays`: Mamba's `h [n_slots, N, C]`
  float32 and its tail `[K-1, n_slots, C]`, the convolution's tail
  `[K-1, n_slots, D]`) and advances it a token at a time (`state_step`);
  an attention layer declares (K, V) pages and follows the paged
  protocol like `ParallelAttentionMoEBlock`'s full layer
  (docs/SERVING.md), whose functions it calls.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import moe, parallel
from deeplearning4j_tpu.nn.layers.base import register_layer
from deeplearning4j_tpu.nn.layers.latent import rms_norm, swiglu
from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrentLayer

MAMBA, ATTENTION, CONV = "mamba", "attention", "conv"
MIXERS = (MAMBA, ATTENTION, CONV)


def scan_recurrence(x, delta, a, b, c, d, h0, lengths):
    """The selective scan over a wave (the arguments and results of
    `kernels/selective_scan.py::selective_scan`): the kernel where the
    kernels' shared switch is on and it can tile the shapes, else the
    plain `lax.scan` over time."""
    from deeplearning4j_tpu import kernels
    from deeplearning4j_tpu.kernels import selective_scan as ss
    if kernels.kernels_enabled() and ss.unsupported_reason(
            x.shape, a.shape[0]) is None:
        return ss.selective_scan(x, delta, a, b, c, d, h0, lengths)
    return ss.selective_scan_reference(x, delta, a, b, c, d, h0, lengths)


@register_layer
@dataclasses.dataclass(eq=False)
class HybridStateSpaceBlock(BaseRecurrentLayer):
    """x + Mixer(RMSNorm(x)), then h + FFN(RMSNorm(h)) over [B, T, D];
    `mixer` is "mamba", "conv" or "attention", the FFN dense or routed
    (the module's docstring)."""

    layer_name = "hybrid_state_space_block"
    stackable_params = False      # the mixers differ in tree
    paged_stream_limit = None     # no table, no length of its own
    # the handoff wire carries pages and has no place for a state
    paged_handoff_heads = None

    n_in: int = 0
    mixer: str = MAMBA
    ffn_hidden: int = 0
    eps: float = 1e-6
    init_std: float = 0.02
    # attention mixer
    n_heads: int = 4
    n_kv_heads: int = 1
    head_dim: int = 16
    qk_norm: bool = False
    rope_theta: Optional[float] = None   # None: no position of any kind
    # Mamba mixer (`d_conv` also the short convolution's taps)
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 8
    # routed experts in place of the dense FFN (0: dense), all held
    n_routed: int = 0
    experts_per_token: int = 0
    route_eps: float = 0.0
    routed_scaling: float = 1.0
    # length of the monolithic cache of `generate()` / `rnn_time_step`
    # (an attention mixer's; the paged path takes its budget from the
    # server)
    cache_len: int = 512
    query_block: int = 128
    key_block: int = 4096

    def __post_init__(self):
        if self.activation is None:
            self.activation = "identity"
        if self.mixer not in MIXERS:
            raise ValueError(f"mixer must be one of {MIXERS}; "
                             f"got {self.mixer!r}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"n_heads {self.n_heads} is not a multiple of "
                             f"n_kv_heads {self.n_kv_heads}")
        super().__post_init__()

    # ----------------------------------------------------------- shapes
    @property
    def paged_cache(self) -> bool:
        """Does the layer keep pages (the engine's paged protocol)?"""
        return self.mixer == ATTENTION

    @property
    def slot_state(self) -> bool:
        """Does the layer keep a state of fixed size a slot?"""
        return self.mixer != ATTENTION

    @property
    def slot_state_axes(self):
        """The slot's axis of each array of `slot_state_arrays`."""
        return (0, 1) if self.mixer == MAMBA else (1,)

    @property
    def rotary(self) -> bool:
        return self.rope_theta is not None

    @property
    def stream_limit(self):
        return self.cache_len if self.mixer == ATTENTION else None

    @property
    def d_inner(self) -> int:
        return self.expand * self.n_in

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
        """Matrices N(0, init_std) in `dtype`; the short convolution's
        taps U(+-1/sqrt(K)) (PyTorch's `Conv1d` default); the
        recurrence's own constants (`A_log = log(1..N)` a channel,
        `D = 1`, `dt_bias` the inverse softplus of a step log-uniform in
        [1e-3, 1e-1]: Mamba's published initialisation), the experts'
        selection bias and the norms' gains in float32."""
        D, F = self.n_in, self.ffn_hidden
        ks = jax.random.split(rng, 10)
        f32 = jnp.float32

        def n(k, *shape):
            return (self.init_std * jax.random.normal(k, shape, f32)
                    ).astype(dtype)

        p = {"mixer_norm": jnp.ones((D,), f32),
             "mlp_norm": jnp.ones((D,), f32)}
        if self.n_routed:
            E = self.n_routed
            p.update(router=n(jax.random.fold_in(rng, 10), D, E),
                     expert_bias=jnp.zeros((E,), f32),
                     e_gate=n(ks[0], E, D, F), e_up=n(ks[1], E, D, F),
                     e_down=n(ks[2], E, F, D))
        else:
            p.update(w_gate=n(ks[0], D, F), w_up=n(ks[1], D, F),
                     w_down=n(ks[2], F, D))
        if self.mixer == ATTENTION:
            Q = self.n_heads * self.head_dim
            p.update(wq=n(ks[3], D, Q), wk=n(ks[4], D, self.kv_width),
                     wv=n(ks[5], D, self.kv_width), wo=n(ks[6], Q, D))
            if self.qk_norm:
                p.update(q_norm=jnp.ones((self.head_dim,), f32),
                         k_norm=jnp.ones((self.head_dim,), f32))
            return p
        if self.mixer == CONV:
            K = self.d_conv
            p.update(in_proj=n(ks[3], D, 3 * D), out_proj=n(ks[7], D, D),
                     conv_w=jax.random.uniform(
                         ks[4], (K, D), f32, -K ** -0.5, K ** -0.5
                     ).astype(dtype))
            return p
        C, N, R = self.d_inner, self.d_state, self.dt_rank
        step = jnp.exp(jax.random.uniform(ks[9], (C,), f32)
                       * (np.log(1e-1) - np.log(1e-3)) + np.log(1e-3))
        p.update(
            in_proj=n(ks[3], D, 2 * C), conv_w=n(ks[4], self.d_conv, C),
            conv_b=n(ks[8], C), x_proj=n(ks[5], C, R + 2 * N),
            dt_norm=jnp.ones((R,), f32), b_norm=jnp.ones((N,), f32),
            c_norm=jnp.ones((N,), f32), dt_proj=n(ks[6], R, C),
            dt_bias=step + jnp.log(-jnp.expm1(-step)),
            A_log=jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=f32))[:, None], (N, C)),
            D=jnp.ones((C,), f32), out_proj=n(ks[7], C, D))
        return p

    # ------------------------------------------------------- Mamba mixer
    def _mix(self, params, u, h0, tail, lengths=None, live=None):
        """u [B, T, D] (after the mixer's norm) from the state `h0`
        [B, N, C] float32 and the convolution's `tail` [K-1, B, C] (the
        inputs before u) -> (out [B, T, D], h, tail) after the last
        position; with `lengths` [B] after each row's last REAL position
        (`lengths >= 1`).  `live` [B] (a single-token step): a row that
        is not live keeps its state."""
        B, T, _ = u.shape
        C, N, R, K = self.d_inner, self.d_state, self.dt_rank, self.d_conv
        f32 = jnp.float32
        xz = jnp.matmul(u, params["in_proj"])
        x, z = xz[..., :C], xz[..., C:]
        # the last K-1 inputs, then the sequence's own: [B, K-1+T, C]
        seq = jnp.concatenate(
            [jnp.swapaxes(tail, 0, 1).astype(x.dtype), x], 1)
        w = params["conv_w"].astype(f32)
        conv = params["conv_b"].astype(f32) + sum(
            w[k] * seq[:, k:k + T].astype(f32) for k in range(K))
        xc = jax.nn.silu(conv).astype(u.dtype)
        dbc = jnp.matmul(xc, params["x_proj"])
        dt = rms_norm(dbc[..., :R], params["dt_norm"], self.eps)
        bm = rms_norm(dbc[..., R:R + N], params["b_norm"], self.eps)
        cm = rms_norm(dbc[..., R + N:], params["c_norm"], self.eps)
        delta = jax.nn.softplus(
            jnp.matmul(dt, params["dt_proj"], preferred_element_type=f32)
            + params["dt_bias"].astype(f32))
        a = -jnp.exp(params["A_log"].astype(f32))              # [N, C]
        d = params["D"].astype(f32)
        if T == 1:
            # the decode step: a few fused elementwise operations
            dl, xv = delta[:, 0], xc[:, 0].astype(f32)
            h = jnp.exp(dl[:, None, :] * a) * h0 \
                + (dl * xv)[:, None, :] * bm[:, 0].astype(f32)[:, :, None]
            y = (jnp.sum(h * cm[:, 0].astype(f32)[:, :, None], axis=1)
                 + d * xv)[:, None]
            new_tail = jnp.swapaxes(seq[:, 1:], 0, 1)
            if live is not None:
                h = jnp.where(live[:, None, None], h, h0)
                new_tail = jnp.where(live[None, :, None], new_tail,
                                     tail.astype(new_tail.dtype))
        else:
            if lengths is None:
                lengths = jnp.full((B,), T, jnp.int32)
            y, h = scan_recurrence(xc, delta, a, bm, cm, d, h0, lengths)
            # inputs lengths-K+1 .. lengths-1 of the sequence: `seq`
            # holds input i at index i + K - 1
            at = lengths[:, None] + jnp.arange(K - 1)[None, :]
            new_tail = jnp.swapaxes(jnp.take_along_axis(
                seq, at[:, :, None], axis=1), 0, 1)
        out = jnp.matmul((y * jax.nn.silu(z.astype(f32))).astype(u.dtype),
                         params["out_proj"])
        return out, h, new_tail.astype(tail.dtype)

    def _zero_state(self, batch, dtype):
        if self.mixer == CONV:
            return (jnp.zeros((self.d_conv - 1, batch, self.n_in), dtype),)
        return (jnp.zeros((batch, self.d_state, self.d_inner), jnp.float32),
                jnp.zeros((self.d_conv - 1, batch, self.d_inner), dtype))

    # ------------------------------------------- gated short convolution
    def _conv(self, params, u, tail, lengths=None, live=None):
        """u [B, T, D] (after the mixer's norm) from the last K-1 rows of
        `v` before it, `tail` [K-1, B, D] -> (out [B, T, D], tail after
        the last position; with `lengths` [B] after each row's last REAL
        position).  The K shifted products are in the compute dtype,
        their sum in float32.  `live` [B]: a row that is not live keeps
        its tail."""
        T, D, K = u.shape[1], self.n_in, self.d_conv
        f32 = jnp.float32
        bcx = jnp.matmul(u, params["in_proj"])
        v = bcx[..., :D] * bcx[..., 2 * D:]
        # the last K-1 rows of v, then the sequence's own: [B, K-1+T, D]
        seq = jnp.concatenate(
            [jnp.swapaxes(tail, 0, 1).astype(v.dtype), v], 1)
        w = params["conv_w"].astype(v.dtype)
        conv = sum((w[k] * seq[:, k:k + T]).astype(f32) for k in range(K))
        y = (bcx[..., D:2 * D].astype(f32) * conv).astype(u.dtype)
        if lengths is None:
            new_tail = seq[:, T:]
        else:
            # rows lengths-K+1 .. lengths-1 of the sequence: `seq` holds
            # row i at index i + K - 1
            at = lengths[:, None] + jnp.arange(K - 1)[None, :]
            new_tail = jnp.take_along_axis(seq, at[:, :, None], axis=1)
        new_tail = jnp.swapaxes(new_tail, 0, 1).astype(tail.dtype)
        if live is not None:
            new_tail = jnp.where(live[None, :, None], new_tail, tail)
        return jnp.matmul(y, params["out_proj"]), new_tail

    def _state_mix(self, params, u, arrays, lengths=None, live=None):
        """The state layer's mixer from `arrays` -> (out, arrays')."""
        if self.mixer == CONV:
            out, tail = self._conv(params, u, *arrays, lengths=lengths,
                                   live=live)
            return out, (tail,)
        out, h, tail = self._mix(params, u, *arrays, lengths=lengths,
                                 live=live)
        return out, (h, tail)

    # --------------------------------------------------------------- FFN
    def _ffn(self, params, x, *, lengths=None, live=None, stats=None):
        """h + FFN(RMSNorm(h)).  Routed: `lengths` [B] (prefill) or
        `live` [B] (a single-token step) mark the real tokens, the
        others are routed to no expert; `stats`, a dict, gets the
        layer's routed rows added (`moe.record_load`)."""
        u = rms_norm(x, params["mlp_norm"], self.eps)
        if not self.n_routed:
            return x + swiglu(u, params["w_gate"], params["w_up"],
                              params["w_down"])
        B, T, D = u.shape
        valid = None
        if lengths is not None:
            valid = jnp.arange(T)[None, :] < lengths[:, None]
        elif live is not None:
            valid = live[:, None]
        flat = u.reshape(B * T, D)
        chosen, gates = moe.sigmoid_topk_route(
            flat, params["router"], params["expert_bias"],
            self.experts_per_token, self.routed_scaling, eps=self.route_eps)
        y = moe.held_experts_swiglu(
            flat, chosen, gates, params["e_gate"], params["e_up"],
            params["e_down"], first=0,
            valid=None if valid is None else valid.reshape(-1), stats=stats)
        return x + y.reshape(B, T, D)

    # --------------------------------------------------- attention mixer
    def _attn(self) -> dict:
        return dict(n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
                    window=None)

    def _qkv(self, params, h, positions=None):
        """h [..., D] at `positions` [...] (read by a rotary layer alone)
        -> (q [..., H, Dh], k and v [..., Hkv*Dh]: the rows the cache
        holds, keys already normed and rotated)."""
        lead = h.shape[:-1]
        q = jnp.matmul(h, params["wq"]).reshape(
            lead + (self.n_heads, self.head_dim))
        k, v = jnp.matmul(h, params["wk"]), jnp.matmul(h, params["wv"])
        if not (self.qk_norm or self.rotary):
            return q, k, v
        kh = k.reshape(lead + (self.n_kv_heads, self.head_dim))
        if self.qk_norm:
            q = rms_norm(q, params["q_norm"], self.eps)
            kh = rms_norm(kh, params["k_norm"], self.eps)
        if self.rotary:
            q = parallel.rotate_half(q, positions, self.rope_theta)
            kh = parallel.rotate_half(kh, positions, self.rope_theta)
        return q, kh.reshape(k.shape), v

    # ------------------------------------------------------- full forward
    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        if mask is not None:
            raise ValueError("HybridStateSpaceBlock is causal and takes no "
                             "padding mask: pad on the right")
        y, _ = self.forward_prefill(params, x, None)
        return y, state

    def forward_prefill(self, params, x, lengths, *, stats=None):
        """Whole right-padded prompts x [B, T, D] of `lengths` [B] (None:
        all T real) from an empty cache -> (y, arrays): an attention
        layer's (K rows, V rows) `[B, T, Hkv*Dh]` its pages are cut
        from; a state layer's arrays after each row's last real token,
        what its slot is given."""
        u = rms_norm(x, params["mixer_norm"], self.eps)
        ffn = dict(lengths=lengths, stats=stats)
        if self.mixer == ATTENTION:
            pos = (jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
                   if self.rotary else None)
            q, k, v = self._qkv(params, u, pos)
            a = parallel.attend_blocks(
                q, k, v, params["wo"], query_block=self.query_block,
                key_block=self.key_block, **self._attn())
            return self._ffn(params, x + a, **ffn), (k, v)
        out, arrays = self._state_mix(
            params, u, self._zero_state(x.shape[0], x.dtype), lengths)
        return self._ffn(params, x + out, **ffn), arrays

    # ---------------------------------------------- monolithic carry path
    def init_carry(self, batch, dtype=jnp.float32):
        if self.mixer != ATTENTION:
            return self._zero_state(batch, dtype)
        shape = (batch, self.cache_len, self.kv_width)
        return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                jnp.zeros((), jnp.int32))

    def forward_with_carry(self, params, state, x, carry, *, train=False,
                           rng=None, mask=None):
        """Streaming step of `generate()` / `rnn_time_step`: a state
        layer goes on from its state; an attention layer's new rows enter
        `[B, cache_len, Hkv*Dh]` caches at the carry's position."""
        if mask is not None:
            raise ValueError("HybridStateSpaceBlock cannot stream with a "
                             "padding mask")
        u = rms_norm(x, params["mixer_norm"], self.eps)
        if self.mixer != ATTENTION:
            out, carry = self._state_mix(params, u, carry)
            return self._ffn(params, x + out), {}, carry
        k_cache, v_cache, pos = carry
        B, T, _ = x.shape
        positions = jnp.broadcast_to(pos + jnp.arange(T), (B, T))
        q, k, v = self._qkv(params, u, positions)
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), pos, 1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), pos, 1)
        k_pos = jnp.broadcast_to(jnp.arange(self.cache_len),
                                 (B, T, self.cache_len))
        a = parallel.attend_cached(q, k_cache, v_cache, positions, k_pos,
                                   params["wo"], **self._attn())
        return self._ffn(params, x + a), {}, (k_cache, v_cache, pos + T)

    # ------------------------------------------- per-slot state protocol
    def slot_state_arrays(self, n_slots, dtype):
        """What a state layer keeps a serving slot, whatever the
        sequence's length, the slot's row of each its whole allocation:
        a Mamba layer's (h `[n_slots, N, C]` float32, the convolution's
        tail `[K-1, n_slots, C]` in `dtype`), a short convolution's
        (tail `[K-1, n_slots, D]` in `dtype`,)."""
        return self._zero_state(n_slots, dtype)

    def state_step(self, params, x, arrays, live=None, *, stats=None):
        """One new token a slot: x [S, 1, D], `live` [S] the slots that
        are decoding (the others keep their rows unchanged).
        -> (y, arrays')."""
        out, arrays = self._state_mix(
            params, rms_norm(x, params["mixer_norm"], self.eps), arrays,
            live=live)
        return self._ffn(params, x + out, live=live, stats=stats), arrays

    # ------------------------------------------------------ paged protocol
    def paged_pool_arrays(self, n_blocks, block_len, dtype):
        """(K pool, V pool), `[n_blocks, block_len, Hkv*Dh]` each."""
        shape = (n_blocks, block_len, self.kv_width)
        return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))

    def paged_in_place(self, arrays) -> bool:
        return parallel.gqa_in_place(arrays, self.n_heads, self.n_kv_heads)

    def paged_step(self, params, x, arrays, block_table, pos, live=None, *,
                   stats=None):
        """One new token a slot of an attention layer: the token's K and
        V rows enter their page, attention runs over the pages the slot
        holds.  -> (y, arrays')."""
        positions = pos[:, None]
        u = rms_norm(x, params["mixer_norm"], self.eps)
        q, k, v = self._qkv(params, u, positions)
        arrays = parallel.write_rows(
            arrays, (k, v), block_table, positions,
            None if live is None else live[:, None])
        a = parallel.gqa_paged_attend(
            q, arrays, block_table, pos, live, params["wo"],
            n_heads=self.n_heads, in_place=self.paged_in_place(arrays),
            dtype=u.dtype, **self._attn())
        return self._ffn(params, x + a, live=live, stats=stats), arrays

"""Transformer encoder building blocks.

Beyond-reference territory (the 2017 codebase predates transformers;
SURVEY §5 long-context names ring/Ulysses SP as first-class new
design): a pre-LN encoder block — x + MHA(LN(x)); x + FFN(LN(x)) —
composed from the existing MultiHeadAttention (which carries the
Pallas flash-attention fast path) and LayerNormalization layers, plus
a parameter-free sinusoidal positional encoding. All shapes static,
the whole block fuses under jit; long sequences shard over a mesh via
ring/Ulysses attention (`parallel/ring.py`, `parallel/ulysses.py`).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.common.weights import init_weights
from deeplearning4j_tpu.nd import quant
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.attention import MultiHeadAttention
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer
from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrentLayer
from deeplearning4j_tpu.nn.layers.normalization import LayerNormalization


@register_layer
@dataclasses.dataclass(eq=False)
class PositionalEncodingLayer(BaseRecurrentLayer):
    """Adds the sinusoidal position signal (parameter-free) to
    [B, T, D] activations. Carry-aware (BaseRecurrentLayer): during
    streaming decode the carry is the position offset, so token t of a
    later call gets the same encoding it would in a full forward."""

    layer_name = "positional_encoding"

    n_out: int = 0
    max_len: int = 2048

    def __post_init__(self):
        if self.activation is None:
            self.activation = "identity"
        super().__post_init__()

    def set_n_in(self, input_type, override=True):
        if override or not self.n_out:
            self.n_out = input_type.size

    def get_output_type(self, input_type):
        return input_type

    @property
    def stream_limit(self):
        return self.max_len

    def _table(self, T, D, dtype):
        pos = np.arange(T)[:, None]
        i = np.arange(D // 2)[None, :]
        angles = pos / np.power(10000.0, 2.0 * i / D)
        table = np.zeros((T, D), np.float32)
        table[:, 0::2] = np.sin(angles)
        table[:, 1::2] = np.cos(angles[:, : D - D // 2])
        return jnp.asarray(table, dtype)

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        T, D = x.shape[1], x.shape[2]
        return x + self._table(T, D, x.dtype), state

    def init_carry(self, batch, dtype=jnp.float32):
        return jnp.zeros((), jnp.int32)

    def forward_with_carry(self, params, state, x, carry, *, train=False,
                           rng=None, mask=None):
        T, D = x.shape[1], x.shape[2]
        table = self._table(self.max_len, D, x.dtype)
        sl = jax.lax.dynamic_slice_in_dim(table, carry, T, 0)
        return x + sl, state, carry + T

    def forward_at_positions(self, params, state, x, positions):
        """Per-slot positional signal for continuous-batching decode:
        `x` [S, 1, D] holds one token per serving slot and
        `positions` [S] each slot's OWN stream position — the carry
        path's scalar offset assumes every row sits at the same depth,
        which stops being true the moment sequences admit/evict
        mid-stream. Same table rows as the carry path (gather instead
        of dynamic_slice), so the added signal is bit-identical.

        A 2-D `positions` [S, K] pairs with `x` [S, K, D] — the
        K-position score program (speculative decoding / shared-prefix
        suffix extension): each of a slot's K tokens gets its own
        table row. Positions past `max_len` (dead score lanes at the
        budget edge) clamp inside the gather; their outputs are
        discarded by the caller."""
        D = x.shape[2]
        table = self._table(self.max_len, D, x.dtype)
        if positions.ndim == 2:
            return x + table[positions], state
        return x + table[positions][:, None, :], state


@register_layer
@dataclasses.dataclass(eq=False)
class TransformerEncoderBlock(BaseRecurrentLayer):
    """Pre-LN transformer encoder block over [B, T, D]:
    h = x + MHA(LN(x)); out = h + FFN(LN(h)). Dropout (the layer's
    `dropout` retain-prob) applies to both sublayer outputs, attention
    dropout via `attention_dropout`."""

    layer_name = "transformer_encoder"

    n_in: int = 0
    n_heads: int = 8
    ff_multiplier: int = 4
    causal: bool = False
    attention_dropout: Optional[float] = None
    ff_activation: str = "gelu"
    use_flash: Optional[bool] = None
    sequence_parallel: Optional[str] = None  # "ring"|"ulysses", see MHA
    # KV-cache length for streaming decode (`forward_with_carry`):
    # fixed-size cache buffers keep shapes static across decode steps
    # (one XLA compile); positions past cache_len are clamped by
    # dynamic_update_slice, so size it to the longest sequence you will
    # decode (the zoo TransformerLM wires max_len here)
    cache_len: int = 512
    # rematerialization: recompute this block's intra-block activations
    # (attention internals, the O(T * ff) hidden) in the backward pass
    # instead of storing them. One block-input residual per layer is
    # still saved, so activation memory scales with depth as
    # O(layers * T * D) + O(one block's internals) rather than
    # O(layers * block internals) — the standard lever for long-context
    # training on HBM-limited chips. FLOPs grow by ~1 extra forward;
    # numerics are identical.
    #
    # Legacy bool, equivalent to `remat_policy="full"` on the Layer
    # base — the generalized per-layer knob (also "dots_saveable").
    # The CONTAINERS apply the policy (scan body, unrolled path, and
    # the carry-threading TBPTT branch alike — see nn/scan_stack.py);
    # layers no longer wrap themselves.
    remat: bool = False

    def __post_init__(self):
        if self.activation is None:
            self.activation = "identity"
        if self.sequence_parallel not in (None, "ring", "ulysses"):
            raise ValueError(
                f"sequence_parallel must be None, 'ring' or 'ulysses'; "
                f"got {self.sequence_parallel!r}")
        super().__post_init__()
        self._mha: Optional[MultiHeadAttention] = None

    def set_n_in(self, input_type, override=True):
        if override or not self.n_in:
            self.n_in = input_type.size
        self._build_sublayers()

    def _build_sublayers(self):
        self._mha = MultiHeadAttention(
            n_in=self.n_in, n_out=self.n_in, n_heads=self.n_heads,
            causal=self.causal, attention_dropout=self.attention_dropout,
            use_flash=self.use_flash, weight_init=self.weight_init,
            sequence_parallel=self.sequence_parallel)
        self._ln1 = LayerNormalization(n_out=self.n_in)
        self._ln2 = LayerNormalization(n_out=self.n_in)

    def get_output_type(self, input_type):
        return InputType.recurrent(self.n_in,
                                   getattr(input_type, "timesteps", None))

    def init_params(self, rng, dtype=jnp.float32):
        if self._mha is None:
            self._build_sublayers()
        d, ff = self.n_in, self.n_in * self.ff_multiplier
        params = {}
        for si, (name, sub) in enumerate((("attn", self._mha),
                                          ("ln1", self._ln1),
                                          ("ln2", self._ln2))):
            for pk, arr in sub.init_params(
                    jax.random.fold_in(rng, si), dtype).items():
                params[f"{name}_{pk}"] = arr
        params["ff_W1"] = init_weights(jax.random.fold_in(rng, 11),
                                       (d, ff), self.weight_init,
                                       fan_in=d, fan_out=ff,
                                       distribution=self.dist, dtype=dtype)
        params["ff_b1"] = jnp.zeros((ff,), dtype)
        params["ff_W2"] = init_weights(jax.random.fold_in(rng, 12),
                                       (ff, d), self.weight_init,
                                       fan_in=ff, fan_out=d,
                                       distribution=self.dist, dtype=dtype)
        params["ff_b2"] = jnp.zeros((d,), dtype)
        return params

    def _sub(self, params, prefix):
        n = len(prefix) + 1
        return {k[n:]: v for k, v in params.items()
                if k.startswith(prefix + "_")}

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        return self._forward_impl(params, x, train=train, rng=rng,
                                  mask=mask), state

    def _forward_impl(self, params, x, *, train, rng, mask):
        from deeplearning4j_tpu.common.activations import get_activation
        from deeplearning4j_tpu.kernels import kernels_enabled

        if self._mha is None:
            self._build_sublayers()
        r1 = None if rng is None else jax.random.fold_in(rng, 1)
        h, _ = self._ln1.forward(self._sub(params, "ln1"), {}, x)
        h, _ = self._mha.forward(self._sub(params, "attn"), {}, h,
                                 train=train, rng=r1, mask=mask)
        h = self.apply_input_dropout(h, train,
                                     None if rng is None
                                     else jax.random.fold_in(rng, 2))
        if kernels_enabled():
            # fused residual+LayerNorm Pallas kernel: the [B, T, D]
            # residual sum and the fp32 row statistics share one HBM
            # pass (kernels/layernorm.py; DL4J_PALLAS_KERNELS gates)
            from deeplearning4j_tpu.kernels.layernorm import (
                residual_layer_norm)
            ln2 = self._sub(params, "ln2")
            x, h = residual_layer_norm(x, h, ln2["gamma"], ln2["beta"],
                                       self._ln2.eps)
        else:
            x = x + h
            h, _ = self._ln2.forward(self._sub(params, "ln2"), {}, x)
        act = get_activation(self.ff_activation)
        h = act(quant.matmul(h, params["ff_W1"]) + params["ff_b1"])
        h = quant.matmul(h, params["ff_W2"]) + params["ff_b2"]
        h = self.apply_input_dropout(h, train,
                                     None if rng is None
                                     else jax.random.fold_in(rng, 3))
        return x + h

    def quantizable_weights(self):
        # the block's matmul weights: attention projections (prefixed
        # sublayer params) + the FF pair. LN gain/shift and biases
        # stay floating (nd/quant.py).
        return ("attn_Wq", "attn_Wk", "attn_Wv", "attn_Wo",
                "ff_W1", "ff_W2")

    def adapter_weights(self):
        # attention projections + FF pair take per-tenant LoRA deltas
        # through the same `quant.matmul` seams (tenancy/lora.py)
        return ("attn_Wq", "attn_Wk", "attn_Wv", "attn_Wo",
                "ff_W1", "ff_W2")

    def init_carry(self, batch, dtype=jnp.float32):
        if self._mha is None:
            self._build_sublayers()
        shape = (batch, self.cache_len, self.n_heads,
                 self.n_in // self.n_heads)
        return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                jnp.zeros((), jnp.int32))

    def forward_with_carry(self, params, state, x, carry, *, train=False,
                           rng=None, mask=None):
        """KV-cache streaming step: same pre-LN block, attention against
        the fixed-size cache (`MultiHeadAttention.forward_with_cache`).
        This is the transformer analogue of the LSTM rnnTimeStep carry;
        under TBPTT training it gives Transformer-XL-style chunk
        recurrence (previous-chunk K/V enter stop-gradiented via the
        TBPTT wrapper), honoring `remat`. attention_dropout and the
        flash / sequence-parallel fast paths do not apply on this path
        (residual/FFN dropout still does); padding masks are rejected
        loudly because a masked token's K/V would silently enter the
        cache and corrupt every later attention read."""
        if mask is not None:
            raise ValueError(
                "TransformerEncoderBlock cannot stream (forward_with_"
                "carry) with a padding mask: masked tokens' K/V would "
                "enter the cache; strip padding before streaming / "
                "TBPTT-training this block")
        y, new_carry = self._carry_impl(params, x, carry, train=train,
                                        rng=rng)
        return y, {}, new_carry

    # ------------------------------------------------- the paged protocol
    # What the serving engine asks of a layer that keeps per-token state
    # in the paged pool (docs/SERVING.md): the arrays it wants, how a
    # filled monolithic carry is cut into their pages, and the cached
    # steps.  `LatentAttentionBlock` (nn/layers/latent.py) implements
    # the same names over one latent array.
    paged_cache = True

    @property
    def stream_limit(self):
        return self.cache_len

    @property
    def paged_stream_limit(self):
        """Prefill runs through the monolithic carry, so the paged path
        is bounded by `cache_len` too."""
        return self.cache_len

    @property
    def paged_handoff_heads(self):
        """The prefill->decode handoff wire keeps heads apart."""
        return self.n_heads

    def paged_pool_arrays(self, n_blocks, block_len, dtype):
        """(K pool, V pool), `[n_blocks, block_len, H*Dh]` each."""
        shape = (n_blocks, block_len, self.n_in)
        return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))

    def carry_pages(self, carry):
        """A filled `init_carry` -> the `[B, cache_len, ...]` arrays the
        pool arrays' pages are cut from, in `paged_pool_arrays` order."""
        return (carry[0], carry[1])

    def paged_in_place(self, arrays) -> bool:
        return self.paged_decode_in_place(arrays[0])

    def paged_step(self, params, x, arrays, block_table, pos, live=None, *,
                   stats=None):
        y, k_pool, v_pool = self.forward_paged(
            params, x, arrays[0], arrays[1], block_table, pos, live)
        return y, (k_pool, v_pool)

    def paged_step_multi(self, params, x, arrays, block_table, pos, n_valid,
                         *, stats=None):
        y, k_pool, v_pool = self.forward_paged_multi(
            params, x, arrays[0], arrays[1], block_table, pos, n_valid)
        return y, (k_pool, v_pool)

    def paged_decode_in_place(self, k_pool) -> bool:
        """`MultiHeadAttention.paged_decode_in_place` of this block's
        attention: whether `forward_paged`, traced now, reads the pool
        in place (the kernel) or gathers it."""
        if self._mha is None:
            self._build_sublayers()
        return self._mha.paged_decode_in_place(k_pool)

    def forward_paged(self, params, x, k_pool, v_pool, block_table, pos,
                      live=None, *, train=False, rng=None):
        """Paged-KV decode step (`cache_pages=` mode): the same pre-LN
        block as `_carry_impl`, with attention reading/writing the
        shared block pool through this slot-batch's block table
        (`MultiHeadAttention.forward_with_paged_cache`). `pos` [S] is
        per-slot — sequences admitted mid-stream sit at different
        depths; `live` [S] marks the slots that are decoding (None:
        all), so the in-place kernel reads no page for the others. The
        non-attention math IS the carry path's
        (`_stream_tail` — one body, not a synchronized copy), which is
        what the serving tier's decode-parity contract (docs/SERVING.md)
        rests on. Returns (y, k_pool', v_pool')."""
        if self._mha is None:
            self._build_sublayers()
        h, _ = self._ln1.forward(self._sub(params, "ln1"), {}, x)
        h, k_pool, v_pool = self._mha.forward_with_paged_cache(
            self._sub(params, "attn"), h, k_pool, v_pool, block_table, pos,
            live)
        return (self._stream_tail(params, x, h, train=train, rng=rng),
                k_pool, v_pool)

    def forward_paged_multi(self, params, x, k_pool, v_pool, block_table,
                            pos, n_valid, *, train=False, rng=None):
        """K-position paged decode step (the speculative score program
        and the CoW suffix-extension path): `x` [S, K, D] carries K
        consecutive tokens per slot at positions `pos[s]..pos[s]+K-1`,
        `n_valid` [S] bounds each slot's real lanes (writes past it go
        to the garbage block — `MultiHeadAttention.forward_with_paged_
        cache_multi`). The non-attention math is `_stream_tail`, the
        same single body the one-token paged path and the monolithic
        carry path run — per-lane outputs are therefore bit-equal to K
        sequential `forward_paged` calls, the speculative parity
        contract's layer-level half."""
        if self._mha is None:
            self._build_sublayers()
        h, _ = self._ln1.forward(self._sub(params, "ln1"), {}, x)
        h, k_pool, v_pool = self._mha.forward_with_paged_cache_multi(
            self._sub(params, "attn"), h, k_pool, v_pool, block_table,
            pos, n_valid)
        return (self._stream_tail(params, x, h, train=train, rng=rng),
                k_pool, v_pool)

    def _stream_tail(self, params, x, h, *, train, rng):
        """Post-attention half of the streaming block — sublayer
        dropout, residual, LN2, FFN, residual — shared verbatim by the
        monolithic-carry and paged decode paths (the kernels_enabled
        fused-LN fast path applies to the full `forward` only)."""
        from deeplearning4j_tpu.common.activations import get_activation

        h = self.apply_input_dropout(h, train,
                                     None if rng is None
                                     else jax.random.fold_in(rng, 2))
        x = x + h
        h, _ = self._ln2.forward(self._sub(params, "ln2"), {}, x)
        act = get_activation(self.ff_activation)
        h = act(quant.matmul(h, params["ff_W1"]) + params["ff_b1"])
        h = quant.matmul(h, params["ff_W2"]) + params["ff_b2"]
        h = self.apply_input_dropout(h, train,
                                     None if rng is None
                                     else jax.random.fold_in(rng, 3))
        return x + h

    def _carry_impl(self, params, x, carry, *, train, rng):
        if self._mha is None:
            self._build_sublayers()
        k_cache, v_cache, pos = carry
        h, _ = self._ln1.forward(self._sub(params, "ln1"), {}, x)
        h, k_cache, v_cache = self._mha.forward_with_cache(
            self._sub(params, "attn"), h, k_cache, v_cache, pos)
        y = self._stream_tail(params, x, h, train=train, rng=rng)
        return y, (k_cache, v_cache, pos + x.shape[1])


def stream_budget(layers):
    """Smallest bounded stream length in a layer stack, or None.

    KV caches (`TransformerEncoderBlock.cache_len`, the latent cache of
    `LatentAttentionBlock`) and positional tables
    (`PositionalEncodingLayer.max_len`) declare a `stream_limit`: both
    clamp writes/reads
    past their length (dynamic_update_slice / dynamic_slice semantics)
    — silently corrupting every later token while still emitting
    valid-looking activations. Streaming entry points (`rnn_time_step`,
    TBPTT drivers, zoo generate/beam_search) call this to enforce the
    budget eagerly on the host, where the accumulated position is
    known."""
    limits = [l.stream_limit for l in layers
              if getattr(l, "stream_limit", None) is not None]
    return min(limits) if limits else None

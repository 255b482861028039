"""Multi-head attention layer.

Not in the 2017 reference (its sequence scaling is TBPTT only —
SURVEY §5); this layer is the long-context foundation the TPU rebuild
treats as first-class. Param names follow the framework convention:
"Wq", "Wk", "Wv", "Wo" (+ optional biases "bq".."bo").

The single-device path is standard scaled dot-product attention (XLA
fuses QK^T → softmax → PV into MXU-friendly blocks); the
sequence-parallel path swaps in ring attention over a mesh axis
(`parallel/ring.py`) with identical math.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.common.weights import init_weights
from deeplearning4j_tpu.nd import quant
from deeplearning4j_tpu.nn.conf.inputs import InputType, InputTypeRecurrent
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer

_SP_FALLBACK_WARNED = set()
_PAGED_FALLBACK_WARNED = set()


def _warn_sp_fallback(layer_name, reason):
    """One-time notice when a layer CONFIGURED for sequence parallelism
    takes the local-attention path — exactly the long-context cases the
    user enabled SP for, so silence would read as 'SP is on' while
    memory/perf stay unchanged."""
    key = (layer_name, reason)
    if key not in _SP_FALLBACK_WARNED:
        _SP_FALLBACK_WARNED.add(key)
        import logging
        logging.getLogger(__name__).warning(
            "layer %s has sequence_parallel configured but fell back to "
            "local attention: %s — sequence-parallel memory/perf benefits "
            "do NOT apply to this forward",
            layer_name, reason)


def _warn_paged_fallback(reason):
    """One-time notice when kernels are on but a pool's shape keeps
    the single-token decode on the gather path — the step then moves
    every slot's whole block table per layer, which is what the
    kernel exists to avoid."""
    if reason not in _PAGED_FALLBACK_WARNED:
        _PAGED_FALLBACK_WARNED.add(reason)
        import logging
        logging.getLogger(__name__).warning(
            "paged decode attention fell back to the gather path: %s",
            reason)


@register_layer
@dataclasses.dataclass(eq=False)
class MultiHeadAttention(Layer):
    layer_name = "multi_head_attention"

    n_in: int = 0
    n_out: int = 0          # model dim (defaults to n_in)
    n_heads: int = 4
    causal: bool = False
    has_bias: bool = True
    attention_dropout: Optional[float] = None  # retain prob on attn weights
    use_flash: Optional[bool] = None  # Pallas kernel; None → on a TPU
    # long-context: "ring" (ppermute K/V rotation) or "ulysses"
    # (all-to-all head sharding) over the ambient mesh installed by
    # `parallel.sequence_sharding(mesh, axis)`. The config carries only
    # the strategy name (serializable); the mesh is runtime state. Falls
    # back to the local path when no mesh is active or a padding mask /
    # attention dropout is in play.
    sequence_parallel: Optional[str] = None

    def __post_init__(self):
        if self.activation is None:
            self.activation = "identity"
        if self.sequence_parallel not in (None, "ring", "ulysses"):
            raise ValueError(
                f"sequence_parallel must be None, 'ring' or 'ulysses'; "
                f"got {self.sequence_parallel!r}")
        super().__post_init__()

    def set_n_in(self, input_type, override=True):
        if override or not self.n_in:
            self.n_in = input_type.size
        if not self.n_out:
            self.n_out = self.n_in

    def get_output_type(self, input_type):
        return InputType.recurrent(self.n_out or self.n_in,
                                   getattr(input_type, "timesteps", None))

    @property
    def head_dim(self):
        return (self.n_out or self.n_in) // self.n_heads

    def init_params(self, rng, dtype=jnp.float32):
        d = self.n_out or self.n_in
        assert d % self.n_heads == 0, "n_out must divide n_heads"
        params = {}
        for i, name in enumerate(("Wq", "Wk", "Wv", "Wo")):
            n_in = self.n_in if name != "Wo" else d
            n_o = d if name != "Wo" else d
            params[name] = init_weights(
                jax.random.fold_in(rng, i), (n_in, n_o), self.weight_init,
                fan_in=n_in, fan_out=n_o, distribution=self.dist, dtype=dtype)
            if self.has_bias:
                params["b" + name[1:]] = jnp.zeros((n_o,), dtype)
        return params

    def quantizable_weights(self):
        # qkv/out projections: the decode-path HBM heavyweights
        # (nd/quant.py int8 serving quantization; biases stay fp)
        return ("Wq", "Wk", "Wv", "Wo")

    def adapter_weights(self):
        # the same projections carry per-tenant LoRA deltas — every
        # one routes through `quant.matmul` (tenancy/lora.py)
        return ("Wq", "Wk", "Wv", "Wo")

    def _project(self, params, x, name):
        z = quant.matmul(x, params[name])
        if self.has_bias:
            z = z + params["b" + name[1:]]
        return z

    def heads(self, z):
        b, t, d = z.shape
        return z.reshape(b, t, self.n_heads, d // self.n_heads)

    def forward_with_cache(self, params, x, k_cache, v_cache, pos):
        """Incremental causal attention for autoregressive decoding
        (the transformer analogue of the reference's `rnnTimeStep`
        streaming state). `x` [B, T, D] holds NEW tokens whose global
        positions are [pos, pos+T); `k_cache`/`v_cache` [B, L, H, Dh]
        are fixed-size buffers (static shapes — the TPU way: one
        compile, a dynamic write index, masked reads) holding the
        first `pos` positions. Returns (y, k_cache', v_cache').

        The causal mask `k_pos <= q_pos` also hides every unwritten
        cache slot (those have k_pos >= pos+T > q_pos), so no separate
        validity mask is needed. Positions past L are clamped by XLA's
        dynamic_update_slice — callers size L (the block's
        `cache_len`) to the longest sequence they will decode."""
        assert self.causal, "KV-cache decoding requires causal=True"
        q = self.heads(self._project(params, x, "Wq"))   # [B,T,H,Dh]
        k = self.heads(self._project(params, x, "Wk"))
        v = self.heads(self._project(params, x, "Wv"))
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), pos, 1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), pos, 1)
        B, T = x.shape[0], x.shape[1]
        q_pos = jnp.broadcast_to(pos + jnp.arange(T), (B, T))
        return (self._attend_cached(params, q, k_cache, v_cache, q_pos),
                k_cache, v_cache)

    def _attend_cached(self, params, q, k_seq, v_seq, q_pos):
        """Shared masked-softmax attention core for BOTH cached decode
        paths (monolithic carry and paged pool): `q` [B, T, H, Dh]
        against a cache view `k_seq`/`v_seq` [B, L, H, Dh], with
        per-row query positions `q_pos` [B, T] hiding every cache slot
        past the row's stream position. One body, one set of numerics
        — the serving bit-parity contract (docs/SERVING.md) holds by
        construction instead of by hand-synchronized copies."""
        B, T = q.shape[0], q.shape[1]
        L = k_seq.shape[1]
        scale = 1.0 / jnp.sqrt(jnp.asarray(self.head_dim, q.dtype))
        s = jnp.einsum("bqhd,bkhd->bhqk", q,
                       k_seq.astype(q.dtype)) * scale
        valid = jnp.arange(L)[None, None, :] <= q_pos[:, :, None]
        s = jnp.where(valid[:, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", w, v_seq.astype(q.dtype))
        return self.activation(
            self._project(params, o.reshape(B, T, -1), "Wo"))

    def paged_decode_in_place(self, k_pool) -> bool:
        """Which single-token paged path a program traced NOW takes:
        True = the `dl4tpu_paged_decode` kernel over the pool in place
        (kernels on, and a pool the kernel can tile), False = gather +
        `_attend_cached`. Decided from what the code observes — the
        backend/env switch the kernels share and the pool's own shape
        — and asked by the engine for its read-share counter, so the
        layer and the counter cannot disagree."""
        from deeplearning4j_tpu import kernels
        from deeplearning4j_tpu.kernels import paged_attention
        if not kernels.kernels_enabled():
            return False
        reason = paged_attention.unsupported_reason(
            k_pool.shape, k_pool.dtype, self.n_heads)
        if reason is not None:
            _warn_paged_fallback(reason)
            return False
        return True

    def _paged_view(self, pool, block_table):
        """Gather-by-block-table view of a pool: [S, maxB, bl, H*Dh] ->
        [S, L, H, Dh] with L = maxB * bl; position p of slot s sits at
        gathered index p (tables map position-space blocks in order),
        so the layout — and therefore the attention math — matches the
        monolithic cache exactly."""
        seq = pool[block_table]
        return seq.reshape(seq.shape[0], -1, self.n_heads, self.head_dim)

    def forward_with_paged_cache(self, params, x, k_pool, v_pool,
                                 block_table, pos, live=None):
        """Incremental causal attention over a PAGED KV-cache pool — the
        continuous-batching serving mode (`cache_pages=`): instead of one
        monolithic `[B, L, H, Dh]` buffer per sequence, K/V live in a
        shared pool of fixed-size blocks `[n_blocks, block_len, H*Dh]`
        (a page holds all heads side by side) and each slot addresses
        its blocks through a block table.

        `x` [S, 1, D] holds ONE new token per serving slot; `pos` [S]
        is each slot's own stream position (slots decode different
        sequences at different depths — the per-slot generalization of
        `forward_with_cache`'s single scalar `pos`). `block_table`
        [S, max_blocks] maps slot-local block index -> pool block id.
        `live` [S] bool (None: every slot) says which slots are
        decoding; the others' outputs are never used by the caller.
        Returns (y, k_pool', v_pool').

        Two attention cores, selected by `paged_decode_in_place`:
        - the `dl4tpu_paged_decode` kernel (the chip): each live slot
          reads the `ceil((pos+1)/block_len)` pages it holds, in
          place; a slot that is not live reads nothing. Held to a
          tolerance against the other core (online softmax, fp32);
        - gather + `_attend_cached` (CPU, kernels off, shapes the
          kernel cannot tile): the plain reference, bit-identical to
          the monolithic cache.

        Invariants the scheduler maintains (serving/paged.py): active
        slots own disjoint block sets; block id 0 is the reserved
        garbage block that inactive slots and table padding point at —
        every position past a slot's `pos` is masked before the
        softmax, so garbage content never reaches the output
        (0-weight * finite garbage == exactly 0.0)."""
        assert self.causal, "paged KV-cache decoding requires causal=True"
        S, bl = x.shape[0], k_pool.shape[1]
        q = self._project(params, x, "Wq")               # [S,1,H*Dh]
        k = self._project(params, x, "Wk")
        v = self._project(params, x, "Wv")
        blk = block_table[jnp.arange(S), pos // bl]      # [S] pool ids
        off = pos % bl
        k_pool = k_pool.at[blk, off].set(k[:, 0].astype(k_pool.dtype))
        v_pool = v_pool.at[blk, off].set(v[:, 0].astype(v_pool.dtype))
        if self.paged_decode_in_place(k_pool):
            from deeplearning4j_tpu.kernels.paged_attention import (
                paged_decode_attention)
            lengths = pos + 1
            if live is not None:
                lengths = jnp.where(live, lengths, 0)
            o = paged_decode_attention(q, k_pool, v_pool, block_table,
                                       lengths, n_heads=self.n_heads)
            return (self.activation(self._project(params, o, "Wo")),
                    k_pool, v_pool)
        return (self._attend_cached(params, self.heads(q),
                                    self._paged_view(k_pool, block_table),
                                    self._paged_view(v_pool, block_table),
                                    pos[:, None]),
                k_pool, v_pool)

    def forward_with_paged_cache_multi(self, params, x, k_pool, v_pool,
                                       block_table, pos, n_valid):
        """K-POSITION causal attention over the paged pool — the score
        program of speculative decoding and the suffix-extension path
        of copy-on-write shared-prefix admission (docs/SERVING.md).

        `x` [S, K, D] holds K consecutive tokens per slot occupying
        stream positions `pos[s] .. pos[s]+K-1`; `n_valid` [S] is how
        many of those K are REAL for each slot (0 = the slot does not
        participate in this dispatch). Writes for lanes `j >= n_valid`
        are redirected to the reserved garbage block — position-space
        indices past a slot's granted table (the budget edge of a dead
        lane) are clamped BEFORE the table lookup so an out-of-range
        gather can never alias a live block. Real lanes scatter exactly
        where the single-token path would have, one dispatch later at a
        time: lane j's K/V is the same projection of the same
        activations, and its query attends over `<= pos+j` — so K
        sequential single-token dispatches and one K-wide dispatch
        write the same bytes and read the same masked view, which is
        what makes the speculative greedy contract BIT-equality rather
        than tolerance wherever the single-token path runs this same
        gather + `_attend_cached` core (where it runs the
        `dl4tpu_paged_decode` kernel the two agree to a tolerance:
        docs/SERVING.md). Returns (y [S, K, D], k_pool', v_pool')."""
        assert self.causal, "paged KV-cache decoding requires causal=True"
        S, K = x.shape[0], x.shape[1]
        bl = k_pool.shape[1]
        q = self.heads(self._project(params, x, "Wq"))   # [S,K,H,Dh]
        k = self._project(params, x, "Wk")               # [S,K,H*Dh]
        v = self._project(params, x, "Wv")
        j = jnp.arange(K)[None, :]                       # [1, K]
        posj = pos[:, None] + j                          # [S, K]
        blk_idx = jnp.minimum(posj // bl, block_table.shape[1] - 1)
        blk = jnp.take_along_axis(block_table, blk_idx, axis=1)
        live = j < n_valid[:, None]
        blk = jnp.where(live, blk, 0)                    # garbage block
        off = posj % bl
        # dead lanes may collide on (garbage, off) — scatter order is
        # unspecified there, and irrelevant: garbage content is never
        # read (every gather masks by the reader's own position)
        k_pool = k_pool.at[blk, off].set(k.astype(k_pool.dtype))
        v_pool = v_pool.at[blk, off].set(v.astype(v_pool.dtype))
        return (self._attend_cached(params, q,
                                    self._paged_view(k_pool, block_table),
                                    self._paged_view(v_pool, block_table),
                                    posj),
                k_pool, v_pool)

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.apply_input_dropout(x, train, rng)
        q = self.heads(self._project(params, x, "Wq"))   # [B,T,H,Dh]
        k = self.heads(self._project(params, x, "Wk"))
        v = self.heads(self._project(params, x, "Wv"))
        plain = mask is None and (not train or self.attention_dropout is None)
        if self.sequence_parallel and plain:
            from deeplearning4j_tpu.parallel.context import current_sequence_mesh
            ctx = current_sequence_mesh()
            if ctx is None:
                _warn_sp_fallback(
                    self.name or type(self).__name__,
                    "no sequence_sharding(mesh) context active — wrap "
                    "fit/output in `with sequence_sharding(mesh):`")
            if ctx is not None:
                mesh, axis = ctx
                # the SP schedules accept the same flash fast path: the
                # per-shard (ring) / per-head-subset (ulysses) attention
                # runs through the Pallas kernels when the layer's flash
                # verdict is on — sequence parallelism and flash memory
                # behavior compose (both fwd and bwd are kernel-backed)
                sp_flash = self.use_flash
                if sp_flash is None:
                    sp_flash = jax.default_backend() == "tpu"
                if self.sequence_parallel == "ring":
                    from deeplearning4j_tpu.parallel import (
                        sequence_parallel_attention)
                    o = sequence_parallel_attention(q, k, v, mesh,
                                                    seq_axis=axis,
                                                    causal=self.causal,
                                                    use_flash=sp_flash)
                elif self.sequence_parallel == "ulysses":
                    from deeplearning4j_tpu.parallel import (
                        ulysses_parallel_attention)
                    o = ulysses_parallel_attention(q, k, v, mesh,
                                                   axis_name=axis,
                                                   causal=self.causal,
                                                   use_flash=sp_flash)
                else:
                    raise ValueError(
                        f"sequence_parallel must be 'ring'|'ulysses', "
                        f"got {self.sequence_parallel!r}")
                o = o.reshape(x.shape[0], x.shape[1], -1)
                return self.activation(self._project(params, o, "Wo")), state
        if self.sequence_parallel and not plain:
            reasons = []
            if mask is not None:
                reasons.append("padding mask present (ring/ulysses paths "
                               "are mask-free)")
            if train and self.attention_dropout is not None:
                reasons.append("attention_dropout active in training")
            _warn_sp_fallback(self.name or type(self).__name__,
                              "; ".join(reasons))
        use_flash = self.use_flash
        if use_flash is None:
            # auto = the platform: on a TPU the kernel IS the attention
            # path, and a kernel Mosaic refuses fails the step's compile
            use_flash = jax.default_backend() == "tpu"
        if (use_flash and plain):
            # Pallas fused fast path (the cuDNN-helper role)
            from deeplearning4j_tpu.kernels import flash_attention
            o = flash_attention(q, k, v, self.causal)
            o = o.reshape(x.shape[0], x.shape[1], -1)
            return self.activation(self._project(params, o, "Wo")), state
        scale = 1.0 / jnp.sqrt(jnp.asarray(self.head_dim, x.dtype))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        T = x.shape[1]
        if self.causal:
            causal = jnp.tril(jnp.ones((T, T), bool))
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
        if mask is not None:  # [B,T] padding mask on keys
            scores = jnp.where(mask[:, None, None, :] > 0, scores, -jnp.inf)
        w = jax.nn.softmax(scores, axis=-1)
        if train and self.attention_dropout is not None and rng is not None:
            keep = self.attention_dropout
            w = jnp.where(jax.random.bernoulli(rng, keep, w.shape),
                          w / keep, jnp.zeros_like(w))
        o = jnp.einsum("bhqk,bkhd->bqhd", w, v)
        o = o.reshape(x.shape[0], T, -1)
        return self.activation(self._project(params, o, "Wo")), state

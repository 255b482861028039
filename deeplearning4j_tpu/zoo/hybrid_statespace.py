"""Hybrid state-space language model: token ids -> embedding -> hybrid
blocks (`nn/layers/statespace.py`) -> RMSNorm -> the head, which is the
embedding.  Built into a `MultiLayerNetwork`, so `generate()`,
`rnn_time_step` and `GenerationServer` take it like `TransformerLM`,
`LatentMoELM` and `ParallelMoELM`.

Two families, by the arguments:

- AI21's Jamba (`AI21-Jamba2-3B`): layer `i` an attention layer where
  `i % attn_period == attn_offset` and a Mamba layer everywhere else,
  each followed by the same dense SwiGLU MLP; attention has no position
  of any kind (the state-space layers carry order);
- Liquid AI's LFM2 (`LFM2-8B-A1B`): the kinds given as a list
  (`layer_types`, `"conv"` a gated short convolution), attention with
  QK RMSNorm and rotate-half positions (`qk_norm`, `rope_theta`), and
  from layer `n_dense_layers` on `n_routed` experts of width
  `expert_hidden` in place of the dense MLP.

The container has no tie between two layers' parameters: `init()` gives
the head's leaf the embedding's values, as whoever installs other
weights has to.
"""

from __future__ import annotations

from typing import Optional, Sequence

from deeplearning4j_tpu.common.updaters import Adam
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import EmbeddingLayer
from deeplearning4j_tpu.nn.layers.latent import RMSNormLayer
from deeplearning4j_tpu.nn.layers.parallel import TiedLMHead
from deeplearning4j_tpu.nn.layers.statespace import (ATTENTION, MAMBA,
                                                     HybridStateSpaceBlock)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.zoo.base import ZooModel


class HybridStateSpaceLM(ZooModel):
    def __init__(self, vocab_size: int, *, d_model: int = 64,
                 n_layers: int = 4, attn_period: int = 4,
                 attn_offset: int = 2,
                 layer_types: Optional[Sequence[str]] = None,
                 n_heads: int = 4, n_kv_heads: int = 1, head_dim: int = 16,
                 qk_norm: bool = False, rope_theta: Optional[float] = None,
                 mlp_hidden: int = 128, d_state: int = 16, d_conv: int = 4,
                 expand: int = 2, dt_rank: int = 8, n_dense_layers: int = 0,
                 n_routed: int = 0, experts_per_token: int = 0,
                 expert_hidden: int = 0, route_eps: float = 0.0,
                 routed_scaling: float = 1.0, eps: float = 1e-6,
                 cache_len: int = 512, seed: int = 123):
        super().__init__(num_classes=vocab_size, seed=seed)
        self.vocab_size = vocab_size
        self.d_model = d_model
        self.layer_types = tuple(layer_types) if layer_types else tuple(
            ATTENTION if i % attn_period == attn_offset else MAMBA
            for i in range(n_layers))
        self.eps = eps
        self.block = dict(
            eps=eps, n_heads=n_heads, n_kv_heads=n_kv_heads,
            head_dim=head_dim, qk_norm=qk_norm, rope_theta=rope_theta,
            d_state=d_state, d_conv=d_conv, expand=expand, dt_rank=dt_rank,
            cache_len=cache_len)
        self.dense = dict(ffn_hidden=mlp_hidden)
        self.routed = dict(ffn_hidden=expert_hidden, n_routed=n_routed,
                           experts_per_token=experts_per_token,
                           route_eps=route_eps,
                           routed_scaling=routed_scaling)
        self.n_dense_layers = n_dense_layers if n_routed else len(
            self.layer_types)

    def conf(self):
        b = (NeuralNetConfiguration.builder()
             .seed(self.seed).updater(Adam(1e-3)).list()
             .layer(EmbeddingLayer(n_in=self.vocab_size, n_out=self.d_model,
                                   has_bias=False)))
        for i, kind in enumerate(self.layer_types):
            ffn = self.dense if i < self.n_dense_layers else self.routed
            b.layer(HybridStateSpaceBlock(mixer=kind, **self.block, **ffn))
        b.layer(RMSNormLayer(eps=self.eps))
        b.layer(TiedLMHead(n_out=self.vocab_size, activation="softmax",
                           loss="mcxent"))
        b.set_input_type(InputType.recurrent(self.vocab_size))
        return b.build()

    def init(self) -> MultiLayerNetwork:
        net = MultiLayerNetwork(self.conf()).init(self.seed)
        net.params[str(len(net.layers) - 1)]["W"] = net.params["0"]["W"]
        return net

"""Hybrid state-space language model (AI21's Jamba family as
`AI21-Jamba2-3B` configures it): token ids -> embedding -> hybrid blocks
(`nn/layers/statespace.py`), layer `i` an attention layer where
`i % attn_period == attn_offset` and a Mamba layer everywhere else, each
followed by the same dense SwiGLU MLP -> RMSNorm -> the head, which is
the embedding.  No position of any kind: the state-space layers carry
order.  Built into a `MultiLayerNetwork`, so `generate()`,
`rnn_time_step` and `GenerationServer` take it like `TransformerLM`,
`LatentMoELM` and `ParallelMoELM`.

The container has no tie between two layers' parameters: `init()` gives
the head's leaf the embedding's values, as whoever installs other
weights has to.
"""

from __future__ import annotations

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
                 attn_offset: int = 2, n_heads: int = 4,
                 n_kv_heads: int = 1, head_dim: int = 16,
                 mlp_hidden: int = 128, d_state: int = 16, d_conv: int = 4,
                 expand: int = 2, dt_rank: int = 8, eps: float = 1e-6,
                 cache_len: int = 512, seed: int = 123):
        super().__init__(num_classes=vocab_size, seed=seed)
        self.vocab_size = vocab_size
        self.d_model = d_model
        self.layer_types = tuple(
            ATTENTION if i % attn_period == attn_offset else MAMBA
            for i in range(n_layers))
        self.eps = eps
        self.block = dict(
            ffn_hidden=mlp_hidden, eps=eps, n_heads=n_heads,
            n_kv_heads=n_kv_heads, head_dim=head_dim, d_state=d_state,
            d_conv=d_conv, expand=expand, dt_rank=dt_rank,
            cache_len=cache_len)

    def conf(self):
        b = (NeuralNetConfiguration.builder()
             .seed(self.seed).updater(Adam(1e-3)).list()
             .layer(EmbeddingLayer(n_in=self.vocab_size, n_out=self.d_model,
                                   has_bias=False)))
        for kind in self.layer_types:
            b.layer(HybridStateSpaceBlock(mixer=kind, **self.block))
        b.layer(RMSNormLayer(eps=self.eps))
        b.layer(TiedLMHead(n_out=self.vocab_size, activation="softmax",
                           loss="mcxent"))
        b.set_input_type(InputType.recurrent(self.vocab_size))
        return b.build()

    def init(self) -> MultiLayerNetwork:
        net = MultiLayerNetwork(self.conf()).init(self.seed)
        net.params[str(len(net.layers) - 1)]["W"] = net.params["0"]["W"]
        return net

"""Latent-attention mixture-of-experts language model (the 2024-26
family: DeepSeek-V2/V3's block as Sarvam-105B configures it): token ids
-> embedding -> `first_dense` blocks with a dense SwiGLU -> blocks with a
routed expert layer of which this net HOLDS a share -> RMSNorm -> an
untied head.  Built into a `MultiLayerNetwork`, so `fit`, `generate()`
and `GenerationServer` take it like `TransformerLM`.

`held = (first, count)` is the chip's share of each expert layer under
expert parallelism: the router keeps `n_routed` outputs and
`experts_per_token`, the layer computes the tokens routed to its own
experts and leaves out what the absent ones would add
(`nn/layers/moe.py`).  A sliced vocabulary is a smaller vocabulary:
`vocab_size` is the slice.
"""

from __future__ import annotations

from deeplearning4j_tpu.common.updaters import Adam
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import EmbeddingLayer
from deeplearning4j_tpu.nn.layers.latent import (LatentAttentionBlock, LMHead,
                                                 RMSNormLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.zoo.base import ZooModel


class LatentMoELM(ZooModel):
    def __init__(self, vocab_size: int, *, d_model: int = 64,
                 n_layers: int = 3, first_dense: int = 1, n_heads: int = 4,
                 kv_lora_rank: int = 32, qk_nope_head_dim: int = 16,
                 qk_rope_head_dim: int = 8, v_head_dim: int = 16,
                 dense_hidden: int = 128, expert_hidden: int = 32,
                 n_routed: int = 16, experts_per_token: int = 2,
                 held: tuple = (0, 4), routed_scaling: float = 2.5,
                 router_bias_std: float = 0.1, rope_theta: float = 10000.0,
                 rope_scaling: dict = None, eps: float = 1e-6,
                 cache_len: int = 512, seed: int = 123):
        super().__init__(num_classes=vocab_size, seed=seed)
        self.vocab_size = vocab_size
        self.d_model = d_model
        self.n_layers = n_layers
        self.first_dense = first_dense
        self.eps = eps
        self.block = dict(
            n_heads=n_heads, kv_lora_rank=kv_lora_rank,
            qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rope_theta=rope_theta, rope_scaling=rope_scaling, eps=eps,
            cache_len=cache_len)
        self.dense = dict(ffn="dense", ffn_hidden=dense_hidden)
        self.experts = dict(
            ffn="experts", ffn_hidden=expert_hidden, n_routed=n_routed,
            experts_per_token=experts_per_token, held_first=held[0],
            held_count=held[1], routed_scaling=routed_scaling,
            router_bias_std=router_bias_std)

    def conf(self):
        b = (NeuralNetConfiguration.builder()
             .seed(self.seed).updater(Adam(1e-3)).list()
             .layer(EmbeddingLayer(n_in=self.vocab_size, n_out=self.d_model,
                                   has_bias=False)))
        for i in range(self.n_layers):
            ffn = self.dense if i < self.first_dense else self.experts
            b.layer(LatentAttentionBlock(**self.block, **ffn))
        b.layer(RMSNormLayer(eps=self.eps))
        b.layer(LMHead(n_out=self.vocab_size, activation="softmax",
                       loss="mcxent"))
        b.set_input_type(InputType.recurrent(self.vocab_size))
        return b.build()

    def init(self) -> MultiLayerNetwork:
        return MultiLayerNetwork(self.conf()).init(self.seed)

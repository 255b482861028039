"""Parallel-block mixture-of-experts language model (Cohere's Command
family as `command-a-plus-05-2026` configures it): token ids ->
embedding -> parallel blocks (`nn/layers/parallel.py`), window layers
with rotary positions and full layers with none interleaved by
`layer_types`, each with a routed expert layer of which this net HOLDS a
share and shared experts that are averaged -> a LayerNorm without bias
-> the head, which is the embedding.  Built into a `MultiLayerNetwork`,
so `generate()`, `rnn_time_step` and `GenerationServer` take it like
`TransformerLM` and `LatentMoELM`.

`held = (first, count)` is the chip's share of each expert layer under
expert parallelism, as in `LatentMoELM`; a sliced vocabulary is a
smaller vocabulary: `vocab_size` is the slice.  The container has no tie
between two layers' parameters: `init()` gives the head's leaf the
embedding's values, as whoever installs other weights has to.
"""

from __future__ import annotations

from deeplearning4j_tpu.common.updaters import Adam
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import EmbeddingLayer
from deeplearning4j_tpu.nn.layers.parallel import (GainLayerNorm,
                                                   ParallelAttentionMoEBlock,
                                                   TiedLMHead)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.zoo.base import ZooModel

WINDOW, FULL = "sliding_attention", "full_attention"


class ParallelMoELM(ZooModel):
    def __init__(self, vocab_size: int, *, d_model: int = 64,
                 layer_types=(WINDOW, WINDOW, WINDOW, FULL),
                 n_heads: int = 4, n_kv_heads: int = 2, head_dim: int = 16,
                 window: int = 16, rope_theta: float = 50000.0,
                 expert_hidden: int = 32, n_routed: int = 16,
                 experts_per_token: int = 2, held: tuple = (0, 4),
                 n_shared: int = 2, eps: float = 1e-5,
                 logit_scale: float = 1.0, cache_len: int = 512,
                 seed: int = 123):
        super().__init__(num_classes=vocab_size, seed=seed)
        unknown = set(layer_types) - {WINDOW, FULL}
        if unknown:
            raise ValueError(f"layer_types may hold {WINDOW!r} and {FULL!r}; "
                             f"got {sorted(unknown)}")
        self.vocab_size = vocab_size
        self.d_model = d_model
        self.layer_types = tuple(layer_types)
        self.window = window
        self.eps = eps
        self.logit_scale = logit_scale
        self.block = dict(
            n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
            rope_theta=rope_theta, eps=eps, ffn_hidden=expert_hidden,
            n_routed=n_routed, experts_per_token=experts_per_token,
            held_first=held[0], held_count=held[1], n_shared=n_shared,
            cache_len=cache_len)

    def conf(self):
        b = (NeuralNetConfiguration.builder()
             .seed(self.seed).updater(Adam(1e-3)).list()
             .layer(EmbeddingLayer(n_in=self.vocab_size, n_out=self.d_model,
                                   has_bias=False)))
        for kind in self.layer_types:
            windowed = kind == WINDOW
            b.layer(ParallelAttentionMoEBlock(
                window=self.window if windowed else None, rotary=windowed,
                **self.block))
        b.layer(GainLayerNorm(eps=self.eps))
        b.layer(TiedLMHead(n_out=self.vocab_size, activation="softmax",
                           loss="mcxent", logit_scale=self.logit_scale))
        b.set_input_type(InputType.recurrent(self.vocab_size))
        return b.build()

    def init(self) -> MultiLayerNetwork:
        net = MultiLayerNetwork(self.conf()).init(self.seed)
        net.params[str(len(net.layers) - 1)]["W"] = net.params["0"]["W"]
        return net

"""Transformer encoder stack: LayerNormalization, encoder block,
positional encoding, zoo TransformerClassifier / TransformerLM
(beyond-reference long-context models; SURVEY §5)."""

import pytest
import numpy as np
import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers import (
    LayerNormalization,
    PositionalEncodingLayer,
    TransformerEncoderBlock,
)
from deeplearning4j_tpu.zoo import TransformerClassifier, TransformerLM


class TestLayerNormalization:
    def test_normalizes_last_axis(self):
        ln = LayerNormalization(n_out=8)
        p = ln.init_params(jax.random.PRNGKey(0))
        x = jnp.asarray(np.random.default_rng(0)
                        .standard_normal((4, 6, 8)) * 5 + 3, jnp.float32)
        y, _ = ln.forward(p, {}, x)
        np.testing.assert_allclose(np.asarray(y.mean(-1)), 0.0, atol=1e-5)
        np.testing.assert_allclose(np.asarray(y.std(-1)), 1.0, atol=1e-2)

    def test_gamma_beta_applied(self):
        ln = LayerNormalization(n_out=4)
        p = {"gamma": jnp.full((4,), 2.0), "beta": jnp.full((4,), 1.0)}
        x = jnp.asarray(np.random.default_rng(1)
                        .standard_normal((3, 4)), jnp.float32)
        y, _ = ln.forward(p, {}, x)
        np.testing.assert_allclose(np.asarray(y.mean(-1)), 1.0, atol=1e-5)


class TestPositionalEncoding:
    def test_signal_added_and_distinct_positions(self):
        pe = PositionalEncodingLayer(n_out=16)
        x = jnp.zeros((2, 10, 16))
        y, _ = pe.forward({}, {}, x)
        y = np.asarray(y)
        assert y.shape == (2, 10, 16)
        # all positions get distinct encodings
        assert len({tuple(np.round(y[0, t], 5)) for t in range(10)}) == 10
        np.testing.assert_allclose(y[0], y[1])   # batch-independent


class TestEncoderBlock:
    def test_shape_preserved_and_grads_flow(self):
        blk = TransformerEncoderBlock(n_in=32, n_heads=4)
        p = blk.init_params(jax.random.PRNGKey(0))
        x = jnp.asarray(np.random.default_rng(0)
                        .standard_normal((2, 7, 32)), jnp.float32)
        y, _ = blk.forward(p, {}, x)
        assert y.shape == x.shape

        def loss(pp):
            out, _ = blk.forward(pp, {}, x)
            return jnp.sum(out ** 2)

        g = jax.grad(loss)(p)
        for k, v in g.items():
            assert np.isfinite(np.asarray(v)).all(), k
        assert float(jnp.abs(g["attn_Wq"]).sum()) > 0
        assert float(jnp.abs(g["ff_W1"]).sum()) > 0

    def test_causal_blocks_no_future_leak(self):
        blk = TransformerEncoderBlock(n_in=16, n_heads=2, causal=True,
                                      use_flash=False)
        p = blk.init_params(jax.random.PRNGKey(1))
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((1, 6, 16)), jnp.float32)
        y1, _ = blk.forward(p, {}, x)
        x2 = x.at[0, 4].set(99.0)     # perturb a LATER position
        y2, _ = blk.forward(p, {}, x2)
        np.testing.assert_allclose(np.asarray(y1[0, :4]),
                                   np.asarray(y2[0, :4]), rtol=1e-5)


class TestTransformerZoo:
    def test_classifier_learns_token_presence(self):
        # class 1 iff token 0 appears in the sequence
        rng = np.random.default_rng(0)
        n, T = 256, 12
        ids = rng.integers(1, 30, (n, T))
        has = rng.random(n) < 0.5
        for i in np.nonzero(has)[0]:
            ids[i, rng.integers(0, T)] = 0
        y = np.eye(2, dtype=np.float32)[has.astype(int)]
        from deeplearning4j_tpu.nn.layers.pooling import PoolingType
        net = TransformerClassifier(vocab_size=30, num_classes=2,
                                    d_model=32, n_layers=1, n_heads=4,
                                    pooling=PoolingType.MAX, seed=7).init()
        net.fit(ids.astype(np.float32), y, epochs=20, batch_size=64)
        pred = np.asarray(net.output(ids.astype(np.float32))).argmax(1)
        acc = (pred == has.astype(int)).mean()
        assert acc > 0.9, acc

    def test_lm_learns_deterministic_sequence(self):
        # cyclic sequence: next token = (t + 1) % V — causal LM must nail it
        V, T, B = 11, 16, 32
        starts = np.arange(B) % V
        ids = (starts[:, None] + np.arange(T)[None, :]) % V
        x = ids.astype(np.float32)
        y = np.eye(V, dtype=np.float32)[(ids + 1) % V]
        lm = TransformerLM(vocab_size=V, d_model=32, n_layers=1,
                           n_heads=4, seed=3).init()
        lm.fit(x, y, epochs=60, batch_size=B, shuffle=False)
        out = np.asarray(lm.output(x))
        pred = out.argmax(-1)
        acc = (pred == (ids + 1) % V).mean()
        assert acc > 0.95, acc

    def test_serde_roundtrip(self, tmp_path):
        from deeplearning4j_tpu.util import ModelSerializer
        net = TransformerClassifier(vocab_size=20, num_classes=3,
                                    d_model=16, n_layers=1,
                                    n_heads=2).init()
        ids = np.random.default_rng(0).integers(0, 20, (4, 8)).astype(np.float32)
        want = np.asarray(net.output(ids))
        path = str(tmp_path / "tf.zip")
        ModelSerializer.write_model(net, path)
        clone = ModelSerializer.restore_model(path)
        got = np.asarray(clone.output(ids))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_embedding_vocab_inferred_from_recurrent_input():
    # regression: n_in must come from the recurrent type's feature size
    from deeplearning4j_tpu.common.updaters import Adam
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import (
        EmbeddingLayer, GlobalPoolingLayer, OutputLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-3))
            .list()
            .layer(EmbeddingLayer(n_out=8))
            .layer(GlobalPoolingLayer())
            .layer(OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.recurrent(100))
            .build())
    assert conf.layers[0].n_in == 100
    net = MultiLayerNetwork(conf).init()
    assert net.params["0"]["W"].shape == (100, 8)
    ids = np.random.default_rng(0).integers(0, 100, (3, 5)).astype(np.float32)
    assert np.asarray(net.output(ids)).shape == (3, 2)


class TestRematParity:
    """`remat=True` recomputes block activations in backward — loss,
    gradients, and the training trajectory must be identical to the
    stored-activation path (jax.checkpoint changes memory, not math)."""

    def test_lm_training_trajectory_identical(self):
        import numpy as np
        from deeplearning4j_tpu.zoo.transformer import TransformerLM

        V, B, T = 20, 4, 12
        rng = np.random.default_rng(0)
        ids = rng.integers(0, V, (B, T))
        x = ids.astype(np.float32)
        y = np.eye(V, dtype=np.float32)[(ids + 1) % V]

        losses = {}
        for remat in (False, True):
            lm = TransformerLM(vocab_size=V, d_model=16, n_layers=2,
                               n_heads=4, max_len=T, remat=remat)
            net = lm.init()
            net.fit(x, y, epochs=3, batch_size=B, shuffle=False)
            losses[remat] = net.score_value
        np.testing.assert_allclose(losses[True], losses[False],
                                   rtol=1e-5, atol=1e-6)

    def test_parity_holds_with_dropout(self):
        """rng rides through jax.checkpoint as an explicit argument, so
        the backward-pass recompute draws the SAME dropout masks — with
        dropout enabled, remat on/off must still match exactly."""
        import numpy as np
        from deeplearning4j_tpu.zoo.transformer import TransformerClassifier

        V, B, T = 16, 8, 10
        rng = np.random.default_rng(1)
        ids = rng.integers(0, V, (B, T)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, B)]

        losses = {}
        for remat in (False, True):
            clf = TransformerClassifier(vocab_size=V, num_classes=3,
                                        d_model=16, n_layers=2, n_heads=4,
                                        max_len=T, dropout=0.8, remat=remat)
            net = clf.init()
            net.fit(ids, y, epochs=3, batch_size=B, shuffle=False)
            losses[remat] = net.score_value
        np.testing.assert_allclose(losses[True], losses[False],
                                   rtol=1e-5, atol=1e-6)

    def test_remat_survives_config_roundtrip(self):
        from deeplearning4j_tpu.nn.conf.builder import MultiLayerConfiguration
        from deeplearning4j_tpu.zoo.transformer import TransformerLM

        conf = TransformerLM(vocab_size=10, d_model=8, n_layers=1,
                             n_heads=2, max_len=8, remat=True).conf()
        js = conf.to_json()
        clone = MultiLayerConfiguration.from_json(js)
        blocks = [l for l in clone.layers
                  if getattr(l, "layer_name", "") == "transformer_encoder"]
        assert blocks and all(b.remat for b in blocks)


class TestTransformerTransferLearning:
    """Fine-tune a 'pretrained' TransformerClassifier on a new label
    set: freeze the encoder stack, replace the head — the reference
    transfer-learning workflow applied to the beyond-reference model
    family."""

    def test_freeze_encoder_swap_head(self):
        from deeplearning4j_tpu.transferlearning import TransferLearning

        V, B, T = 20, 16, 10
        rng = np.random.default_rng(0)
        ids = rng.integers(1, V, (B, T)).astype(np.float32)
        y2 = np.eye(2, dtype=np.float32)[rng.integers(0, 2, B)]

        base = TransformerClassifier(vocab_size=V, num_classes=4,
                                     d_model=16, n_layers=1, n_heads=4,
                                     max_len=T).init()
        base.fit(ids, np.eye(4, dtype=np.float32)[rng.integers(0, 4, B)],
                 epochs=1, batch_size=B)

        # freeze through the pooling layer (index of last non-output
        # layer), re-head for 2 classes
        n_layers = len(base.layers)
        tuned = (TransferLearning.Builder(base)
                 .set_feature_extractor(n_layers - 2)
                 .n_out_replace(n_layers - 1, 2)
                 .build())
        before = {k: np.asarray(v).copy()
                  for k, v in tuned.param_table().items()}
        head = str(n_layers - 1)
        tuned.fit(ids, y2, epochs=2, batch_size=B)
        out = np.asarray(tuned.output(ids))
        assert out.shape == (B, 2)
        # frozen encoder params unchanged; the head must actually move
        head_moved = False
        for k, v in tuned.param_table().items():
            if k.startswith(head):
                head_moved = head_moved or not np.allclose(
                    np.asarray(v), before[k], atol=1e-7)
            else:
                np.testing.assert_allclose(np.asarray(v), before[k],
                                           atol=1e-7, err_msg=k)
        assert head_moved, "output layer params did not train"


class TestKVCacheDecoding:
    """Streaming decode with fixed-size KV caches (the transformer
    analogue of rnnTimeStep): stepwise cached outputs must equal the
    full causal forward at every position."""

    def _net(self, V=17, T=12):
        from deeplearning4j_tpu.zoo.transformer import TransformerLM
        return TransformerLM(vocab_size=V, d_model=16, n_layers=2,
                             n_heads=4, max_len=T, seed=3).init(), V, T

    def test_stepwise_matches_full_forward(self):
        import jax.numpy as jnp
        from deeplearning4j_tpu.nn.layers.recurrent import (
            BaseRecurrentLayer)
        net, V, T = self._net()
        rng = np.random.default_rng(0)
        ids = rng.integers(0, V, (2, T)).astype(np.float32)
        full = np.asarray(net.output(ids))            # [B, T, V]

        carries = {str(i): layer.init_carry(2, jnp.float32)
                   for i, layer in enumerate(net.layers)
                   if isinstance(layer, BaseRecurrentLayer)}
        for t in range(T):
            h, _, carries, _, _ = net._forward_core(
                net.params, net.net_state, ids[:, t:t + 1],
                train=False, rng=None, carries=carries)
            np.testing.assert_allclose(np.asarray(h[:, 0]), full[:, t],
                                       rtol=2e-4, atol=2e-5,
                                       err_msg=f"position {t}")

    def test_prompt_then_steps_matches(self):
        import jax.numpy as jnp
        from deeplearning4j_tpu.nn.layers.recurrent import (
            BaseRecurrentLayer)
        net, V, T = self._net()
        rng = np.random.default_rng(1)
        ids = rng.integers(0, V, (2, T)).astype(np.float32)
        full = np.asarray(net.output(ids))
        carries = {str(i): layer.init_carry(2, jnp.float32)
                   for i, layer in enumerate(net.layers)
                   if isinstance(layer, BaseRecurrentLayer)}
        # multi-token prompt in one call, then single-token steps
        P = 5
        h, _, carries, _, _ = net._forward_core(
            net.params, net.net_state, ids[:, :P], train=False,
            rng=None, carries=carries)
        np.testing.assert_allclose(np.asarray(h), full[:, :P],
                                   rtol=2e-4, atol=2e-5)
        for t in range(P, T):
            h, _, carries, _, _ = net._forward_core(
                net.params, net.net_state, ids[:, t:t + 1],
                train=False, rng=None, carries=carries)
            np.testing.assert_allclose(np.asarray(h[:, 0]), full[:, t],
                                       rtol=2e-4, atol=2e-5)

    def test_generate_shapes_and_greedy_determinism(self):
        from deeplearning4j_tpu.zoo.transformer import generate
        net, V, T = self._net()
        rng = np.random.default_rng(2)
        prompt = rng.integers(0, V, (3, 4))
        out1 = generate(net, prompt, 6, temperature=0)
        out2 = generate(net, prompt, 6, temperature=0)
        assert out1.shape == (3, 6)
        assert (out1 == out2).all()
        assert ((0 <= out1) & (out1 < V)).all()
        # greedy continuation must equal argmax of the full forward fed
        # with the sampled prefix (teacher-forcing cross-check)
        seq = np.concatenate([prompt.astype(np.float32),
                              out1.astype(np.float32)], axis=1)
        full = np.asarray(net.output(seq))
        want = full[:, prompt.shape[1] - 1:-1].argmax(-1)
        np.testing.assert_array_equal(out1, want)

    def test_generate_rejects_cache_overflow(self):
        from deeplearning4j_tpu.zoo.transformer import generate
        net, V, T = self._net(T=8)
        prompt = np.zeros((1, 4), np.int32)
        with pytest.raises(ValueError, match="cache length"):
            generate(net, prompt, 10, temperature=0)


class TestTransformerStreamingDepth:
    def test_graph_container_kv_cache_stream(self):
        # transformer blocks stream inside ComputationGraph too (same
        # BaseRecurrentLayer carry plumbing as MultiLayerNetwork)
        import jax.numpy as jnp
        from deeplearning4j_tpu.common.updaters import Adam
        from deeplearning4j_tpu.common.weights import WeightInit
        from deeplearning4j_tpu.nn.conf import (
            InputType, NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.nn.layers import (
            EmbeddingLayer, PositionalEncodingLayer, RnnOutputLayer,
            TransformerEncoderBlock)
        from deeplearning4j_tpu.nn.layers.recurrent import (
            BaseRecurrentLayer)

        from deeplearning4j_tpu.nn.graph import (
            ComputationGraphConfiguration)

        V, T = 13, 10
        g = ComputationGraphConfiguration.graph_builder(
            NeuralNetConfiguration.builder().seed(5).updater(Adam(1e-3))
            .weight_init(WeightInit.XAVIER))
        g.add_inputs("ids")
        g.add_layer("emb", EmbeddingLayer(n_in=V, n_out=16), "ids")
        g.add_layer("pos", PositionalEncodingLayer(max_len=T), "emb")
        g.add_layer("blk", TransformerEncoderBlock(
            n_heads=4, causal=True, cache_len=T), "pos")
        g.add_layer("out", RnnOutputLayer(
            n_out=V, activation="softmax", loss="mcxent"), "blk")
        g.set_outputs("out")
        g.set_input_types(InputType.recurrent(V))
        net = ComputationGraph(g.build()).init(5)
        rng = np.random.default_rng(3)
        ids = rng.integers(0, V, (2, T)).astype(np.float32)
        full = np.asarray(net.output(ids))

        carries = {n: layer.init_carry(2, jnp.float32)
                   for n, layer in net._recurrent_layers()}
        for t in range(T):
            acts, _, _, _ = net._forward_all(
                net.params, net.net_state, [ids[:, t:t + 1]],
                train=False, rng=None, carries=carries)
            h = acts[net.conf.network_outputs[0]]
            np.testing.assert_allclose(np.asarray(h[:, 0]), full[:, t],
                                       rtol=2e-4, atol=2e-5,
                                       err_msg=f"position {t}")

    def test_tbptt_transformer_xl_training(self):
        # TBPTT chunks thread the KV cache (Transformer-XL recurrence):
        # training runs, loss decreases, positions continue across
        # chunk boundaries (would diverge if the cache reset)
        from deeplearning4j_tpu.nn.conf.builder import BackpropType
        from deeplearning4j_tpu.zoo.transformer import TransformerLM
        lm = TransformerLM(vocab_size=11, d_model=16, n_layers=1,
                           n_heads=4, max_len=16, seed=9)
        conf = lm.conf()
        conf.backprop_type = BackpropType.TRUNCATED_BPTT
        conf.tbptt_fwd_length = 4
        conf.tbptt_back_length = 4
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        net = MultiLayerNetwork(conf).init(9)
        rng = np.random.default_rng(4)
        ids = rng.integers(0, 11, (4, 16))
        x = ids.astype(np.float32)
        y = np.eye(11, dtype=np.float32)[(ids + 1) % 11]
        scores = []
        for _ in range(6):
            net.fit(x, y, epochs=1, batch_size=4)
            scores.append(net.score_value)
        assert all(np.isfinite(s) for s in scores)
        assert scores[-1] < scores[0]

    def test_streaming_rejects_padding_mask(self):
        import jax.numpy as jnp
        from deeplearning4j_tpu.nn.layers import TransformerEncoderBlock
        blk = TransformerEncoderBlock(n_in=8, n_heads=2, causal=True,
                                      cache_len=8)
        params = blk.init_params(jax.random.PRNGKey(0))
        x = jnp.zeros((1, 2, 8))
        with pytest.raises(ValueError, match="padding mask"):
            blk.forward_with_carry(params, {}, x, blk.init_carry(1),
                                   mask=jnp.ones((1, 2)))

    def test_rnn_time_step_streams_token_ids(self):
        # the reference rnnTimeStep API works for transformers too:
        # rank-2 [B, T] is token ids for embedding-input nets (incl.
        # [B, 1] single-step decode), not a [B, F] feature row
        from deeplearning4j_tpu.zoo.transformer import TransformerLM
        net = TransformerLM(vocab_size=13, d_model=16, n_layers=1,
                            n_heads=4, max_len=10, seed=11).init()
        rng = np.random.default_rng(6)
        ids = rng.integers(0, 13, (2, 10)).astype(np.float32)
        full = np.asarray(net.output(ids))
        net.rnn_clear_previous_state()
        h = np.asarray(net.rnn_time_step(ids[:, :4]))     # prompt
        np.testing.assert_allclose(h, full[:, :4], rtol=2e-4, atol=2e-5)
        for t in range(4, 10):
            h = np.asarray(net.rnn_time_step(ids[:, t:t + 1]))
            np.testing.assert_allclose(h[:, 0], full[:, t],
                                       rtol=2e-4, atol=2e-5)

    def test_rnn_time_step_enforces_stream_budget(self):
        # streaming past cache_len used to silently clamp the last KV
        # slot (dynamic_update_slice) and corrupt later outputs; now
        # the host-side position tracker raises at the entry point
        from deeplearning4j_tpu.zoo.transformer import TransformerLM
        net = TransformerLM(vocab_size=13, d_model=16, n_layers=1,
                            n_heads=4, max_len=6, seed=11).init()
        ids = np.zeros((1, 4), np.float32)
        net.rnn_clear_previous_state()
        net.rnn_time_step(ids)                     # pos → 4
        net.rnn_time_step(ids[:, :2])              # pos → 6 (== budget)
        with pytest.raises(ValueError, match="stream budget"):
            net.rnn_time_step(ids[:, :1])
        # a new sequence resets the tracker
        net.rnn_clear_previous_state()
        net.rnn_time_step(ids)
        # an over-budget single call also raises
        net.rnn_clear_previous_state()
        with pytest.raises(ValueError, match="stream budget"):
            net.rnn_time_step(np.zeros((1, 7), np.float32))

    def test_tbptt_rejects_sequences_beyond_cache(self):
        from deeplearning4j_tpu.nn.conf.builder import BackpropType
        from deeplearning4j_tpu.common.updaters import Adam
        from deeplearning4j_tpu.nn.conf import (
            InputType, NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.layers import (
            EmbeddingLayer, RnnOutputLayer, TransformerEncoderBlock)
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.nn.layers import DenseLayer
        V, T = 7, 12
        conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-3))
                .list()
                .layer(DenseLayer(n_in=V, n_out=8))
                .layer(TransformerEncoderBlock(n_heads=2, causal=True,
                                               cache_len=8))
                .layer(RnnOutputLayer(n_out=V, activation="softmax",
                                      loss="mcxent"))
                .set_input_type(InputType.recurrent(V))
                .backprop_type(BackpropType.TRUNCATED_BPTT, 4)
                .build())
        net = MultiLayerNetwork(conf).init()
        x = np.zeros((2, T, V), np.float32)    # rank-3 → TBPTT chunking
        y = np.zeros((2, T, V), np.float32)
        y[..., 0] = 1.0
        with pytest.raises(ValueError, match="carry budget"):
            net.fit(x, y, epochs=1, batch_size=2)

    def test_graph_mixed_id_and_feature_inputs_squeeze_per_input(self):
        # advisor scenario: a graph mixing a token-id input with a
        # rank-2 [B, F] feature input must squeeze only the feature one
        from deeplearning4j_tpu.nn.graph import (
            ComputationGraph, ComputationGraphConfiguration)
        from deeplearning4j_tpu.common.updaters import Adam
        from deeplearning4j_tpu.nn.conf import (
            InputType, NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.conf.graph import MergeVertex
        from deeplearning4j_tpu.nn.layers import (
            EmbeddingLayer, LSTM, RnnOutputLayer)
        V, D = 11, 6
        g = ComputationGraphConfiguration.graph_builder(
            NeuralNetConfiguration.builder().seed(3).updater(Adam(1e-3)))
        g.add_inputs("ids", "feat")
        g.add_layer("emb", EmbeddingLayer(n_in=V, n_out=D), "ids")
        # the feature input feeds a recurrent consumer directly: at
        # rnn_time_step a rank-2 [B, F] here is ONE timestep and must
        # be expanded to [B, 1, F] even though an id input coexists
        # (the old global flag disabled the squeeze for all inputs)
        g.add_layer("rnn2", LSTM(n_in=4, n_out=D), "feat")
        g.add_vertex("cat", MergeVertex(), "emb", "rnn2")
        g.add_layer("rnn", LSTM(n_in=2 * D, n_out=D), "cat")
        g.add_layer("out", RnnOutputLayer(n_out=V, activation="softmax",
                                          loss="mcxent"), "rnn")
        g.set_outputs("out")
        g.set_input_types(InputType.recurrent(V),
                          InputType.recurrent(4))
        net = ComputationGraph(g.build()).init(3)
        # full-sequence reference
        T = 5
        rng = np.random.default_rng(4)
        ids_seq = rng.integers(0, V, (2, T)).astype(np.float32)
        feat_seq = rng.standard_normal((2, T, 4)).astype(np.float32)
        full = np.asarray(net.output(ids_seq, feat_seq))
        # stream one step at a time: ids as [B,1], features as [B,F]
        net.rnn_clear_previous_state()
        for t in range(T):
            out = np.asarray(net.rnn_time_step(
                ids_seq[:, t:t + 1], feat_seq[:, t]))
            assert out.shape == (2, V)
            np.testing.assert_allclose(out, full[:, t], rtol=2e-4,
                                       atol=2e-5)

    def test_generate_topk_topp_filters(self):
        from deeplearning4j_tpu.zoo.transformer import generate
        import jax
        net_lm = __import__("deeplearning4j_tpu.zoo.transformer",
                            fromlist=["TransformerLM"]).TransformerLM(
            vocab_size=17, d_model=16, n_layers=1, n_heads=4,
            max_len=24, seed=13).init()
        prompt = np.zeros((2, 2), np.int32)
        k0 = jax.random.PRNGKey(7)
        # top_k=1 is greedy regardless of temperature
        a = generate(net_lm, prompt, 6, temperature=1.0, top_k=1, rng=k0)
        g = generate(net_lm, prompt, 6, temperature=0)
        np.testing.assert_array_equal(a, g)
        # no-op filters reproduce unfiltered sampling bit-for-bit
        b = generate(net_lm, prompt, 6, temperature=1.0, rng=k0)
        c = generate(net_lm, prompt, 6, temperature=1.0, top_k=17,
                     rng=k0)
        d = generate(net_lm, prompt, 6, temperature=1.0, top_p=1.0,
                     rng=k0)
        np.testing.assert_array_equal(b, c)
        np.testing.assert_array_equal(b, d)

    def test_generate_rejects_bad_sampling_args(self):
        from deeplearning4j_tpu.zoo.transformer import (
            TransformerLM, generate)
        net = TransformerLM(vocab_size=17, d_model=16, n_layers=1,
                            n_heads=4, max_len=24, seed=13).init()
        prompt = np.zeros((1, 2), np.int32)
        with pytest.raises(ValueError, match="top_p"):
            generate(net, prompt, 4, top_p=0.0)
        with pytest.raises(ValueError, match="top_k"):
            generate(net, prompt, 4, top_k=0)
        with pytest.raises(ValueError, match="top_k"):
            generate(net, prompt, 4, top_k=99)

    def test_graph_rnn_time_step_token_ids(self):
        # the graph container's rnnTimeStep API streams token-id models
        from deeplearning4j_tpu.nn.graph import (
            ComputationGraph, ComputationGraphConfiguration)
        from deeplearning4j_tpu.common.updaters import Adam
        from deeplearning4j_tpu.common.weights import WeightInit
        from deeplearning4j_tpu.nn.conf import (
            InputType, NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.layers import (
            EmbeddingLayer, PositionalEncodingLayer, RnnOutputLayer,
            TransformerEncoderBlock)
        V, T = 13, 8
        g = ComputationGraphConfiguration.graph_builder(
            NeuralNetConfiguration.builder().seed(5).updater(Adam(1e-3))
            .weight_init(WeightInit.XAVIER))
        g.add_inputs("ids")
        g.add_layer("emb", EmbeddingLayer(n_in=V, n_out=16), "ids")
        g.add_layer("pos", PositionalEncodingLayer(max_len=T), "emb")
        g.add_layer("blk", TransformerEncoderBlock(
            n_heads=4, causal=True, cache_len=T), "pos")
        g.add_layer("out", RnnOutputLayer(
            n_out=V, activation="softmax", loss="mcxent"), "blk")
        g.set_outputs("out")
        g.set_input_types(InputType.recurrent(V))
        net = ComputationGraph(g.build()).init(5)
        rng = np.random.default_rng(8)
        ids = rng.integers(0, V, (2, T)).astype(np.float32)
        full = np.asarray(net.output(ids))
        net.rnn_clear_previous_state()
        h = np.asarray(net.rnn_time_step(ids[:, :3]))
        np.testing.assert_allclose(h, full[:, :3], rtol=2e-4, atol=2e-5)
        for t in range(3, T):
            h = np.asarray(net.rnn_time_step(ids[:, t:t + 1]))
            np.testing.assert_allclose(h[:, 0], full[:, t],
                                       rtol=2e-4, atol=2e-5)

    def test_beam_search(self):
        from deeplearning4j_tpu.zoo.transformer import (
            TransformerLM, beam_search, generate)
        net = TransformerLM(vocab_size=17, d_model=16, n_layers=1,
                            n_heads=4, max_len=24, seed=13).init()
        rng = np.random.default_rng(5)
        prompt = rng.integers(0, 17, (2, 3))
        ids, scores = beam_search(net, prompt, 6, beam_width=4)
        assert ids.shape == (2, 4, 6) and scores.shape == (2, 4)
        # beams sorted best-first
        assert (np.diff(scores, axis=1) <= 1e-6).all()
        # beam_width=1 equals greedy decoding
        g = generate(net, prompt, 6, temperature=0)
        b1, _ = beam_search(net, prompt, 6, beam_width=1)
        np.testing.assert_array_equal(b1[:, 0], g)
        # the reported beam scores must equal the true teacher-forced
        # accumulated logprob of the returned sequences (beam search is
        # NOT guaranteed to beat greedy for W>1, so assert bookkeeping
        # correctness, not monotonicity)
        def seq_logp_rows(seq):
            full = np.concatenate([prompt.astype(np.float32),
                                   seq.astype(np.float32)], 1)
            probs = np.asarray(net.output(full))
            out = np.zeros(seq.shape[0])
            for b in range(seq.shape[0]):
                for t in range(seq.shape[1]):
                    out[b] += np.log(max(
                        probs[b, prompt.shape[1] - 1 + t, seq[b, t]],
                        1e-9))
            return out
        np.testing.assert_allclose(seq_logp_rows(ids[:, 0]),
                                   scores[:, 0], rtol=1e-4, atol=1e-3)

    def test_beam_search_eos_freezes_finished(self):
        from deeplearning4j_tpu.zoo.transformer import (
            TransformerLM, beam_search)
        net = TransformerLM(vocab_size=11, d_model=16, n_layers=1,
                            n_heads=4, max_len=24, seed=3).init()
        prompt = np.zeros((1, 2), np.int32)
        ids, scores = beam_search(net, prompt, 8, beam_width=3, eos_id=5)
        # once a beam emits eos, every later token is eos
        for w in range(3):
            seq = ids[0, w]
            hits = np.nonzero(seq == 5)[0]
            if hits.size:
                assert (seq[hits[0]:] == 5).all()

    def test_beam_search_length_penalty(self):
        from deeplearning4j_tpu.zoo.transformer import (
            TransformerLM, beam_search)
        net = TransformerLM(vocab_size=11, d_model=16, n_layers=1,
                            n_heads=4, max_len=24, seed=3).init()
        prompt = np.zeros((1, 2), np.int32)
        # alpha=0 is the unnormalized ordering (argsort of raw scores)
        ids0, s0 = beam_search(net, prompt, 8, beam_width=3, eos_id=5,
                               length_penalty=0.0)
        assert (np.diff(s0, axis=1) <= 1e-6).all()
        # with alpha the beam SET is unchanged (pure rerank), and the
        # ORDER must equal the recomputed normalized-score ordering —
        # this fails if the norm is inverted, multiplied, or lengths
        # are computed wrong
        alpha = 1.0
        ids1, s1 = beam_search(net, prompt, 8, beam_width=3, eos_id=5,
                               length_penalty=alpha)
        assert sorted(map(tuple, ids0[0])) == sorted(map(tuple, ids1[0]))

        def norm_score(seq, raw):
            hit = np.nonzero(seq == 5)[0]
            L = hit[0] + 1 if hit.size else seq.size
            return raw / (((5.0 + L) / 6.0) ** alpha)

        ns = [norm_score(ids1[0, w], s1[0, w]) for w in range(3)]
        assert (np.diff(ns) <= 1e-6).all(), ns
        # and when beams have different lengths, alpha must actually be
        # able to change the winner relative to raw ordering whenever
        # the normalized ordering differs
        ns0 = [norm_score(ids0[0, w], s0[0, w]) for w in range(3)]
        if np.argmax(ns0) != 0:
            assert tuple(ids1[0, 0]) != tuple(ids0[0, 0])


class TestIntegerIdCarry:
    """generate()/beam_search() keep token ids INTEGER while carried
    standalone: a float32 round-trip silently collapses ids at the
    2^24 precision edge (16777217.0 == 16777216.0) — only the
    embedding gather consumes them, and it indexes with int32 either
    way."""

    def test_generate_feeds_integer_ids_to_embedding(self, monkeypatch):
        import jax.numpy as jnp
        from deeplearning4j_tpu.nn.layers.feedforward import (
            EmbeddingLayer)
        from deeplearning4j_tpu.zoo.transformer import (
            TransformerLM, generate)

        seen = []
        orig = EmbeddingLayer.forward

        def spy(self, params, state, x, **kw):
            seen.append(jnp.asarray(x).dtype)
            return orig(self, params, state, x, **kw)

        monkeypatch.setattr(EmbeddingLayer, "forward", spy)
        # fresh net -> fresh jit cache -> the prefill/decode traces run
        # through the spy exactly once each
        net = TransformerLM(vocab_size=17, d_model=16, n_layers=1,
                            n_heads=4, max_len=12, seed=9).init()
        out = generate(net, np.zeros((1, 3), np.int64), 4, temperature=0)
        assert out.shape == (1, 4)
        assert seen, "embedding never traced"
        assert all(np.issubdtype(d, np.integer) for d in seen), (
            f"token ids reached the embedding as {seen} — the float "
            "carry corrupts ids at the 2^24 edge")

    def test_embedding_gather_exact_at_float_precision_edge(self):
        """Ids straddling 2^24, gathered through a huge-vocab embedding
        table: the int path must hit exact rows where a float32 carry
        provably collapses neighbors."""
        import jax.numpy as jnp
        from deeplearning4j_tpu.nn.layers.feedforward import (
            EmbeddingLayer)

        edge = 2 ** 24
        V = edge + 8
        layer = EmbeddingLayer(n_in=V, n_out=1, has_bias=False)
        layer.time_series_input = True
        # rows distinguishable mod 7 without allocating V*D rands
        W = (jnp.arange(V, dtype=jnp.int32) % 7).astype(
            jnp.float32)[:, None]
        ids = np.asarray([[edge - 1, edge, edge + 1, edge + 3]],
                         np.int64)
        out, _ = layer.forward({"W": W}, {}, jnp.asarray(ids))
        want = (ids % 7).astype(np.float32)[..., None]
        np.testing.assert_array_equal(np.asarray(out), want)
        # the float32 carry this guards against IS lossy here
        as_f32 = ids.astype(np.float32).astype(np.int64)
        assert (as_f32 != ids).any()

    def test_generate_beam_unchanged_by_int_carry(self):
        """Trajectory regression: greedy generate and beam_search stay
        deterministic and in-vocab after the int-id change (numerics
        must be untouched — the gather rows are identical)."""
        from deeplearning4j_tpu.zoo.transformer import (
            TransformerLM, beam_search, generate)

        net = TransformerLM(vocab_size=17, d_model=16, n_layers=2,
                            n_heads=4, max_len=12, seed=4).init()
        prompt = np.asarray([[3, 5, 1], [2, 2, 4]])
        g1 = generate(net, prompt, 5, temperature=0)
        g2 = generate(net, prompt.astype(np.float32), 5, temperature=0)
        np.testing.assert_array_equal(g1, g2)   # float prompts still ok
        seqs, scores = beam_search(net, prompt, 5, beam_width=2)
        assert seqs.shape == (2, 2, 5)
        np.testing.assert_array_equal(seqs[:, 0], g1)  # top beam = greedy

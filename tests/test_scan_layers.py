"""Scan-over-layers compilation + generalized remat (nn/scan_stack.py).

The scan path must be a pure compilation strategy: same loss
trajectory, same gradients (within fp tolerance) as the Python-unrolled
loop on identical inits — while compiling a several-times-smaller
program in a fraction of the time for deep homogeneous stacks (the
whole-program-compilation premise of the TPU port, arXiv:1810.09868;
loop-rolled graph cost discipline per arXiv:1605.08695).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.common.updaters import Adam, Sgd
from deeplearning4j_tpu.nn import scan_stack
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import (
    DenseLayer,
    OutputLayer,
    TransformerEncoderBlock,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.zoo.transformer import TransformerLM, generate


def _deep_mlp_conf(scan, n_hidden=6, width=16, updater=None):
    b = (NeuralNetConfiguration.builder().seed(0)
         .updater(updater or Adam(1e-3)).list()
         .layer(DenseLayer(n_in=8, n_out=width, activation="relu")))
    for _ in range(n_hidden):
        b.layer(DenseLayer(n_in=width, n_out=width, activation="relu"))
    b.layer(OutputLayer(n_in=width, n_out=3))
    return b.scan_layers(scan).build()


def _mlp_data(n=24, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 8)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return x, y


def _lm(scan, n_layers=3, remat_policy=None, **kw):
    lm = TransformerLM(vocab_size=24, d_model=16, n_layers=n_layers,
                       n_heads=2, max_len=12, remat_policy=remat_policy,
                       **kw)
    conf = lm.conf()
    conf.scan_layers = scan
    return MultiLayerNetwork(conf).init(11)


def _lm_data(B=6, T=12, V=24, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, V, (B, T)).astype(np.float32)
    y = np.eye(V, dtype=np.float32)[rng.integers(0, V, (B, T))]
    return ids, y


def _fit_losses(net, x, y, batch_size, **kw):
    losses = []
    from deeplearning4j_tpu.optimize.listeners import TrainingListener

    class Rec(TrainingListener):
        def iteration_done(self, model, it, ep, score, **kwargs):
            losses.append(score)

    net.set_listeners(Rec())
    net.fit(x, y, epochs=1, batch_size=batch_size, shuffle=False, **kw)
    return np.asarray(losses)


class TestScanParity:
    def test_deep_mlp_loss_trajectory_and_params_match_unrolled(self):
        x, y = _mlp_data()
        nets = {}
        losses = {}
        for scan in (True, False):
            net = MultiLayerNetwork(_deep_mlp_conf(scan)).init(5)
            losses[scan] = _fit_losses(net, x, y, batch_size=8)
            nets[scan] = net
        np.testing.assert_allclose(losses[True], losses[False], rtol=1e-6)
        for k, a in nets[True].param_table().items():
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(nets[False].param_table()[k]),
                rtol=1e-4, atol=1e-6, err_msg=k)

    def test_scan_plan_detects_the_homogeneous_run(self):
        net = MultiLayerNetwork(_deep_mlp_conf(True)).init(5)
        plan = scan_stack.build_layer_plan(
            net.layers, net.params, net.conf.input_preprocessors,
            len(net.layers))
        runs = [seg for seg in plan if seg[0] == "scan"]
        # the 6 identical hidden layers scan; the first (8->16) dense
        # and the output layer stay unrolled
        assert runs == [("scan", 1, 7)]

    def test_transformer_lm_losses_and_grads_match_unrolled(self):
        ids, y = _lm_data()
        grads = {}
        for scan in (True, False):
            net = _lm(scan)
            loss, g = jax.value_and_grad(
                lambda p, n=net: n._loss_fn(
                    p, n.net_state, jnp.asarray(ids), jnp.asarray(y),
                    jax.random.PRNGKey(3), None, None, train=True)[0])(
                        net.params)
            grads[scan] = (float(loss), g)
        assert grads[True][0] == pytest.approx(grads[False][0], rel=1e-6)
        flat_s = jax.tree_util.tree_leaves(grads[True][1])
        flat_u = jax.tree_util.tree_leaves(grads[False][1])
        for a, b in zip(flat_s, flat_u):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)

    def test_fused_steps_match_single_steps_under_scan(self):
        ids, y = _lm_data(B=18)
        l1 = _fit_losses(_lm(True), ids, y, batch_size=6)
        l2 = _fit_losses(_lm(True), ids, y, batch_size=6,
                         steps_per_execution=3)
        np.testing.assert_allclose(l1, l2, rtol=1e-6)

    def test_dropout_rng_parity(self):
        """Per-layer rng folds inside the scan body are the unrolled
        path's folds — dropout draws match exactly."""
        ids, y = _lm_data()
        losses = {}
        for scan in (True, False):
            lm = TransformerLM(vocab_size=24, d_model=16, n_layers=3,
                               n_heads=2, max_len=12)
            conf = lm.conf()
            conf.scan_layers = scan
            for layer in conf.layers:
                if isinstance(layer, TransformerEncoderBlock):
                    layer.dropout = 0.8
            net = MultiLayerNetwork(conf).init(11)
            losses[scan] = _fit_losses(net, ids, y, batch_size=6)
        np.testing.assert_allclose(losses[True], losses[False], rtol=1e-6)

    def test_env_override_disables_scan(self, monkeypatch):
        net = _lm(True)
        assert scan_stack.scan_enabled(net.conf)
        monkeypatch.setenv("DL4J_SCAN_LAYERS", "0")
        assert not scan_stack.scan_enabled(net.conf)


class TestExclusionsAndFallbacks:
    def test_heterogeneous_stack_has_no_scan_runs_and_trains(self):
        b = (NeuralNetConfiguration.builder().seed(0)
             .updater(Sgd(1e-2)).list()
             .layer(DenseLayer(n_in=8, n_out=16, activation="relu"))
             .layer(DenseLayer(n_in=16, n_out=12, activation="relu"))
             .layer(DenseLayer(n_in=12, n_out=16, activation="tanh"))
             .layer(OutputLayer(n_in=16, n_out=3)))
        conf = b.build()
        net = MultiLayerNetwork(conf).init(1)
        plan = scan_stack.build_layer_plan(
            net.layers, net.params, conf.input_preprocessors,
            len(net.layers))
        assert all(seg[0] == "layer" for seg in plan)
        x, y = _mlp_data()
        net.fit(x, y, epochs=1, batch_size=8)
        assert np.isfinite(net.score_value)

    def test_different_activation_breaks_the_run(self):
        """Same shapes, different config — must NOT merge (the scan
        body would silently run the first layer's activation)."""
        relu = DenseLayer(n_in=16, n_out=16, activation="relu")
        tanh = DenseLayer(n_in=16, n_out=16, activation="tanh")
        k = jax.random.PRNGKey(0)
        p1, p2 = relu.init_params(k), tanh.init_params(k)
        assert (scan_stack.layer_signature(relu, p1)
                != scan_stack.layer_signature(tanh, p2))

    def test_recurrent_carry_path_stays_unrolled_and_streams(self):
        """generate() / rnn_time_step thread per-layer KV-cache carries
        — the carry path is excluded from scanning and must produce the
        same tokens as an unrolled-configured model."""
        outs = {}
        for scan in (True, False):
            net = _lm(scan)
            prompt = np.asarray([[1, 2, 3, 4]], np.float32)
            outs[scan] = generate(net, prompt, 6, temperature=0)
        np.testing.assert_array_equal(outs[True], outs[False])

    def test_moe_layers_opt_out_of_stacking(self):
        from deeplearning4j_tpu.nn.layers.moe import MixtureOfExperts
        assert MixtureOfExperts.stackable_params is False

    def test_masked_batches_still_match_unrolled(self):
        """Masks ride the scan body closure when the run propagates
        them unchanged (transformer blocks do) — same loss either
        way."""
        ids, y = _lm_data()
        mask = np.ones(ids.shape, np.float32)
        mask[:, -3:] = 0.0
        vals = {}
        for scan in (True, False):
            net = _lm(scan)
            loss, _ = net._loss_fn(net.params, net.net_state,
                                   jnp.asarray(ids), jnp.asarray(y), None,
                                   jnp.asarray(mask), None, train=True)
            vals[scan] = float(loss)
        assert vals[True] == pytest.approx(vals[False], rel=1e-6)


class TestGraphChains:
    def _graph(self, scan):
        from deeplearning4j_tpu.nn.graph import (
            ComputationGraph,
            ComputationGraphConfiguration,
        )
        g = (ComputationGraphConfiguration.graph_builder()
             .add_inputs("in")
             .add_layer("d0", DenseLayer(n_in=8, n_out=16,
                                         activation="relu",
                                         updater=Sgd(1e-2)), "in")
             .add_layer("d1", DenseLayer(n_in=16, n_out=16,
                                         activation="relu",
                                         updater=Sgd(1e-2)), "d0")
             .add_layer("d2", DenseLayer(n_in=16, n_out=16,
                                         activation="relu",
                                         updater=Sgd(1e-2)), "d1")
             .add_layer("d3", DenseLayer(n_in=16, n_out=16,
                                         activation="relu",
                                         updater=Sgd(1e-2)), "d2")
             .add_layer("out", OutputLayer(n_in=16, n_out=3,
                                           updater=Sgd(1e-2)), "d3")
             .set_outputs("out")
             .scan_layers(scan)
             .build())
        return ComputationGraph(g).init(2)

    def test_chain_detection(self):
        net = self._graph(True)
        chains, members = scan_stack.build_graph_plan(
            net.conf, net.params, net.output_layer_names)
        assert chains == {"d1": ["d1", "d2", "d3"]} or \
            chains == {"d0": ["d0", "d1", "d2", "d3"]}
        # d0 differs (8->16) so the canonical chain is d1..d3
        assert "d1" in set().union(*([c for c in chains.values()]))

    def test_graph_training_parity_scan_vs_unrolled(self):
        x, y = _mlp_data()
        results = {}
        for scan in (True, False):
            net = self._graph(scan)
            net.fit(x, y, epochs=2, batch_size=8)
            results[scan] = (net.score_value, net.param_table())
        assert results[True][0] == pytest.approx(results[False][0],
                                                 rel=1e-5)
        for k, a in results[True][1].items():
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(results[False][1][k]),
                rtol=1e-4, atol=1e-6, err_msg=k)

    def test_feed_forward_materializes_every_node(self):
        net = self._graph(True)
        x, _ = _mlp_data(n=4)
        acts = net.feed_forward(x)
        assert {"d0", "d1", "d2", "d3", "out"} <= set(acts)


class TestRematPolicy:
    def test_serde_round_trip(self):
        conf = _lm(True, remat_policy="dots_saveable").conf
        again = type(conf).from_json(conf.to_json())
        blocks = [l for l in again.layers
                  if isinstance(l, TransformerEncoderBlock)]
        assert blocks and all(b.remat_policy == "dots_saveable"
                              for b in blocks)
        assert again.scan_layers is True

    def test_scan_layers_flag_round_trips(self):
        conf = _lm(False).conf
        again = type(conf).from_json(conf.to_json())
        assert again.scan_layers is False

    def test_legacy_remat_bool_maps_to_full(self):
        block = TransformerEncoderBlock(n_in=16, n_heads=2, remat=True)
        assert scan_stack.effective_remat_policy(block) == "full"
        block2 = TransformerEncoderBlock(n_in=16, n_heads=2,
                                         remat_policy="dots_saveable")
        assert scan_stack.effective_remat_policy(block2) == "dots_saveable"

    def test_invalid_policy_rejected_eagerly(self):
        with pytest.raises(ValueError, match="remat_policy"):
            DenseLayer(n_in=4, n_out=4, remat_policy="everything")

    def test_global_builder_default_pushes_into_layers(self):
        b = (NeuralNetConfiguration.builder().seed(0)
             .remat_policy("dots_saveable").list()
             .layer(DenseLayer(n_in=8, n_out=8))
             .layer(DenseLayer(n_in=8, n_out=8,
                               remat_policy="none"))
             .layer(OutputLayer(n_in=8, n_out=3)))
        conf = b.build()
        assert conf.layers[0].remat_policy == "dots_saveable"
        # layer-level override wins
        assert conf.layers[1].remat_policy == "none"

    @pytest.mark.parametrize("policy", ["full", "dots_saveable"])
    def test_remat_is_numerically_transparent(self, policy):
        ids, y = _lm_data()
        base = _fit_losses(_lm(True), ids, y, batch_size=6)
        remat = _fit_losses(_lm(True, remat_policy=policy), ids, y,
                            batch_size=6)
        np.testing.assert_allclose(base, remat, rtol=1e-6)

    def test_remat_applies_on_tbptt_carry_path(self):
        """The carry-threading branch wraps forward_with_carry for ANY
        recurrent layer type — an LSTM with remat_policy under TBPTT
        must train to the same losses as without it."""
        from deeplearning4j_tpu.nn.conf.builder import BackpropType
        from deeplearning4j_tpu.nn.layers import LSTM, RnnOutputLayer

        rng = np.random.default_rng(4)
        x = rng.standard_normal((6, 8, 5)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, (6, 8))]
        losses = {}
        for policy in (None, "full"):
            b = (NeuralNetConfiguration.builder().seed(0)
                 .updater(Sgd(1e-2)).list()
                 .layer(LSTM(n_in=5, n_out=8, remat_policy=policy))
                 .layer(RnnOutputLayer(n_in=8, n_out=3)))
            b.backprop_type(BackpropType.TRUNCATED_BPTT, 4)
            net = MultiLayerNetwork(b.build()).init(2)
            net.fit(x, y, epochs=1, batch_size=6)
            losses[policy] = net.score_value
        assert losses["full"] == pytest.approx(losses[None], rel=1e-6)

    def test_remat_applies_on_unrolled_path_too(self):
        ids, y = _lm_data()
        base = _fit_losses(_lm(False), ids, y, batch_size=6)
        remat = _fit_losses(_lm(False, remat_policy="full"), ids, y,
                            batch_size=6)
        np.testing.assert_allclose(base, remat, rtol=1e-6)


def _count_eqns(closed):
    from benchtools.hlo_cost import count_jaxpr_eqns
    return count_jaxpr_eqns(closed)


class TestCompileRegression:
    """The committed win: the scan path must compile a several-times
    smaller program in less time for a deep homogeneous stack. Uses a
    16-block TransformerLM at tiny widths — jaxpr equation counts are
    shape-independent, so this is the same program structure the
    committed PROFILE_aot evidence measures."""

    def _nets(self, n_layers):
        out = {}
        for scan in (True, False):
            lm = TransformerLM(vocab_size=32, d_model=16,
                               n_layers=n_layers, n_heads=2, max_len=16)
            conf = lm.conf()
            conf.scan_layers = scan
            out[scan] = MultiLayerNetwork(conf).init(1)
        x = jax.ShapeDtypeStruct((4, 16), jnp.float32)
        y = jax.ShapeDtypeStruct((4, 16, 32), jnp.float32)
        return out, x, y

    @staticmethod
    def _loss_grad_eqns(net, x, y):
        """The part of the step the scan rolls: the loss and its
        gradient with respect to the packed compute-dtype tree
        (`Trainable._pack`), without the updater walk."""
        compute, _ = net._pack(net.params)

        def loss_and_grad(p, x, y):
            return jax.value_and_grad(lambda p: net._loss_fn(
                p, net.net_state, x, y, jax.random.PRNGKey(0), None, None,
                train=True)[0])(p)

        return _count_eqns(jax.make_jaxpr(loss_and_grad)(compute, x, y))

    def test_scan_program_is_3x_smaller_at_depth_16(self):
        nets, x, y = self._nets(16)
        scan_eqns = self._loss_grad_eqns(nets[True], x, y)
        unrolled_eqns = self._loss_grad_eqns(nets[False], x, y)
        assert unrolled_eqns / scan_eqns >= 3.0, (scan_eqns, unrolled_eqns)
        # the whole step keeps a per-layer updater walk (masters and
        # updater state are updated leaf by leaf, where they lie: a few
        # elementwise equations a leaf), so it shrinks by less
        scan_step = _count_eqns(nets[True].train_step_jaxpr(x, y, steps=2))
        unrolled_step = _count_eqns(
            nets[False].train_step_jaxpr(x, y, steps=2))
        assert unrolled_step / scan_step >= 1.5, (scan_step, unrolled_step)

    def test_program_size_is_depth_independent_under_scan(self):
        nets8, x, y = self._nets(8)
        nets16, _, _ = self._nets(16)
        # forward and backward: the traced block body does not grow;
        # only the per-layer rng folds do (4 equations a block)
        g8 = self._loss_grad_eqns(nets8[True], x, y)
        g16 = self._loss_grad_eqns(nets16[True], x, y)
        assert g16 - g8 < 8 * 10, (g8, g16)
        # the whole step grows by the cast-and-stack of the compute copy
        # and the updater walk alone: 16 leaves a block, under 25
        # elementwise equations a leaf
        e8 = _count_eqns(nets8[True].train_step_jaxpr(x, y, steps=2))
        e16 = _count_eqns(nets16[True].train_step_jaxpr(x, y, steps=2))
        assert e16 - e8 < 8 * 16 * 25, (e8, e16)

    def test_scan_compiles_faster_jit_compile_collector(self):
        """JitCompileCollector-measured backend-compile seconds: the
        scan path must compile faster than the unrolled path on the
        same deep stack (generous 1.2x bar; measured 2.9x at depth 16
        with the per-layer updater walk, 1.5x at depth 8)."""
        from benchtools.hlo_cost import compile_program
        nets, x, y = self._nets(16)
        scan_rep = compile_program(
            nets[True].lower_train_step(x, y, steps=2))
        unrolled_rep = compile_program(
            nets[False].lower_train_step(x, y, steps=2))
        assert "error" not in scan_rep and "error" not in unrolled_rep
        assert scan_rep["xla_compiles"] >= 1
        assert (scan_rep["compile_seconds"] * 1.2
                < unrolled_rep["compile_seconds"]), (scan_rep,
                                                    unrolled_rep)
        assert scan_rep["peak_temp_bytes"] > 0

    def test_remat_full_reduces_peak_temp_bytes(self):
        from benchtools.hlo_cost import compile_program
        reps = {}
        for policy in (None, "full"):
            lm = TransformerLM(vocab_size=32, d_model=32, n_layers=8,
                               n_heads=2, max_len=64,
                               remat_policy=policy)
            net = MultiLayerNetwork(lm.conf()).init(1)
            x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
            y = jax.ShapeDtypeStruct((8, 64, 32), jnp.float32)
            reps[policy] = compile_program(
                net.lower_train_step(x, y, steps=2))
        assert (reps["full"]["peak_temp_bytes"]
                < reps[None]["peak_temp_bytes"]), reps

"""Threshold-encoded gradient sharing (parallel/gradient_sharing.py):
encode/decode/error-feedback units, adaptive-τ controller, mode
resolution + conf serde, convergence parity vs dense sync training
(deep MLP with packed ``stacked::`` runs, TransformerLM with
scan_layers + fused multi-step, DP x TP), and the comm-bytes
accounting seam (benchtools/hlo_cost.collective_table /
comm_bytes_block)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.common.updaters import Adam
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import ComputationGraphConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.listeners import CollectScoresListener
from deeplearning4j_tpu.parallel import gradient_sharing as gs
from deeplearning4j_tpu.parallel.mesh import MeshSpec, device_mesh, make_mesh
from deeplearning4j_tpu.parallel.tensor import ShardedParallelTrainer
from deeplearning4j_tpu.parallel.trainer import ParallelTrainer


def deep_mlp(n_hidden=6, seed=7, lr=0.01):
    """Deep homogeneous MLP — the hidden stack forms ONE scan run that
    packs at the train-step boundary (stacked:: entries)."""
    b = NeuralNetConfiguration.builder().seed(seed).updater(Adam(lr)).list()
    for _ in range(n_hidden):
        b = b.layer(DenseLayer(n_in=16, n_out=16, activation="tanh"))
    conf = (b.layer(OutputLayer(n_in=16, n_out=4, activation="softmax",
                                loss="mcxent"))
            .set_input_type(InputType.feed_forward(16)).build())
    return MultiLayerNetwork(conf).init()


def toy_data(n=320, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 16)).astype(np.float32)
    w = rng.standard_normal((16, 4))
    y = np.eye(4, dtype=np.float32)[np.argmax(x @ w, axis=1)]
    return x, y


# ---------------------------------------------------------------- unit level
class TestEncodeDecode:
    def test_error_feedback_identity(self):
        """enc*τ + residual == grad + old residual, exactly: nothing is
        ever lost to the compression."""
        rng = np.random.default_rng(3)
        acc = rng.standard_normal((64,)).astype(np.float32) * 0.01
        tau = jnp.float32(0.005)
        enc, res, sent = gs.encode_leaf(jnp.asarray(acc), tau, jnp.int8)
        rebuilt = (np.asarray(enc).astype(np.float32) * np.float32(0.005)
                   + np.asarray(res))
        np.testing.assert_allclose(rebuilt, acc, rtol=0, atol=1e-8)
        assert np.asarray(enc).dtype == np.int8
        assert set(np.unique(np.asarray(enc))) <= {-1, 0, 1}
        assert float(sent) == float(np.sum(np.abs(acc) >= 0.005))

    def test_wire_dtype(self):
        assert gs.wire_dtype(8) == jnp.int8
        assert gs.wire_dtype(127) == jnp.int8
        assert gs.wire_dtype(128) == jnp.int16
        with pytest.raises(ValueError, match="32767"):
            gs.wire_dtype(40000)

    def test_adapt_threshold_band(self):
        cfg = gs.ThresholdConfig()
        tau = jnp.float32(1e-3)
        # above the band: boost (send less)
        up = gs.adapt_threshold(tau, jnp.float32(0.5), cfg)
        assert float(up) == pytest.approx(1e-3 * cfg.boost)
        # below the band: decay (send more)
        down = gs.adapt_threshold(tau, jnp.float32(1e-5), cfg)
        assert float(down) == pytest.approx(1e-3 * cfg.decay)
        # inside: unchanged
        mid = gs.adapt_threshold(tau, jnp.float32(0.05), cfg)
        assert float(mid) == pytest.approx(1e-3)
        # clamp
        lo = gs.adapt_threshold(jnp.float32(1e-8), jnp.float32(0.0), cfg)
        assert float(lo) >= float(np.float32(cfg.min_threshold))

    def test_config_validation(self):
        with pytest.raises(ValueError, match="band"):
            gs.ThresholdConfig(sparsity_target_min=0.5,
                               sparsity_target_max=0.1)
        with pytest.raises(ValueError, match="decay"):
            gs.ThresholdConfig(decay=1.5)
        with pytest.raises(ValueError, match="min_threshold"):
            gs.ThresholdConfig(initial_threshold=2.0)


class TestModeResolution:
    def test_precedence(self, monkeypatch):
        conf = deep_mlp(2).conf
        assert gs.resolve_mode(None, conf) == "dense"
        conf.gradient_sharing = "threshold"
        assert gs.resolve_mode(None, conf) == "threshold"
        assert gs.resolve_mode("dense", conf) == "dense"
        monkeypatch.setenv("DL4J_GRADIENT_SHARING", "threshold")
        assert gs.resolve_mode("dense", conf) == "threshold"
        monkeypatch.setenv("DL4J_GRADIENT_SHARING", "0")
        assert gs.resolve_mode("threshold", conf) == "dense"
        monkeypatch.setenv("DL4J_GRADIENT_SHARING", "bogus")
        with pytest.raises(ValueError, match="DL4J_GRADIENT_SHARING"):
            gs.resolve_mode(None, conf)

    def test_env_override_reaches_trainer(self, monkeypatch):
        monkeypatch.setenv("DL4J_GRADIENT_SHARING", "dense")
        t = ParallelTrainer(deep_mlp(2), device_mesh(), mode="sync",
                            gradient_sharing="threshold")
        assert t.gradient_sharing == "dense"

    def test_threshold_rejects_averaging_mode(self):
        with pytest.raises(ValueError, match="sync"):
            ParallelTrainer(deep_mlp(2), device_mesh(), mode="averaging",
                            gradient_sharing="threshold")

    def test_env_toggle_degrades_gracefully_for_averaging(self, monkeypatch):
        """The global DL4J_GRADIENT_SHARING=threshold A/B toggle must
        not crash unrelated averaging-mode trainers (it falls back to
        dense there); only an EXPLICIT arg/conf request hard-errors."""
        monkeypatch.setenv("DL4J_GRADIENT_SHARING", "threshold")
        t = ParallelTrainer(deep_mlp(2), device_mesh(), mode="averaging")
        assert t.gradient_sharing == "dense"
        with pytest.raises(ValueError, match="sync"):
            ParallelTrainer(deep_mlp(2), device_mesh(), mode="averaging",
                            gradient_sharing="threshold")

    def test_mlc_serde_round_trip(self):
        conf = (NeuralNetConfiguration.builder().seed(1).list()
                .layer(DenseLayer(n_in=4, n_out=8))
                .layer(OutputLayer(n_in=8, n_out=3))
                .gradient_sharing("threshold", threshold=5e-4)
                .build())
        assert conf.gradient_sharing == "threshold"
        assert conf.gradient_sharing_threshold == 5e-4
        back = type(conf).from_json(conf.to_json())
        assert back.gradient_sharing == "threshold"
        assert back.gradient_sharing_threshold == 5e-4
        # trainer picks the conf flag + τ0 up
        net = MultiLayerNetwork(back).init()
        t = ParallelTrainer(net, device_mesh(), mode="sync")
        assert t.gradient_sharing == "threshold"
        assert t.threshold_config.initial_threshold == 5e-4

    def test_graph_serde_round_trip(self):
        conf = (ComputationGraphConfiguration.graph_builder()
                .add_inputs("in")
                .add_layer("d", DenseLayer(n_in=4, n_out=8), "in")
                .add_layer("out", OutputLayer(n_in=8, n_out=3), "d")
                .set_outputs("out")
                .gradient_sharing("threshold", threshold=2e-3)
                .build())
        back = ComputationGraphConfiguration.from_json(conf.to_json())
        assert back.gradient_sharing == "threshold"
        assert back.gradient_sharing_threshold == 2e-3
        with pytest.raises(ValueError, match="dense|threshold"):
            (ComputationGraphConfiguration.graph_builder()
             .gradient_sharing("sparse"))


# --------------------------------------------------------- convergence parity
class TestConvergenceParity:
    def test_deep_mlp_threshold_tracks_dense(self):
        """Deep MLP (one packed stacked:: run), 50 sync steps: threshold
        with error feedback must learn and stay within tolerance of the
        dense trajectory; the per-replica residual must survive the
        pack/unpack boundary with per-LAYER keys."""
        x, y = toy_data()
        ds = DataSet(x, y)
        init = float(deep_mlp().score(ds))

        dense = deep_mlp()
        ParallelTrainer(dense, device_mesh(), mode="sync").fit(
            x, y, epochs=5, batch_size=32)
        thr = deep_mlp()
        t = ParallelTrainer(thr, device_mesh(), mode="sync",
                            gradient_sharing="threshold")
        t.fit(x, y, epochs=5, batch_size=32)

        d, th = float(dense.score(ds)), float(thr.score(ds))
        assert d < 0.5 * init, f"dense failed to learn {init}->{d}"
        assert th < 0.5 * init, f"threshold failed to learn {init}->{th}"
        assert abs(th - d) <= 0.35 * init, (init, d, th)

        # residual: per-layer keys (stacked:: packing never leaks out),
        # per-replica leading axis, and nonzero — error feedback active
        res = t.threshold_residual()
        assert set(res.keys()) == set(thr.params.keys())
        assert not any(k.startswith("stacked::") for k in res)
        lead = res["0"]["W"].shape
        assert lead == (t.n_workers,) + thr.params["0"]["W"].shape
        assert any(float(np.abs(l).max()) > 0
                   for l in jax.tree_util.tree_leaves(res))
        # τ adapted away from its initial value — per-bucket tree on
        # the (default) bucketed path, per-layer keys like the residual
        assert isinstance(t._thr_tau, dict)
        assert set(t._thr_tau.keys()) == set(thr.params.keys())
        assert gs.tau_scalar(t._thr_tau) != pytest.approx(
            t.threshold_config.initial_threshold)

    def test_fused_multi_step_bit_identical(self):
        """steps_per_execution>1 (residual + τ riding the scan carry)
        must reproduce the per-step trajectory exactly — same numeric
        contract the dense fused path keeps."""
        x, y = toy_data(n=256, seed=1)

        def run(spe):
            net = deep_mlp(4)
            listener = CollectScoresListener()
            net.set_listeners(listener)
            t = ParallelTrainer(net, device_mesh(), mode="sync",
                                gradient_sharing="threshold")
            t.fit(x, y, epochs=3, batch_size=32, steps_per_execution=spe)
            return ([s for _, s in listener.scores],
                    {k: float(np.asarray(v))
                     for k, v in t._thr_tau.items()})

        per_step, tau1 = run(1)
        fused, tau4 = run(4)
        assert len(per_step) == len(fused) == 24
        np.testing.assert_allclose(per_step, fused, rtol=0, atol=0)
        assert tau1 == tau4

    def test_transformer_lm_threshold_tracks_dense(self):
        """TransformerLM with scan_layers on + fused multi-step: the
        threshold exchange must hold convergence parity through the
        scan-compiled, boundary-packed program."""
        from deeplearning4j_tpu.zoo.transformer import TransformerLM
        B, T, V = 16, 16, 37
        rng = np.random.default_rng(5)
        ids = rng.integers(0, V, (B * 4, T + 1))
        x = ids[:, :-1].astype(np.float32)
        y = np.eye(V, dtype=np.float32)[ids[:, 1:]]

        def build():
            lm = TransformerLM(vocab_size=V, d_model=32, n_layers=3,
                               n_heads=2, max_len=T)
            conf = lm.conf()
            assert conf.scan_layers
            net = MultiLayerNetwork(conf).init(11)
            return net

        def run(mode):
            net = build()
            listener = CollectScoresListener()
            net.set_listeners(listener)
            ParallelTrainer(net, device_mesh(), mode="sync",
                            gradient_sharing=mode).fit(
                x, y, epochs=6, batch_size=B, steps_per_execution=4)
            return [s for _, s in listener.scores]

        dense = run("dense")
        thr = run("threshold")
        assert len(dense) == len(thr) == 24
        assert dense[-1] < dense[0]
        assert thr[-1] < thr[0], f"threshold LM failed to learn: {thr}"
        # parity band: same scale of progress from the same start
        assert abs(thr[-1] - dense[-1]) <= 0.35 * dense[0], (dense, thr)

    def test_sharded_dp_tp_threshold(self):
        """DP x TP (auto model axis): the compressed data-axis exchange
        composes with GSPMD tensor parallelism."""
        x, y = toy_data(n=256, seed=2)
        ds = DataSet(x, y)
        mesh = make_mesh(MeshSpec.of(data=4, model=2))
        init = float(deep_mlp(3).score(ds))

        thr = deep_mlp(3)
        t = ShardedParallelTrainer(thr, mesh, gradient_sharing="threshold")
        t.fit(x, y, epochs=6, batch_size=32)
        th = float(thr.score(ds))
        assert th < 0.6 * init, f"TP threshold failed to learn {init}->{th}"
        assert t._thr_residual_r is not None
        assert gs.tau_scalar(t._thr_tau) > 0


# ------------------------------------------------ bucketed (overlapped) exchange
def wide_mlp(seed=7, lr=0.01):
    """MLP wide enough that the default rs plan actually shards (the
    128-wide W leaves divide by the 8-way data axis and clear
    min_shard_elems) and deep enough to pack a stacked:: run."""
    b = NeuralNetConfiguration.builder().seed(seed).updater(Adam(lr)).list()
    b = b.layer(DenseLayer(n_in=16, n_out=128, activation="tanh"))
    for _ in range(2):
        b = b.layer(DenseLayer(n_in=128, n_out=128, activation="tanh"))
    conf = (b.layer(OutputLayer(n_in=128, n_out=4, activation="softmax",
                                loss="mcxent"))
            .set_input_type(InputType.feed_forward(16)).build())
    return MultiLayerNetwork(conf).init()


def params_bitwise(a, b) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(p), np.asarray(q))
        for p, q in zip(la, lb))


class TestBucketedExchange:
    def test_bucketed_resolution(self, monkeypatch):
        """env > arg > default(True), mirroring DL4J_SCAN_LAYERS."""
        assert gs.resolve_bucketed() is True
        assert gs.resolve_bucketed(False) is False
        monkeypatch.setenv("DL4J_BUCKETED_EXCHANGE", "0")
        assert gs.resolve_bucketed(True) is False
        monkeypatch.setenv("DL4J_BUCKETED_EXCHANGE", "1")
        assert gs.resolve_bucketed(False) is True
        # a typo'd opt-out must raise, not silently stay bucketed
        monkeypatch.setenv("DL4J_BUCKETED_EXCHANGE", "flase")
        with pytest.raises(ValueError, match="DL4J_BUCKETED_EXCHANGE"):
            gs.resolve_bucketed()
        monkeypatch.delenv("DL4J_BUCKETED_EXCHANGE")
        t = ParallelTrainer(deep_mlp(2), device_mesh(), mode="sync")
        assert t.bucketed is True

    def test_dense_bucketed_tracks_single_barrier(self):
        """Bucketed dense (per-run pmean inside backward) vs the PR-4
        single-barrier GSPMD program: same math, different association
        — loss trajectories must agree within fp tolerance on a deep
        MLP whose hidden stack packs one stacked:: run."""
        x, y = toy_data(n=256, seed=4)

        def run(bucketed, scan):
            net = deep_mlp(4)
            net.conf.scan_layers = scan
            listener = CollectScoresListener()
            net.set_listeners(listener)
            ParallelTrainer(net, device_mesh(), mode="sync",
                            bucketed=bucketed).fit(
                x, y, epochs=3, batch_size=32)
            return np.asarray([s for _, s in listener.scores])

        for scan in (True, False):
            mono = run(False, scan)
            bkt = run(True, scan)
            assert len(mono) == len(bkt) == 24
            np.testing.assert_allclose(bkt, mono, rtol=0, atol=5e-5,
                                       err_msg=f"scan_layers={scan}")

    def test_threshold_bucketed_tracks_single_barrier(self):
        """Bucketed threshold (per-bucket residual/τ inside backward)
        vs the PR-4 single-barrier program: per-bucket τ adapts
        independently, so trajectories agree within the error-feedback
        band, and both learn."""
        x, y = toy_data(n=256, seed=5)
        ds = DataSet(x, y)
        init = float(deep_mlp().score(ds))

        def run(bucketed):
            net = deep_mlp()
            ParallelTrainer(net, device_mesh(), mode="sync",
                            gradient_sharing="threshold",
                            bucketed=bucketed).fit(
                x, y, epochs=6, batch_size=32)
            return float(net.score(ds))

        mono, bkt = run(False), run(True)
        assert bkt < 0.6 * init, f"bucketed threshold failed: {init}->{bkt}"
        assert abs(bkt - mono) <= 0.35 * init, (init, mono, bkt)

    def test_transformer_bucketed_parity(self):
        """TransformerLM (scan_layers on and off): bucketed dense must
        track the single-barrier trajectory within fp tolerance through
        the scan-compiled, boundary-packed program, fused dispatch."""
        from deeplearning4j_tpu.zoo.transformer import TransformerLM
        B, T, V = 16, 16, 37
        rng = np.random.default_rng(6)
        ids = rng.integers(0, V, (B * 4, T + 1))
        x = ids[:, :-1].astype(np.float32)
        y = np.eye(V, dtype=np.float32)[ids[:, 1:]]

        def run(bucketed, scan, mode):
            lm = TransformerLM(vocab_size=V, d_model=32, n_layers=3,
                               n_heads=2, max_len=T)
            conf = lm.conf()
            conf.scan_layers = scan
            net = MultiLayerNetwork(conf).init(11)
            listener = CollectScoresListener()
            net.set_listeners(listener)
            ParallelTrainer(net, device_mesh(), mode="sync",
                            gradient_sharing=mode, bucketed=bucketed).fit(
                x, y, epochs=3, batch_size=B, steps_per_execution=4)
            return np.asarray([s for _, s in listener.scores])

        for scan in (True, False):
            mono = run(False, scan, "dense")
            bkt = run(True, scan, "dense")
            np.testing.assert_allclose(bkt, mono, rtol=0, atol=2e-4,
                                       err_msg=f"scan_layers={scan}")
        thr = run(True, True, "threshold")
        assert thr[-1] < thr[0], f"bucketed threshold LM failed: {thr}"

    def _dense_vs_rs(self, batch_size, epochs):
        mesh = make_mesh(MeshSpec.of(data=4))
        rng = np.random.default_rng(7)
        x = rng.standard_normal((128, 16)).astype(np.float32)
        w = rng.standard_normal((16, 4))
        y = np.eye(4, dtype=np.float32)[np.argmax(x @ w, axis=1)]

        def run(mode):
            net = wide_mlp()
            t = ParallelTrainer(net, mesh, mode="sync",
                                gradient_sharing=mode)
            t.fit(x, y, epochs=epochs, batch_size=batch_size)
            return net, t

        dense, _ = run("dense")
        rs_net, rs_t = run("dense_rs")
        plan = rs_t._rs_plan()
        assert any(v for lp in plan.values() for v in lp.values()), plan
        return dense, rs_net

    def test_dense_rs_first_step_bit_exact_vs_dense(self):
        """The ZeRO algebra is exact: reduce-scatter + sharded updater +
        all-gather computes the SAME sums as the all-reduce, so where
        the compiler has no rounding choice to make — the first Adam
        step, m = v = 0, where `b*0 + (1-b)*g` is one product however it
        is contracted — dense_rs matches bucketed dense BIT for bit,
        params AND updater state, on a 4-way mesh where the rs plan
        genuinely shards."""
        dense, rs_net = self._dense_vs_rs(batch_size=128, epochs=1)
        assert params_bitwise(dense.params, rs_net.params)
        assert params_bitwise(dense.updater_state, rs_net.updater_state)

    def test_dense_rs_tracks_dense_to_rounding(self):
        """From the second step on the two programs run the updater on
        different SHAPES (full leaf vs 1/4 shard), and XLA:CPU's choice
        of which product of `b*m + (1-b)*g` / `p - lr*u` to contract
        into an FMA follows the shape's vectorization — a <= 1-ulp
        difference per step in the touched leaves that
        `optimization_barrier` does not pin under jax 0.9 (PR 21:
        step 1 bit-equal, step 2 m/v still bit-equal with params one
        ulp apart in three leaves). Not a defect in the exchange, so
        the contract over 12 steps is the rounding it can promise:
        12 x (1 ulp of an O(0.3) weight + lr x a few eps) < 1e-6 on
        params (measured 1.2e-7), and the moments inside 1e-7
        (measured 7e-9 / 2e-11)."""
        dense, rs_net = self._dense_vs_rs(batch_size=32, epochs=3)
        for a, b in zip(jax.tree_util.tree_leaves(dense.params),
                        jax.tree_util.tree_leaves(rs_net.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0, atol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(dense.updater_state),
                        jax.tree_util.tree_leaves(rs_net.updater_state)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0, atol=1e-7)
        # the full per-layer updater view survives the shard round-trip
        assert rs_net.updater_state["1"]["W"]["m"].shape == (128, 128)

    def test_threshold_rs_learns_and_composes_with_fsdp_specs(self):
        """threshold_rs: int8 reduce-scatter + sharded updater. The rs
        plan built from fsdp_param_specs (the FSDP composition seam)
        must match the shape-derived default, the mode must learn, and
        per-bucket residual/τ must persist like the threshold mode's."""
        from deeplearning4j_tpu.parallel.tensor import fsdp_param_specs
        x, y = toy_data(n=256, seed=8)
        ds = DataSet(x, y)
        net = wide_mlp()
        init = float(net.score(ds))
        specs = fsdp_param_specs(net, axis_size=8)
        t = ParallelTrainer(net, device_mesh(), mode="sync",
                            gradient_sharing="threshold_rs",
                            rs_param_specs=specs)
        assert t._rs_plan() == gs.rs_shard_plan(net.params, 8)
        t.fit(x, y, epochs=6, batch_size=32)
        got = float(net.score(ds))
        assert got < 0.7 * init, f"threshold_rs failed to learn: {init}->{got}"
        assert isinstance(t._thr_tau, dict)
        res = t.threshold_residual()
        assert res["1"]["W"].shape == (8, 128, 128)  # full-size residual

    def test_rs_mode_guards(self, monkeypatch):
        """rs modes: sync-only (env toggle degrades, explicit raises),
        elementwise-GN-only, rejected under ShardedParallelTrainer,
        serde accepts the mode strings."""
        with pytest.raises(ValueError, match="sync"):
            ParallelTrainer(deep_mlp(2), device_mesh(), mode="averaging",
                            gradient_sharing="dense_rs")
        monkeypatch.setenv("DL4J_GRADIENT_SHARING", "dense_rs")
        t = ParallelTrainer(deep_mlp(2), device_mesh(), mode="averaging")
        assert t.gradient_sharing == "dense"
        monkeypatch.delenv("DL4J_GRADIENT_SHARING")
        # whole-layer gradient normalization cannot run on shards
        from deeplearning4j_tpu.nn.conf.builder import GradientNormalization
        net = deep_mlp(2)
        net.conf.gradient_normalization = \
            GradientNormalization.CLIP_L2_PER_LAYER
        net.conf.gradient_normalization_threshold = 1.0
        with pytest.raises(ValueError, match="elementwise"):
            ParallelTrainer(net, device_mesh(), mode="sync",
                            gradient_sharing="threshold_rs")
        mesh = make_mesh(MeshSpec.of(data=4, model=2))
        with pytest.raises(NotImplementedError, match="fsdp_param_specs"):
            ShardedParallelTrainer(deep_mlp(2), mesh,
                                   gradient_sharing="dense_rs")
        conf = (NeuralNetConfiguration.builder().seed(1).list()
                .layer(DenseLayer(n_in=4, n_out=8))
                .layer(OutputLayer(n_in=8, n_out=3))
                .gradient_sharing("threshold_rs", threshold=5e-4)
                .build())
        back = type(conf).from_json(conf.to_json())
        assert back.gradient_sharing == "threshold_rs"

    def test_rs_wire_bytes_and_jaxpr(self):
        """rs comm accounting: reduce-scatter + param all-gather
        payloads, visible in the traced exchange as reduce_scatter /
        all_gather collectives."""
        from benchtools.hlo_cost import collective_table
        net = wide_mlp()
        n = 8
        plan = gs.rs_shard_plan(net.params, n)
        dense_b = gs.exchange_wire_bytes(net.params, "dense")
        rs_b = gs.exchange_wire_bytes(net.params, "dense_rs", n_workers=n)
        # grads move the same fp32 bytes; the param all-gather adds the
        # sharded fraction / n on top
        shard_elems = sum(
            int(np.prod(np.shape(net.params[lk][pn])))
            for lk in plan for pn, on in plan[lk].items() if on)
        assert rs_b == pytest.approx(dense_b + 4.0 * shard_elems / n)
        trs_b = gs.exchange_wire_bytes(net.params, "threshold_rs",
                                       n_workers=n)
        assert trs_b < rs_b  # int8 wire beats fp32
        tbl = collective_table(gs.exchange_jaxpr(net.params, "dense_rs", n))
        assert tbl["by_collective"]["reduce_scatter"]["count"] > 0
        assert tbl["by_collective"]["all_gather"]["count"] > 0
        tbl = collective_table(
            gs.exchange_jaxpr(net.params, "threshold_rs", n))
        assert tbl["by_collective"]["reduce_scatter"]["count"] > 0


class TestBucketedGraphContainer:
    def _graph(self, seed=9):
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        adam = lambda: Adam(0.01)
        conf = (ComputationGraphConfiguration.graph_builder()
                .add_inputs("in")
                .add_layer("d1", DenseLayer(n_in=16, n_out=16,
                                            activation="tanh",
                                            updater=adam()), "in")
                .add_layer("d2", DenseLayer(n_in=16, n_out=16,
                                            activation="tanh",
                                            updater=adam()), "d1")
                .add_layer("out", OutputLayer(n_in=16, n_out=4,
                                              activation="softmax",
                                              loss="mcxent",
                                              updater=adam()), "d2")
                .set_outputs("out").build())
        conf.seed = seed
        return ComputationGraph(conf).init(seed)

    def test_graph_bucketed_dense_tracks_single_barrier(self):
        """Single-in/out ComputationGraph through ParallelTrainer: the
        (default) bucketed dense path must train it — regression guard
        for the graph-container crash — and track the single-barrier
        program within fp tolerance."""
        x, y = toy_data(n=128, seed=9)
        ds = DataSet(x, y)
        init = float(self._graph().score(ds))

        def run(bucketed):
            net = self._graph()
            t = ParallelTrainer(net, device_mesh(), mode="sync",
                                bucketed=bucketed)
            assert net.single_io and not t._multi_io_graph
            t.fit(x, y, epochs=4, batch_size=32)
            return float(net.score(ds))

        mono, bkt = run(False), run(True)
        assert bkt < 0.7 * init, f"graph bucketed dense failed: {init}->{bkt}"
        assert abs(bkt - mono) <= 1e-3 * max(1.0, init), (init, mono, bkt)

    def test_graph_bucketed_threshold_learns(self):
        x, y = toy_data(n=128, seed=10)
        ds = DataSet(x, y)
        net = self._graph()
        init = float(net.score(ds))
        t = ParallelTrainer(net, device_mesh(), mode="sync",
                            gradient_sharing="threshold")
        t.fit(x, y, epochs=4, batch_size=32)
        assert float(net.score(ds)) < init
        assert set(t._thr_tau.keys()) == set(net.params.keys())

    def test_multi_io_graph_falls_back_or_raises(self):
        """Multi-io graphs: dense silently keeps the GSPMD
        single-barrier program; the bucketed-only modes name the
        limitation instead of crashing mid-trace."""
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        conf = (ComputationGraphConfiguration.graph_builder()
                .add_inputs("a", "b")
                .add_layer("da", DenseLayer(n_in=8, n_out=8), "a")
                .add_layer("db", DenseLayer(n_in=8, n_out=8), "b")
                .add_layer("oa", OutputLayer(n_in=8, n_out=3), "da")
                .add_layer("ob", OutputLayer(n_in=8, n_out=3), "db")
                .set_outputs("oa", "ob").build())
        net = ComputationGraph(conf).init(3)
        t = ParallelTrainer(net, device_mesh(), mode="sync",
                            gradient_sharing="threshold")
        assert t._multi_io_graph
        with pytest.raises(NotImplementedError, match="single-"):
            t.fit(np.zeros((8, 8), np.float32),
                  np.zeros((8, 3), np.float32), epochs=1, batch_size=8)


class TestScanUnderPartialManual:
    def test_dp_tp_step_keeps_scan_over_layers(self, monkeypatch):
        """The DP x TP threshold step is a partially-manual shard_map
        (manual over data, the model axis left to GSPMD). On the
        installed JAX the partitioner handles an inner `lax.scan` there,
        so the step traces the layer run as ONE scan — there is no
        unrolled fallback and no probe deciding between them: if the
        partitioner could not, the fit below would raise."""
        from deeplearning4j_tpu.nn import scan_stack
        lengths = []
        real = jax.lax.scan

        def spy(f, init, xs=None, length=None, **kw):
            lengths.append(length if length is not None else
                           jax.tree_util.tree_leaves(xs)[0].shape[0])
            return real(f, init, xs, length=length, **kw)

        monkeypatch.setattr(jax.lax, "scan", spy)
        net = deep_mlp(3)
        runs = net._packed_runs(net.params)
        assert runs, "fixture must pack a scannable layer run"
        x, y = toy_data(n=32, seed=2)
        t = ShardedParallelTrainer(
            net, make_mesh(MeshSpec.of(data=4, model=2)),
            gradient_sharing="threshold")
        t.fit(x, y, epochs=1, batch_size=32)
        assert len(runs[0]) in lengths, (runs, lengths)
        assert scan_stack.scan_enabled(net.conf)


# ------------------------------------------------------- comm-bytes accounting
class TestCommAccounting:
    def test_exchange_jaxpr_bytes(self):
        """The traced exchange programs carry the wire contract: dense
        moves 4 bytes/element, threshold 1 byte/element (+ scalars)."""
        from benchtools.hlo_cost import collective_table
        net = deep_mlp(2)
        elems = sum(int(np.prod(np.shape(l)))
                    for l in jax.tree_util.tree_leaves(net.params))
        dense = collective_table(gs.exchange_jaxpr(net.params, "dense", 8))
        thr = collective_table(gs.exchange_jaxpr(net.params, "threshold", 8))
        assert dense["comm_bytes_per_step"] == 4 * elems
        assert thr["comm_bytes_per_step"] == elems + 4  # + sent-count psum
        assert dense["by_collective"]["all_reduce"]["count"] > 0
        ratio = dense["comm_bytes_per_step"] / thr["comm_bytes_per_step"]
        assert ratio > 3.5

    def test_wire_bytes_accounting(self):
        net = deep_mlp(2)
        elems = sum(int(np.prod(np.shape(l)))
                    for l in jax.tree_util.tree_leaves(net.params))
        assert gs.exchange_wire_bytes(net.params, "dense") == 4 * elems
        assert gs.exchange_wire_bytes(net.params, "threshold",
                                      n_workers=8) == elems + 8
        # int16 widening beyond 127 replicas
        assert gs.exchange_wire_bytes(net.params, "threshold",
                                      n_workers=200) == 2 * elems + 8

    def test_comm_bytes_block_and_gauges(self):
        """hlo_cost's program-section block + the aot_comm_bytes_*
        gauges the /metrics route serves."""
        from benchtools import hlo_cost
        from deeplearning4j_tpu import monitor
        from deeplearning4j_tpu.monitor import MetricsRegistry, xprof
        net = deep_mlp(2)
        blk = hlo_cost.comm_bytes_block(net, n_workers=8)
        assert "error" not in blk, blk
        assert blk["threshold_bytes_per_step"] < blk["dense_bytes_per_step"]
        assert blk["reduction"] >= 3.5
        reg = MetricsRegistry()
        xprof.publish_cost_report(
            {"model": "gs_test", "program": {"comm_bytes": blk}},
            registry=reg)
        expo = reg.exposition()
        assert 'aot_comm_bytes_dense{model="gs_test"}' in expo
        assert 'aot_comm_bytes_threshold{model="gs_test"}' in expo
        assert 'aot_comm_bytes_reduction{model="gs_test"}' in expo

    def test_trainer_comm_counters(self):
        """The trainers count exchanged bytes + compression ratio on the
        monitor registry (host math, both modes)."""
        from deeplearning4j_tpu import monitor
        from deeplearning4j_tpu.monitor import MetricsRegistry
        reg = monitor.enable(registry=MetricsRegistry())
        try:
            x, y = toy_data(n=64, seed=3)
            for mode in ("dense", "threshold"):
                net = deep_mlp(2)
                ParallelTrainer(net, device_mesh(), mode="sync",
                                gradient_sharing=mode).fit(
                    x, y, epochs=1, batch_size=32)
            expo = reg.exposition()
            assert 'gradient_exchange_bytes_total{mode="dense"' in expo
            assert 'gradient_exchange_bytes_total{mode="threshold"' in expo
            assert "gradient_sharing_compression_ratio" in expo
            assert "gradient_sharing_threshold" in expo
            assert "gradient_sharing_sparsity" in expo
            snap = reg.snapshot()["gradient_exchange_bytes_total"]["values"]
            by_mode = {e["labels"]["mode"]: e["value"] for e in snap}
            assert by_mode["dense"] > by_mode["threshold"] * 3.5
        finally:
            monitor.disable()

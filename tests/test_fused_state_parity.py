"""Pin the DOCUMENTED fused-vs-per-step net_state divergence
(`nn/multilayer.py` `_multi_step_fn`): the scan carry keeps a constant
pytree structure, so state keys a train forward emits that were absent
at init (MoE's functional aux-loss slot) are not carried across fused
steps, while the per-step path merges them into net_state outside jit.

If a future layer puts MEANINGFUL dynamic state in such keys, these
assertions fail loudly instead of the state being silently lost."""

import numpy as np
import pytest

from deeplearning4j_tpu.common.updaters import Adam
from deeplearning4j_tpu.common.weights import WeightInit
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import (
    MixtureOfExperts,
    OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork


def _moe_net():
    conf = (NeuralNetConfiguration.builder().seed(5).updater(Adam(1e-2))
            .weight_init(WeightInit.XAVIER)
            .list()
            .layer(MixtureOfExperts(n_experts=2, hidden_size=8, top_k=1))
            .layer(OutputLayer(n_in=6, n_out=3, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(6))
            .build())
    return MultiLayerNetwork(conf).init()


def _data(n=32):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, 6)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return x, y


def _dynamic_entries(net):
    """net_state entries (layer slots and keys) absent at a fresh init.
    MoE's aux_loss is popped by the container's loss fn, so what the
    per-step merge leaves behind is the popped-EMPTY layer slot."""
    fresh = _moe_net()
    out = []
    for lk, st in net.net_state.items():
        if lk not in fresh.net_state:
            out.append((lk, sorted(st)))
        else:
            extra = set(st) - set(fresh.net_state[lk])
            if extra:
                out.append((lk, sorted(extra)))
    return out


class TestFusedStateParity:
    def test_per_step_path_merges_dynamic_state(self):
        net = _moe_net()
        x, y = _data()
        net.fit(x, y, epochs=1, batch_size=16)  # spe=1
        # MoE threads aux_loss functionally through state; the per-step
        # path merges the popped-empty slot into net_state
        assert _dynamic_entries(net) == [("0", [])], (
            "the per-step path's dynamic-state merge changed — update "
            "_multi_step_fn's docstring and this divergence contract: "
            f"{_dynamic_entries(net)}")

    def test_fused_path_drops_dynamic_state_params_identical(self):
        x, y = _data()
        net_a = _moe_net()
        net_a.fit(x, y, epochs=1, batch_size=16, steps_per_execution=1)
        net_b = _moe_net()
        net_b.fit(x, y, epochs=1, batch_size=16, steps_per_execution=2)
        # 1. the documented divergence: fused path carries NO dynamic
        # state (scan-carry structure is fixed at init)
        assert not _dynamic_entries(net_b), (
            "fused path now carries dynamic state — the scan-carry "
            "constraint was lifted; delete this pin and the docstring")
        # 2. the divergence is OBSERVABLE only in those keys: params and
        # init-present state must be numerically identical
        for lk in net_a.params:
            for pn in net_a.params[lk]:
                np.testing.assert_allclose(
                    np.asarray(net_a.params[lk][pn]),
                    np.asarray(net_b.params[lk][pn]),
                    rtol=2e-5, atol=2e-6,
                    err_msg=f"params {lk}/{pn} diverged between per-step "
                            f"and fused execution")
        fresh = _moe_net()
        for lk, st in fresh.net_state.items():
            for sk in st:
                np.testing.assert_allclose(
                    np.asarray(net_a.net_state[lk][sk]),
                    np.asarray(net_b.net_state[lk][sk]),
                    rtol=2e-5, atol=2e-6,
                    err_msg=f"init-present state {lk}/{sk} diverged")

    def test_dynamic_state_values_are_disposable(self):
        """The contract is only safe while dynamic slots hold DISPOSABLE
        values (per-step scratch like the popped-empty aux slot). A
        layer leaving meaningful arrays in a dynamic slot would be
        silently wrong under fusion — fail here instead."""
        net = _moe_net()
        x, y = _data()
        net.fit(x, y, epochs=1, batch_size=16)
        for lk, keys in _dynamic_entries(net):
            for sk in keys:
                v = np.asarray(net.net_state[lk][sk])
                assert v.size <= 1, (
                    f"dynamic state {lk}/{sk} holds a {v.shape} array — "
                    f"too big to be disposable scratch; the fused path "
                    f"would silently drop it (see _multi_step_fn)")


# ===================================================== one step, two containers
# The fused step, the updater walk and the fit loop live once in
# nn/trainable.py; a list container and the single-chain graph of the same
# layers differ only in how they find a layer from a key and in whether a
# batch is an array or a tuple — so from the same weights they must walk the
# same trajectory, bit for bit.
import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.graph import (
    ComputationGraph,
    ComputationGraphConfiguration,
)
from deeplearning4j_tpu.nn.layers import DenseLayer
from deeplearning4j_tpu.tenancy import lora


def _chain_layers():
    dense = [DenseLayer(n_in=6 if i == 0 else 16, n_out=16,
                        activation="relu", updater=Adam(1e-2))
             for i in range(4)]
    return dense + [OutputLayer(n_in=16, n_out=3, activation="softmax",
                                loss="mcxent", updater=Adam(1e-2))]


def _list_chain():
    b = NeuralNetConfiguration.builder().seed(7).list()
    for layer in _chain_layers():
        b = b.layer(layer)
    return MultiLayerNetwork(b.build()).init()


def _graph_chain():
    g = ComputationGraphConfiguration.graph_builder().add_inputs("in")
    names = ["d0", "d1", "d2", "d3", "out"]
    for name, layer, src in zip(names, _chain_layers(), ["in"] + names):
        g.add_layer(name, layer, src)
    return ComputationGraph(g.set_outputs("out").build()).init(7), names


def _like(net, names, tree):
    """The list container's tree under the graph's node names, in fresh
    buffers (fit donates its arguments)."""
    return {names[int(k)]: jax.tree_util.tree_map(jnp.array, v)
            for k, v in tree.items()}


@pytest.mark.parametrize("spe", [1, 4])
def test_list_and_graph_walk_one_trajectory(spe):
    x, y = _data(24)
    net = _list_chain()
    graph, names = _graph_chain()
    graph.params = _like(net, names, net.params)
    graph.updater_state = _like(net, names, net.updater_state)
    # a packed run rides both steps
    assert net._packed_runs(net.params) == [["1", "2", "3"]]
    assert graph._packed_runs(graph.params) == [["d1", "d2", "d3"]]
    net.fit(x, y, epochs=1, batch_size=8, shuffle=False,
            steps_per_execution=spe)
    graph.fit(x, y, epochs=1, batch_size=8, steps_per_execution=spe)
    assert net.iteration_count == graph.iteration_count == 3
    assert net.score_value == graph.score_value
    for ours, theirs in ((net.params, graph.params),
                         (net.updater_state, graph.updater_state)):
        theirs = {str(names.index(k)): v for k, v in theirs.items()}
        assert (jax.tree_util.tree_structure(ours)
                == jax.tree_util.tree_structure(theirs))
        for a, b in zip(jax.tree_util.tree_leaves(ours),
                        jax.tree_util.tree_leaves(theirs)):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_graph_frozen_adapter_holds_every_base_leaf():
    """The frozen-base branch of `_apply_updates` serves the graph too
    (it lived in the list container's copy only): with a frozen
    `LoRAWeight` attached, one step moves the adapter and nothing else —
    wrapped weights, biases, the packed run's other members, the output
    layer."""
    graph, _ = _graph_chain()
    x, y = _data(8)
    before = {lk: {pk: np.asarray(v).tobytes() for pk, v in lv.items()}
              for lk, lv in graph.params.items()}
    adapter = lora.init_adapter(graph, rank=2, seed=5)
    assert set(adapter) == set(graph.params)      # every node's W
    lora.attach_adapter(graph, adapter, rank=2, alpha=4.0, frozen=True)
    graph.fit(x, y, epochs=1, batch_size=8)
    w = graph.params["d1"]["W"]                   # a packed run's member
    assert type(w).__name__ == "LoRAWeight"
    assert np.asarray(w.B).any(), "the adapter never moved"
    after = {lk: {pk: np.asarray(getattr(v, "base", v)).tobytes()
                  for pk, v in lv.items()}
             for lk, lv in graph.params.items()}
    assert after == before

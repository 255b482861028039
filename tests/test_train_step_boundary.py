"""What crosses the train step's boundary, and in which form
(`nn/trainable.py`: `_pack`, `_grad_update`, `_apply_updates`).

Only the COMPUTE-dtype copy of a packable run is stacked for the scan;
the float32 masters and the updater state stay per-layer leaves from the
program's arguments to its results, each read once and written once in
place. Three contracts:

(a) the traced per-step and fused-step programs hold no `concatenate`,
    `pad` or `dynamic_update_slice` that reads a master, `m` or `v`
    leaf (under a float32 policy the cast is the identity, so stacking
    the compute copy reads each master of a run once, and nothing
    reads `m` or `v`), and every donated leaf is aliased to an output;
(b) the trajectory is the unrolled path's, leaf for leaf: masters, `m`
    and `v`, at one step a program and at two;
(c) gradient normalisation sees what it saw: the elementwise mode on
    the stacked gradients, the per-layer modes on per-layer trees.
"""

import collections
import re

import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.common.updaters import Adam
from deeplearning4j_tpu.nd import dtype as dt
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.builder import GradientNormalization
from deeplearning4j_tpu.nn.graph import (
    ComputationGraph,
    ComputationGraphConfiguration,
)
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.zoo.transformer import TransformerLM

MOVES = ("concatenate", "pad", "dynamic_update_slice")
VIEWS = ("reshape", "broadcast_in_dim", "squeeze", "expand_dims")
N_BLOCKS = 4


def _lm(policy, scan=True):
    conf = TransformerLM(vocab_size=24, d_model=16, n_layers=N_BLOCKS,
                         n_heads=2, max_len=12).conf()
    conf.scan_layers = scan
    return MultiLayerNetwork(
        conf, dtype_policy=dt.policy_from_name(policy)).init(11)


def _lm_data(n=12, T=12, V=24, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, V, (n, T)).astype(np.float32)
    y = np.eye(V, dtype=np.float32)[rng.integers(0, V, (n, T))]
    return ids, y


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (tuple, list)) else (v,)):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def master_moves(closed, labels):
    """Count, by (primitive, kind of leaf), the `MOVES` equations of a
    traced step that read a float32 leaf the program was handed
    (`labels` names each input of the jaxpr "p", "m", "v" or None),
    directly or through a view, anywhere in the nest of sub-programs
    (the jitted step, the fused steps' scan)."""
    found = collections.Counter()

    def walk(jaxpr, kind):
        for eqn in jaxpr.eqns:
            ins = [None if isinstance(v, jax.extend.core.Literal) else kind.get(v)
                   for v in eqn.invars]
            name = eqn.primitive.name
            if name in MOVES:
                for v, k in zip(eqn.invars, ins):
                    if k and v.aval.dtype == jnp.float32:
                        found[(name, k)] += 1
            if name in VIEWS and ins[0]:
                kind[eqn.outvars[0]] = ins[0]
            for sub in _sub_jaxprs(eqn):
                if len(sub.invars) == len(eqn.invars):
                    walk(sub, {iv: k for iv, k in zip(sub.invars, ins) if k})

    walk(closed.jaxpr, {v: k for v, k in zip(closed.jaxpr.invars, labels)
                        if k})
    return found


def _labels(net, n_inputs):
    """"p" / "m" / "v" for each leaf of (params, updater_state), None
    for every other input of the step."""
    p = ["p"] * len(jax.tree_util.tree_leaves(net.params))
    u = [path[-1].key for path, _ in
         jax.tree_util.tree_flatten_with_path(net.updater_state)[0]]
    assert set(u) == {"m", "v"}
    return p + u + [None] * (n_inputs - len(p) - len(u))


def _traced(net, steps, monkeypatch):
    """The step `fit(steps_per_execution=steps)` dispatches, BUILT as
    the chip would build it (its arguments donated: nd/donation.py asks
    the backend when the jit is made) and traced here, where the
    blocks take XLA's attention and LayerNorm: this reads the program's
    boundary, not its blocks."""
    with monkeypatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        step = (net._make_train_step(tbptt=False) if steps == 1
                else net._make_multi_step())
    ids, y = _lm_data(4)
    if steps == 1:
        return step.trace(
            net.params, net.updater_state, net.net_state, 0, ids, y,
            jax.random.PRNGKey(0), None, None, None)
    xs, ys, rngs = net._train_step_avals(ids, y, steps)
    return step.trace(
        net.params, net.updater_state, net.net_state, 0, xs, ys, rngs)


class TestNothingButTheComputeCopyIsStacked:
    @pytest.mark.parametrize("steps", [1, 2])
    @pytest.mark.parametrize("policy", ["mixed_bf16", "float32"])
    def test_masters_and_state_are_never_moved(self, policy, steps,
                                               monkeypatch):
        net = _lm(policy)
        run = net._packed_runs(net.params)
        assert [len(r) for r in run] == [N_BLOCKS]
        traced = _traced(net, steps, monkeypatch)
        closed = traced.jaxpr
        found = master_moves(closed, _labels(net, len(closed.jaxpr.invars)))
        # a float32 net's compute copy IS its masters: each leaf of the
        # run is read once by the stack that the scan consumes (one copy
        # of p); a mixed net's stack reads the cast, never a master
        leaves = len(net.params[run[0][0]])
        want = ({("concatenate", "p"): N_BLOCKS * leaves}
                if policy == "float32" else {})
        assert dict(found) == want
        # every donated leaf of params / updater_state (and the layer
        # state) comes back in the buffer it arrived in
        text = traced.lower().as_text()
        main = text[text.index("func.func public @main("):]
        args = main[:main.index("->")]
        n_state = len(jax.tree_util.tree_leaves(
            (net.params, net.updater_state, net.net_state)))
        aliased = re.findall(r"tf\.aliasing_output = (\d+)", args)
        assert len(aliased) == len(set(aliased)) == n_state

    def test_the_reading_catches_a_packed_master(self):
        """The walk itself: stacking masters and state the way the step
        did before (pack at entry, unpack at exit) is seen, through the
        jit and through the expand_dims of `jnp.stack`."""
        from deeplearning4j_tpu.nn import scan_stack
        net = _lm("float32")
        runs = net._packed_runs(net.params)

        @jax.jit
        def boundary(params, upd):
            p = scan_stack.pack_tree(params, runs)
            u = scan_stack.pack_tree(upd, runs)
            return (scan_stack.unpack_tree(p, runs),
                    scan_stack.unpack_tree(u, runs))

        closed = jax.make_jaxpr(boundary)(net.params, net.updater_state)
        found = master_moves(closed, _labels(net, len(closed.jaxpr.invars)))
        leaves = len(net.params[runs[0][0]])
        assert found == {("concatenate", k): N_BLOCKS * leaves
                         for k in "pmv"}


# ------------------------------------------------------------ (b) parity
def _dense_chain():
    layers = [DenseLayer(n_in=6 if i == 0 else 16, n_out=16,
                         activation="tanh", updater=Adam(1e-2))
              for i in range(5)]
    return layers + [OutputLayer(n_in=16, n_out=3, activation="softmax",
                                 loss="mcxent", updater=Adam(1e-2))]


def _list_net(scan, policy="float32", gn=None, threshold=1.0):
    b = NeuralNetConfiguration.builder().seed(7)
    if gn is not None:
        b = b.gradient_normalization(gn, threshold)
    b = b.list()
    for layer in _dense_chain():
        b = b.layer(layer)
    conf = b.scan_layers(scan).build()
    return MultiLayerNetwork(
        conf, dtype_policy=dt.policy_from_name(policy)).init()


def _graph_net(scan, policy="float32"):
    g = ComputationGraphConfiguration.graph_builder().add_inputs("in")
    names = ["d0", "d1", "d2", "d3", "d4", "out"]
    for name, layer, src in zip(names, _dense_chain(), ["in"] + names):
        g.add_layer(name, layer, src)
    conf = g.set_outputs("out").scan_layers(scan).build()
    return ComputationGraph(
        conf, dtype_policy=dt.policy_from_name(policy)).init(7)


def _chain_data(n=40, seed=3):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 6)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return x, y


def _fit5(net, x, y, spe):
    kw = {} if isinstance(net, ComputationGraph) else {"shuffle": False}
    net.fit(x, y, epochs=1, batch_size=len(x) // 5,
            steps_per_execution=spe, **kw)
    assert net.iteration_count == 5
    return net.params, net.updater_state


def _assert_same_leaves(got, want, rtol, atol, frobenius=None):
    """Leaf for leaf, elementwise; with `frobenius`, by each leaf's
    relative Frobenius distance instead (bfloat16 rounds the two paths'
    gradients apart, and Adam's normalised update turns one element's
    rounding near zero into a whole step of that element). The causal
    blocks' key biases are left out: softmax is invariant to them, their
    gradient is rounding noise and Adam normalises noise to full steps
    (the benchmark's comparison leaves them out by the same rule)."""
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        assert a.dtype == b.dtype == jnp.float32      # masters and state
        if "attn_bk" in name:
            continue
        a, b = np.asarray(a), np.asarray(b)
        if frobenius is None:
            np.testing.assert_allclose(a, b, rtol=rtol, atol=atol,
                                       err_msg=name)
        else:
            gap = np.linalg.norm(a - b) / max(np.linalg.norm(b), atol)
            assert gap < frobenius, (name, gap)


class TestTrajectoryIsTheUnrolledPaths:
    @pytest.mark.parametrize("spe", [1, 2])
    @pytest.mark.parametrize("policy", ["float32", "mixed_bf16"])
    @pytest.mark.parametrize("container", ["list", "graph", "lm"])
    def test_five_steps_leaf_for_leaf(self, container, policy, spe):
        build = {"list": _list_net, "graph": _graph_net,
                 "lm": lambda scan, policy: _lm(policy, scan)}[container]
        x, y = _lm_data(30) if container == "lm" else _chain_data()
        packed, unrolled = build(True, policy), build(False, policy)
        assert packed._packed_runs(packed.params)
        # a per-layer tree at the boundary, whatever rides the program
        assert set(packed.params) == set(unrolled.params)
        got = _fit5(packed, x, y, spe)
        want = _fit5(unrolled, x, y, 1)
        # float32: the scan body and the unrolled layers are the same
        # arithmetic (fp reassociation aside) and the contract is
        # elementwise; bfloat16 rounds the two paths apart, and there a
        # leaf handed another layer's or another leaf's gradient would
        # read a distance over 1
        tol = (dict(rtol=2e-5, atol=1e-7) if policy == "float32"
               else dict(rtol=0, atol=1e-3, frobenius=0.25))
        for g, w in zip(got, want):
            _assert_same_leaves(g, w, **tol)
        assert packed.score_value == pytest.approx(
            unrolled.score_value, rel=1e-5 if policy == "float32" else 0.02)

    def test_one_step_and_two_fused_agree_bit_for_bit(self):
        """The fused steps' scan carries the same per-layer trees and
        takes the same body: at float32 its five steps are the per-step
        program's, bit for bit."""
        x, y = _chain_data()
        a = _fit5(_list_net(True), x, y, 1)
        b = _fit5(_list_net(True), x, y, 2)
        for g, w in zip(a, b):
            for la, lb in zip(jax.tree_util.tree_leaves(g),
                              jax.tree_util.tree_leaves(w)):
                np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ------------------------------------------------- (c) normalisation
class TestGradientNormalisationOnARun:
    @pytest.mark.parametrize("gn,packs", [
        (GradientNormalization.CLIP_ELEMENTWISE_ABSOLUTE_VALUE, True),
        (GradientNormalization.CLIP_L2_PER_LAYER, False),
        (GradientNormalization.RENORMALIZE_L2_PER_LAYER, False),
        (GradientNormalization.CLIP_L2_PER_PARAM_TYPE, False),
    ])
    def test_unchanged_against_the_unrolled_path(self, gn, packs):
        x, y = _chain_data()
        # a threshold the early gradients cross, so the clip does work
        packed = _list_net(True, gn=gn, threshold=0.01)
        unrolled = _list_net(False, gn=gn, threshold=0.01)
        assert bool(packed._packed_runs(packed.params)) == packs
        got = _fit5(packed, x, y, 1)
        want = _fit5(unrolled, x, y, 1)
        for g, w in zip(got, want):
            _assert_same_leaves(g, w, rtol=2e-5, atol=1e-7)
        # and the clip bit: an unnormalised run walks elsewhere
        free = _fit5(_list_net(True), x, y, 1)[0]
        assert not np.allclose(np.asarray(free["2"]["W"]),
                               np.asarray(got[0]["2"]["W"]), rtol=1e-3)


# ------------------------------------- scripts/train_step_hlo.py --moves
_HLO = """\
HloModule jit_step_fn

%fused_computation.1 (param_0: bf16[24,8,128], param_1: f32[8,128]) -> bf16[24,8,128] {
  %param_0 = bf16[24,8,128]{2,1,0:T(8,128)(2,1)} parameter(0)
  %param_1 = f32[8,128]{1,0:T(8,128)} parameter(1)
  %convert.1 = bf16[8,128]{1,0:T(8,128)(2,1)} convert(%param_1)
  %bitcast.1 = bf16[1,8,128]{2,1,0:T(8,128)(2,1)} bitcast(%convert.1)
  %constant.1 = s32[]{:T(128)} constant(3)
  %constant.2 = s32[]{:T(128)} constant(0)
  ROOT %dynamic-update-slice.1 = bf16[24,8,128]{2,1,0:T(8,128)(2,1)} dynamic-update-slice(%param_0, %bitcast.1, %constant.1, %constant.2, %constant.2)
}

%fused_computation.2 (param_0.1: f32[1000]) -> f32[1024] {
  %param_0.1 = f32[1000]{0:T(1024)} parameter(0)
  %constant.3 = f32[]{:T(128)} constant(0)
  ROOT %pad.1 = f32[1024]{0:T(1024)} pad(%param_0.1, %constant.3), padding=0_24
}

%region_1.5 (arg_tuple.1: (s32[], bf16[24,8,128])) -> (s32[], bf16[24,8,128]) {
  %arg_tuple.1 = (s32[]{:T(128)}, bf16[24,8,128]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %get-tuple-element.1 = s32[]{:T(128)} get-tuple-element(%arg_tuple.1), index=0
  %get-tuple-element.2 = bf16[24,8,128]{2,1,0:T(8,128)(2,1)} get-tuple-element(%arg_tuple.1), index=1
  %copy.7 = bf16[24,8,128]{1,2,0:T(8,128)(2,1)} copy(%get-tuple-element.2)
  %slice-start.1 = (bf16[24,8,128]{2,1,0}, bf16[2,8,128]{2,1,0:S(1)}, u32[]) slice-start(%get-tuple-element.2), slice={[0:2], [0:8], [0:128]}
  %slice-done.1 = bf16[2,8,128]{2,1,0:T(8,128)(2,1)S(1)} slice-done(%slice-start.1)
  ROOT %tuple.1 = (s32[]{:T(128)}, bf16[24,8,128]{2,1,0:T(8,128)(2,1)}) tuple(%get-tuple-element.1, %get-tuple-element.2)
}

ENTRY %main.9 (p0: f32[8,128], p1: bf16[24,8,128], p2: f32[1000]) -> (bf16[24,8,128], f32[1024]) {
  %p0 = f32[8,128]{1,0:T(8,128)} parameter(0)
  %p1 = bf16[24,8,128]{2,1,0:T(8,128)(2,1)} parameter(1)
  %p2 = f32[1000]{0:T(1024)} parameter(2)
  %bitcast_dynamic-update-slice_fusion.3 = bf16[24,8,128]{2,1,0:T(8,128)(2,1)} fusion(%p1, %p0), kind=kLoop, calls=%fused_computation.1
  %pad_bitcast_fusion.4.remat2 = f32[1024]{0:T(1024)} fusion(%p2), kind=kLoop, calls=%fused_computation.2
  %reshape.5 = f32[8,128]{1,0:T(8,128)} reshape(%pad_bitcast_fusion.4.remat2)
  %while.1 = (s32[]{:T(128)}, bf16[24,8,128]{2,1,0:T(8,128)(2,1)}) while(%tuple.0), condition=%cond.1, body=%region_1.5
  ROOT %tuple.9 = (bf16[24,8,128]{2,1,0:T(8,128)(2,1)}, f32[1024]{0:T(1024)}) tuple(%bitcast_dynamic-update-slice_fusion.3, %pad_bitcast_fusion.4.remat2)
}
"""


@pytest.fixture(scope="module")
def train_step_hlo():
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "train_step_hlo.py")
    spec = importlib.util.spec_from_file_location("train_step_hlo", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestMovesTable:
    def test_entry_and_scan_body_by_class_and_type(self, train_step_hlo):
        rows = train_step_hlo.moves_table(_HLO)
        entry = {(k, d): (n, b) for c, k, d, n, b in rows if c == "ENTRY"}
        body = {(k, d): (n, b) for c, k, d, n, b in rows
                if c == "region_1.5"}
        assert {c for c, *_ in rows} == {"ENTRY", "region_1.5"}
        # a fusion is classed by the name the compiler gave it, with its
        # numbering and `.remat` suffix dropped: the pad, not the bitcast
        assert entry[("pad", "f32")] == (1, 4096)
        assert entry[("reshape", "f32")] == (1, 4096)
        # the async copy's start half is skipped, its done half is listed
        # under its own name beside the plain copy
        assert body[("copy", "bf16")] == (1, 24 * 8 * 128 * 2)
        assert body[("slice-done", "bf16")] == (1, 2 * 8 * 128 * 2)
        assert ("slice", "bf16") not in body

    def test_in_place_update_counts_what_it_writes(self, train_step_hlo):
        rows = train_step_hlo.moves_table(_HLO)
        (n, nbytes), = [(n, b) for c, k, d, n, b in rows
                        if (c, k) == ("ENTRY", "dynamic-update-slice")]
        # one layer's slot of the stacked array, not the whole of it
        assert (n, nbytes) == (1, 8 * 128 * 2)

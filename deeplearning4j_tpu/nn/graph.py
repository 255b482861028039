"""ComputationGraph — the DAG model container.

Reference: `nn/graph/ComputationGraph.java` (3,363 LoC; topological sort
:1190, fit :863/:988, backprop :1629) +
`nn/conf/ComputationGraphConfiguration.java` (GraphBuilder :509).

Same TPU-first redesign as MultiLayerNetwork: forward is a pure
function walking the topo order; loss sums every output layer's loss;
autodiff replaces the reverse-topo epsilon bookkeeping
(`setVertexEpsilon` fan-out summation comes for free from autodiff).
Multiple inputs/outputs are supported via MultiDataSet.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.common.updaters import Sgd
from deeplearning4j_tpu.nd.dtype import DataTypePolicy
from deeplearning4j_tpu.nn.conf.builder import (
    CONFIG_FORMAT_VERSION,
    check_format_version,
    BackpropType,
    GradientNormalization,
    NeuralNetConfiguration,
    infer_preprocessor,
)
from deeplearning4j_tpu.nn.conf.graph import GraphVertex, vertex_from_dict
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn import scan_stack
from deeplearning4j_tpu.nn.layers.base import Layer, layer_from_dict
from deeplearning4j_tpu.nn.layers.feedforward import BaseOutputLayerMixin
from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrentLayer
from deeplearning4j_tpu.nn.trainable import TrainableNetwork, _convert_features
from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator, as_iterator
from deeplearning4j_tpu.datasets.multidataset import MultiDataSet
from deeplearning4j_tpu import monitor



@dataclasses.dataclass
class GraphNode:
    name: str
    kind: str  # "input" | "layer" | "vertex"
    layer: Optional[Layer] = None
    vertex: Optional[GraphVertex] = None
    inputs: List[str] = dataclasses.field(default_factory=list)
    preprocessor: Any = None  # optional InputPreProcessor before a layer


class ComputationGraphConfiguration:
    """Serializable DAG description (reference
    `ComputationGraphConfiguration`)."""

    def __init__(self):
        self.network_inputs: List[str] = []
        self.network_outputs: List[str] = []
        self.nodes: Dict[str, GraphNode] = {}
        self.input_types: Dict[str, InputType] = {}
        self.seed: int = 12345
        self.backprop_type = BackpropType.STANDARD
        self.tbptt_fwd_length = 20
        self.gradient_normalization = GradientNormalization.NONE
        self.gradient_normalization_threshold = 1.0
        self.max_norm: Optional[float] = None
        self.optimization_algo: str = "sgd"
        self.max_iterations: int = 5
        self.scan_layers: bool = True  # roll homogeneous chains into lax.scan
        # gradient exchange mode for the distributed sync trainers
        # (parallel/gradient_sharing.py; DL4J_GRADIENT_SHARING overrides)
        self.gradient_sharing: str = "dense"
        self.gradient_sharing_threshold: float = 1e-3
        # mixed-precision policy (nd/dtype.py; DL4J_DTYPE_POLICY wins)
        self.dtype_policy = None
        # in-graph diagnostics (monitor/diagnostics.py;
        # DL4J_DIAGNOSTICS wins). None = off.
        self.diagnostics = None
        self.topo_order: List[str] = []

    # ------------------------------------------------------------- builder
    @staticmethod
    def graph_builder(global_conf: Optional[NeuralNetConfiguration] = None
                      ) -> "GraphBuilder":
        return GraphBuilder(global_conf or NeuralNetConfiguration())

    # ---------------------------------------------------------------- topo
    def topological_sort(self) -> List[str]:
        """Kahn's algorithm (reference `topologicalSortOrder`
        ComputationGraph.java:1190)."""
        indeg = {n: 0 for n in self.nodes}
        dependents: Dict[str, List[str]] = {n: [] for n in self.nodes}
        for n, node in self.nodes.items():
            for src in node.inputs:
                indeg[n] += 1
                dependents[src].append(n)
        queue = [n for n in self.network_inputs]
        order, seen = [], set()
        while queue:
            n = queue.pop(0)
            if n in seen:
                continue
            seen.add(n)
            order.append(n)
            for d in dependents[n]:
                indeg[d] -= 1
                if indeg[d] == 0:
                    queue.append(d)
        if len(order) != len(self.nodes):
            missing = set(self.nodes) - set(order)
            raise ValueError(f"Graph has a cycle or disconnected nodes: {missing}")
        return order

    # ---------------------------------------------------------------- serde
    def to_dict(self):
        return {
            "format": "deeplearning4j_tpu.ComputationGraphConfiguration",
            "format_version": CONFIG_FORMAT_VERSION,
            "network_inputs": self.network_inputs,
            "network_outputs": self.network_outputs,
            "seed": self.seed,
            "backprop_type": self.backprop_type.value,
            "tbptt_fwd_length": self.tbptt_fwd_length,
            "gradient_normalization": self.gradient_normalization.value,
            "gradient_normalization_threshold": self.gradient_normalization_threshold,
            "max_norm": self.max_norm,
            "optimization_algo": self.optimization_algo,
            "max_iterations": self.max_iterations,
            "scan_layers": self.scan_layers,
            "gradient_sharing": self.gradient_sharing,
            "gradient_sharing_threshold": self.gradient_sharing_threshold,
            "dtype_policy": (None if self.dtype_policy is None
                             else self.dtype_policy.to_dict()),
            "diagnostics": (None if self.diagnostics is None
                            else monitor.diagnostics.as_diagnostics(
                                self.diagnostics).to_dict()),
            "input_types": {k: v.to_dict() for k, v in self.input_types.items()},
            "nodes": [
                {
                    "name": n.name,
                    "kind": n.kind,
                    "inputs": n.inputs,
                    "layer": n.layer.to_dict() if n.layer is not None else None,
                    "vertex": n.vertex.to_dict() if n.vertex is not None else None,
                    "preprocessor": n.preprocessor.to_dict() if n.preprocessor is not None else None,
                }
                for n in self.nodes.values()
            ],
            "topo_order": self.topo_order,
        }

    def to_json(self, **kw):
        return json.dumps(self.to_dict(), **kw)

    @staticmethod
    def from_dict(d: dict) -> "ComputationGraphConfiguration":
        from deeplearning4j_tpu.nn.conf.preprocessors import preprocessor_from_dict
        check_format_version(d, "ComputationGraphConfiguration")
        conf = ComputationGraphConfiguration()
        conf.network_inputs = list(d["network_inputs"])
        conf.network_outputs = list(d["network_outputs"])
        conf.seed = d.get("seed", 12345)
        conf.backprop_type = BackpropType(d.get("backprop_type", "standard"))
        conf.tbptt_fwd_length = d.get("tbptt_fwd_length", 20)
        conf.gradient_normalization = GradientNormalization(
            d.get("gradient_normalization", "none"))
        conf.gradient_normalization_threshold = d.get("gradient_normalization_threshold", 1.0)
        conf.max_norm = d.get("max_norm")
        conf.optimization_algo = d.get("optimization_algo", "sgd")
        conf.max_iterations = d.get("max_iterations", 5)
        conf.scan_layers = d.get("scan_layers", True)
        conf.gradient_sharing = d.get("gradient_sharing", "dense")
        conf.gradient_sharing_threshold = d.get("gradient_sharing_threshold",
                                                1e-3)
        if d.get("dtype_policy") is not None:
            from deeplearning4j_tpu.nd.dtype import as_policy
            conf.dtype_policy = as_policy(d["dtype_policy"])
        if d.get("diagnostics") is not None:
            conf.diagnostics = monitor.diagnostics.as_diagnostics(
                d["diagnostics"])
        conf.input_types = {k: InputType.from_dict(v)
                            for k, v in d.get("input_types", {}).items()}
        for nd in d["nodes"]:
            conf.nodes[nd["name"]] = GraphNode(
                name=nd["name"], kind=nd["kind"], inputs=list(nd["inputs"]),
                layer=layer_from_dict(nd["layer"]) if nd.get("layer") else None,
                vertex=vertex_from_dict(nd["vertex"]) if nd.get("vertex") else None,
                preprocessor=preprocessor_from_dict(nd["preprocessor"])
                if nd.get("preprocessor") else None,
            )
        conf.topo_order = list(d.get("topo_order") or conf.topological_sort())
        return conf

    @staticmethod
    def from_json(s: str):
        return ComputationGraphConfiguration.from_dict(json.loads(s))


class GraphBuilder:
    """Fluent DAG builder (reference
    `ComputationGraphConfiguration.GraphBuilder`)."""

    def __init__(self, global_conf: NeuralNetConfiguration):
        self._g = global_conf
        self._conf = ComputationGraphConfiguration()

    def add_inputs(self, *names: str) -> "GraphBuilder":
        for n in names:
            self._conf.network_inputs.append(n)
            self._conf.nodes[n] = GraphNode(name=n, kind="input")
        return self

    def set_input_types(self, *types: InputType) -> "GraphBuilder":
        for name, t in zip(self._conf.network_inputs, types):
            self._conf.input_types[name] = t
        return self

    def add_layer(self, name: str, layer: Layer, *inputs: str) -> "GraphBuilder":
        layer = layer.clone()
        self._g.apply_global_defaults(layer)
        self._conf.nodes[name] = GraphNode(name=name, kind="layer", layer=layer,
                                           inputs=list(inputs))
        return self

    def add_vertex(self, name: str, vertex: GraphVertex, *inputs: str) -> "GraphBuilder":
        self._conf.nodes[name] = GraphNode(name=name, kind="vertex", vertex=vertex,
                                           inputs=list(inputs))
        return self

    def set_outputs(self, *names: str) -> "GraphBuilder":
        self._conf.network_outputs = list(names)
        return self

    def backprop_type(self, bptype, fwd_length: int = 20) -> "GraphBuilder":
        self._conf.backprop_type = BackpropType(bptype)
        self._conf.tbptt_fwd_length = fwd_length
        return self

    def scan_layers(self, flag: bool) -> "GraphBuilder":
        """Enable/disable scan-over-layers compilation of homogeneous
        layer chains (default on; see nn/scan_stack.py)."""
        self._conf.scan_layers = bool(flag)
        return self

    def gradient_sharing(self, mode: str, threshold=None) -> "GraphBuilder":
        """Gradient exchange mode for the distributed sync trainers:
        "dense" (default), "threshold" (error-feedback compressed
        collectives), or "dense_rs"/"threshold_rs" (ZeRO-style sharded
        updater — parallel/gradient_sharing.py)."""
        if mode not in ("dense", "threshold", "dense_rs", "threshold_rs"):
            raise ValueError(
                f"gradient_sharing must be dense|threshold|dense_rs|"
                f"threshold_rs, got {mode!r}")
        self._conf.gradient_sharing = mode
        if threshold is not None:
            self._conf.gradient_sharing_threshold = float(threshold)
        return self

    def dtype_policy(self, policy) -> "GraphBuilder":
        """Mixed-precision policy for this graph (nd/dtype.py): a
        DataTypePolicy or preset name ("mixed_bf16" / "float32");
        `DL4J_DTYPE_POLICY` env wins."""
        from deeplearning4j_tpu.nd.dtype import as_policy
        self._conf.dtype_policy = as_policy(policy)
        return self

    def diagnostics(self, spec) -> "GraphBuilder":
        """In-graph model-internals diagnostics for this graph
        (monitor/diagnostics.py): True/"on", a watchdog policy name
        ("warn"/"skip"/"halt"), a DiagnosticsConfig, or None/False for
        off. `DL4J_DIAGNOSTICS` env wins."""
        self._conf.diagnostics = monitor.diagnostics.as_diagnostics(spec)
        return self

    def build(self) -> ComputationGraphConfiguration:
        conf = self._conf
        conf.seed = self._g.seed_value
        conf.gradient_normalization = self._g.gradient_normalization_value
        conf.gradient_normalization_threshold = self._g.gradient_normalization_threshold_value
        conf.max_norm = self._g.max_norm_value
        conf.optimization_algo = self._g.optimization_algo_value
        conf.max_iterations = self._g.max_iterations_value
        if conf.dtype_policy is None:
            conf.dtype_policy = getattr(self._g, "dtype_policy_value", None)
        if conf.diagnostics is None:
            conf.diagnostics = getattr(self._g, "diagnostics_value", None)
        conf.topo_order = conf.topological_sort()
        # shape inference + automatic preprocessors (reference
        # GraphBuilder.build → addPreProcessors)
        if conf.input_types:
            types: Dict[str, InputType] = dict(conf.input_types)
            for name in conf.topo_order:
                node = conf.nodes[name]
                if node.kind == "input":
                    continue
                in_types = [types[i] for i in node.inputs if i in types]
                if len(in_types) != len(node.inputs):
                    continue  # un-inferable path; layer must have explicit n_in
                if node.kind == "layer":
                    it = in_types[0]
                    if node.preprocessor is None:
                        auto = infer_preprocessor(it, node.layer)
                        if auto is not None:
                            node.preprocessor = auto
                    if node.preprocessor is not None:
                        it = node.preprocessor.get_output_type(it)
                    node.layer.set_n_in(it, override=getattr(node.layer, "n_in", 0) in (0, None))
                    types[name] = node.layer.get_output_type(it)
                else:
                    types[name] = node.vertex.get_output_type(in_types)
        return conf


class ComputationGraph(TrainableNetwork):
    def __init__(self, conf: ComputationGraphConfiguration,
                 dtype_policy: DataTypePolicy = None, diagnostics=None):
        super().__init__(conf, dtype_policy, diagnostics)
        # scan-over-layers chain plan (nn/scan_stack.py), built lazily
        # from traced shapes: {head: [members]}, skip set, fold indices
        self._chain_plan = None
        self.output_layer_names = [
            n for n in conf.network_outputs
            if conf.nodes[n].kind == "layer"
            and isinstance(conf.nodes[n].layer, BaseOutputLayerMixin)
        ]

    # ------------------------------------------------------------------ init
    def _init_trees(self, seed: int):
        """Pure init: build (params, net_state, updater_state) without
        touching self — also usable under `jax.eval_shape`."""
        root = jax.random.PRNGKey(seed)
        pdt = self.dtype.param_dtype
        params, state, upd = {}, {}, {}
        for idx, name in enumerate(self.conf.topo_order):
            node = self.conf.nodes[name]
            if node.kind != "layer":
                continue
            key = jax.random.fold_in(root, idx)
            p = node.layer.init_params(key, pdt)
            s = node.layer.init_state(pdt)
            if p:
                params[name] = p
                updater = node.layer.updater or Sgd(1e-3)
                upd[name] = {k: updater.init_state(a) for k, a in p.items()}
            if s:
                state[name] = s
        return params, state, upd

    # ------------------------------------------------ the shared code's answers
    def _layer(self, lk: str):
        return self.conf.nodes[lk].layer

    def _keyed_layers(self):
        return [(n, node.layer) for n, node in self.conf.nodes.items()
                if node.kind == "layer"]

    def _scan_runs(self, params):
        return list(self._chains(params)[0].values())

    def _as_io(self, v):
        return tuple(v) if isinstance(v, (list, tuple)) else (v,)

    def _step_batch(self, ds, data_format=None):
        """One tuple entry per graph input / output."""
        def arrays(vals, n=None):
            vals = vals if vals is not None else [None] * n
            return tuple(None if v is None else jnp.asarray(v) for v in vals)

        if isinstance(ds, MultiDataSet):
            xs, ys = arrays(ds.features), arrays(ds.labels)
            fmasks = arrays(ds.features_masks, len(xs))
            lmasks = arrays(ds.labels_masks, len(ys))
        else:
            xs, ys = arrays([ds.features]), arrays([ds.labels])
            fmasks = arrays([ds.features_mask])
            lmasks = arrays([ds.labels_mask])
        xs = tuple(_convert_features(x, data_format) for x in xs)
        return xs, ys, fmasks, lmasks, ds.num_examples()

    def _predict(self, ds, data_format=None):
        masks = (None if ds.features_mask is None
                 else [jnp.asarray(ds.features_mask)])
        return self.output(_convert_features(ds.features, data_format),
                           masks=masks)

    # --------------------------------------------------------------- forward
    def _input_feeds_ids(self, input_name: str) -> bool:
        """True when some embedding layer (possibly frozen-wrapped)
        consumes this network input directly — its activations are
        token ids, not features. Ids routed through intermediate
        vertices should be carried as INT arrays (non-floating inputs
        are never cast; docs/PRECISION.md)."""
        if getattr(self, "_ids_inputs_cache", None) is None:
            self._ids_inputs_cache = {
                inp: any(scan_stack.consumes_token_ids(n.layer)
                         for n in self.conf.nodes.values()
                         if n.layer is not None and inp in n.inputs)
                for inp in self.conf.network_inputs}
        return self._ids_inputs_cache.get(input_name, False)

    def _chains(self, params):
        """Scan-over-layers chain plan: maximal single-consumer chains
        of structurally identical layer nodes (nn/scan_stack.py).
        Cached — node structure and param shapes are fixed per model.
        Returns ({head: [members]}, skip_set, {name: topo_index})."""
        if self._chain_plan is None:
            chains, members = scan_stack.build_graph_plan(
                self.conf, params, self.output_layer_names)
            topo_index = {n: i for i, n in enumerate(self.conf.topo_order)}
            self._chain_plan = (chains, members, topo_index)
        return self._chain_plan

    def _forward_all(self, params, state, inputs: Sequence, *, train, rng,
                     masks: Optional[Sequence] = None, stop_at_loss: bool = False,
                     carries: Optional[Dict] = None, unrolled: bool = False,
                     stats_out=None):
        """Walk topo order. Returns (activations dict, preout dict,
        new_state, mask dict). When `carries` is given (a dict keyed by
        node name), recurrent layers run `forward_with_carry` and the
        updated carries are written back into it (TBPTT / rnn_time_step
        state threading, reference ComputationGraph rnnTimeStep /
        rnnActivateUsingStoredState).

        Maximal single-consumer chains of structurally identical layer
        nodes execute as ONE `lax.scan` over stacked params — interior
        chain activations are not materialized, so callers that need
        every node's activation (feed_forward) pass `unrolled=True`."""
        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        masks = list(masks) if masks else [None] * len(inputs)
        # mixed precision: param leaves compute in compute_dtype
        # (identity for the fp32 policy / an already-cast tree — the
        # train step casts OUTSIDE value_and_grad so grads are bf16)
        params = self.dtype.cast_params(params)
        acts: Dict[str, jnp.ndarray] = {}
        mask_map: Dict[str, Any] = {}
        preouts: Dict[str, jnp.ndarray] = {}
        new_state: Dict[str, Dict] = {}
        for i, name in enumerate(self.conf.network_inputs):
            x = jnp.asarray(inputs[i])
            if not self._input_feeds_ids(name):
                # token-id inputs pass uncast: a bf16 round corrupts
                # ids above 256 (embedding gathers float-carried ids)
                x = self.dtype.cast_compute(x)
            acts[name] = x
            mask_map[name] = masks[i] if i < len(masks) else None
        use_scan = (carries is None and not unrolled
                    and scan_stack.scan_enabled(self.conf))
        chains, chain_skip, topo_index = (
            self._chains(params) if use_scan else ({}, set(), {}))
        chain_skip = set(chain_skip)
        for li, name in enumerate(self.conf.topo_order):
            node = self.conf.nodes[name]
            if node.kind == "input":
                continue
            if name in chain_skip:
                continue  # interior chain member — covered by its head
            if use_scan and name in chains:
                members = chains[name]
                template = node.layer
                h = acts[node.inputs[0]]
                mask = mask_map.get(node.inputs[0])
                packed = params.get(scan_stack.run_key(members))
                if scan_stack.mask_invariant(template, mask):
                    if packed is None:
                        packed = scan_stack.stack_params(
                            [params[m] for m in members])
                    if stats_out is not None:
                        h, run_stats = scan_stack.scan_forward(
                            template, packed, h, train=train, rng=rng,
                            fold_ids=[topo_index[m] for m in members],
                            mask=mask, collect_stats=True)
                        stats_out[scan_stack.run_key(members)] = run_stats
                    else:
                        h = scan_stack.scan_forward(
                            template, packed, h, train=train, rng=rng,
                            fold_ids=[topo_index[m] for m in members],
                            mask=mask)
                    tail = members[-1]
                    acts[tail] = h
                    mask_map[tail] = mask
                    continue
                # mask transforms per layer — replay the chain unrolled
                # (the per-node body below handles the head; unskip the
                # interior members so the walk reaches them too)
                if packed is not None:
                    params = {**params,
                              **dict(zip(members, scan_stack.unstack_entry(
                                  packed, len(members))))}
                chain_skip -= set(members[1:])
            in_acts = [acts[s] for s in node.inputs]
            in_masks = [mask_map.get(s) for s in node.inputs]
            if node.kind == "vertex":
                acts[name] = node.vertex.forward(in_acts, masks=in_masks, train=train)
                mask_map[name] = node.vertex.forward_mask(in_masks)
                continue
            layer = node.layer
            h = in_acts[0]
            mask = in_masks[0]
            if node.preprocessor is not None:
                h = node.preprocessor.pre_process(h, mask)
                mask = node.preprocessor.process_mask(mask)
            lrng = None if rng is None else jax.random.fold_in(rng, li)
            is_output = name in self.output_layer_names
            if is_output and stop_at_loss:
                preouts[name] = (h, mask, lrng)
                continue
            lparams = layer.apply_weight_noise(
                params.get(name, {}), train,
                None if lrng is None else jax.random.fold_in(lrng, 0x5EED))
            if carries is not None and isinstance(layer, BaseRecurrentLayer):
                carry_in = carries.get(name)
                if carry_in is None:
                    carry_in = layer.init_carry(h.shape[0], h.dtype)
                h, st, carry_out = scan_stack.layer_forward_with_carry(
                    layer, lparams, state.get(name, {}), h, carry_in,
                    train=train, rng=lrng, mask=mask)
                carries[name] = carry_out
            else:
                h, st = scan_stack.layer_forward(
                    layer, lparams, state.get(name, {}), h,
                    train=train, rng=lrng, mask=mask)
            if st:
                new_state[name] = st
            acts[name] = h
            if stats_out is not None:
                from deeplearning4j_tpu.monitor.diagnostics import (
                    activation_stats)
                stats_out[name] = activation_stats(h)
            mask_map[name] = layer.forward_mask(mask, None)
        return acts, preouts, new_state, mask_map

    def _loss_fn(self, params, state, inputs, labels, rng, fmasks, lmasks, *,
                 train, carries=None, act_stats=False):
        if not isinstance(labels, (list, tuple)):
            labels = [labels]
        lmasks = list(lmasks) if lmasks else [None] * len(labels)
        out_carries = None if carries is None else dict(carries)
        stats_out = {} if act_stats else None
        acts, preouts, new_state, _ = self._forward_all(
            params, state, inputs, train=train, rng=rng, masks=fmasks,
            stop_at_loss=True, carries=out_carries, stats_out=stats_out)
        total = 0.0
        for oi, name in enumerate(self.output_layer_names):
            layer = self.conf.nodes[name].layer
            h, mask, lrng = preouts[name]
            # losses / softmax statistics stay fp32 under a mixed
            # policy (activations, labels and output-layer params all
            # upcast to output_dtype)
            h = self.dtype.cast_output(h)
            y = self.dtype.cast_output(jnp.asarray(labels[oi]))
            lparams = self.dtype.cast_output_params(
                self.dtype.cast_params(params.get(name, {})))
            lmask = lmasks[oi] if lmasks[oi] is not None else mask
            lparams = layer.apply_weight_noise(
                lparams, train,
                None if lrng is None else jax.random.fold_in(lrng, 0x5EED))
            total = total + layer.compute_loss(lparams, state.get(name, {}),
                                               h, y, train=train, rng=lrng, mask=lmask)
        for name, node in self.conf.nodes.items():
            if node.kind == "layer" and name in params:
                total = total + node.layer.regularization_score(params[name])
        for k, p in params.items():
            if scan_stack.is_run_key(k):
                # stacked run entry: the template's l1/l2 sums over the
                # stacked array — identical to summing per layer
                template = self.conf.nodes[scan_stack.run_members(k)[0]].layer
                total = total + template.regularization_score(p)
        # auxiliary losses threaded through layer state (e.g. MoE load
        # balance) — consumed here, not persisted across steps
        for st in new_state.values():
            if "aux_loss" in st:
                total = total + st.pop("aux_loss")
        total = self.dtype.cast_output(total)
        if act_stats:
            return total, (new_state, out_carries, stats_out)
        return total, (new_state, out_carries)

    # ------------------------------------------------------------------- fit
    def fit(self, data, labels=None, *, epochs: int = 1, batch_size: int = 32,
            steps_per_execution: int = 1):
        """Train. `data`: DataSetIterator / DataSet / MultiDataSet /
        (features, labels) arrays; the loop is `TrainableNetwork._fit`."""
        iterator = (ListDataSetIterator([data]) if isinstance(data, MultiDataSet)
                    else as_iterator(data, labels, batch_size=batch_size))
        return self._fit(iterator, epochs=epochs,
                         steps_per_execution=steps_per_execution)

    # ------------------------------------------------------ rnn streaming
    def rnn_time_step(self, *inputs, masks=None):
        """Streaming inference carrying RNN state across calls
        (reference `ComputationGraph.rnnTimeStep`). Each input may be
        [B, F] (single step) or [B, T, F]; inputs consumed by an
        embedding layer over a recurrent input type are [B, T] token
        ids — including [B, 1] single-step decode (same disambiguation
        as MultiLayerNetwork.rnn_time_step). Jitted with the carries as
        arguments so per-token streaming is one compiled dispatch."""
        xs = [jnp.asarray(x) for x in inputs]
        # an input feeds token ids iff some layer directly consuming
        # THAT input was built with time_series_input (embedding over
        # ids) — decided per input, so a graph mixing an id input with
        # a rank-2 [B, F] feature input still squeezes the feature one.
        # Pure function of the (fixed) config — cached: this sits on
        # the per-token decode path
        if getattr(self, "_ids_by_input", None) is None:
            self._ids_by_input = {
                inp: any(getattr(n.layer, "time_series_input", False)
                         for n in self.conf.nodes.values()
                         if n.layer is not None and inp in n.inputs)
                for inp in self.conf.network_inputs}
        ids_by_input = self._ids_by_input
        squeezed = [x.ndim == 2 and not ids_by_input.get(inp, False)
                    for inp, x in zip(self.conf.network_inputs, xs)]
        xs = [x[:, None, :] if sq else x for sq, x in zip(squeezed, xs)]
        squeeze = any(squeezed)   # single-step call → outputs drop T
        # new positions this call = longest time axis among the
        # sequence inputs (rank-3 [B,T,F] or rank-2 id [B,T]; a rank-4
        # conv input has no time axis and is not counted)
        t_new = 1
        for inp, x in zip(self.conf.network_inputs, xs):
            if x.ndim == 3 or (x.ndim == 2 and ids_by_input.get(inp, False)):
                t_new = max(t_new, int(x.shape[1]))
        self._check_stream_budget(t_new)
        carries = dict(self._rnn_carries)
        batch = xs[0].shape[0]
        for n, layer in self._recurrent_layers():
            if n not in carries:
                carries[n] = layer.init_carry(batch, self.dtype.compute_dtype)
        if self._jit_rnn_step is None:
            def rnn_fwd(params, state, xs, masks, carries):
                c = dict(carries)
                acts, _, _, _ = self._forward_all(params, state, list(xs),
                                                  train=False, rng=None,
                                                  masks=masks, carries=c)
                return {n: acts[n] for n in self.conf.network_outputs}, c
            self._jit_rnn_step = jax.jit(rnn_fwd)
        acts, carries = self._jit_rnn_step(self.params, self.net_state,
                                           tuple(xs), masks, carries)
        self._rnn_carries.update(carries)
        self._rnn_stream_pos += t_new
        outs = []
        for n in self.conf.network_outputs:
            h = acts[n]
            outs.append(h[:, -1, :] if squeeze and h.ndim == 3 else h)
        return outs[0] if len(outs) == 1 else tuple(outs)

    # ------------------------------------------------------------ pretrain
    def pretrain(self, data, *, epochs: int = 1, batch_size: int = 32):
        """Greedy layerwise pretraining of AutoEncoder-style layer nodes
        in topological order (reference `ComputationGraph.pretrain`)."""
        if not self._initialized:
            self.init()
        iterator = as_iterator(data, batch_size=batch_size)
        rng_root = jax.random.PRNGKey(self.conf.seed + 2)
        for li, name in enumerate(self.conf.topo_order):
            node = self.conf.nodes[name]
            if node.kind != "layer" or not hasattr(node.layer, "pretrain_loss"):
                continue
            layer = node.layer
            updater = layer.updater or Sgd(1e-3)

            @jax.jit
            def pt_step(lparams, upd_state, h, rng, it, layer=layer,
                        updater=updater):
                def lf(p):
                    return layer.pretrain_loss(p, h, rng)
                loss, grads = jax.value_and_grad(lf)(lparams)
                new_p, new_u = {}, {}
                for pk, g in grads.items():
                    delta, ns = updater.apply(g, upd_state[pk], it)
                    new_p[pk] = lparams[pk] - delta
                    new_u[pk] = ns
                return new_p, new_u, loss

            # jitted featurizer walking only the ancestors of this node
            # (the downstream graph and output heads are never computed)
            target = node.inputs[0]
            ancestors = {target}
            changed = True
            while changed:
                changed = False
                for n in self.conf.topo_order:
                    if n in ancestors:
                        for src in self.conf.nodes[n].inputs:
                            if src not in ancestors:
                                ancestors.add(src)
                                changed = True
            sub_order = [n for n in self.conf.topo_order if n in ancestors]

            def featurize(params, state, xs, node=node, sub_order=sub_order,
                          target=target):
                acts = {n: self.dtype.cast_compute(x)
                        for n, x in zip(self.conf.network_inputs, xs)}
                for n in sub_order:
                    sub = self.conf.nodes[n]
                    if sub.kind == "input":
                        continue
                    ins = [acts[s] for s in sub.inputs]
                    if sub.kind == "vertex":
                        acts[n] = sub.vertex.forward(ins, masks=[None] * len(ins),
                                                     train=False)
                        continue
                    h = ins[0]
                    if sub.preprocessor is not None:
                        h = sub.preprocessor.pre_process(h, None)
                    h, _ = sub.layer.forward(params.get(n, {}),
                                             state.get(n, {}), h,
                                             train=False, rng=None)
                    acts[n] = h
                h = acts[target]
                if node.preprocessor is not None:
                    h = node.preprocessor.pre_process(h, None)
                return h

            featurize = jax.jit(featurize)
            lparams = self.params[name]
            upd_state = {pk: updater.init_state(v) for pk, v in lparams.items()}
            it = 0
            for _ in range(epochs):
                iterator.reset()
                for ds in iterator:
                    feats = ds.features if isinstance(ds.features, (list, tuple)) \
                        else [ds.features]
                    h = featurize(self.params, self.net_state,
                                  tuple(jnp.asarray(f) for f in feats))
                    rng = jax.random.fold_in(rng_root, it * 997 + li)
                    lparams, upd_state, _ = pt_step(lparams, upd_state, h, rng, it)
                    it += 1
            self.params[name] = lparams
        return self

    # ------------------------------------------------------------- inference
    @property
    def single_io(self) -> bool:
        return (len(self.conf.network_inputs) == 1
                and len(self.conf.network_outputs) == 1)

    def _forward_output(self, params, state, x):
        """Eval-mode forward of a single-io graph's one features array
        to its one output, pure (what the mesh trainers re-jit)."""
        acts = self._forward_all(params, state, [x], train=False, rng=None)[0]
        return acts[self.conf.network_outputs[0]]

    def output(self, *inputs, train: bool = False, masks=None):
        if not self._initialized:
            self.init()
        self._sync_ambient_context()
        if self._jit_output is None:
            def fwd(params, state, xs, masks):
                acts, _, _, _ = self._forward_all(params, state, xs, train=False,
                                                  rng=None, masks=masks)
                # eval numerics stay fp32 under a mixed policy
                return tuple(self.dtype.cast_output(acts[n])
                             for n in self.conf.network_outputs)
            self._jit_output = jax.jit(fwd)
        xs = tuple(jnp.asarray(x) for x in inputs)
        outs = self._jit_output(self.params, self.net_state, xs, masks)
        return outs[0] if len(outs) == 1 else outs

    def feed_forward(self, *inputs, train: bool = False, masks=None):
        # unrolled: every node's activation must materialize (a scanned
        # chain would skip its interior members)
        acts, _, _, _ = self._forward_all(self.params, self.net_state, list(inputs),
                                          train=train, rng=None, masks=masks,
                                          unrolled=True)
        return acts

    def evaluate(self, iterator, labels_list=None, top_n: int = 1):
        # the reference's graph overload: no data_format before the labels
        return super().evaluate(iterator, labels_list=labels_list,
                                top_n=top_n)

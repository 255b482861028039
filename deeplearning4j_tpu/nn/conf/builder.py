"""NeuralNetConfiguration builder → MultiLayerConfiguration.

Reference: `nn/conf/NeuralNetConfiguration.java:570` (Builder; global
defaults cloned into every layer), `:727` (`list()` → ListBuilder),
`nn/conf/MultiLayerConfiguration.java` (the serializable product), with
`setInputType` driving nIn inference + automatic preprocessor insertion
(`ListBuilder.setInputType` → `LayerValidation`/preprocessor logic).

Global defaults (updater, weight-init, l1/l2, dropout, gradient
normalization) are applied to a layer when the layer still carries its
dataclass default for that field — the moral equivalent of the
reference's "clone global conf per layer, layer overrides win".
"""

from __future__ import annotations

import dataclasses
import json
from enum import Enum
from typing import Any, Dict, List, Optional

# Serialized-config format version (reference role: the legacy-format
# migration deserializers, `nn/conf/serde/MultiLayerConfigurationDeserializer
# .java:36,67` — DL4J migrates old enum-style JSON on read; stamping a
# version NOW is what makes such migrations possible later). Bump when
# the on-disk layout changes incompatibly; from_dict accepts <= current
# (older payloads migrate forward) and rejects newer-than-current.
CONFIG_FORMAT_VERSION = 1


def check_format_version(d: dict, what: str):
    v = d.get("format_version", 1)  # pre-versioning payloads are v1
    if not isinstance(v, int) or v < 1:
        raise ValueError(f"{what}: invalid format_version {v!r}")
    if v > CONFIG_FORMAT_VERSION:
        raise ValueError(
            f"{what}: payload format_version {v} is newer than this "
            f"build's {CONFIG_FORMAT_VERSION} — upgrade the library to "
            f"load it")


from deeplearning4j_tpu.common.updaters import Sgd, Updater, get_updater
from deeplearning4j_tpu.common.weights import WeightInit
from deeplearning4j_tpu.nn.conf.inputs import (
    InputType,
    InputTypeConvolutional,
    InputTypeConvolutionalFlat,
    InputTypeFeedForward,
    InputTypeRecurrent,
)
from deeplearning4j_tpu.nn.conf.preprocessors import (
    CnnToFeedForwardPreProcessor,
    CnnToRnnPreProcessor,
    FeedForwardToCnnPreProcessor,
    FeedForwardToRnnPreProcessor,
    InputPreProcessor,
    RnnToCnnPreProcessor,
    RnnToFeedForwardPreProcessor,
    preprocessor_from_dict,
)
from typing import TYPE_CHECKING

if TYPE_CHECKING:  # import-time cycle guard: layers.base imports conf.*
    # submodules, and importing any of those runs this package's
    # __init__ → builder. `Layer` is only needed as an annotation
    # (PEP 563 strings); `layer_from_dict` is imported lazily where used.
    from deeplearning4j_tpu.nn.layers.base import Layer


class GradientNormalization(str, Enum):
    """Reference `nn/conf/GradientNormalization.java`."""

    NONE = "none"
    RENORMALIZE_L2_PER_LAYER = "renormalize_l2_per_layer"
    RENORMALIZE_L2_PER_PARAM_TYPE = "renormalize_l2_per_param_type"
    CLIP_ELEMENTWISE_ABSOLUTE_VALUE = "clip_elementwise_absolute_value"
    CLIP_L2_PER_LAYER = "clip_l2_per_layer"
    CLIP_L2_PER_PARAM_TYPE = "clip_l2_per_param_type"


class BackpropType(str, Enum):
    STANDARD = "standard"
    TRUNCATED_BPTT = "tbptt"


@dataclasses.dataclass
class MultiLayerConfiguration:
    """Serializable product: everything a MultiLayerNetwork needs.

    Reference: `nn/conf/MultiLayerConfiguration.java` — configs are data
    and ship inside checkpoints (`ModelSerializer` writes
    configuration.json)."""

    layers: List[Layer] = dataclasses.field(default_factory=list)
    input_preprocessors: Dict[int, InputPreProcessor] = dataclasses.field(default_factory=dict)
    input_type: Optional[InputType] = None
    seed: int = 12345
    backprop_type: BackpropType = BackpropType.STANDARD
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 20
    gradient_normalization: GradientNormalization = GradientNormalization.NONE
    gradient_normalization_threshold: float = 1.0
    max_norm: Optional[float] = None  # constraint applied post-update
    pretrain: bool = False
    optimization_algo: str = "sgd"  # OptimizationAlgorithm value
    max_iterations: int = 5  # line-search solver iterations per batch
    # scan-over-layers compilation (nn/scan_stack.py): roll maximal
    # homogeneous layer runs into one lax.scan so compile time /
    # program size stop scaling with depth. Numerics are identical to
    # the unrolled loop; disable for A/B or debugging (also via the
    # DL4J_SCAN_LAYERS=0 env override).
    scan_layers: bool = True
    # gradient exchange mode for the distributed sync trainers
    # (parallel/gradient_sharing.py): "dense" fp32 all-reduce, or
    # "threshold" error-feedback sign-magnitude encoding (the reference
    # SharedTrainingMaster wire format; DL4J_GRADIENT_SHARING env
    # overrides). `gradient_sharing_threshold` is the initial adaptive
    # τ (reference threshold default 1e-3).
    gradient_sharing: str = "dense"
    gradient_sharing_threshold: float = 1e-3
    # mixed-precision policy (nd/dtype.py): None = process default
    # (float32), or a DataTypePolicy — "mixed_bf16" is fp32 master
    # params / bf16 compute / fp32 losses. The DL4J_DTYPE_POLICY env
    # override beats this field (mirroring DL4J_SCAN_LAYERS).
    dtype_policy: Optional[Any] = None
    # in-graph model-internals diagnostics (monitor/diagnostics.py):
    # None = off, or a DiagnosticsConfig / spec ("on", a watchdog
    # policy name, a serde dict). DL4J_DIAGNOSTICS env wins.
    diagnostics: Optional[Any] = None

    def to_dict(self):
        return {
            "format": "deeplearning4j_tpu.MultiLayerConfiguration",
            "format_version": CONFIG_FORMAT_VERSION,
            "layers": [l.to_dict() for l in self.layers],
            "input_preprocessors": {str(i): p.to_dict() for i, p in self.input_preprocessors.items()},
            "input_type": None if self.input_type is None else self.input_type.to_dict(),
            "seed": self.seed,
            "backprop_type": self.backprop_type.value,
            "tbptt_fwd_length": self.tbptt_fwd_length,
            "tbptt_back_length": self.tbptt_back_length,
            "gradient_normalization": self.gradient_normalization.value,
            "gradient_normalization_threshold": self.gradient_normalization_threshold,
            "max_norm": self.max_norm,
            "pretrain": self.pretrain,
            "optimization_algo": self.optimization_algo,
            "max_iterations": self.max_iterations,
            "scan_layers": self.scan_layers,
            "gradient_sharing": self.gradient_sharing,
            "gradient_sharing_threshold": self.gradient_sharing_threshold,
            "dtype_policy": (None if self.dtype_policy is None
                             else _policy_to_dict(self.dtype_policy)),
            "diagnostics": (None if self.diagnostics is None
                            else _diagnostics_to_dict(self.diagnostics)),
        }

    def to_json(self, **kw):
        return json.dumps(self.to_dict(), **kw)

    @staticmethod
    def from_dict(d: dict) -> "MultiLayerConfiguration":
        from deeplearning4j_tpu.nn.layers.base import layer_from_dict
        check_format_version(d, "MultiLayerConfiguration")
        return MultiLayerConfiguration(
            layers=[layer_from_dict(ld) for ld in d["layers"]],
            input_preprocessors={int(i): preprocessor_from_dict(p)
                                 for i, p in d.get("input_preprocessors", {}).items()},
            input_type=None if d.get("input_type") is None else InputType.from_dict(d["input_type"]),
            seed=d.get("seed", 12345),
            backprop_type=BackpropType(d.get("backprop_type", "standard")),
            tbptt_fwd_length=d.get("tbptt_fwd_length", 20),
            tbptt_back_length=d.get("tbptt_back_length", 20),
            gradient_normalization=GradientNormalization(d.get("gradient_normalization", "none")),
            gradient_normalization_threshold=d.get("gradient_normalization_threshold", 1.0),
            max_norm=d.get("max_norm"),
            pretrain=d.get("pretrain", False),
            optimization_algo=d.get("optimization_algo", "sgd"),
            max_iterations=d.get("max_iterations", 5),
            scan_layers=d.get("scan_layers", True),
            gradient_sharing=d.get("gradient_sharing", "dense"),
            gradient_sharing_threshold=d.get("gradient_sharing_threshold",
                                             1e-3),
            dtype_policy=_policy_from_serde(d.get("dtype_policy")),
            diagnostics=_diagnostics_from_serde(d.get("diagnostics")),
        )

    @staticmethod
    def from_json(s: str) -> "MultiLayerConfiguration":
        return MultiLayerConfiguration.from_dict(json.loads(s))


def _policy_to_dict(p):
    """Serde form of a dtype_policy field value (a DataTypePolicy, a
    preset name, or an already-serialized dict)."""
    from deeplearning4j_tpu.nd.dtype import as_policy
    return as_policy(p).to_dict()


def _policy_from_serde(d):
    if d is None:
        return None
    from deeplearning4j_tpu.nd.dtype import as_policy
    return as_policy(d)


def _diagnostics_to_dict(spec):
    """Serde form of a diagnostics field value (a DiagnosticsConfig, a
    spec name, or an already-serialized dict)."""
    from deeplearning4j_tpu.monitor.diagnostics import as_diagnostics
    cfg = as_diagnostics(spec)
    return None if cfg is None else cfg.to_dict()


def _diagnostics_from_serde(d):
    if d is None:
        return None
    from deeplearning4j_tpu.monitor.diagnostics import as_diagnostics
    return as_diagnostics(d)


def _family(input_type: InputType) -> str:
    if isinstance(input_type, InputTypeConvolutional):
        return "cnn"
    if isinstance(input_type, InputTypeConvolutionalFlat):
        return "cnnflat"
    if isinstance(input_type, InputTypeRecurrent):
        return "rnn"
    return "ff"


def _expected_family(layer: Layer) -> str:
    # which input family does this layer natively consume?
    if layer.layer_name == "frozen" and getattr(layer, "layer", None) is not None:
        return _expected_family(layer.layer)  # delegate through the wrapper
    name = layer.layer_name
    if name in ("convolution", "subsampling", "upsampling2d", "zeropadding",
                "space_to_depth", "lrn", "yolo2_output",
                "separable_convolution2d", "pool_helper"):
        return "cnn"
    if name in ("lstm", "graves_lstm", "graves_bidirectional_lstm", "simple_rnn",
                "rnn_output", "convolution1d", "subsampling1d", "zeropadding1d",
                "upsampling1d", "last_time_step", "multi_head_attention",
                "lm_head", "tied_lm_head"):
        return "rnn"
    if name in ("batchnorm", "activation", "dropout_layer", "global_pooling",
                "loss", "reshape", "permute", "layernorm",
                # shape-agnostic sequence layers: embedding gathers per
                # position; positional-encoding/transformer blocks keep
                # [B,T,D] — none of them wants a time-flattening insert
                "embedding", "positional_encoding", "transformer_encoder",
                "latent_attention_block", "rms_norm",
                "parallel_attention_moe_block", "gain_layer_norm",
                "hybrid_state_space_block"):
        return "any"
    return "ff"


def infer_preprocessor(input_type: InputType, layer: Layer) -> Optional[InputPreProcessor]:
    """Automatic preprocessor insertion (reference ListBuilder.setInputType)."""
    have, want = _family(input_type), _expected_family(layer)
    if want == "any" or have == want:
        return None
    it = input_type
    if have == "cnnflat" and want == "cnn":
        return FeedForwardToCnnPreProcessor(it.height, it.width, it.channels)
    if have == "cnnflat" and want == "ff":
        return None  # already flat
    if have == "cnn" and want == "ff":
        return CnnToFeedForwardPreProcessor(it.height, it.width, it.channels)
    if have == "cnn" and want == "rnn":
        return CnnToRnnPreProcessor(it.height, it.width, it.channels)
    if have == "rnn" and want == "ff":
        return RnnToFeedForwardPreProcessor()
    if have == "ff" and want == "rnn":
        return FeedForwardToRnnPreProcessor(timesteps=0)
    if have == "rnn" and want == "cnn":
        raise ValueError("rnn→cnn requires an explicit RnnToCnnPreProcessor with h/w/c")
    if have == "cnnflat" and want == "rnn":
        return FeedForwardToRnnPreProcessor(timesteps=0)
    if have == "ff" and want == "cnn":
        raise ValueError(
            "feed-forward→cnn requires setInputType(InputType.convolutional_flat(...)) "
            "or an explicit FeedForwardToCnnPreProcessor")
    return None


class ListBuilder:
    """`NeuralNetConfiguration.Builder.list()` equivalent."""

    def __init__(self, global_conf: "NeuralNetConfiguration"):
        self._g = global_conf
        self._layers: List[Layer] = []
        self._preprocessors: Dict[int, InputPreProcessor] = {}
        self._input_type: Optional[InputType] = None
        self._backprop_type = BackpropType.STANDARD
        self._tbptt_fwd = 20
        self._tbptt_back = 20
        self._pretrain = False
        self._scan_layers = True
        self._gradient_sharing = "dense"
        self._gradient_sharing_threshold = 1e-3
        self._dtype_policy = global_conf.dtype_policy_value
        self._diagnostics = getattr(global_conf, "diagnostics_value", None)

    def layer(self, layer_or_idx, maybe_layer=None) -> "ListBuilder":
        layer = maybe_layer if maybe_layer is not None else layer_or_idx
        self._layers.append(layer)
        return self

    def input_preprocessor(self, idx: int, p: InputPreProcessor) -> "ListBuilder":
        self._preprocessors[idx] = p
        return self

    def set_input_type(self, input_type: InputType) -> "ListBuilder":
        self._input_type = input_type
        return self

    def backprop_type(self, bptype, fwd_length: int = 20, back_length: int = None) -> "ListBuilder":
        self._backprop_type = BackpropType(bptype)
        self._tbptt_fwd = fwd_length
        self._tbptt_back = back_length if back_length is not None else fwd_length
        return self

    def t_bptt_lengths(self, fwd: int, back: int = None) -> "ListBuilder":
        return self.backprop_type(BackpropType.TRUNCATED_BPTT, fwd, back)

    def pretrain(self, flag: bool) -> "ListBuilder":
        self._pretrain = flag
        return self

    def scan_layers(self, flag: bool) -> "ListBuilder":
        """Enable/disable scan-over-layers compilation of homogeneous
        layer runs (default on; see nn/scan_stack.py)."""
        self._scan_layers = bool(flag)
        return self

    def gradient_sharing(self, mode: str,
                         threshold: Optional[float] = None) -> "ListBuilder":
        """Gradient exchange mode for the distributed sync trainers:
        "dense" (default), "threshold" (error-feedback compressed
        collectives), or the ZeRO-style reduce-scatter modes
        "dense_rs"/"threshold_rs" (updater state sharded over the data
        axis — parallel/gradient_sharing.py). `threshold` sets the
        initial adaptive τ (reference SharedTrainingMaster threshold,
        default 1e-3)."""
        if mode not in ("dense", "threshold", "dense_rs", "threshold_rs"):
            raise ValueError(
                f"gradient_sharing must be dense|threshold|dense_rs|"
                f"threshold_rs, got {mode!r}")
        self._gradient_sharing = mode
        if threshold is not None:
            self._gradient_sharing_threshold = float(threshold)
        return self

    def dtype_policy(self, policy) -> "ListBuilder":
        """Mixed-precision policy for this model (nd/dtype.py): a
        DataTypePolicy, a preset name ("mixed_bf16" / "float32"), or
        None for the process default. `DL4J_DTYPE_POLICY` env wins."""
        from deeplearning4j_tpu.nd.dtype import as_policy
        self._dtype_policy = as_policy(policy)
        return self

    def diagnostics(self, spec) -> "ListBuilder":
        """In-graph model-internals diagnostics
        (monitor/diagnostics.py): True/"on" for the defaults, a
        watchdog policy name ("warn"/"skip"/"halt"), a
        DiagnosticsConfig, or None/False for off. `DL4J_DIAGNOSTICS`
        env wins."""
        from deeplearning4j_tpu.monitor.diagnostics import as_diagnostics
        self._diagnostics = as_diagnostics(spec)
        return self

    def build(self) -> MultiLayerConfiguration:
        g = self._g
        layers = [l.clone() for l in self._layers]
        for l in layers:
            g.apply_global_defaults(l)

        preprocessors = dict(self._preprocessors)
        current = self._input_type
        if (current is None and layers and _has_explicit_n_in(layers[0])
                and _expected_family(layers[0]) in ("ff", "any")):
            # DL4J-style config: nIn on the first layer, no input type —
            # synthesize the feed-forward InputType so the n_in chain
            # resolves (reference: LayerValidation + builder nIn plumb)
            current = InputType.feed_forward(layers[0].n_in)
        if current is not None:
            for i, l in enumerate(layers):
                if i in preprocessors:
                    current = preprocessors[i].get_output_type(current)
                else:
                    auto = infer_preprocessor(current, l)
                    if auto is not None:
                        preprocessors[i] = auto
                        current = auto.get_output_type(current)
                    elif _family(current) == "cnnflat" and _expected_family(l) in ("ff", "any"):
                        current = InputType.feed_forward(current.arity())
                l.set_n_in(current, override=not _has_explicit_n_in(l))
                current = l.get_output_type(current)

        return MultiLayerConfiguration(
            layers=layers,
            input_preprocessors=preprocessors,
            input_type=self._input_type,
            seed=g.seed_value,
            backprop_type=self._backprop_type,
            tbptt_fwd_length=self._tbptt_fwd,
            tbptt_back_length=self._tbptt_back,
            gradient_normalization=g.gradient_normalization_value,
            gradient_normalization_threshold=g.gradient_normalization_threshold_value,
            max_norm=g.max_norm_value,
            pretrain=self._pretrain,
            optimization_algo=g.optimization_algo_value,
            max_iterations=g.max_iterations_value,
            scan_layers=self._scan_layers,
            gradient_sharing=self._gradient_sharing,
            gradient_sharing_threshold=self._gradient_sharing_threshold,
            dtype_policy=self._dtype_policy,
            diagnostics=self._diagnostics,
        )


def _has_explicit_n_in(layer: Layer) -> bool:
    return getattr(layer, "n_in", 0) not in (0, None)


class NeuralNetConfiguration:
    """Fluent global-defaults builder (reference
    `NeuralNetConfiguration.Builder`)."""

    def __init__(self):
        self.seed_value = 12345
        self.updater_value: Updater = Sgd(1e-3)
        self.weight_init_value: Optional[WeightInit] = None
        self.dist_value = None
        self.l1_value = 0.0
        self.l2_value = 0.0
        self.l1_bias_value = 0.0
        self.l2_bias_value = 0.0
        self.dropout_value: Optional[float] = None
        self.gradient_normalization_value = GradientNormalization.NONE
        self.gradient_normalization_threshold_value = 1.0
        self.max_norm_value: Optional[float] = None
        self.remat_policy_value: Optional[str] = None
        self.activation_value = None
        self.optimization_algo_value = "sgd"
        self.max_iterations_value = 5
        self.mini_batch = True
        self.dtype_policy_value = None
        self.diagnostics_value = None

    @staticmethod
    def builder() -> "NeuralNetConfiguration":
        return NeuralNetConfiguration()

    def seed(self, s: int):
        self.seed_value = int(s)
        return self

    def updater(self, u):
        self.updater_value = get_updater(u)
        return self

    def weight_init(self, wi, dist=None):
        self.weight_init_value = WeightInit(wi)
        if dist is not None:
            self.dist_value = dist
        return self

    def dist(self, d):
        self.dist_value = d
        self.weight_init_value = WeightInit.DISTRIBUTION
        return self

    def activation(self, a):
        self.activation_value = a
        return self

    def l1(self, v):
        self.l1_value = v
        return self

    def l2(self, v):
        self.l2_value = v
        return self

    def l1_bias(self, v):
        self.l1_bias_value = v
        return self

    def l2_bias(self, v):
        self.l2_bias_value = v
        return self

    def dropout(self, retain_prob):
        self.dropout_value = retain_prob
        return self

    def gradient_normalization(self, gn, threshold: float = 1.0):
        self.gradient_normalization_value = GradientNormalization(gn)
        self.gradient_normalization_threshold_value = threshold
        return self

    def remat_policy(self, policy: Optional[str]):
        """Global rematerialization default pushed into every layer
        that doesn't set its own: "none"/None stores activations,
        "full" recomputes the layer in backward, "dots_saveable"
        recomputes everything except matmul outputs (the
        peak-activation-memory lever for deep stacks — see
        nn/scan_stack.py and docs/COMPILE.md)."""
        from deeplearning4j_tpu.nn.scan_stack import validate_remat_policy
        validate_remat_policy(policy)
        self.remat_policy_value = policy
        return self

    def optimization_algo(self, algo):
        """Reference `NeuralNetConfiguration.Builder.optimizationAlgo`
        (`nn/api/OptimizationAlgorithm.java`): sgd runs the jitted
        train step; the line-search family routes fit() batches through
        `optimize.solvers.Solver`."""
        from deeplearning4j_tpu.optimize.solvers import OptimizationAlgorithm
        self.optimization_algo_value = OptimizationAlgorithm(algo).value
        return self

    def max_iterations(self, n: int):
        self.max_iterations_value = int(n)
        return self

    def dtype_policy(self, policy):
        """Mixed-precision policy threaded into the built configuration
        (nd/dtype.py): a DataTypePolicy object or a preset name —
        ``"mixed_bf16"`` selects fp32 master params / bf16 compute /
        fp32 losses; ``"float32"`` forces pure fp32. ``None`` keeps the
        process default. A/B without code changes via the
        ``DL4J_DTYPE_POLICY`` env override, which beats this field."""
        from deeplearning4j_tpu.nd.dtype import as_policy
        self.dtype_policy_value = as_policy(policy)
        return self

    def diagnostics(self, spec):
        """In-graph model-internals diagnostics default threaded into
        the built configuration (monitor/diagnostics.py): per-layer
        grad/update/param/activation stats as aux outputs of the fused
        train step, plus the non-finite watchdog
        (``"warn"``/``"skip"``/``"halt"``). ``True``/"on" enables the
        defaults; the ``DL4J_DIAGNOSTICS`` env override beats this
        field (mirroring DL4J_SCAN_LAYERS)."""
        from deeplearning4j_tpu.monitor.diagnostics import as_diagnostics
        self.diagnostics_value = as_diagnostics(spec)
        return self

    def constrain_max_norm(self, v: float):
        self.max_norm_value = v
        return self

    def apply_global_defaults(self, layer: Layer):
        """Push builder-level defaults into a layer, honoring layer-level
        overrides (reference: global conf cloned per layer)."""
        if layer.updater is None:
            layer.updater = self.updater_value
        if self.weight_init_value is not None and layer.weight_init == WeightInit.XAVIER:
            layer.weight_init = self.weight_init_value
        if self.dist_value is not None and layer.dist is None:
            layer.dist = self.dist_value
        if layer.l1 == 0.0:
            layer.l1 = self.l1_value
        if layer.l2 == 0.0:
            layer.l2 = self.l2_value
        if layer.l1_bias == 0.0:
            layer.l1_bias = self.l1_bias_value
        if layer.l2_bias == 0.0:
            layer.l2_bias = self.l2_bias_value
        if (getattr(layer, "remat_policy", None) is None
                and self.remat_policy_value is not None):
            layer.remat_policy = self.remat_policy_value
        if layer.dropout is None and self.dropout_value is not None:
            # output-ish layers don't get input dropout by default in the
            # reference either; applied uniformly here, harmless for eval.
            layer.dropout = self.dropout_value

    def list(self) -> ListBuilder:
        return ListBuilder(self)

"""Layer catalog: config dataclasses with functional init/forward.

Reference split `nn/conf/layers/*` (config) from `nn/layers/*` (runtime
impl); here each layer is ONE dataclass carrying serializable config
fields plus pure-JAX `init_params` / `forward` — config-as-data is
preserved (JSON round-trip covers only the dataclass fields).
"""

from deeplearning4j_tpu.nn.layers.base import Layer, layer_from_dict, register_layer
from deeplearning4j_tpu.nn.layers.feedforward import (
    DenseLayer,
    OutputLayer,
    LossLayer,
    ActivationLayer,
    DropoutLayer,
    EmbeddingLayer,
    AutoEncoder,
)
from deeplearning4j_tpu.nn.layers.convolution import (
    ConvolutionLayer,
    Convolution1DLayer,
    SubsamplingLayer,
    Subsampling1DLayer,
    Upsampling1D,
    Upsampling2D,
    ZeroPaddingLayer,
    ZeroPadding1DLayer,
    SpaceToDepthLayer,
    SeparableConvolution2D,
)
from deeplearning4j_tpu.nn.layers.normalization import (
    BatchNormalization,
    LayerNormalization,
    LocalResponseNormalization,
)
from deeplearning4j_tpu.nn.layers.transformer import (
    PositionalEncodingLayer,
    TransformerEncoderBlock,
)
from deeplearning4j_tpu.nn.layers.recurrent import (
    LSTM,
    GravesLSTM,
    GravesBidirectionalLSTM,
    SimpleRnn,
    RnnOutputLayer,
    LastTimeStep,
)
from deeplearning4j_tpu.nn.layers.pooling import GlobalPoolingLayer, PoolingType
from deeplearning4j_tpu.nn.layers.variational import (
    VariationalAutoencoder,
    GaussianReconstructionDistribution,
    BernoulliReconstructionDistribution,
    ExponentialReconstructionDistribution,
)
from deeplearning4j_tpu.nn.layers.rbm import RBM, HiddenUnit, VisibleUnit
from deeplearning4j_tpu.nn.layers.misc import (
    FrozenLayer,
    PermuteLayer,
    PoolHelperLayer,
    ReshapeLayer,
)
from deeplearning4j_tpu.nn.layers.training import CenterLossOutputLayer
from deeplearning4j_tpu.nn.layers.objdetect import Yolo2OutputLayer
from deeplearning4j_tpu.nn.layers.attention import MultiHeadAttention
from deeplearning4j_tpu.nn.layers.moe import MixtureOfExperts
from deeplearning4j_tpu.nn.layers.latent import (
    LatentAttentionBlock,
    LMHead,
    RMSNormLayer,
)
from deeplearning4j_tpu.nn.layers.parallel import (
    GainLayerNorm,
    ParallelAttentionMoEBlock,
    TiedLMHead,
)
from deeplearning4j_tpu.nn.layers.statespace import HybridStateSpaceBlock

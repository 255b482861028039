"""Model zoo (reference: deeplearning4j-zoo `zoo/model/*`):
LeNet, AlexNet, VGG16/19, SimpleCNN, ResNet50, GoogLeNet,
InceptionResNetV1, FaceNetNN4Small2, TextGenerationLSTM — each a
config-builder producing a MultiLayerNetwork or ComputationGraph.
"""

from deeplearning4j_tpu.zoo.base import ZooModel, PretrainedType
from deeplearning4j_tpu.zoo.lenet import LeNet
from deeplearning4j_tpu.zoo.alexnet import AlexNet
from deeplearning4j_tpu.zoo.vgg import VGG16, VGG19
from deeplearning4j_tpu.zoo.simplecnn import SimpleCNN
from deeplearning4j_tpu.zoo.resnet50 import ResNet50
from deeplearning4j_tpu.zoo.textgenlstm import TextGenerationLSTM
from deeplearning4j_tpu.zoo.googlenet import GoogLeNet
from deeplearning4j_tpu.zoo.inceptionresnet import InceptionResNetV1
from deeplearning4j_tpu.zoo.facenet import FaceNetNN4Small2
from deeplearning4j_tpu.zoo.transformer import TransformerClassifier, TransformerLM
from deeplearning4j_tpu.zoo.latent_moe import LatentMoELM
from deeplearning4j_tpu.zoo.parallel_moe import ParallelMoELM
from deeplearning4j_tpu.zoo.hybrid_statespace import HybridStateSpaceLM

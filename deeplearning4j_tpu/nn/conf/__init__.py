"""Configuration DSL.

Analog of the reference's nn/conf package: a declarative, JSON-serializable
description of a network (NeuralNetConfiguration.java, 1,189 LoC;
MultiLayerConfiguration.java; layer configs in nn/conf/layers/). The JSON
form is the persistence/compat surface, exactly as in the reference
(SURVEY.md §5 "Config/flag system").
"""

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    ActivationLayer,
    AutoEncoder,
    BatchNormalization,
    CenterLossOutputLayer,
    Convolution1DLayer,
    ConvolutionLayer,
    DenseLayer,
    DropoutLayer,
    EmbeddingLayer,
    EmbeddingSequenceLayer,
    ExpertRouterLayer,
    GatedMLPLayer,
    GlobalPoolingLayer,
    GravesBidirectionalLSTM,
    GravesLSTM,
    GroupedQueryAttentionLayer,
    LatentAttentionLayer,
    LocalResponseNormalization,
    LossLayer,
    LSTM,
    Mamba2Layer,
    OutputLayer,
    RMSNorm,
    RnnOutputLayer,
    SelfAttentionLayer,
    SparseExpertsLayer,
    Subsampling1DLayer,
    SubsamplingLayer,
    VariationalAutoencoder,
    ZeroPaddingLayer,
)
from deeplearning4j_tpu.nn.conf.network import (
    BackpropType,
    GradientNormalization,
    MultiLayerConfiguration,
    NeuralNetConfiguration,
    Updater,
)
from deeplearning4j_tpu.nn.conf.graph import (
    ComputationGraphConfiguration,
    DuplicateToTimeSeriesVertex,
    ElementWiseVertex,
    GraphBuilder,
    L2NormalizeVertex,
    L2Vertex,
    LastTimeStepVertex,
    LayerVertex,
    MergeVertex,
    PreprocessorVertex,
    ReshapeVertex,
    ScaleVertex,
    ShiftVertex,
    StackVertex,
    SubsetVertex,
    UnstackVertex,
)
from deeplearning4j_tpu.nn.conf.serde import config_from_dict, config_to_dict

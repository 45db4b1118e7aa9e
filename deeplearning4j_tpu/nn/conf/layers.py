"""Layer configuration dataclasses.

One config class per layer type, mirroring the reference's nn/conf/layers/
catalog (28 classes — SURVEY.md §2.1 "Layer configs"). Configs are pure
data: JSON-serializable dataclasses with two responsibilities the reference
splits between InputTypeUtil and each Layer conf:

- output_type(input_type): shape inference through the network
- infer_n_in(input_type): fill in n_in/channels when the user set an
  InputType instead of wiring sizes by hand (reference: setNIn overrides)

Fields defaulting to None inherit the network-level default from
NeuralNetConfiguration (reference: Builder.layer(...) cloning global
hyperparameters into each layer's conf).

Convolutional layers use NHWC and "same"/"truncate" border modes
(reference ConvolutionMode.Same/Truncate, nn/conf/ConvolutionMode.java).
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence, Tuple

from deeplearning4j_tpu.nn.conf.inputs import (
    ConvolutionalFlatInput,
    ConvolutionalInput,
    FeedForwardInput,
    RecurrentInput,
    TokenSequenceInput,
)
from deeplearning4j_tpu.nn.conf.serde import register_config


class PoolingType:
    MAX = "max"
    AVG = "avg"
    SUM = "sum"
    PNORM = "pnorm"


class ConvolutionMode:
    SAME = "same"
    TRUNCATE = "truncate"


def _conv_out(size: int, k: int, s: int, p: int, mode: str) -> int:
    if mode == ConvolutionMode.SAME:
        return int(math.ceil(size / s))
    return (size + 2 * p - k) // s + 1


@dataclasses.dataclass(kw_only=True)
class LayerConf:
    """Base fields shared by every layer (reference: nn/conf/layers/Layer.java
    + BaseLayer hyperparameters)."""

    name: Optional[str] = None
    dropout: Optional[float] = None  # keep DL4J semantics: retain probability

    def output_type(self, it):
        return it

    def infer_n_in(self, it) -> None:
        pass

    def has_params(self) -> bool:
        return True

    def n_inputs(self) -> int:
        """Activations a graph vertex hands this layer. More than one: the
        first is the layer's input, the rest reach its forward as
        `LayerContext.extra_inputs` (no MergeVertex is put in front)."""
        return 1


@dataclasses.dataclass(kw_only=True)
class BaseLayerConf(LayerConf):
    activation: Optional[str] = None
    weight_init: Optional[str] = None
    dist: Optional[dict] = None
    bias_init: Optional[float] = None
    l1: Optional[float] = None
    l2: Optional[float] = None
    learning_rate: Optional[float] = None
    bias_learning_rate: Optional[float] = None


@dataclasses.dataclass(kw_only=True)
class FeedForwardLayerConf(BaseLayerConf):
    n_in: Optional[int] = None
    n_out: int = 0

    def output_type(self, it):
        return FeedForwardInput(self.n_out)

    def infer_n_in(self, it) -> None:
        if self.n_in is None:
            self.n_in = it.arity()


@register_config("layer.dense")
@dataclasses.dataclass(kw_only=True)
class DenseLayer(FeedForwardLayerConf):
    """Fully connected layer (reference: nn/conf/layers/DenseLayer.java)."""


@register_config("layer.output")
@dataclasses.dataclass(kw_only=True)
class OutputLayer(FeedForwardLayerConf):
    """Dense + loss head (reference: nn/conf/layers/OutputLayer.java)."""

    loss: str = "mcxent"


@register_config("layer.rnn_output")
@dataclasses.dataclass(kw_only=True)
class RnnOutputLayer(FeedForwardLayerConf):
    """Time-distributed output layer (reference: RnnOutputLayer.java).
    Input [batch, time, nIn] -> [batch, time, nOut], loss summed over time
    (`sparse_mcxent` on integer labels `[batch, time]`: the mean over
    time). `has_bias=False` is a language model's bias-free head."""

    loss: str = "mcxent"
    has_bias: bool = True
    # ComputationGraph, `sparse_mcxent` with softmax: take the head and its
    # loss this many rows of the batch at a time inside the step, so the
    # whole batch's logits never exist at once (None: all rows at once).
    # MultiLayerNetwork refuses it
    head_rows_block: Optional[int] = None

    def output_type(self, it):
        ts = it.timesteps if isinstance(it, RecurrentInput) else None
        return RecurrentInput(self.n_out, ts)


@register_config("layer.center_loss_output")
@dataclasses.dataclass(kw_only=True)
class CenterLossOutputLayer(FeedForwardLayerConf):
    """Output layer with center-loss auxiliary term
    (reference: CenterLossOutputLayer.java: intra-class center pull)."""

    loss: str = "mcxent"
    alpha: float = 0.05
    lambda_: float = 2e-4

    def output_type(self, it):
        return FeedForwardInput(self.n_out)


@register_config("layer.loss")
@dataclasses.dataclass(kw_only=True)
class LossLayer(BaseLayerConf):
    """Parameterless loss head (reference: LossLayer.java)."""

    loss: str = "mcxent"

    def has_params(self):
        return False


@register_config("layer.activation")
@dataclasses.dataclass(kw_only=True)
class ActivationLayer(BaseLayerConf):
    """Standalone activation (reference: ActivationLayer.java)."""

    def has_params(self):
        return False


@register_config("layer.dropout")
@dataclasses.dataclass(kw_only=True)
class DropoutLayer(BaseLayerConf):
    """Standalone dropout (reference: DropoutLayer.java)."""

    def has_params(self):
        return False


@register_config("layer.embedding")
@dataclasses.dataclass(kw_only=True)
class EmbeddingLayer(FeedForwardLayerConf):
    """Index lookup layer (reference: EmbeddingLayer.java). Input: integer
    indices [batch] or [batch, 1]. On TPU the lookup compiles to a gather;
    a one-hot-matmul path is used under jit where gather scatter-grads are
    slow (see ops/embedding_ops).

    `host_resident=True` declares the table lives on the HOST (sharded
    across paramserver endpoints, rows pulled/pushed through
    parallel/sparse.SparseEmbeddingPipeline) rather than in device HBM —
    the residency audit (JX008) and dead-weight liveness (JX005) then
    exempt its weights from the per-chip memory picture."""

    has_bias: bool = True
    host_resident: bool = False


@register_config("layer.convolution")
@dataclasses.dataclass(kw_only=True)
class ConvolutionLayer(FeedForwardLayerConf):
    """2D convolution, NHWC (reference: nn/conf/layers/ConvolutionLayer.java;
    runtime im2col+gemm at nn/layers/convolution/ConvolutionLayer.java:177-201
    — here it lowers to XLA conv_general_dilated which tiles directly onto
    the MXU, no explicit im2col)."""

    kernel_size: Sequence[int] = (5, 5)
    stride: Sequence[int] = (1, 1)
    padding: Sequence[int] = (0, 0)
    convolution_mode: str = ConvolutionMode.TRUNCATE
    dilation: Sequence[int] = (1, 1)
    has_bias: bool = True

    def output_type(self, it):
        if not isinstance(it, ConvolutionalInput):
            raise ValueError(f"ConvolutionLayer needs convolutional input, got {it}")
        h = _conv_out(it.height, self.kernel_size[0], self.stride[0], self.padding[0], self.convolution_mode)
        w = _conv_out(it.width, self.kernel_size[1], self.stride[1], self.padding[1], self.convolution_mode)
        return ConvolutionalInput(h, w, self.n_out)

    def infer_n_in(self, it) -> None:
        if self.n_in is None and isinstance(it, ConvolutionalInput):
            self.n_in = it.channels


@register_config("layer.convolution1d")
@dataclasses.dataclass(kw_only=True)
class Convolution1DLayer(FeedForwardLayerConf):
    """1D convolution over time (reference: Convolution1DLayer.java).
    Input [batch, time, nIn] -> [batch, time', nOut]."""

    kernel_size: int = 5
    stride: int = 1
    padding: int = 0
    convolution_mode: str = ConvolutionMode.TRUNCATE
    has_bias: bool = True

    def output_type(self, it):
        if not isinstance(it, RecurrentInput):
            raise ValueError(f"Convolution1DLayer needs recurrent input, got {it}")
        ts = it.timesteps
        if ts is not None:
            ts = _conv_out(ts, self.kernel_size, self.stride, self.padding, self.convolution_mode)
        return RecurrentInput(self.n_out, ts)

    def infer_n_in(self, it) -> None:
        if self.n_in is None:
            self.n_in = it.size


@register_config("layer.subsampling")
@dataclasses.dataclass(kw_only=True)
class SubsamplingLayer(LayerConf):
    """2D pooling (reference: SubsamplingLayer.java; XLA reduce_window).

    A MAX pool whose windows tile its input (kernel == stride, nothing
    padded or cut off, a floating 4-D input) takes its gradient from an
    int8 argmax saved in the forward pass instead of XLA's
    select-and-scatter; every other pool differentiates reduce_window
    (nn/layers/conv._pool chooses from this configuration and the input's
    shape and dtype)."""

    pooling_type: str = PoolingType.MAX
    kernel_size: Sequence[int] = (2, 2)
    stride: Sequence[int] = (2, 2)
    padding: Sequence[int] = (0, 0)
    convolution_mode: str = ConvolutionMode.TRUNCATE
    pnorm: int = 2

    def has_params(self):
        return False

    def output_type(self, it):
        if not isinstance(it, ConvolutionalInput):
            raise ValueError(f"SubsamplingLayer needs convolutional input, got {it}")
        h = _conv_out(it.height, self.kernel_size[0], self.stride[0], self.padding[0], self.convolution_mode)
        w = _conv_out(it.width, self.kernel_size[1], self.stride[1], self.padding[1], self.convolution_mode)
        return ConvolutionalInput(h, w, it.channels)


@register_config("layer.subsampling1d")
@dataclasses.dataclass(kw_only=True)
class Subsampling1DLayer(LayerConf):
    """1D pooling over time (reference: Subsampling1DLayer.java)."""

    pooling_type: str = PoolingType.MAX
    kernel_size: int = 2
    stride: int = 2
    padding: int = 0
    convolution_mode: str = ConvolutionMode.TRUNCATE
    pnorm: int = 2

    def has_params(self):
        return False

    def output_type(self, it):
        ts = it.timesteps
        if ts is not None:
            ts = _conv_out(ts, self.kernel_size, self.stride, self.padding, self.convolution_mode)
        return RecurrentInput(it.size, ts)


@register_config("layer.batch_norm")
@dataclasses.dataclass(kw_only=True)
class BatchNormalization(BaseLayerConf):
    """Batch normalization (reference: nn/conf/layers/BatchNormalization.java;
    cuDNN helper in deeplearning4j-cuda — here a fused XLA computation).
    Normalizes over all axes except the last (channels/features)."""

    decay: float = 0.9
    eps: float = 1e-5
    gamma: float = 1.0  # init value
    beta: float = 0.0
    lock_gamma_beta: bool = False
    n_in: Optional[int] = None

    def infer_n_in(self, it) -> None:
        if self.n_in is None:
            self.n_in = it.channels if isinstance(it, ConvolutionalInput) else it.arity()


@register_config("layer.lrn")
@dataclasses.dataclass(kw_only=True)
class LocalResponseNormalization(LayerConf):
    """Cross-channel LRN (reference: LocalResponseNormalization.java,
    CudnnLocalResponseNormalizationHelper — here jnp window sum over the
    channel axis)."""

    k: float = 2.0
    n: float = 5.0
    alpha: float = 1e-4
    beta: float = 0.75

    def has_params(self):
        return False


@register_config("layer.zero_padding")
@dataclasses.dataclass(kw_only=True)
class ZeroPaddingLayer(LayerConf):
    """Spatial zero padding (reference: ZeroPaddingLayer.java).
    padding = (top, bottom, left, right)."""

    padding: Sequence[int] = (1, 1, 1, 1)

    def has_params(self):
        return False

    def output_type(self, it):
        pt, pb, pl, pr = self.padding
        return ConvolutionalInput(it.height + pt + pb, it.width + pl + pr, it.channels)


@register_config("layer.global_pooling")
@dataclasses.dataclass(kw_only=True)
class GlobalPoolingLayer(LayerConf):
    """Global pooling over spatial or time dims
    (reference: GlobalPoolingLayer.java). CNN input -> pool H,W;
    RNN input -> pool time (mask-aware)."""

    pooling_type: str = PoolingType.MAX
    pnorm: int = 2
    collapse_dimensions: bool = True

    def has_params(self):
        return False

    def output_type(self, it):
        if isinstance(it, ConvolutionalInput):
            return FeedForwardInput(it.channels)
        if isinstance(it, RecurrentInput):
            return FeedForwardInput(it.size)
        return it


@dataclasses.dataclass(kw_only=True)
class BaseRecurrentLayerConf(FeedForwardLayerConf):
    def output_type(self, it):
        ts = it.timesteps if isinstance(it, RecurrentInput) else None
        return RecurrentInput(self.n_out, ts)

    def infer_n_in(self, it) -> None:
        if self.n_in is None:
            self.n_in = it.size if isinstance(it, RecurrentInput) else it.arity()


@register_config("layer.lstm")
@dataclasses.dataclass(kw_only=True)
class LSTM(BaseRecurrentLayerConf):
    """LSTM without peepholes (reference: nn/conf/layers/LSTM.java;
    runtime LSTMHelpers.java — here a lax.scan over a fused gate matmul,
    with an optional Pallas kernel for the cell)."""

    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"


@register_config("layer.self_attention")
@dataclasses.dataclass(kw_only=True)
class SelfAttentionLayer(BaseRecurrentLayerConf):
    """Multi-head self-attention over the time axis — capability BEYOND
    the reference (DL4J 0.8 predates attention; SURVEY §5 lists
    long-context as greenfield). [b, t, nIn] -> [b, t, nOut]; nOut must
    be divisible by n_heads. ``causal`` masks future positions. The
    sequence-parallel execution of the same math is
    parallel/sequence.ring_self_attention."""

    n_heads: int = 4
    causal: bool = False
    projection_bias: bool = True

    def output_type(self, it):
        ts = it.timesteps if isinstance(it, RecurrentInput) else None
        return RecurrentInput(self.n_out, ts)


@register_config("layer.graves_lstm")
@dataclasses.dataclass(kw_only=True)
class GravesLSTM(BaseRecurrentLayerConf):
    """LSTM with peephole connections, Graves (2013) formulation
    (reference: GravesLSTM.java + LSTMHelpers.java:62,291)."""

    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"


@register_config("layer.graves_bidirectional_lstm")
@dataclasses.dataclass(kw_only=True)
class GravesBidirectionalLSTM(BaseRecurrentLayerConf):
    """Bidirectional peephole LSTM. Separate forward/backward parameter sets;
    the two directions' outputs are element-wise ADDED, so n_out stays n_out
    (reference: nn/layers/recurrent/GravesBidirectionalLSTM.java:205
    `fwdOutput.addi(backOutput)`)."""

    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"


@register_config("layer.autoencoder")
@dataclasses.dataclass(kw_only=True)
class AutoEncoder(FeedForwardLayerConf):
    """Denoising autoencoder (reference: nn/conf/layers/AutoEncoder.java,
    runtime nn/layers/feedforward/autoencoder/AutoEncoder.java). Supervised
    path behaves like a dense layer; unsupervised pretraining reconstructs
    corrupted input."""

    corruption_level: float = 0.3
    sparsity: float = 0.0
    loss: str = "mse"


@register_config("layer.rbm")
@dataclasses.dataclass(kw_only=True)
class RBM(FeedForwardLayerConf):
    """Restricted Boltzmann machine (reference: nn/conf/layers/RBM.java +
    nn/layers/feedforward/rbm/RBM.java — CD-k contrastive divergence with
    HiddenUnit/VisibleUnit types, :102,223-279). Supervised path behaves
    like a dense layer (propUp); unsupervised pretraining runs CD-k."""

    hidden_unit: str = "binary"  # binary | gaussian | rectified
    visible_unit: str = "binary"  # binary | gaussian
    k: int = 1  # CD-k Gibbs steps
    sparsity: float = 0.0


@register_config("layer.vae")
@dataclasses.dataclass(kw_only=True)
class VariationalAutoencoder(FeedForwardLayerConf):
    """VAE as a layer (reference: nn/conf/layers/variational/
    VariationalAutoencoder.java:40-54 — encoder/decoder MLP sizes, pluggable
    reconstruction distribution, ELBO objective; runtime impl 1,120 LoC)."""

    encoder_layer_sizes: List[int] = dataclasses.field(default_factory=lambda: [100])
    decoder_layer_sizes: List[int] = dataclasses.field(default_factory=lambda: [100])
    pzx_activation: str = "identity"
    reconstruction_distribution: Optional[dict] = None  # {"type": "gaussian"|"bernoulli", "activation": ...}
    num_samples: int = 1


@register_config("layer.frozen")
@dataclasses.dataclass(kw_only=True)
class FrozenLayer(LayerConf):
    """Wrapper marking an inner layer's params as non-trainable
    (reference: nn/layers/FrozenLayer.java, used by TransferLearning)."""

    inner: Optional[LayerConf] = None

    def output_type(self, it):
        return self.inner.output_type(it)

    def infer_n_in(self, it) -> None:
        self.inner.infer_n_in(it)

    def has_params(self):
        return self.inner.has_params()


# -- decoder-block vocabulary: token embedding, RMS norm, grouped-query
# attention, the Mamba-2 mixer, the sparse-expert layer ------------------------


@register_config("layer.embedding_sequence")
@dataclasses.dataclass(kw_only=True)
class EmbeddingSequenceLayer(FeedForwardLayerConf):
    """Token lookup over a sequence: integer ids `[batch, time]` ->
    `[batch, time, n_out]`, no bias. `n_in` is the vocabulary (the rows of
    the table), as `EmbeddingLayer`'s is. The result is in the network's
    compute dtype: this is where a net fed integers enters it."""

    def output_type(self, it):
        ts = it.timesteps if isinstance(
            it, (RecurrentInput, TokenSequenceInput)) else None
        return RecurrentInput(self.n_out, ts)

    def infer_n_in(self, it) -> None:
        if self.n_in is None and isinstance(it, TokenSequenceInput):
            self.n_in = it.vocab


@register_config("layer.rms_norm")
@dataclasses.dataclass(kw_only=True)
class RMSNorm(LayerConf):
    """`x * rsqrt(mean(x^2) + eps) * gamma` over the last axis, statistics
    in float32."""

    n_in: Optional[int] = None
    eps: float = 1e-5

    def infer_n_in(self, it) -> None:
        if self.n_in is None:
            self.n_in = it.size if isinstance(it, RecurrentInput) \
                else it.arity()


@register_config("layer.grouped_query_attention")
@dataclasses.dataclass(kw_only=True)
class GroupedQueryAttentionLayer(BaseRecurrentLayerConf):
    """Causal self-attention whose `n_heads` query heads share
    `n_kv_heads` key-value heads (each read by `n_heads // n_kv_heads`
    query heads), heads of `head_dim`; no bias. `[b, t, n_in] -> [b, t,
    n_out]`. With `n_kv_heads == n_heads` and `head_dim == n_out //
    n_heads` it computes what a bias-free `SelfAttentionLayer` does.

    `window` (positions; None: all that came before) lets the query at
    `p` see the keys `p - window + 1 .. p`, its own among them. `rope_theta`
    (None: no positional term) rotates queries and keys by their position
    `0 .. t - 1` over the whole `head_dim`, dimension `i` paired with `i +
    head_dim / 2` (the half-split pairing), at frequencies `rope_theta **
    (-2 i / head_dim)`. Both are a layer's own, so that one net may hold
    layers of either kind."""

    n_heads: int = 4
    n_kv_heads: int = 1
    head_dim: int = 64
    causal: bool = True
    window: Optional[int] = None
    rope_theta: Optional[float] = None


@register_config("layer.latent_attention")
@dataclasses.dataclass(kw_only=True)
class LatentAttentionLayer(BaseRecurrentLayerConf):
    """Causal multi-head attention whose keys and values come from one
    low-rank latent (the DeepSeek family's latent attention, no query
    bottleneck); no bias. `[b, t, n_in] -> [b, t, n_out]`:

        q  = u Wq                        n_heads x (qk_nope + qk_rope)
        c  = u Wkv_a                     [latent kv_lora_rank | k_rope]
        kv = RMSNorm(latent; kv_norm, eps) Wkv_b
                                         n_heads x (qk_nope k | v_head_dim v)
        k  = [k_nope | k_rope, the one rotary key, for every head]
        out = softmax_causal(q k^T (qk_nope + qk_rope)^-0.5) v Wo

    Queries and keys are `qk_nope_head_dim + qk_rope_head_dim` wide, values
    `v_head_dim`. `rope_theta` (None: no positional term) rotates the last
    `qk_rope_head_dim` dimensions of every query head and the shared
    `k_rope` by their position `0 .. t - 1` at frequencies `rope_theta **
    (-2 j / qk_rope_head_dim)`, over the adjacent pairs `(2 j, 2 j + 1)`
    (the family's `rope_interleave`)."""

    n_heads: int = 4
    qk_nope_head_dim: int = 32
    qk_rope_head_dim: int = 16
    v_head_dim: int = 32
    kv_lora_rank: int = 64
    rope_theta: Optional[float] = 1e4
    eps: float = 1e-6


@register_config("layer.gated_mlp")
@dataclasses.dataclass(kw_only=True)
class GatedMLPLayer(BaseRecurrentLayerConf):
    """The gated feed-forward `W_down(act(W_gate u) * (W_up u))` at every
    position, `n_in -> width -> n_out`, no bias: a decoder's dense MLP, or
    its shared experts as one MLP of their summed width."""

    width: int = 0


@register_config("layer.mamba2")
@dataclasses.dataclass(kw_only=True)
class Mamba2Layer(BaseRecurrentLayerConf):
    """Mamba-2 mixer (Dao & Gu 2024, state-space duality): in-projection to
    `[z | xBC | dt]`, causal depthwise conv (with bias) and silu over
    `xBC`, the recurrence `H_t = exp(dt_t A) H_(t-1) + dt_t x_t B_t^T`,
    `y_t = H_t C_t + D x_t` a head (computed in chunks of `chunk_size`),
    `GroupRMSNorm(y * silu(z))` over `n_groups` groups, out-projection.
    Head `h` reads the `B`, `C` of group `h // (n_heads // n_groups)`.
    `[b, t, n_in] -> [b, t, n_out]`."""

    n_heads: int = 8
    head_dim: int = 64
    state_size: int = 128
    n_groups: int = 1
    conv_kernel: int = 4
    chunk_size: int = 128
    norm_eps: float = 1e-5
    time_step_min: float = 1e-3
    time_step_max: float = 1e-1
    time_step_floor: float = 1e-4


@register_config("layer.sparse_experts")
@dataclasses.dataclass(kw_only=True)
class SparseExpertsLayer(BaseRecurrentLayerConf):
    """Sparse-expert feed-forward that is told which experts it holds.

    The router scores every token over all `router_width` experts
    (`score`: `sigmoid` or `softmax`, float32), picks the
    `experts_per_token` largest, weights them `scaling * s_i / sum of the
    chosen s` (under `softmax` that is the softmax over the chosen logits)
    and computes the part of the result that the experts in `experts_held`
    give (each `n_in -> width -> n_in`: `W2 act(W1 u)`, or with `gated`
    the three-matrix `W2 (act(W1 u) * (W3 u))`), plus one shared expert of
    `shared_width` for every token. What the experts held elsewhere would
    add is left out: on one chip the layer runs without its exchange.

    With `router_input` the layer holds no router of its own: the logits
    `[b, t, router_width]` arrive as the vertex's second input (an
    `ExpertRouterLayer` that may read another activation than the experts
    do, as in models whose router sits before the attention block).

    Dropless on static shapes: assignments to held experts are gathered,
    grouped by expert, into a buffer of `capacity_factor` times the uniform
    mean `tokens * experts_per_token / router_width` rows a held expert. No
    assignment is dropped: a step that sends a held expert more than its
    rows takes the exact dense path (`tokens / rows` times the products) and
    is counted (`experts_overflow_total`). The default is sized so that such
    steps are rare without load balancing: on uniform tokens the fullest
    held expert met 5.4 times the uniform mean within 27 steps of training
    (4,159 of 6,144 rows, `experts_buffer_fill` 0.68; PERF.md, PR 28), and
    the held experts of the worst layer together 3.5 times theirs, so that
    one buffer shared by the held experts would need nearly the same rows
    (PERF.md, PR 29, which also says why `lax.ragged_dot` over such a
    buffer did not replace this one). The layer's books (tokens routed to
    each expert, overflow, peak load and the buffer's rows) are layer
    state, published where the fit loop already blocks."""

    router_width: int = 8
    experts_held: Optional[List[int]] = None   # None: all of them
    experts_per_token: int = 2
    width: int = 0
    shared_width: int = 0
    scaling: float = 1.0
    capacity_factor: float = 8.0
    gated: bool = False
    score: str = "sigmoid"
    router_input: bool = False
    select_bias: bool = False

    def held(self) -> List[int]:
        return list(range(self.router_width)) if self.experts_held is None \
            else [int(e) for e in self.experts_held]

    def n_inputs(self) -> int:
        return 2 if self.router_input else 1


@register_config("layer.expert_router")
@dataclasses.dataclass(kw_only=True)
class ExpertRouterLayer(BaseRecurrentLayerConf):
    """The router of a sparse-expert layer as a vertex of its own: the
    logits `x W`, `[b, t, n_in] -> [b, t, n_out]` (`n_out` the router's
    width), in float32 at full precision whatever the net's compute type:
    near-ties among the largest would otherwise flip with the operands'
    rounding. A `SparseExpertsLayer(router_input=True)` takes them as its
    second input."""

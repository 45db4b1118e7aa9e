"""Core feed-forward layers: dense, output heads, embedding, activation,
dropout, the gated dense MLP, autoencoder.

Reference impls: nn/layers/feedforward/dense/DenseLayer.java (preOutput =
input·W + b then activation, BaseLayer.java), BaseOutputLayer.java,
feedforward/embedding/EmbeddingLayer.java, DropoutLayer, ActivationLayer,
feedforward/autoencoder/AutoEncoder.java.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.layers.registry import LayerContext, register_layer
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.ops.activations import apply_activation
from deeplearning4j_tpu.ops.pallas_conv_bn import take_fused_relu


def apply_dropout(x, retain_prob, ctx: LayerContext):
    """Inverted dropout on a layer's *input*, matching the reference
    (BaseLayer.preOutput applies Dropout.applyDropout to the input;
    `dropout` is the retain probability, util/Dropout.java)."""
    if not ctx.training or retain_prob is None or retain_prob <= 0.0 or retain_prob >= 1.0:
        return x
    if ctx.rng is None:
        return x
    keep = jax.random.bernoulli(ctx.rng, retain_prob, x.shape)
    return jnp.where(keep, x / retain_prob, 0.0)


# -- dense -------------------------------------------------------------------

def dense_init(key, conf: L.DenseLayer, dtype):
    kw, _ = jax.random.split(key)
    W = init_weights(kw, (conf.n_in, conf.n_out), conf.n_in, conf.n_out,
                     conf.weight_init, conf.dist, dtype)
    b = jnp.full((conf.n_out,), conf.bias_init or 0.0, dtype)
    return {"W": W, "b": b}


def dense_forward(conf, params, x, ctx: LayerContext):
    x = apply_dropout(x, conf.dropout, ctx)
    z = x @ params["W"] + params["b"]
    return apply_activation(conf.activation, z, key=ctx.rng, training=ctx.training), None


register_layer(L.DenseLayer, dense_init, dense_forward)


# -- output heads ------------------------------------------------------------
# OutputLayer / RnnOutputLayer forward = dense + activation; the loss is
# applied by the network (reference: BaseOutputLayer.computeScore uses the
# layer's preOutput). RnnOutputLayer applies the same W time-distributed.

register_layer(L.OutputLayer, dense_init, dense_forward)


def rnn_output_init(key, conf: L.RnnOutputLayer, dtype):
    p = dense_init(key, conf, dtype)
    if not conf.has_bias:
        del p["b"]
    return p


def rnn_output_preout(params, x):
    # x: [batch, time, nIn] — einsum keeps the time axis batched for the MXU
    z = jnp.einsum("bti,io->bto", x, params["W"])
    return z + params["b"] if "b" in params else z


def rnn_output_forward(conf, params, x, ctx: LayerContext):
    return apply_activation(conf.activation, rnn_output_preout(params, x),
                            key=ctx.rng, training=ctx.training), None


def rnn_output_order(conf):
    return ("W", "b") if conf.has_bias else ("W",)


register_layer(L.RnnOutputLayer, rnn_output_init, rnn_output_forward,
               order_fn=rnn_output_order)


def center_loss_init(key, conf: L.CenterLossOutputLayer, dtype):
    return dense_init(key, conf, dtype)


def center_loss_state(conf: L.CenterLossOutputLayer, dtype):
    # per-class feature centers, EMA-updated outside the gradient
    # (reference: CenterLossOutputLayer / CenterLossParamInitializer 'cL')
    return {"centers": jnp.zeros((conf.n_out, conf.n_in), dtype)}


register_layer(L.CenterLossOutputLayer, center_loss_init, dense_forward,
               state_fn=center_loss_state)


# -- activation / dropout / loss (parameterless) -----------------------------

def _no_params(key, conf, dtype):
    return {}


def activation_forward(conf, params, x, ctx: LayerContext):
    if conf.activation == "relu":
        # deferred-ReLU hook of the Pallas conv/BN epilogue fusion: when x
        # is a stashed fused-BN output, swap in the normalize+ReLU variant
        # of that kernel (the plain-normalize call is then dead code and
        # XLA eliminates it) instead of a separate elementwise pass
        fused = take_fused_relu(x)
        if fused is not None:
            return fused, None
    return apply_activation(conf.activation, x, key=ctx.rng, training=ctx.training), None


register_layer(L.ActivationLayer, _no_params, activation_forward)


def dropout_forward(conf, params, x, ctx: LayerContext):
    return apply_dropout(x, conf.dropout, ctx), None


register_layer(L.DropoutLayer, _no_params, dropout_forward)


def loss_layer_forward(conf, params, x, ctx: LayerContext):
    return apply_activation(conf.activation, x, key=ctx.rng, training=ctx.training), None


register_layer(L.LossLayer, _no_params, loss_layer_forward)


# -- embedding ---------------------------------------------------------------

def embedding_init(key, conf: L.EmbeddingLayer, dtype):
    kw, _ = jax.random.split(key)
    W = init_weights(kw, (conf.n_in, conf.n_out), conf.n_in, conf.n_out,
                     conf.weight_init, conf.dist, dtype)
    out = {"W": W}
    if conf.has_bias:
        out["b"] = jnp.full((conf.n_out,), conf.bias_init or 0.0, dtype)
    return out


def embedding_forward(conf, params, x, ctx: LayerContext):
    """x: integer indices [batch] or [batch, 1] (reference:
    EmbeddingLayer.java — one-hot-equivalent lookup). XLA lowers the gather
    + scatter-add gradient natively on TPU."""
    idx = x.astype(jnp.int32)
    if idx.ndim == 2 and idx.shape[-1] == 1:
        idx = idx[:, 0]
    z = jnp.take(params["W"], idx, axis=0)
    if conf.has_bias:
        z = z + params["b"]
    return apply_activation(conf.activation, z, key=ctx.rng, training=ctx.training), None


def embedding_order(conf):
    return ("W", "b") if conf.has_bias else ("W",)


register_layer(L.EmbeddingLayer, embedding_init, embedding_forward, order_fn=embedding_order)


def embedding_sequence_init(key, conf: L.EmbeddingSequenceLayer, dtype):
    return {"W": init_weights(key, (conf.n_in, conf.n_out), conf.n_in,
                              conf.n_out, conf.weight_init, conf.dist, dtype)}


def embedding_sequence_forward(conf, params, x, ctx: LayerContext):
    """x: integer ids [batch, time] -> rows of the table [batch, time,
    n_out], in the table's dtype: the residual stream of a decoder starts
    here in the parameters' float32, whatever the matrix products use."""
    if not jnp.issubdtype(x.dtype, jnp.integer):
        raise TypeError(
            f"EmbeddingSequenceLayer takes integer ids, got {x.dtype}: a "
            "float cast has already rounded them (bf16 holds integers "
            "exactly only up to 256)")
    return jnp.take(params["W"], x, axis=0), None


register_layer(L.EmbeddingSequenceLayer, embedding_sequence_init,
               embedding_sequence_forward, order_fn=lambda conf: ("W",))


# -- gated dense MLP -----------------------------------------------------------

def gated_mlp_init(key, conf: L.GatedMLPLayer, dtype):
    n_in, n_out, width = int(conf.n_in), int(conf.n_out), int(conf.width)
    ks = jax.random.split(key, 3)
    mk = lambda k, i, o: init_weights(k, (i, o), i, o, conf.weight_init,
                                      conf.dist, dtype)
    return {"W_gate": mk(ks[0], n_in, width), "W_up": mk(ks[1], n_in, width),
            "W_down": mk(ks[2], width, n_out)}


def gated_mlp_forward(conf: L.GatedMLPLayer, params, x, ctx: LayerContext):
    """x: [..., n_in] -> [..., n_out] in x's dtype: `W_down(act(W_gate u) *
    (W_up u))`, the three products in the net's compute dtype with float32
    accumulation, the activation and the gate's product in float32."""
    cd = ctx.compute_dtype or x.dtype
    mm = lambda a, name: jnp.matmul(a, params[name].astype(cd),
                                    preferred_element_type=jnp.float32)
    u = x.astype(cd)
    hidden = apply_activation(conf.activation, mm(u, "W_gate")) \
        * mm(u, "W_up")
    return mm(hidden.astype(cd), "W_down").astype(x.dtype), None


register_layer(L.GatedMLPLayer, gated_mlp_init, gated_mlp_forward,
               order_fn=lambda conf: ("W_gate", "W_up", "W_down"))


# -- autoencoder (supervised path) ------------------------------------------

def autoencoder_init(key, conf: L.AutoEncoder, dtype):
    kw, _ = jax.random.split(key)
    W = init_weights(kw, (conf.n_in, conf.n_out), conf.n_in, conf.n_out,
                     conf.weight_init, conf.dist, dtype)
    b = jnp.full((conf.n_out,), conf.bias_init or 0.0, dtype)
    vb = jnp.zeros((conf.n_in,), dtype)  # visible bias for reconstruction
    return {"W": W, "b": b, "vb": vb}


def autoencoder_forward(conf, params, x, ctx: LayerContext):
    x = apply_dropout(x, conf.dropout, ctx)
    z = x @ params["W"] + params["b"]
    return apply_activation(conf.activation, z, key=ctx.rng, training=ctx.training), None


def autoencoder_reconstruct(conf, params, x, ctx: LayerContext, corrupt: bool = True):
    """Unsupervised pass: corrupt -> encode -> decode with tied weights
    (reference: AutoEncoder.java decode uses W^T + visible bias)."""
    h_in = x
    if corrupt and ctx.training and ctx.rng is not None and conf.corruption_level > 0:
        keep = jax.random.bernoulli(ctx.rng, 1.0 - conf.corruption_level, x.shape)
        h_in = jnp.where(keep, x, 0.0)
    h = apply_activation(conf.activation, h_in @ params["W"] + params["b"])
    recon = apply_activation(conf.activation, h @ params["W"].T + params["vb"])
    return recon


def autoencoder_order(conf):
    return ("W", "b", "vb")


register_layer(L.AutoEncoder, autoencoder_init, autoencoder_forward,
               order_fn=autoencoder_order)

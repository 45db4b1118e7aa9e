"""Activation functions.

Covers the full activation enum the reference's config DSL accepts
(org.nd4j.linalg.activations.Activation, accepted by
NeuralNetConfiguration.Builder.activation(...) — see
deeplearning4j-nn/.../nn/conf/NeuralNetConfiguration.java and the
gradient-check whitelist at gradientcheck/GradientCheckUtil.java:48-59),
plus an SPI for custom activations (the reference's IActivation).

All functions are pure jnp element-wise maps; XLA fuses them into the
surrounding matmul/conv so there is no per-op dispatch cost. RReLU's random
alpha at train time needs an rng key, so activation_fn takes an optional key.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

# name -> fn(x, key=None, training=False) -> jnp.ndarray
_REGISTRY: Dict[str, Callable] = {}


def register_activation(name: str, fn: Callable) -> None:
    """Custom-activation SPI (reference: IActivation implementations)."""
    _REGISTRY[name.lower()] = fn


def _simple(name):
    def deco(fn):
        register_activation(name, lambda x, key=None, training=False: fn(x))
        return fn

    return deco


@_simple("identity")
def identity(x):
    return x


@_simple("sigmoid")
def sigmoid(x):
    return jax.nn.sigmoid(x)


@_simple("tanh")
def tanh(x):
    return jnp.tanh(x)


@_simple("relu")
def relu(x):
    return jax.nn.relu(x)


@_simple("relu2")
def relu2(x):
    """Squared ReLU (Primer; the expert activation of the nemotron_h family)."""
    r = jax.nn.relu(x)
    return r * r


@_simple("relu6")
def relu6(x):
    return jax.nn.relu6(x)


@_simple("leakyrelu")
def leakyrelu(x):
    # Reference default alpha 0.01 (ActivationLReLU.DEFAULT_ALPHA)
    return jax.nn.leaky_relu(x, negative_slope=0.01)


@_simple("elu")
def elu(x):
    return jax.nn.elu(x)


@_simple("selu")
def selu(x):
    return jax.nn.selu(x)


@_simple("softplus")
def softplus(x):
    return jax.nn.softplus(x)


@_simple("softsign")
def softsign(x):
    return jax.nn.soft_sign(x)


@_simple("hardtanh")
def hardtanh(x):
    return jnp.clip(x, -1.0, 1.0)


@_simple("hardsigmoid")
def hardsigmoid(x):
    # Reference ActivationHardSigmoid: clip(0.2*x + 0.5, 0, 1)
    return jnp.clip(0.2 * x + 0.5, 0.0, 1.0)


@_simple("cube")
def cube(x):
    return x * x * x


@_simple("rationaltanh")
def rationaltanh(x):
    # Reference ActivationRationalTanh: 1.7159 * tanh_approx(2x/3) where
    # tanh_approx(y) = sign(y) * (1 - 1/(1+|y|+y^2+1.41645*y^4))
    y = 2.0 * x / 3.0
    a = jnp.abs(y)
    approx = jnp.sign(y) * (1.0 - 1.0 / (1.0 + a + y * y + 1.41645 * (y**4)))
    return 1.7159 * approx


@_simple("rectifiedtanh")
def rectifiedtanh(x):
    return jnp.maximum(0.0, jnp.tanh(x))


@_simple("silu")   # the name the transformer families' configs give it
@_simple("swish")
def swish(x):
    return jax.nn.silu(x)


@_simple("gelu")
def gelu(x):
    return jax.nn.gelu(x)


@_simple("mish")
def mish(x):
    return x * jnp.tanh(jax.nn.softplus(x))


@_simple("thresholdedrelu")
def thresholdedrelu(x):
    return jnp.where(x > 1.0, x, 0.0)


@_simple("softmax")
def softmax(x):
    # Row softmax over the feature axis (last axis), matching the reference's
    # 2d [batch, nOut] / time-distributed conventions.
    return jax.nn.softmax(x, axis=-1)


@_simple("logsoftmax")
def logsoftmax(x):
    return jax.nn.log_softmax(x, axis=-1)


def _rrelu(x, key=None, training=False, lower=1.0 / 8.0, upper=1.0 / 3.0):
    """Randomized leaky ReLU (reference ActivationRReLU: U[l,u] alpha when
    training, (l+u)/2 at inference)."""
    if training and key is not None:
        alpha = jax.random.uniform(key, x.shape, minval=lower, maxval=upper, dtype=x.dtype)
    else:
        alpha = (lower + upper) / 2.0
    return jnp.where(x >= 0, x, alpha * x)


register_activation("rrelu", _rrelu)


class Activation:
    """Enum-style names (string constants) mirroring the reference enum."""

    CUBE = "cube"
    ELU = "elu"
    HARDSIGMOID = "hardsigmoid"
    HARDTANH = "hardtanh"
    IDENTITY = "identity"
    LEAKYRELU = "leakyrelu"
    RATIONALTANH = "rationaltanh"
    RECTIFIEDTANH = "rectifiedtanh"
    RELU = "relu"
    RRELU = "rrelu"
    SELU = "selu"
    SIGMOID = "sigmoid"
    SOFTMAX = "softmax"
    SOFTPLUS = "softplus"
    SOFTSIGN = "softsign"
    SWISH = "swish"
    GELU = "gelu"
    TANH = "tanh"


def activation_fn(name: str) -> Callable:
    """Look up an activation by name. Returned callable has signature
    fn(x, key=None, training=False)."""
    try:
        return _REGISTRY[name.lower()]
    except KeyError:
        raise ValueError(
            f"unknown activation {name!r}; known: {sorted(_REGISTRY)}"
        ) from None


def apply_activation(name: str, x, key: Optional[jax.Array] = None, training: bool = False):
    return activation_fn(name)(x, key=key, training=training)

"""Loss functions.

Covers the reference's LossFunctions.LossFunction enum and ILossFunction SPI
(used throughout deeplearning4j-nn; the full implementation set is exercised
by LossFunctionGradientCheck.java). Signature follows the reference's
ILossFunction contract: a loss sees the layer's *pre-output* (logits) plus
the output activation, which lets us fuse softmax+cross-entropy into the
numerically stable log-softmax form — the TPU-friendly formulation — instead
of computing probabilities first the way the reference does.

All functions return a per-example score vector of shape [batch]; the
network averages over the batch (reference: BaseOutputLayer.computeScore
sums then divides by minibatch). Masks multiply per-element scores before
the feature-axis reduction (reference: LossUtil / masked score arrays).

Gradients are never hand-written: jax.grad differentiates through these.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.activations import apply_activation

_EPS = 1e-8

# name -> fn(labels, preout, activation, mask) -> per-example score [batch]
_REGISTRY: Dict[str, Callable] = {}


def register_loss(name: str, fn: Callable) -> None:
    """Custom-loss SPI (reference: ILossFunction implementations)."""
    _REGISTRY[name.lower()] = fn


def _reduce(per_elem, mask):
    """Apply an element mask then sum over all non-batch axes."""
    if mask is not None:
        # mask may be [batch], [batch, 1] or full element shape; broadcast.
        while mask.ndim < per_elem.ndim:
            mask = mask[..., None]
        per_elem = per_elem * mask
    axes = tuple(range(1, per_elem.ndim))
    return jnp.sum(per_elem, axis=axes) if axes else per_elem


def _out(preout, activation):
    return apply_activation(activation, preout)


def _loss(name):
    def deco(fn):
        register_loss(name, fn)
        return fn

    return deco


@_loss("mse")
def mse(labels, preout, activation, mask=None):
    out = _out(preout, activation)
    d = out - labels
    n = labels.shape[-1]
    return _reduce(d * d, mask) / n


@_loss("l2")
def l2(labels, preout, activation, mask=None):
    # Reference LossL2 = sum of squared errors (no 1/n)
    out = _out(preout, activation)
    d = out - labels
    return _reduce(d * d, mask)


@_loss("l1")
def l1(labels, preout, activation, mask=None):
    out = _out(preout, activation)
    return _reduce(jnp.abs(out - labels), mask)


@_loss("mean_absolute_error")
def mean_absolute_error(labels, preout, activation, mask=None):
    return l1(labels, preout, activation, mask) / labels.shape[-1]


@_loss("mean_absolute_percentage_error")
def mape(labels, preout, activation, mask=None):
    out = _out(preout, activation)
    per = jnp.abs((labels - out) / (labels + _EPS)) * 100.0
    return _reduce(per, mask) / labels.shape[-1]


@_loss("mean_squared_logarithmic_error")
def msle(labels, preout, activation, mask=None):
    out = _out(preout, activation)
    d = jnp.log1p(out) - jnp.log1p(labels)
    return _reduce(d * d, mask) / labels.shape[-1]


@_loss("xent")
def xent(labels, preout, activation, mask=None):
    """Binary cross-entropy. Stable path when activation is sigmoid:
    computed from logits directly."""
    if activation == "sigmoid":
        # log(sigmoid(z)) = -softplus(-z); log(1-sigmoid(z)) = -softplus(z)
        per = labels * jax.nn.softplus(-preout) + (1.0 - labels) * jax.nn.softplus(preout)
    else:
        out = _out(preout, activation)
        out = jnp.clip(out, _EPS, 1.0 - _EPS)
        per = -(labels * jnp.log(out) + (1.0 - labels) * jnp.log(1.0 - out))
    return _reduce(per, mask)


@_loss("mcxent")
def mcxent(labels, preout, activation, mask=None):
    """Multi-class cross-entropy. Fused log-softmax path when the output
    activation is softmax (the common OutputLayer configuration)."""
    if activation == "softmax":
        logp = jax.nn.log_softmax(preout, axis=-1)
    else:
        out = _out(preout, activation)
        logp = jnp.log(jnp.clip(out, _EPS, None))
    return _reduce(-labels * logp, mask)


def _token_mean(per_token, mask):
    """[batch, ...] per-position losses -> [batch]: the mean over an
    example's positions (over its unmasked ones under a mask)."""
    if per_token.ndim == 1:
        return per_token if mask is None else per_token * mask.reshape(-1)
    axes = tuple(range(1, per_token.ndim))
    if mask is None:
        return jnp.mean(per_token, axis=axes)
    m = mask.astype(per_token.dtype).reshape(per_token.shape)
    return jnp.sum(per_token * m, axis=axes) \
        / jnp.maximum(jnp.sum(m, axis=axes), 1.0)


@_loss("sparse_mcxent")
def sparse_mcxent(labels, preout, activation, mask=None):
    """Multi-class cross-entropy on INTEGER labels: `labels` holds the
    class of each row, `[batch]` against `preout [batch, classes]` or
    `[batch, time]` against `[batch, time, classes]`; no one-hot tensor is
    built. Log-softmax in float32, then the label's entry. For `[batch]`
    labels it equals `mcxent` on their one-hot rows; over a sequence it is
    the MEAN over the example's positions (next-token loss: the mean over
    `batch * time`), where `mcxent` sums over time."""
    if not jnp.issubdtype(labels.dtype, jnp.integer):
        raise TypeError(f"sparse_mcxent takes integer labels, got "
                        f"{labels.dtype}")
    z = preout.astype(jnp.promote_types(preout.dtype, jnp.float32))
    if activation == "softmax":
        logp = jax.nn.log_softmax(z, axis=-1)
    else:
        logp = jnp.log(jnp.clip(_out(z, activation), _EPS, None))
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return _token_mean(-picked, mask)


def sparse_head_loss(features, params, labels, mask=None, *, rows_block: int,
                     compute_dtype=None):
    """A bias-or-not linear head and `sparse_mcxent` (softmax) on it, taken
    `rows_block` rows of the batch at a time: `features [batch, time, n_in]`
    times `params["W"] [n_in, classes]` (operands in `compute_dtype`,
    float32 accumulation) against integer `labels [batch, time]`, to the
    per-example loss `[batch]`. Each block runs under `jax.checkpoint`
    inside a `lax.map`, so one block's logits (and their gradient) live at
    a time: `[4, 4096, 16384]` float32 logits are 1.07 GB, a row of them a
    quarter of that. The same numbers as the whole batch at once."""
    batch = features.shape[0]
    if batch % rows_block:
        raise ValueError(f"head_rows_block {rows_block} does not divide the "
                         f"batch of {batch} rows")
    cd = compute_dtype or features.dtype
    w = params["W"].astype(cd)
    b = params.get("b")

    def block(args):
        x, y, m = args
        z = jnp.einsum("bti,io->bto", x.astype(cd), w,
                       preferred_element_type=jnp.float32)
        if b is not None:
            z = z + b.astype(jnp.float32)
        return sparse_mcxent(y, z, "softmax", m)

    split = lambda a: None if a is None else a.reshape(
        (batch // rows_block, rows_block) + a.shape[1:])
    if mask is not None and mask.ndim == 1:
        mask = jnp.broadcast_to(mask[:, None], labels.shape)
    per_ex = jax.lax.map(jax.checkpoint(block),
                         (split(features), split(labels), split(mask)))
    return per_ex.reshape(batch)


@_loss("negativeloglikelihood")
def negativeloglikelihood(labels, preout, activation, mask=None):
    # Reference LossNegativeLogLikelihood extends LossMCXENT.
    return mcxent(labels, preout, activation, mask)


@_loss("kl_divergence")
def kl_divergence(labels, preout, activation, mask=None):
    out = _out(preout, activation)
    out = jnp.clip(out, _EPS, 1.0 - _EPS)
    lab = jnp.clip(labels, _EPS, 1.0 - _EPS)
    return _reduce(lab * (jnp.log(lab) - jnp.log(out)), mask)


@_loss("reconstruction_crossentropy")
def reconstruction_crossentropy(labels, preout, activation, mask=None):
    return xent(labels, preout, activation, mask)


@_loss("cosine_proximity")
def cosine_proximity(labels, preout, activation, mask=None):
    out = _out(preout, activation)
    if mask is not None:
        m = mask
        while m.ndim < out.ndim:
            m = m[..., None]
        out = out * m
        labels = labels * m
    dot = jnp.sum(labels * out, axis=-1)
    norm = jnp.linalg.norm(labels, axis=-1) * jnp.linalg.norm(out, axis=-1)
    cos = dot / jnp.maximum(norm, _EPS)
    # reduce any remaining time axes
    while cos.ndim > 1:
        cos = jnp.sum(cos, axis=-1)
    return -cos


@_loss("hinge")
def hinge(labels, preout, activation, mask=None):
    # labels in {-1, 1}
    out = _out(preout, activation)
    return _reduce(jnp.maximum(0.0, 1.0 - labels * out), mask)


@_loss("squared_hinge")
def squared_hinge(labels, preout, activation, mask=None):
    out = _out(preout, activation)
    h = jnp.maximum(0.0, 1.0 - labels * out)
    return _reduce(h * h, mask)


@_loss("poisson")
def poisson(labels, preout, activation, mask=None):
    out = _out(preout, activation)
    return _reduce(out - labels * jnp.log(jnp.clip(out, _EPS, None)), mask)


@_loss("squared_loss")
def squared_loss(labels, preout, activation, mask=None):
    return l2(labels, preout, activation, mask)


@_loss("rmse_xent")
def rmse_xent(labels, preout, activation, mask=None):
    # Reference legacy LossFunction; implemented as sqrt of per-example SSE.
    out = _out(preout, activation)
    d = out - labels
    return jnp.sqrt(_reduce(d * d, mask) + _EPS)


class LossFunction:
    """Enum-style names mirroring LossFunctions.LossFunction."""

    MSE = "mse"
    L1 = "l1"
    L2 = "l2"
    XENT = "xent"
    MCXENT = "mcxent"
    SPARSE_MCXENT = "sparse_mcxent"
    SQUARED_LOSS = "squared_loss"
    RECONSTRUCTION_CROSSENTROPY = "reconstruction_crossentropy"
    NEGATIVELOGLIKELIHOOD = "negativeloglikelihood"
    COSINE_PROXIMITY = "cosine_proximity"
    HINGE = "hinge"
    SQUARED_HINGE = "squared_hinge"
    KL_DIVERGENCE = "kl_divergence"
    MEAN_ABSOLUTE_ERROR = "mean_absolute_error"
    MEAN_ABSOLUTE_PERCENTAGE_ERROR = "mean_absolute_percentage_error"
    MEAN_SQUARED_LOGARITHMIC_ERROR = "mean_squared_logarithmic_error"
    POISSON = "poisson"
    RMSE_XENT = "rmse_xent"


def example_presence(per_ex, mask: Optional[jax.Array]):
    """[batch] 0/1 presence from a labels mask: an example whose mask is
    all-zero (a pad row from ParallelWrapper's pad-and-mask tail handling)
    is absent. None mask -> all present."""
    if mask is None:
        return jnp.ones(per_ex.shape[0], per_ex.dtype)
    m = mask
    while m.ndim > 1:
        m = jnp.max(m, axis=-1)
    return (m > 0).astype(per_ex.dtype)


def masked_example_mean(per_ex, mask: Optional[jax.Array]):
    """Mean of per-example losses over PRESENT examples only. Identical to
    jnp.mean when no example is fully masked; excludes zero-mask pad rows
    so a padded tail batch yields exactly the unpadded score/gradients.

    Intentional deviation from the reference: DL4J divides by the full
    batch count even when sequences are fully masked, so batches with
    more padding train with a silently smaller effective lr. Dividing by
    the present count keeps the per-REAL-example gradient scale constant
    across batches — and is what makes ParallelWrapper's pad-and-mask
    tail numerically exact."""
    if mask is None:
        return jnp.mean(per_ex)
    present = example_presence(per_ex, mask)
    return jnp.sum(per_ex * present) / jnp.maximum(jnp.sum(present), 1.0)


def loss_value(name: str, labels, preout, activation: str, mask: Optional[jax.Array] = None):
    """Per-example loss [batch] for the named loss function."""
    try:
        fn = _REGISTRY[name.lower()]
    except KeyError:
        raise ValueError(f"unknown loss {name!r}; known: {sorted(_REGISTRY)}") from None
    return fn(labels, preout, activation, mask)

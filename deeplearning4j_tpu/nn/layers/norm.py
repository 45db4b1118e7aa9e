"""Normalization layers: batch normalization and local response normalization.

Reference impls: nn/layers/normalization/BatchNormalization.java (+
CudnnBatchNormalizationHelper) and LocalResponseNormalization.java (+ cuDNN
helper). Both compile to fused XLA element-wise/reduction code here; no
helper SPI required for the base path.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.layers.registry import LayerContext, register_layer
from deeplearning4j_tpu.ops.activations import apply_activation
from deeplearning4j_tpu.ops.helpers import HelperError, get_helper


# -- batch normalization -----------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _bn_train(x, gamma, beta, eps):
    """Fused training-mode batch norm with a hand-written VJP.

    jnp.mean/jnp.var upcast sub-f32 inputs to f32 internally, and autodiff
    of that pattern drags f32 activation-sized cotangents through the whole
    backward pass (2x HBM traffic on a bandwidth-bound op — measured 15%
    vs 40%+ train-step MFU on ResNet-50/v5e). Here every full-size tensor
    stays in x.dtype; only per-channel statistics are f32.
    """
    y, _, mean, var = _bn_train_fwd_res(x, gamma, beta, eps)
    return y, mean, var


def _acc_dtype(dtype):
    """Statistics accumulator dtype: f32, or f64 when the network itself
    runs f64 (the gradient-check configuration)."""
    return jnp.promote_types(dtype, jnp.float32)


def _sum_to_f32(x2, n):
    """Column sums of a [n, c] tensor with f32 accumulation WITHOUT an
    explicit upcast: a dot against a ones vector with
    preferred_element_type=f32. Crucial on TPU: reduce(convert(x)) makes
    XLA's bf16-propagation keep the PRODUCER of x (the conv output) in
    f32, doubling HBM traffic for the whole residual trunk — the dot
    keeps every stored tensor bf16 and runs the accumulation on the MXU."""
    ones = jnp.ones((n,), x2.dtype)
    return lax.dot_general(
        ones, x2, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _bn_stats(x):
    """Per-channel mean/var in the accumulator dtype. bf16 inputs use a
    centered two-pass MXU-dot reduction (f32 accumulation, no full-size
    f32 tensor); f32/f64 (gradient-check) inputs use the plain stable
    two-pass form."""
    if x.dtype == jnp.bfloat16:
        mean, var, _, _ = _bn_stats_centered(x)
        return mean, var
    axes = tuple(range(x.ndim - 1))
    xf = x.astype(_acc_dtype(x.dtype))
    mean = jnp.mean(xf, axis=axes)
    var = jnp.mean(jnp.square(xf - mean), axis=axes)
    return mean, var


def _bn_stats_centered(x):
    """bf16 statistics without catastrophic cancellation: first pass gets
    the mean (bf16 dot, f32 accumulation); xc = x - bf16(mean) is EXACT in
    bf16 wherever x is within 2x of the mean (Sterbenz), so the residual
    terms E[xc^2] and E[xc] are both small and their difference is safe in
    f32 — unlike raw E[x^2]-E[x]^2, which loses everything for
    large-mean/small-variance channels. Returns (mean, var, xc, delta)
    with mean = true mean (f32), delta = mean - bf16(mean) so that
    x - mean == xc - delta."""
    c = x.shape[-1]
    n = x.size // c
    x2 = x.reshape(n, c)
    mean = _sum_to_f32(x2, n) / n
    mean_b = mean.astype(x.dtype)
    xc = x - jnp.broadcast_to(mean_b, x.shape)
    xc2 = xc.reshape(n, c)
    mu_r = _sum_to_f32(xc2, n) / n            # == delta up to f32 rounding
    var = jnp.maximum(_sum_to_f32(xc2 * xc2, n) / n - mu_r * mu_r, 0.0)
    delta = mean - mean_b.astype(jnp.float32)
    return mean, var, xc, delta


def _bn_train_fwd_res(x, gamma, beta, eps):
    acc = _acc_dtype(x.dtype)
    if x.dtype == jnp.bfloat16:
        mean, var, xc, delta = _bn_stats_centered(x)
        inv = lax.rsqrt(var + eps)
        scale = gamma.astype(acc) * inv
        # y = scale*(x - mean) + beta = scale*(xc - delta) + beta
        shift = beta.astype(acc) - delta * scale
        y = xc * scale.astype(x.dtype) + shift.astype(x.dtype)
        # residual saves X (already materialized as the producing conv's
        # output) + the bf16 mean, NOT xc: the backward recomputes
        # xc = x - bf16(mean) in-register, bit-identical (bf16 subtract
        # is deterministic). Measured NEUTRAL on the ResNet-50 bench
        # (48.8 ms/step either way — XLA rematerializes the centered
        # tensor itself); kept because it states the true data
        # dependency instead of relying on that remat
        return y, (x, gamma, mean.astype(x.dtype), delta, inv), mean, var
    mean, var = _bn_stats(x)
    inv = lax.rsqrt(var + eps)
    scale = gamma.astype(acc) * inv
    shift = beta.astype(acc) - mean * scale
    y = x * scale.astype(x.dtype) + shift.astype(x.dtype)
    return y, (x, gamma, mean, None, inv), mean, var


def _bn_train_fwd(x, gamma, beta, eps):
    y, res, mean, var = _bn_train_fwd_res(x, gamma, beta, eps)
    return (y, mean, var), res


def _bn_train_bwd(eps, res, cts):
    """Standard BN backward, per-channel coefficients in f32, full-size
    math in x.dtype. The mean/var outputs feed the (non-trainable) running
    EMA only, so their cotangents are dropped — matching the reference,
    where global stats never receive gradient
    (BatchNormalization.java running mean/var are state, not params)."""
    g, _, _ = cts
    x, gamma, mean_saved, delta, inv = res
    g = g.astype(x.dtype)
    c = x.shape[-1]
    n = x.size // c
    acc = _acc_dtype(x.dtype)
    if x.dtype == jnp.bfloat16:
        # recompute xc = x - bf16(mean) in-register (see fwd residual
        # note); center = delta so x - mean == xc - delta and sums of
        # g*xc stay small — no large-mean cancellation in sum_gx
        xc = x - jnp.broadcast_to(mean_saved, x.shape)
        center = delta
        x_for_dx = xc
    else:
        center = mean_saved
        x_for_dx = x
    # fused Pallas pullback when registered + supported: one reduce pass
    # (both per-channel sums) + one apply pass instead of three separate
    # XLA re-reads of the saved activation; same kill-switch/auto-disable
    # containment as the forward helpers — a raising kernel disables
    # itself and the builtin reductions below finish the same backward
    helper = get_helper("bn_backward", x_shape=tuple(x.shape),
                        dtype=x.dtype, training=True)
    if helper is not None:
        try:
            dx, dgamma, dbeta = helper(g, x_for_dx, center, gamma, inv, n)
            return dx, dgamma.astype(gamma.dtype), dbeta.astype(gamma.dtype)
        except HelperError:
            pass  # helper auto-disabled itself; builtin path below
    if x.dtype == jnp.bfloat16:
        g2 = g.reshape(n, c)
        x2 = x_for_dx.reshape(n, c)
        sum_g = _sum_to_f32(g2, n)
        sum_gx = _sum_to_f32(g2 * x2, n) - center * sum_g
    else:
        axes = tuple(range(x.ndim - 1))
        gf = g.astype(acc)
        xf = x.astype(acc)
        sum_g = jnp.sum(gf, axis=axes)
        sum_gx = jnp.sum(gf * xf, axis=axes) - center * sum_g
    dgamma = (inv * sum_gx).astype(gamma.dtype)
    dbeta = sum_g.astype(gamma.dtype)
    gamma_f = gamma.astype(acc)
    c1 = gamma_f * inv
    c3 = gamma_f * inv * inv * inv * sum_gx / n
    # dx = c1*g - c3*(x - mean) - c1*sum_g/n, with (x - mean) =
    # x_for_dx - center in both branches (bf16: xc - delta; else: x - mean)
    c0 = -(c1 * sum_g / n) + c3 * center
    dx = (c1.astype(x.dtype) * g - c3.astype(x.dtype) * x_for_dx
          + c0.astype(x.dtype))
    return dx, dgamma, dbeta


_bn_train.defvjp(_bn_train_fwd, _bn_train_bwd)

def batchnorm_init(key, conf: L.BatchNormalization, dtype):
    n = int(conf.n_in)
    return {
        "gamma": jnp.full((n,), conf.gamma, dtype),
        "beta": jnp.full((n,), conf.beta, dtype),
    }


def batchnorm_state(conf: L.BatchNormalization, dtype):
    n = int(conf.n_in)
    return {"mean": jnp.zeros((n,), dtype), "var": jnp.ones((n,), dtype)}


def batchnorm_forward(conf: L.BatchNormalization, params, x, ctx: LayerContext):
    """Normalizes over all axes but the last (channels for NHWC, features
    for 2d). Training uses batch statistics and EMA-updates the running
    stats (decay semantics as the reference: global = decay*global +
    (1-decay)*batch); inference uses the running stats."""
    eps = conf.eps
    state = ctx.state or {}
    if ctx.training:
        if conf.lock_gamma_beta:
            # locked = fixed at the conf constants, not trainable
            # (reference: BatchNormalization.java lockGammaBeta applies
            # the configured gamma/beta without learning them)
            c = params["gamma"].shape[0] if "gamma" in params else x.shape[-1]
            gamma = jnp.full((c,), conf.gamma, _acc_dtype(x.dtype))
            beta = jnp.full((c,), conf.beta, _acc_dtype(x.dtype))
        else:
            gamma, beta = params["gamma"], params["beta"]
        # vendor-kernel plugin point (the CudnnBatchNormalizationHelper
        # analog): when this input is a stashed conv+stats-epilogue output
        # (ops/pallas_conv_bn.py), the fused normalize kernel consumes the
        # precomputed statistics — one read of x instead of two. The probe
        # matches by tensor identity, so anything else falls through to
        # the built-in fused path below.
        y = mean = var = None
        helper = get_helper("batch_norm", x=x, training=True)
        if helper is not None:
            try:
                y, mean, var = helper(x, gamma, beta, eps)
            except HelperError:
                y = None
        if y is None:
            y, mean, var = _bn_train(x, gamma, beta, eps)
        d = conf.decay
        mean = lax.stop_gradient(mean)
        var = lax.stop_gradient(var)
        st_mean = state.get("mean")
        st_var = state.get("var")
        acc = _acc_dtype(x.dtype)
        new_state = {
            "mean": (d * st_mean.astype(acc) + (1 - d) * mean
                     ).astype(st_mean.dtype) if st_mean is not None
                    else mean,
            "var": (d * st_var.astype(acc) + (1 - d) * var
                    ).astype(st_var.dtype) if st_var is not None
                   else var,
        }
        return y, new_state
    mean = state.get("mean")
    var = state.get("var")
    if mean is None:
        mean, var = _bn_stats(x)
    inv = lax.rsqrt(var.astype(_acc_dtype(x.dtype)) + eps)
    xhat = (x - mean.astype(x.dtype)) * inv.astype(x.dtype)
    if conf.lock_gamma_beta:
        y = jnp.asarray(conf.gamma, x.dtype) * xhat \
            + jnp.asarray(conf.beta, x.dtype)
    else:
        y = params["gamma"].astype(x.dtype) * xhat + params["beta"].astype(x.dtype)
    return y, None


def batchnorm_order(conf):
    return ("gamma", "beta")


register_layer(
    L.BatchNormalization, batchnorm_init, batchnorm_forward,
    order_fn=batchnorm_order, state_fn=batchnorm_state,
)


# -- local response normalization -------------------------------------------

def _no_params(key, conf, dtype):
    return {}


def lrn_forward(conf: L.LocalResponseNormalization, params, x, ctx: LayerContext):
    """Cross-channel LRN on NHWC: y = x / (k + alpha*sum_window(x^2))^beta
    (reference: LocalResponseNormalization.java; window of size n centered
    on each channel). reduce_window over the channel axis."""
    n = int(conf.n)
    half = n // 2
    sq = x * x
    window = (1, 1, 1, n)
    strides = (1, 1, 1, 1)
    padding = [(0, 0), (0, 0), (0, 0), (half, n - 1 - half)]
    ssum = lax.reduce_window(sq, 0.0, lax.add, window, strides, padding)
    denom = (conf.k + conf.alpha * ssum) ** conf.beta
    return x / denom, None


register_layer(L.LocalResponseNormalization, _no_params, lrn_forward)


# -- RMS norm ------------------------------------------------------------------

def rms_normalize(x, gamma, eps, groups: int = 1):
    """`x * rsqrt(mean(x^2) + eps) * gamma` over the last axis (in `groups`
    equal groups of it), statistics in float32, result in x's dtype."""
    xf = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    shape = xf.shape
    if groups > 1:
        xf = xf.reshape(shape[:-1] + (groups, shape[-1] // groups))
    xf = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (xf.reshape(shape) * gamma.astype(xf.dtype)).astype(x.dtype)


def rms_norm_init(key, conf: L.RMSNorm, dtype):
    return {"gamma": jnp.ones((int(conf.n_in),), dtype)}


def rms_norm_forward(conf: L.RMSNorm, params, x, ctx: LayerContext):
    return rms_normalize(x, params["gamma"], conf.eps), None


register_layer(L.RMSNorm, rms_norm_init, rms_norm_forward,
               order_fn=lambda conf: ("gamma",))

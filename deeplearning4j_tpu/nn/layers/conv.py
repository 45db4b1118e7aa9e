"""Convolution, pooling, padding and global pooling layers (NHWC).

Reference impls: nn/layers/convolution/ConvolutionLayer.java:177-201
(im2col -> reshape -> Nd4j.gemm) and the cuDNN helper plugin
(deeplearning4j-cuda CudnnConvolutionHelper.java:345). Here the conv lowers
to lax.conv_general_dilated which XLA tiles straight onto the MXU — no
explicit im2col buffer and no helper SPI needed for the base path; Pallas
kernels can still override via ops/ when profiling says so.

Pooling: SubsamplingLayer (max/avg/sum/pnorm) -> lax.reduce_window
(reference: nn/layers/convolution/subsampling/SubsamplingLayer.java,
CudnnSubsamplingHelper). Gradients come from autodiff, which for a max
pool is XLA's select-and-scatter: an op that fuses with nothing and reads
the pool's whole input again. A max pool whose windows tile its input
(`_argmax_pool_applies`) instead saves each window's argmax as int8 in
the forward pass and selects on it in the backward pass: one elementwise
sweep over (index, pooled gradient). Where such a pool's input is the
ReLU of a convolution in the same trace, the ReLU moves behind the pool
(`relu(max(z)) == max(relu(z))`, values and gradients alike), so its
mask and the conv's bias-gradient sum work on the pooled tensor and the
conv writes one tensor, not two. `pool_lowering_total{kind}` counts, per
trace, which backward a pool took.
"""

from __future__ import annotations

import functools
from collections import deque

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.layers import ConvolutionMode, PoolingType
from deeplearning4j_tpu.nn.layers.core import apply_dropout
from deeplearning4j_tpu.nn.layers.registry import LayerContext, register_layer
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.ops.activations import apply_activation
from deeplearning4j_tpu.ops.helpers import HelperError, get_helper
from deeplearning4j_tpu.ops.pallas_conv_bn import _stash_pop
from deeplearning4j_tpu.utils import metrics as _metrics

_DIMS2D = ("NHWC", "HWIO", "NHWC")

# (relu(z), z) of the convs of a training trace, matched by `is` like the
# conv->BN stashes of ops/pallas_conv_bn: a tiling max pool that finds its
# input here pools z and applies the ReLU to the pooled tensor. Bounded:
# a conv whose consumer is no such pool ages out.
_PREACT_STASH: deque = deque(maxlen=8)


def _padding_2d(conf) -> object:
    if conf.convolution_mode == ConvolutionMode.SAME:
        return "SAME"
    p = conf.padding
    return [(int(p[0]), int(p[0])), (int(p[1]), int(p[1]))]


# -- 2D convolution ----------------------------------------------------------

def conv_init(key, conf: L.ConvolutionLayer, dtype):
    kh, kw_ = int(conf.kernel_size[0]), int(conf.kernel_size[1])
    fan_in = conf.n_in * kh * kw_
    fan_out = conf.n_out * kh * kw_
    k1, _ = jax.random.split(key)
    W = init_weights(k1, (kh, kw_, conf.n_in, conf.n_out), fan_in, fan_out,
                     conf.weight_init, conf.dist, dtype)
    out = {"W": W}
    if conf.has_bias:
        out["b"] = jnp.full((conf.n_out,), conf.bias_init or 0.0, dtype)
    return out


def conv_forward(conf: L.ConvolutionLayer, params, x, ctx: LayerContext):
    x = apply_dropout(x, conf.dropout, ctx)
    strides = tuple(int(s) for s in conf.stride)
    # vendor-kernel plugin point (the CudnnConvolutionHelper analog): a
    # registered conv kernel — e.g. the Pallas conv+BN-stats epilogue
    # fusion (ops/pallas_conv_bn.py) — takes over when it supports this
    # configuration; a helper that raises is disabled by the SPI and the
    # built-in XLA lowering below runs instead
    z = None
    helper = get_helper(
        "conv2d",
        kernel=tuple(int(k) for k in conf.kernel_size),
        stride=strides,
        dilation=tuple(int(d) for d in conf.dilation),
        same=conf.convolution_mode == ConvolutionMode.SAME,
        has_bias=conf.has_bias,
        activation=conf.activation,
        dtype=x.dtype,
        n_in=int(x.shape[-1]),
        n_out=int(conf.n_out),
        x_shape=tuple(int(d) for d in x.shape),
        training=ctx.training,
    )
    if helper is not None:
        try:
            z = helper(x, params["W"].astype(x.dtype), strides=strides)
        except HelperError:
            z = None
    if z is None:
        z = lax.conv_general_dilated(
            x,
            params["W"].astype(x.dtype),
            window_strides=strides,
            padding=_padding_2d(conf),
            rhs_dilation=tuple(int(d) for d in conf.dilation),
            dimension_numbers=_DIMS2D,
        )
    if conf.has_bias:
        z = z + params["b"].astype(z.dtype)
    a = apply_activation(conf.activation, z, key=ctx.rng, training=ctx.training)
    if ctx.training and conf.activation.lower() == "relu":
        _PREACT_STASH.append((a, z))
    return a, None


def conv_order(conf):
    return ("W", "b") if conf.has_bias else ("W",)


register_layer(L.ConvolutionLayer, conv_init, conv_forward, order_fn=conv_order)


# -- 1D convolution over time ------------------------------------------------

def conv1d_init(key, conf: L.Convolution1DLayer, dtype):
    k = int(conf.kernel_size)
    fan_in = conf.n_in * k
    fan_out = conf.n_out * k
    k1, _ = jax.random.split(key)
    W = init_weights(k1, (k, conf.n_in, conf.n_out), fan_in, fan_out,
                     conf.weight_init, conf.dist, dtype)
    out = {"W": W}
    if conf.has_bias:
        out["b"] = jnp.full((conf.n_out,), conf.bias_init or 0.0, dtype)
    return out


def conv1d_forward(conf: L.Convolution1DLayer, params, x, ctx: LayerContext):
    # x: [batch, time, nIn]
    x = apply_dropout(x, conf.dropout, ctx)
    if conf.convolution_mode == ConvolutionMode.SAME:
        padding = "SAME"
    else:
        padding = [(int(conf.padding), int(conf.padding))]
    z = lax.conv_general_dilated(
        x, params["W"].astype(x.dtype),
        window_strides=(int(conf.stride),),
        padding=padding,
        dimension_numbers=("NWC", "WIO", "NWC"),
    )
    if conf.has_bias:
        z = z + params["b"].astype(z.dtype)
    return apply_activation(conf.activation, z, key=ctx.rng, training=ctx.training), None


register_layer(L.Convolution1DLayer, conv1d_init, conv1d_forward, order_fn=conv_order)


# -- pooling -----------------------------------------------------------------

def _argmax_pool_applies(x, window, strides, padding) -> bool:
    """True where a window's argmax names exactly one input element per
    output element: a 4-D floating input tiled by its windows (stride ==
    window, nothing padded, nothing cut off), the position fitting int8."""
    if x.ndim != 4 or not jnp.issubdtype(x.dtype, jnp.floating):
        return False
    if tuple(window) != tuple(strides) or window[0] != 1 or window[3] != 1:
        return False
    if isinstance(padding, str):
        padding = lax.padtype_to_pads(x.shape, window, strides, padding)
    if any(lo or hi for lo, hi in padding):
        return False
    kh, kw = window[1], window[2]
    return x.shape[1] % kh == 0 and x.shape[2] % kw == 0 and kh * kw <= 127


def _window_position(view, kw):
    """int8 row-major position of each element in its window, over the
    6-D (N, H/kh, kh, W/kw, kw, C) view."""
    return (lax.broadcasted_iota(jnp.int8, view, 2) * jnp.int8(kw)
            + lax.broadcasted_iota(jnp.int8, view, 4))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _max_pool_tiled(x, kh, kw):
    """Max pool over kh x kw windows that tile x. With no gradient asked
    this is the plain reduce_window, so inference programs do not change."""
    window = (1, kh, kw, 1)
    return lax.reduce_window(x, -jnp.inf, lax.max, window, window, "VALID")


def _max_pool_tiled_fwd(x, kh, kw):
    n, h, w, c = x.shape
    view = (n, h // kh, kh, w // kw, kw, c)

    def larger(a, b):
        # the larger value (NaN counts as largest, as in lax.max); among
        # equals the smaller position: select_and_scatter's `ge` chooser
        # keeps the first maximum in row-major window order
        (av, ai), (bv, bi) = a, b
        a_val = (av > bv) | (av != av)
        a_idx = a_val | ((av == bv) & (ai < bi))
        return lax.select(a_val, av, bv), lax.select(a_idx, ai, bi)

    y, idx = lax.reduce(
        (x.reshape(view), _window_position(view, kw)),
        (jnp.array(-jnp.inf, x.dtype), jnp.array(127, jnp.int8)),
        larger, (2, 4))
    return y, idx


def _max_pool_tiled_bwd(kh, kw, idx, g):
    n, ho, wo, c = g.shape
    view = (n, ho, kh, wo, kw, c)
    hit = _window_position(view, kw) == idx[:, :, None, :, None, :]
    gx = jnp.where(hit, g[:, :, None, :, None, :], jnp.zeros((), g.dtype))
    # The TPU compiler fuses no broadcast across the reshape below: left to
    # itself it clones this select into every 4-D consumer and writes the
    # broadcasts of idx and g out in full first (1.5x the bytes of gx).
    # Behind the barrier the select is one 6-D fusion that reads idx and g
    # and writes gx, and the consumers read gx.
    gx = lax.optimization_barrier(gx)
    return (gx.reshape(n, ho * kh, wo * kw, c),)


_max_pool_tiled.defvjp(_max_pool_tiled_fwd, _max_pool_tiled_bwd)


def _count_pool_lowering(kind: str) -> None:
    """Trace-time, like ops/helpers._count: one event per pool per trace."""
    _metrics.get_registry().counter(
        "pool_lowering_total", "Pooling layers traced, by backward lowering",
        ("kind",)).labels(kind).inc()


def _pool(x, pooling_type, window, strides, padding, pnorm):
    """reduce_window pooling over explicitly-windowed axes. window/strides
    are full-rank tuples (1s for batch/channel)."""
    if (pooling_type == PoolingType.MAX
            and _argmax_pool_applies(x, window, strides, padding)):
        _count_pool_lowering("argmax_vjp")
        kh, kw = window[1], window[2]
        conv = _stash_pop(_PREACT_STASH, x)
        if conv is None:
            return _max_pool_tiled(x, kh, kw)
        # x = relu(z): the max commutes with a non-decreasing map, and the
        # gradient lands on the same element (the first maximum of z is the
        # first maximum of relu(z) wherever it is positive, and where it is
        # not both forms give zero). relu(z) itself is then dead code.
        return apply_activation("relu", _max_pool_tiled(conv[1], kh, kw))
    _count_pool_lowering("reduce_window")
    if pooling_type == PoolingType.MAX:
        neg_inf = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, neg_inf, lax.max, window, strides, padding)
    if pooling_type == PoolingType.SUM:
        return lax.reduce_window(x, 0.0, lax.add, window, strides, padding)
    if pooling_type == PoolingType.AVG:
        s = lax.reduce_window(x, 0.0, lax.add, window, strides, padding)
        n = 1
        for w in window:
            n *= w
        return s / n
    if pooling_type == PoolingType.PNORM:
        p = float(pnorm)
        s = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, window, strides, padding)
        return s ** (1.0 / p)
    raise ValueError(f"unknown pooling type {pooling_type!r}")


def _no_params(key, conf, dtype):
    return {}


def subsampling_forward(conf: L.SubsamplingLayer, params, x, ctx: LayerContext):
    window = (1, int(conf.kernel_size[0]), int(conf.kernel_size[1]), 1)
    strides = (1, int(conf.stride[0]), int(conf.stride[1]), 1)
    if conf.convolution_mode == ConvolutionMode.SAME:
        padding = "SAME"
    else:
        p = conf.padding
        padding = [(0, 0), (int(p[0]), int(p[0])), (int(p[1]), int(p[1])), (0, 0)]
    return _pool(x, conf.pooling_type, window, strides, padding, conf.pnorm), None


register_layer(L.SubsamplingLayer, _no_params, subsampling_forward)


def subsampling1d_forward(conf: L.Subsampling1DLayer, params, x, ctx: LayerContext):
    window = (1, int(conf.kernel_size), 1)
    strides = (1, int(conf.stride), 1)
    if conf.convolution_mode == ConvolutionMode.SAME:
        padding = "SAME"
    else:
        padding = [(0, 0), (int(conf.padding), int(conf.padding)), (0, 0)]
    return _pool(x, conf.pooling_type, window, strides, padding, conf.pnorm), None


register_layer(L.Subsampling1DLayer, _no_params, subsampling1d_forward)


# -- zero padding ------------------------------------------------------------

def zero_padding_forward(conf: L.ZeroPaddingLayer, params, x, ctx: LayerContext):
    pt, pb, pl, pr = (int(v) for v in conf.padding)
    return jnp.pad(x, ((0, 0), (pt, pb), (pl, pr), (0, 0))), None


register_layer(L.ZeroPaddingLayer, _no_params, zero_padding_forward)


# -- global pooling ----------------------------------------------------------

def global_pooling_forward(conf: L.GlobalPoolingLayer, params, x, ctx: LayerContext):
    """CNN input [b,h,w,c]: pool h,w. RNN input [b,t,f]: pool t, honoring the
    time mask (reference: GlobalPoolingLayer.java + MaskedReductionUtil)."""
    pt = conf.pooling_type
    if x.ndim == 4:
        axes = (1, 2)
        mask = None
    elif x.ndim == 3:
        axes = (1,)
        mask = ctx.mask  # [batch, time]
    else:
        raise ValueError(f"global pooling expects 3d/4d input, got shape {x.shape}")

    if mask is not None:
        m = mask[..., None].astype(x.dtype)
        if pt == PoolingType.MAX:
            x = jnp.where(m > 0, x, -jnp.inf)
        else:
            x = x * m
    if pt == PoolingType.MAX:
        return jnp.max(x, axis=axes), None
    if pt == PoolingType.SUM:
        return jnp.sum(x, axis=axes), None
    if pt == PoolingType.AVG:
        if mask is not None:
            denom = jnp.maximum(jnp.sum(mask, axis=1, keepdims=False), 1.0)[..., None]
            return jnp.sum(x, axis=axes) / denom, None
        return jnp.mean(x, axis=axes), None
    if pt == PoolingType.PNORM:
        p = float(conf.pnorm)
        return jnp.sum(jnp.abs(x) ** p, axis=axes) ** (1.0 / p), None
    raise ValueError(f"unknown pooling type {pt!r}")


register_layer(L.GlobalPoolingLayer, _no_params, global_pooling_forward)

"""Max pool over tiling windows: saved argmax instead of select-and-scatter.

`nn/layers/conv._pool` gives a MAX pool whose windows tile its input a
custom VJP (int8 argmax saved in the forward pass, elementwise select in
the backward pass) and, where that input is the ReLU of a conv in the same
training trace, moves the ReLU behind the pool. Both must be invisible in
the numbers: output and gradients equal `lax.reduce_window`'s under
`jax.grad` bit for bit, ties included. Everything else must lower to the
jaxpr it had before.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.layers import conv as C
from deeplearning4j_tpu.nn.layers.registry import LayerContext
from deeplearning4j_tpu.utils import metrics

TRAIN = LayerContext(training=True)


def _reference_pool(x, k):
    """What the layer did before: reduce_window, autodiff's gradient."""
    w = (1, k, k, 1)
    return lax.reduce_window(x, -jnp.inf, lax.max, w, w, "VALID")


def _layer_pool(x, k):
    conf = L.SubsamplingLayer(kernel_size=(k, k), stride=(k, k))
    return C.subsampling_forward(conf, {}, x, TRAIN)[0]


def _bits(a):
    return np.asarray(a, np.float32)


def _lowering_counts():
    values = metrics.get_registry().scalar_values()
    return {kind: values.get(f'pool_lowering_total{{kind="{kind}"}}', 0.0)
            for kind in ("argmax_vjp", "reduce_window")}


def _has_max(fn, args, *shapes):
    """For each shape, whether the compiled program (dead code gone) takes
    an elementwise maximum -- a ReLU -- over a tensor of that shape."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    found = [bool(re.search(
        rf"\[{','.join(str(d) for d in shape)}\]\S* maximum\(", text))
        for shape in shapes]
    return found[0] if len(found) == 1 else found


def _counted(fn):
    """(argmax_vjp, reduce_window) events while fn runs."""
    before = _lowering_counts()
    fn()
    after = _lowering_counts()
    return tuple(int(after[k] - before[k])
                 for k in ("argmax_vjp", "reduce_window"))


# -- the new path against reduce_window, bit for bit --------------------------

def _random(shape, dtype, k):
    return jax.random.normal(jax.random.PRNGKey(sum(shape)), shape, dtype)


def _after_relu(shape, dtype, k):
    """Post-ReLU activations: about half the entries tie at zero."""
    return jnp.maximum(_random(shape, dtype, k), 0)


def _planted_ties(shape, dtype, k):
    x = _after_relu(shape, dtype, k)
    x = x.at[0, :k, :k, :].set(1.5)            # a window of equal positives
    x = x.at[0, k:2 * k, :k, :].set(0.0)       # an all-zero window
    x = x.at[1, :k, :k, :].set(0.25)           # the maximum, repeated ...
    x = x.at[1, 0, 0, :].set(2.0)
    return x.at[1, k - 1, k - 1, :].set(2.0)   # ... in the last position


def _neg_inf(shape, dtype, k):
    x = _random(shape, dtype, k)
    x = x.at[0, :k, :k, :].set(-jnp.inf)       # a window with nothing finite
    return x.at[1, 0, 0, :].set(-jnp.inf)


@pytest.mark.parametrize("make", [_random, _after_relu, _planted_ties,
                                  _neg_inf], ids=lambda f: f.__name__[1:])
@pytest.mark.parametrize("k,shape", [(2, (4, 8, 8, 64)), (3, (2, 12, 12, 128)),
                                     (2, (2, 16, 16, 3)), (3, (2, 12, 12, 3))],
                         ids=["2x2-64ch", "3x3-128ch", "2x2-3ch", "3x3-3ch"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_output_and_gradient_equal_reduce_window(dtype, k, shape, make):
    x = make(shape, dtype, k)
    g = jax.random.normal(jax.random.PRNGKey(1), _reference_pool(x, k).shape,
                          dtype)

    def run(pool):
        return jax.value_and_grad(
            lambda x: (pool(x, k).astype(jnp.float32) * g).sum())(x)

    seen = {}
    assert _counted(lambda: seen.update(new=run(_layer_pool))) == (1, 0)
    (y_new, gx_new), (y_ref, gx_ref) = seen["new"], run(_reference_pool)
    np.testing.assert_array_equal(_bits(_layer_pool(x, k)),
                                  _bits(_reference_pool(x, k)))
    np.testing.assert_array_equal(_bits(y_new), _bits(y_ref))
    np.testing.assert_array_equal(_bits(gx_new), _bits(gx_ref))


def _conv_pool_conv(dtype, through_layer):
    """loss(params, x) of conv -> relu -> pool -> conv, the pool either
    the layer's or the plain reduce_window."""
    c1 = L.ConvolutionLayer(n_in=3, n_out=8, kernel_size=(3, 3),
                            convolution_mode="same", activation="relu")
    c2 = L.ConvolutionLayer(n_in=8, n_out=4, kernel_size=(3, 3),
                            convolution_mode="same", activation="tanh")

    def loss(params, x):
        a = C.conv_forward(c1, params[0], x, TRAIN)[0]
        a = _layer_pool(a, 2) if through_layer else _reference_pool(a, 2)
        a = C.conv_forward(c2, params[1], a, TRAIN)[0]
        return (a.astype(jnp.float32) ** 2).sum()

    return loss


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_relu_behind_the_pool_changes_no_gradient(dtype):
    """conv -> relu -> pool -> conv: the layer pools the pre-activation
    and applies the ReLU to the pooled tensor. Weight and input gradients
    are the old ones to the bit; the bias gradient sums the same numbers
    in another order."""
    key = jax.random.PRNGKey(0)
    params = [{"W": jax.random.normal(key, (3, 3, 3, 8), dtype) * 0.3,
               "b": jnp.full((8,), 0.05, dtype)},
              {"W": jax.random.normal(key, (3, 3, 8, 4), dtype) * 0.3,
               "b": jnp.zeros((4,), dtype)}]
    x = jax.random.normal(key, (2, 8, 8, 3), dtype)
    new_loss, ref_loss = (_conv_pool_conv(dtype, True),
                          _conv_pool_conv(dtype, False))
    # the ReLU works on the pooled 4x4 tensor, never on the 8x8 one
    assert _has_max(jax.grad(new_loss), (params, x),
                    (2, 4, 4, 8), (2, 8, 8, 8)) == [True, False]
    assert _has_max(jax.grad(ref_loss), (params, x), (2, 8, 8, 8))
    new = jax.jit(jax.grad(new_loss, argnums=(0, 1)))(params, x)
    ref = jax.jit(jax.grad(ref_loss, argnums=(0, 1)))(params, x)
    np.testing.assert_array_equal(_bits(new_loss(params, x)),
                                  _bits(ref_loss(params, x)))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(new),
                            jax.tree.leaves(ref)):
        if "'b'" in jax.tree_util.keystr(path):
            np.testing.assert_allclose(
                _bits(a), _bits(b), rtol=1e-6 if dtype == jnp.float32 else 1e-2)
        else:
            np.testing.assert_array_equal(_bits(a), _bits(b))


def test_relu_moves_only_while_training_and_only_for_relu():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 3))
    params = {"W": jnp.ones((3, 3, 3, 8)) * 0.1, "b": jnp.zeros((8,))}

    def forward(activation, ctx, x=x):
        conf = L.ConvolutionLayer(n_in=3, n_out=8, kernel_size=(3, 3),
                                  convolution_mode="same",
                                  activation=activation)
        a = C.conv_forward(conf, params, x, ctx)[0]
        pool = L.SubsamplingLayer()
        return C.subsampling_forward(pool, {}, a, ctx)[0]

    def full_res_max(activation, ctx):
        return _has_max(lambda x: forward(activation, ctx, x), (x,),
                        (2, 8, 8, 8))

    assert not full_res_max("relu", TRAIN)
    assert full_res_max("relu", LayerContext(training=False))
    assert not _has_max(lambda x: forward("tanh", TRAIN, x), (x,),
                        (2, 4, 4, 8))
    np.testing.assert_array_equal(
        np.asarray(forward("relu", TRAIN)),
        np.asarray(forward("relu", LayerContext(training=False))))


def test_vmap_and_scan_over_the_layer():
    x = _after_relu((3, 2, 8, 8, 4), jnp.float32, 2)
    g = jax.random.normal(jax.random.PRNGKey(2), (3, 2, 4, 4, 4))

    def loss(pool):
        return lambda x, g: (pool(x, 2) * g).sum()

    new = jax.vmap(jax.grad(loss(_layer_pool)))(x, g)
    ref = jax.vmap(jax.grad(loss(_reference_pool)))(x, g)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(ref))

    def scanned(pool):
        def body(carry, xs):
            x, g = xs
            return carry + loss(pool)(x, g), None
        return lambda x: lax.scan(body, 0.0, (x, g))[0]

    np.testing.assert_array_equal(
        np.asarray(jax.grad(scanned(_layer_pool))(x)),
        np.asarray(jax.grad(scanned(_reference_pool))(x)))


# -- routing: who keeps the old lowering --------------------------------------

def _old_pool(x, pooling_type, window, strides, padding, pnorm):
    """`_pool` as it was before the argmax path, kept here as the frozen
    reference of the routing test."""
    if pooling_type == "max":
        floating = jnp.issubdtype(x.dtype, jnp.floating)
        lowest = -jnp.inf if floating else jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, lowest, lax.max, window, strides, padding)
    if pooling_type == "sum":
        return lax.reduce_window(x, 0.0, lax.add, window, strides, padding)
    if pooling_type == "avg":
        s = lax.reduce_window(x, 0.0, lax.add, window, strides, padding)
        return s / int(np.prod(window))
    p = float(pnorm)
    s = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, window, strides,
                          padding)
    return s ** (1.0 / p)


def _pool2d_args(conf):
    window = (1, *conf.kernel_size, 1)
    strides = (1, *conf.stride, 1)
    if conf.convolution_mode == "same":
        return window, strides, "SAME"
    p = conf.padding
    return window, strides, [(0, 0), (p[0], p[0]), (p[1], p[1]), (0, 0)]


IMG = (2, 12, 12, 8)
OLD_PATH = {
    "resnet50_stem_3x3s2_same": (
        L.SubsamplingLayer(kernel_size=(3, 3), stride=(2, 2),
                           convolution_mode="same"), IMG, jnp.float32),
    "2x2s2_padding1": (
        L.SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2),
                           padding=(1, 1)), IMG, jnp.float32),
    "2x2s1_overlapping": (
        L.SubsamplingLayer(kernel_size=(2, 2), stride=(1, 1)), IMG,
        jnp.float32),
    "odd_height_truncate": (
        L.SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)),
        (2, 13, 12, 8), jnp.float32),
    "same_needing_padding": (
        L.SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2),
                           convolution_mode="same"), (2, 13, 12, 8),
        jnp.float32),
    "avg": (L.SubsamplingLayer(pooling_type="avg"), IMG, jnp.float32),
    "sum": (L.SubsamplingLayer(pooling_type="sum"), IMG, jnp.float32),
    "pnorm": (L.SubsamplingLayer(pooling_type="pnorm", pnorm=3), IMG,
              jnp.float32),
    "integer_input": (L.SubsamplingLayer(), IMG, jnp.int32),
    "window_over_127": (
        L.SubsamplingLayer(kernel_size=(12, 12), stride=(12, 12)), IMG,
        jnp.float32),
}


@pytest.mark.parametrize("case", sorted(OLD_PATH))
def test_everything_else_keeps_its_jaxpr(case):
    conf, shape, dtype = OLD_PATH[case]
    x = jnp.zeros(shape, dtype)
    window, strides, padding = _pool2d_args(conf)

    def new(x):
        return C.subsampling_forward(conf, {}, x, TRAIN)[0]

    def old(x):
        return _old_pool(x, conf.pooling_type, window, strides, padding,
                         conf.pnorm)

    seen = {}
    assert _counted(lambda: seen.update(
        jaxpr=jax.make_jaxpr(new)(x))) == (0, 1)
    assert str(seen["jaxpr"]) == str(jax.make_jaxpr(old)(x))
    if dtype == jnp.float32:
        assert (str(jax.make_jaxpr(jax.grad(lambda x: new(x).sum()))(x))
                == str(jax.make_jaxpr(jax.grad(lambda x: old(x).sum()))(x)))


def test_subsampling1d_keeps_its_jaxpr():
    conf = L.Subsampling1DLayer(kernel_size=2, stride=2)
    x = jnp.zeros((2, 12, 8))

    def new(x):
        return C.subsampling1d_forward(conf, {}, x, TRAIN)[0]

    def old(x):
        return _old_pool(x, "max", (1, 2, 1), (1, 2, 1),
                         [(0, 0), (0, 0), (0, 0)], 2)

    seen = {}
    assert _counted(lambda: seen.update(
        jaxpr=jax.make_jaxpr(new)(x))) == (0, 1)
    assert str(seen["jaxpr"]) == str(jax.make_jaxpr(old)(x))


@pytest.mark.parametrize("case", ["same_that_needs_no_padding", "3x3s3"])
def test_tiling_pools_take_the_argmax_path(case):
    conf, shape = {
        "same_that_needs_no_padding": (
            L.SubsamplingLayer(convolution_mode="same"), IMG),
        "3x3s3": (L.SubsamplingLayer(kernel_size=(3, 3), stride=(3, 3)), IMG),
    }[case]
    x = jnp.zeros(shape)
    assert _counted(lambda: jax.make_jaxpr(
        lambda x: C.subsampling_forward(conf, {}, x, TRAIN)[0])(x)) == (1, 0)


# -- whole models --------------------------------------------------------------

def _step_jaxpr(net, batch):
    from deeplearning4j_tpu.analysis.costmodel import train_step_args

    step, args = train_step_args(net, batch_size=batch)
    return str(jax.make_jaxpr(step)(*args))


def test_vgg16_step_counts_five_argmax_pools():
    from deeplearning4j_tpu.models.vgg16 import vgg16_conf
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    # the counter reads the layers' configuration and the input's shape and
    # dtype: a 32x32 image through the same 21 layers routes as 224x224 does
    net = MultiLayerNetwork(vgg16_conf(1000, 32, "bf16")).init()
    seen = {}
    assert _counted(lambda: seen.update(jaxpr=_step_jaxpr(net, 2))) == (5, 0)
    assert "select_and_scatter" not in seen["jaxpr"]
    assert "reduce_window" not in seen["jaxpr"]
    assert seen["jaxpr"].count("optimization_barrier") == 5


def test_resnet50_stem_pool_keeps_select_and_scatter():
    from deeplearning4j_tpu.models.resnet import resnet50_conf
    from deeplearning4j_tpu.nn.compgraph import ComputationGraph

    net = ComputationGraph(resnet50_conf(1000, 32, "bf16")).init()
    seen = {}
    assert _counted(lambda: seen.update(jaxpr=_step_jaxpr(net, 2))) == (0, 1)
    assert "select_and_scatter_add" in seen["jaxpr"]
    assert "optimization_barrier" not in seen["jaxpr"]


def test_forward_only_output_is_the_old_program():
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder().seed(1).list()
            .layer(L.ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                      convolution_mode="same",
                                      activation="relu"))
            .layer(L.SubsamplingLayer())
            .layer(L.OutputLayer(n_out=3, activation="softmax",
                                 loss="mcxent"))
            .set_input_type(InputType.convolutional(8, 8, 1))
            .build())
    net = MultiLayerNetwork(conf).init()
    x = jnp.zeros((2, 8, 8, 1))

    def output(x):
        return net._forward(net.params_list, net.state_list, x,
                            training=False, rng=None)[0]

    jaxpr = str(jax.make_jaxpr(output)(x))
    assert "reduce_window_max" in jaxpr
    assert "[2,8,8,4] = max " in jaxpr      # the ReLU stays where it was
    assert "i8[" not in jaxpr               # no (value, position) reduce
    assert "optimization_barrier" not in jaxpr

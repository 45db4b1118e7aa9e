"""Pallas conv+BN-stats epilogue fusion (ops/pallas_conv_bn.py) vs the XLA
path.

Runs the kernels in interpreter mode on the CPU test backend (the real
lowering is exercised on TPU by bench.py resnet50's A/B); correctness =
forward AND hand-written-backward equality against the built-in lowerings
on ResNet-stage shape patterns, an f64 finite-difference check through
train/gradientcheck.py, and fallback proofs: unsupported shapes/platforms
take the built-in path, and a helper fn that raises is disabled with the
layer still producing the built-in result (the SPI bugfix).
"""

import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.ops import pallas_conv_bn as pcb
from deeplearning4j_tpu.ops.helpers import (
    HelperError,
    get_helper,
    helper_names,
    register_helper,
    set_helper_enabled,
)

_DIMS2D = ("NHWC", "HWIO", "NHWC")


@pytest.fixture(autouse=True)
def _interpret_mode():
    old = pcb._INTERPRET
    pcb._INTERPRET = True
    pcb._STATS_STASH.clear()
    pcb._RELU_STASH.clear()
    yield
    pcb._INTERPRET = old
    pcb._STATS_STASH.clear()
    pcb._RELU_STASH.clear()


def _ref_conv(x, w, strides):
    return lax.conv_general_dilated(
        x, w, window_strides=strides, padding="SAME",
        dimension_numbers=_DIMS2D)


# -- kernel numerics ---------------------------------------------------------

@pytest.mark.parametrize(
    "kernel,strides,cin,cout,hw",
    [
        ((1, 1), (1, 1), 8, 32, 6),   # bottleneck expand (1x1 w -> 4w)
        ((1, 1), (2, 2), 16, 8, 6),   # projection shortcut, even spatial
        ((1, 1), (2, 2), 8, 16, 7),   # SAME/odd spatial: ceil(7/2)=4 rows
        ((3, 3), (1, 1), 8, 8, 5),    # bottleneck middle conv
        ((3, 3), (2, 2), 8, 8, 8),    # stage-entry 3x3/s2, even spatial
        ((3, 3), (2, 2), 8, 8, 7),    # 3x3/s2 odd spatial: stride-2 halo
        ((7, 7), (2, 2), 3, 8, 16),   # stem 7x7/s2, even spatial
        ((7, 7), (2, 2), 3, 8, 9),    # stem 7x7/s2, odd: asymmetric SAME pad
    ],
)
def test_conv_stats_matches_xla_forward_and_grad(kernel, strides, cin, cout, hw):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, hw, hw, cin)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((*kernel, cin, cout)) * 0.2,
                    jnp.float32)

    y, s1, s2 = pcb.conv2d_bn_stats(x, w, strides)
    yr = _ref_conv(x, w, strides)
    assert y.shape == yr.shape
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)
    # the epilogue's raw moments == reductions of the conv output
    yf = np.asarray(yr, np.float64).reshape(-1, cout)
    np.testing.assert_allclose(np.asarray(s1), yf.sum(0), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), (yf * yf).sum(0),
                               rtol=1e-4, atol=1e-4)

    # hand-written backward (transposed-conv pullback) == autodiff of XLA
    gf = jax.grad(lambda a, b: jnp.sum(
        jnp.sin(pcb.conv2d_bn_stats(a, b, strides)[0])), argnums=(0, 1))
    gr = jax.grad(lambda a, b: jnp.sum(
        jnp.sin(_ref_conv(a, b, strides))), argnums=(0, 1))
    for a, b, name in zip(gf(x, w), gr(x, w), ("dx", "dW")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("relu", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bn_apply_matches_builtin_bn(relu, dtype):
    """bn_apply from precomputed raw moments == norm.py's fused _bn_train
    (+ ReLU), forward and the reused fused-VJP backward, within the
    dtype's tolerance."""
    from deeplearning4j_tpu.nn.layers.norm import _bn_train

    rng = np.random.default_rng(1)
    c = 8
    x = jnp.asarray(rng.standard_normal((4, 5, 5, c)) * 1.3 + 0.4, dtype)
    gamma = jnp.asarray(rng.standard_normal(c) * 0.2 + 1.0, jnp.float32)
    beta = jnp.asarray(rng.standard_normal(c) * 0.1, jnp.float32)
    n = x.size // c
    # bf16: the epilogue's raw-moment variance and norm.py's centered
    # variance legitimately differ by ~0.2%, which moves a handful of
    # outputs across a bf16 rounding boundary — gradients of those
    # elements then differ by an ulp of the output scale. Structure is
    # pinned by the f32 case (3e-4) and the f64 finite-difference check.
    tol = 1e-1 if dtype == jnp.bfloat16 else 3e-4

    def moments(a):
        a2 = lax.stop_gradient(a).astype(jnp.float32).reshape(n, c)
        return jnp.sum(a2, 0), jnp.sum(a2 * a2, 0)

    s1, s2 = moments(x)
    y, mean, var = pcb.bn_apply(x, s1, s2, gamma, beta, 1e-5, n, relu)
    yr, mean_r, var_r = _bn_train(x, gamma, beta, 1e-5)
    if relu:
        yr = jnp.maximum(yr, jnp.zeros_like(yr))
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(mean_r),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(var), np.asarray(var_r),
                               rtol=tol, atol=tol)

    def loss_fused(a, g_, b_):
        m1, m2 = moments(a)
        out, _, _ = pcb.bn_apply(a, m1, m2, g_, b_, 1e-5, n, relu)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def loss_ref(a, g_, b_):
        out, _, _ = _bn_train(a, g_, b_, 1e-5)
        if relu:
            out = jnp.maximum(out, jnp.zeros_like(out))
        return jnp.sum(out.astype(jnp.float32) ** 2)

    ga = jax.grad(loss_fused, argnums=(0, 1, 2))(x, gamma, beta)
    gb = jax.grad(loss_ref, argnums=(0, 1, 2))(x, gamma, beta)
    for a, b, name in zip(ga, gb, ("dx", "dgamma", "dbeta")):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol, err_msg=name)


def test_fused_op_f64_gradient_check():
    """f64 finite-difference check of the COMPOSED fused op (conv with
    stats epilogue -> stop_gradient'ed moments -> bn_apply normalize+ReLU)
    through train/gradientcheck.py — validates the hand-written VJP pair
    end to end, including the total-derivative treatment of the stats."""
    from deeplearning4j_tpu.train.gradientcheck import check_gradients_fn

    rng = np.random.default_rng(2)
    cin, cout, hw, b = 4, 8, 3, 2
    x = rng.standard_normal((b, hw, hw, cin))
    sizes = [cin * cout, cout, cout]

    def loss_of_flat(flat):
        wf, gamma, beta = jnp.split(flat, np.cumsum(sizes)[:-1])
        w = wf.reshape(1, 1, cin, cout)
        xj = jnp.asarray(x, flat.dtype)
        y, s1, s2 = pcb.conv2d_bn_stats(xj, w, (1, 1))
        s1 = lax.stop_gradient(s1)
        s2 = lax.stop_gradient(s2)
        n = y.size // cout
        out, _, _ = pcb.bn_apply(y, s1, s2, gamma, beta, 1e-5, n, True)
        return jnp.sum(out * jnp.cos(out))

    flat0 = np.concatenate([
        rng.standard_normal(sizes[0]) * 0.3,
        rng.standard_normal(sizes[1]) * 0.1 + 1.0,
        rng.standard_normal(sizes[2]) * 0.1,
    ])
    assert check_gradients_fn(loss_of_flat, flat0, epsilon=1e-6,
                              max_rel_error=1e-5, verbose=True)


@pytest.mark.parametrize("kernel,strides,hw", [
    ((3, 3), (2, 2), 5),   # stage-entry stride, odd spatial halo
    ((7, 7), (2, 2), 6),   # stem kernel: pad wider than the input edge
])
def test_strided_kernels_f64_gradient_check(kernel, strides, hw):
    """f64 finite differences through the NEW strided kernels' VJP (the
    transposed-conv pullback is stride-agnostic by construction — this
    pins that claim numerically, per-tap slice plan included)."""
    from deeplearning4j_tpu.train.gradientcheck import check_gradients_fn

    rng = np.random.default_rng(7)
    cin, cout, b = 2, 4, 2
    x = rng.standard_normal((b, hw, hw, cin))
    nw = kernel[0] * kernel[1] * cin * cout

    def loss_of_flat(flat):
        w = flat.reshape(*kernel, cin, cout)
        xj = jnp.asarray(x, flat.dtype)
        y, _, _ = pcb.conv2d_bn_stats(xj, w, strides)
        return jnp.sum(y * jnp.cos(y))

    flat0 = rng.standard_normal(nw) * 0.3
    assert check_gradients_fn(loss_of_flat, flat0, epsilon=1e-6,
                              max_rel_error=1e-5, verbose=True)


# -- SPI integration ---------------------------------------------------------

def _build_conv_bn_net(seed=5):
    from deeplearning4j_tpu.nn.compgraph import ComputationGraph
    from deeplearning4j_tpu.nn.conf import (
        ActivationLayer,
        BatchNormalization,
        ConvolutionLayer,
        GlobalPoolingLayer,
        InputType,
        NeuralNetConfiguration,
        OutputLayer,
    )

    gb = (NeuralNetConfiguration.builder().seed(seed).learning_rate(0.05)
          .weight_init("relu").graph_builder().add_inputs("input")
          .set_input_types(InputType.convolutional(6, 6, 4)))
    gb.add_layer("c1", ConvolutionLayer(
        kernel_size=(3, 3), stride=(1, 1), n_out=8, convolution_mode="same",
        has_bias=False, activation="identity"), "input")
    gb.add_layer("bn1", BatchNormalization(), "c1")
    gb.add_layer("r1", ActivationLayer(activation="relu"), "bn1")
    gb.add_layer("c2", ConvolutionLayer(
        kernel_size=(1, 1), stride=(2, 2), n_out=16, convolution_mode="same",
        has_bias=False, activation="identity"), "r1")
    gb.add_layer("bn2", BatchNormalization(), "c2")
    gb.add_layer("pool", GlobalPoolingLayer(pooling_type="avg"), "bn2")
    gb.add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                    loss="mcxent"), "pool")
    gb.set_outputs("out")
    return ComputationGraph(gb.build()).init()


def _train_data():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((8, 6, 6, 4)).astype(np.float32)
    y = np.zeros((8, 3), np.float32)
    y[np.arange(8), rng.integers(0, 3, 8)] = 1.0
    return x, y


def test_network_uses_helpers_and_matches_builtin():
    """End to end through the SPI: a conv->BN->ReLU->conv/s2->BN graph
    trained with the fused helpers equals the built-in XLA path — outputs,
    params AND the BN running statistics (the EMA consumes the epilogue's
    mean/var)."""
    x, y = _train_data()

    net_h = _build_conv_bn_net()
    net_h.fit(x, y, batch_size=8, epochs=2, async_prefetch=False)
    out_h = np.asarray(net_h.output(x))

    for op in ("conv2d", "batch_norm", "bn_backward"):
        set_helper_enabled(op, False)
    try:
        net_b = _build_conv_bn_net()
        net_b.fit(x, y, batch_size=8, epochs=2, async_prefetch=False)
        out_b = np.asarray(net_b.output(x))
    finally:
        for op in ("conv2d", "batch_norm", "bn_backward"):
            set_helper_enabled(op, True)

    np.testing.assert_allclose(out_h, out_b, rtol=3e-4, atol=3e-5)
    for p1, p2 in zip(net_h.params_list, net_b.params_list):
        for k in p1:
            np.testing.assert_allclose(
                np.asarray(p1[k]), np.asarray(p2[k]), rtol=3e-4, atol=3e-5,
                err_msg=f"param {k}")
    for s1, s2 in zip(net_h.state_list, net_b.state_list):
        if s1 is not None:
            for k in s1:
                np.testing.assert_allclose(
                    np.asarray(s1[k]), np.asarray(s2[k]), rtol=3e-4,
                    atol=3e-5, err_msg=f"state {k}")


def _build_stem_net(seed=11):
    """A ResNet-stem-shaped graph: 7x7/s2 conv -> BN -> ReLU -> 3x3/s2
    conv -> BN -> pool -> out, on odd 9x9 input so both strided kernels
    exercise the asymmetric-SAME halo path end to end."""
    from deeplearning4j_tpu.nn.compgraph import ComputationGraph
    from deeplearning4j_tpu.nn.conf import (
        ActivationLayer,
        BatchNormalization,
        ConvolutionLayer,
        GlobalPoolingLayer,
        InputType,
        NeuralNetConfiguration,
        OutputLayer,
    )

    gb = (NeuralNetConfiguration.builder().seed(seed).learning_rate(0.05)
          .weight_init("relu").graph_builder().add_inputs("input")
          .set_input_types(InputType.convolutional(9, 9, 3)))
    gb.add_layer("stem", ConvolutionLayer(
        kernel_size=(7, 7), stride=(2, 2), n_out=8, convolution_mode="same",
        has_bias=False, activation="identity"), "input")
    gb.add_layer("bn1", BatchNormalization(), "stem")
    gb.add_layer("r1", ActivationLayer(activation="relu"), "bn1")
    gb.add_layer("entry", ConvolutionLayer(
        kernel_size=(3, 3), stride=(2, 2), n_out=16, convolution_mode="same",
        has_bias=False, activation="identity"), "r1")
    gb.add_layer("bn2", BatchNormalization(), "entry")
    gb.add_layer("pool", GlobalPoolingLayer(pooling_type="avg"), "bn2")
    gb.add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                    loss="mcxent"), "pool")
    gb.set_outputs("out")
    return ComputationGraph(gb.build()).init()


def test_stem_network_helpers_match_builtin():
    """End to end with the NEW kernels (7x7/s2 stem + 3x3/s2 stage entry):
    helpers-on training equals builtin-XLA training — outputs, params and
    the BN running statistics."""
    rng = np.random.default_rng(13)
    x = rng.standard_normal((8, 9, 9, 3)).astype(np.float32)
    y = np.zeros((8, 3), np.float32)
    y[np.arange(8), rng.integers(0, 3, 8)] = 1.0

    net_h = _build_stem_net()
    net_h.fit(x, y, batch_size=8, epochs=2, async_prefetch=False)
    out_h = np.asarray(net_h.output(x))

    for op in ("conv2d", "batch_norm", "bn_backward"):
        set_helper_enabled(op, False)
    try:
        net_b = _build_stem_net()
        net_b.fit(x, y, batch_size=8, epochs=2, async_prefetch=False)
        out_b = np.asarray(net_b.output(x))
    finally:
        for op in ("conv2d", "batch_norm", "bn_backward"):
            set_helper_enabled(op, True)

    np.testing.assert_allclose(out_h, out_b, rtol=3e-4, atol=3e-5)
    for p1, p2 in zip(net_h.params_list, net_b.params_list):
        for k in p1:
            np.testing.assert_allclose(
                np.asarray(p1[k]), np.asarray(p2[k]), rtol=3e-4, atol=3e-5,
                err_msg=f"param {k}")
    for s1, s2 in zip(net_h.state_list, net_b.state_list):
        if s1 is not None:
            for k in s1:
                np.testing.assert_allclose(
                    np.asarray(s1[k]), np.asarray(s2[k]), rtol=3e-4,
                    atol=3e-5, err_msg=f"state {k}")


def test_helpers_registered_and_probed():
    names = helper_names()
    assert names.get("conv2d") == "pallas_conv_bn_stats"
    assert names.get("batch_norm") == "pallas_fused_bn_apply"

    base = dict(kernel=(1, 1), stride=(1, 1), dilation=(1, 1), same=True,
                has_bias=False, activation="identity", dtype=jnp.float32,
                n_in=8, n_out=16, x_shape=(2, 6, 6, 8), training=True)
    assert get_helper("conv2d", **base) is not None
    # the full covered family, stem + stage-entry strided shapes included
    for good in (dict(kernel=(1, 1), stride=(2, 2)),
                 dict(kernel=(3, 3), stride=(1, 1)),
                 dict(kernel=(3, 3), stride=(2, 2)),  # stage-entry 3x3/s2
                 dict(kernel=(7, 7), stride=(2, 2), n_in=3,
                      x_shape=(2, 6, 6, 3))):         # stem
        ctx = dict(base)
        ctx.update(good)
        assert get_helper("conv2d", **ctx) is not None, good
    # fallback whitelist: everything a ResNet trunk conv is NOT
    for bad in (dict(kernel=(5, 5)),
                dict(kernel=(7, 7), stride=(1, 1)),
                dict(has_bias=True),
                dict(activation="relu"),
                dict(dilation=(2, 2)),
                dict(same=False),
                dict(training=False)):
        ctx = dict(base)
        ctx.update(bad)
        assert get_helper("conv2d", **ctx) is None, bad


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bn_backward_fused_matches_builtin_reductions(dtype):
    """The fused bn_backward helper (one Pallas pass over g and x for
    dgamma/dbeta + one for dx) == the builtin jnp reductions it replaces,
    for both the f32 (raw x, center=mean) and bf16 (centered x,
    center=delta) recenterings of `_bn_backward_pieces`."""
    rng = np.random.default_rng(17)
    c, n_shape = 8, (4, 5, 5, 8)
    x = jnp.asarray(rng.standard_normal(n_shape) * 1.2 + 0.3, dtype)
    g = jnp.asarray(rng.standard_normal(n_shape), dtype)
    gamma = jnp.asarray(rng.standard_normal(c) * 0.2 + 1.0, jnp.float32)
    n = x.size // c
    xf = np.asarray(x, np.float64).reshape(-1, c)
    mean = jnp.asarray(xf.mean(0), jnp.float32)
    var = jnp.asarray(xf.var(0), jnp.float32)
    inv = lax.rsqrt(var + 1e-5)

    dx_h, dg_h, db_h = pcb._bn_backward_pieces(g, x, mean, inv, gamma, n)
    set_helper_enabled("bn_backward", False)
    try:
        dx_b, dg_b, db_b = pcb._bn_backward_pieces(g, x, mean, inv, gamma, n)
    finally:
        set_helper_enabled("bn_backward", True)

    # bf16: the kernel casts g and x to f32 BEFORE the product; the
    # builtin reduction multiplies in bf16 first (`_col_sums(g2 * x2)`).
    # The kernel is the more accurate of the two — the comparison
    # tolerance is the bf16 product-rounding bound, not a kernel defect.
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(dx_h, np.float32),
                               np.asarray(dx_b, np.float32),
                               rtol=tol, atol=tol, err_msg="dx")
    for a, b, name in ((dg_h, dg_b, "dgamma"), (db_h, db_b, "dbeta")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=tol, atol=tol, err_msg=name)


def test_roofline_declines_compute_bound_conv():
    """The economic stage of `conv_decision`: a stage-3-like 3x3 conv is
    compute-bound on the modeled roofline (intensity above the ridge) and
    must be DECLINED — the stats epilogue saves an HBM read worth nothing
    there, so a compute-bound shape can never regress through the helper.
    The same kernel family on a memory-bound instance stays covered."""
    big = dict(kernel=(3, 3), stride=(1, 1), dilation=(1, 1), same=True,
               has_bias=False, activation="identity", dtype=jnp.bfloat16,
               n_in=256, n_out=256, x_shape=(8, 14, 14, 256), training=True)
    d = pcb.conv_decision(**big)
    assert d["status"] == "declined"
    assert d["reason"] == "compute_bound"
    assert d["roofline"]["intensity"] > d["roofline"]["ridge_intensity"]
    assert d["family"] == "conv3x3"
    assert get_helper("conv2d", **big) is None

    small = dict(big, dtype=jnp.float32, n_in=8, n_out=8,
                 x_shape=(2, 6, 6, 8))
    ds = pcb.conv_decision(**small)
    assert ds["status"] == "covered"
    assert ds["reason"] == "memory_bound"


def test_resnet50_kernel_coverage_complete():
    """Every ResNet-50 conv instance (batch 128, bf16) is DECIDED: covered,
    declined with a roofline verdict, or refused for a reason the chip's
    compiler gave (`CHIP_REFUSALS`; tests/test_tpu_compile.py holds the
    covered ones and each refusal to that compiler). No instance is
    unsupported because the kernel family has a hole. PR 17's "53/53
    have kernels" held in interpret mode only: the stem (7x7/s2), the
    three 3x3/s2 stage entries and the 64-channel 3x3/s1 do not compile
    for the v5e."""
    from deeplearning4j_tpu.analysis.kernelcoverage import (
        coverage_summary,
        coverage_table,
    )
    from deeplearning4j_tpu.models.resnet import resnet50_conf

    rows = coverage_table(resnet50_conf(), batch=128)
    s = coverage_summary(rows)
    assert (s["total"], s["covered"], s["declined"], s["unsupported"]) \
        == (53, 29, 17, 7)
    by = {r["layer"]: r for r in rows}
    assert (by["stem_conv"]["status"], by["stem_conv"]["reason"],
            by["stem_conv"]["family"]) \
        == ("unsupported", "strided_taps", "conv7x7s2")
    assert (by["s1b0_b_conv"]["status"], by["s1b0_b_conv"]["reason"],
            by["s1b0_b_conv"]["family"]) \
        == ("unsupported", "strided_taps", "conv3x3s2")
    assert (by["s0b0_b_conv"]["status"], by["s0b0_b_conv"]["reason"]) \
        == ("unsupported", "lane_alignment")
    for r in rows:
        if r["status"] == "declined":
            assert r["reason"] == "compute_bound"
            assert r["intensity"] > r["ridge"]
        elif r["status"] == "unsupported":
            assert r["reason"] in pcb.CHIP_REFUSALS, r
        else:  # what the router selects on the chip is the 1x1 kernel
            assert r["family"] in ("conv1x1", "conv1x1s2"), r


def test_fallback_on_cpu_without_interpret():
    """Tier-1/CPU safety: with interpret mode off (the library default),
    the probes refuse the CPU backend outright — the TPU kernel path can
    never run in a CPU process."""
    pcb._INTERPRET = False
    assert get_helper(
        "conv2d", kernel=(1, 1), stride=(1, 1), dilation=(1, 1), same=True,
        has_bias=False, activation="identity", dtype=jnp.bfloat16,
        n_in=64, n_out=256, x_shape=(8, 56, 56, 64), training=True) is None
    x = jnp.zeros((2, 4, 4, 8), jnp.bfloat16)
    assert get_helper("batch_norm", x=x, training=True) is None


def test_stash_match_with_mixed_shapes_pending():
    """Regression: taking a stashed entry that is NOT first in the deque,
    while entries of DIFFERENT shapes are pending (a ResNet block's main
    path + projection shortcut), used to raise — deque.remove compares
    entries with ==, which broadcasts traced arrays. Removal must be by
    identity/index."""
    xa = jnp.zeros((2, 4, 4, 8), jnp.float32)
    xb = jnp.zeros((2, 4, 4, 4), jnp.float32)
    wa = jnp.zeros((1, 1, 8, 16), jnp.float32)
    wb = jnp.zeros((1, 1, 4, 8), jnp.float32)
    ya = pcb._conv2d_helper(xa, wa, strides=(1, 1))   # shape (2,4,4,16)
    yb = pcb._conv2d_helper(xb, wb, strides=(1, 1))   # shape (2,4,4,8)
    assert pcb.take_stats(yb) is not None   # second entry, first still pending
    assert pcb.take_stats(ya) is not None
    assert pcb.take_stats(ya) is None       # consumed; miss answers None
    # same for the deferred-ReLU stash: different-shaped entries pending
    g = jnp.ones((16,), jnp.float32)
    b = jnp.zeros((16,), jnp.float32)
    za = pcb._conv2d_helper(xa, wa, strides=(1, 1))
    ra, _, _ = pcb._bn_helper(za, g, b, 1e-5)
    zb = pcb._conv2d_helper(xb, wb, strides=(1, 1))
    rb, _, _ = pcb._bn_helper(zb, g[:8], b[:8], 1e-5)
    fused_b = pcb.take_fused_relu(rb)       # second entry, first pending
    assert fused_b is not None and fused_b.shape == rb.shape
    assert pcb.take_fused_relu(ra) is not None


def test_bn_probe_requires_stashed_stats():
    """The batch_norm helper only engages for the exact tensor a conv
    epilogue produced — any intervening op breaks identity and falls back."""
    x = jnp.zeros((2, 4, 4, 8), jnp.float32)
    assert get_helper("batch_norm", x=x, training=True) is None
    w = jnp.zeros((1, 1, 8, 8), jnp.float32)
    y = pcb._conv2d_helper(x, w, strides=(1, 1))
    assert get_helper("batch_norm", x=y, training=True) is not None
    assert pcb.take_stats(y) is not None   # consumed...
    assert get_helper("batch_norm", x=y, training=True) is None  # ...once


# -- the SPI raising-fn bugfix ----------------------------------------------

def test_raising_helper_fn_disables_and_falls_back(caplog):
    """Regression (ops/helpers.py): a helper `fn` that raises at trace
    time used to kill the layer with no fallback even though its probe
    passed. Now the SPI catches, logs, disables the helper, and the layer
    retries its built-in path — the network must train identically to the
    builtin-only run, and the helper must be off afterwards."""

    def exploding(*a, **k):
        raise ValueError("synthetic kernel lowering failure")

    x, y = _train_data()
    register_helper("conv2d", exploding, lambda **ctx: True,
                    name="exploding_conv")
    try:
        with caplog.at_level(logging.WARNING, logger="deeplearning4j_tpu"):
            net = _build_conv_bn_net()
            net.fit(x, y, batch_size=8, epochs=1, async_prefetch=False)
        assert any("exploding_conv" in r.message and "disabled" in r.message
                   for r in caplog.records)
        assert helper_names()["conv2d"] == "exploding_conv"
        # disabled => probe-level refusal now, without calling fn
        assert get_helper("conv2d", anything=1) is None

        set_helper_enabled("conv2d", False)
        set_helper_enabled("batch_norm", False)
        try:
            net_b = _build_conv_bn_net()
            net_b.fit(x, y, batch_size=8, epochs=1, async_prefetch=False)
        finally:
            set_helper_enabled("batch_norm", True)
        for p1, p2 in zip(net.params_list, net_b.params_list):
            for k in p1:
                np.testing.assert_allclose(
                    np.asarray(p1[k]), np.asarray(p2[k]),
                    rtol=1e-5, atol=1e-6, err_msg=f"param {k}")
    finally:
        pcb.register()  # restore the real kernels (fresh enabled Helper)
    assert helper_names()["conv2d"] == "pallas_conv_bn_stats"


def test_raising_bn_backward_helper_disables_and_falls_back(caplog):
    """The SPI auto-disable contract for the NEW "bn_backward" slot: a
    fused-backward fn that raises at trace time is caught, logged and
    disabled, and both consumers (`norm.py _bn_train_bwd` and the pallas
    `_bn_bwd`) retry their builtin reductions — the network trains to the
    same parameters as the fully-builtin run."""

    def exploding(*a, **k):
        raise ValueError("synthetic bn-backward lowering failure")

    x, y = _train_data()
    register_helper("bn_backward", exploding, lambda **ctx: True,
                    name="exploding_bn_bwd")
    try:
        with caplog.at_level(logging.WARNING, logger="deeplearning4j_tpu"):
            net = _build_conv_bn_net()
            net.fit(x, y, batch_size=8, epochs=1, async_prefetch=False)
        assert any("exploding_bn_bwd" in r.message and "disabled" in r.message
                   for r in caplog.records)
        assert helper_names()["bn_backward"] == "exploding_bn_bwd"
        # disabled => probe-level refusal now, without calling fn
        assert get_helper("bn_backward", anything=1) is None

        for op in ("conv2d", "batch_norm", "bn_backward"):
            set_helper_enabled(op, False)
        try:
            net_b = _build_conv_bn_net()
            net_b.fit(x, y, batch_size=8, epochs=1, async_prefetch=False)
        finally:
            for op in ("conv2d", "batch_norm"):
                set_helper_enabled(op, True)
        for p1, p2 in zip(net.params_list, net_b.params_list):
            for k in p1:
                np.testing.assert_allclose(
                    np.asarray(p1[k]), np.asarray(p2[k]),
                    rtol=3e-4, atol=3e-5, err_msg=f"param {k}")
    finally:
        pcb.register()  # restore the real kernels (fresh enabled Helper)
    assert helper_names()["bn_backward"] == "pallas_fused_bn_bwd"


def test_guarded_helper_raises_helper_error_directly():
    register_helper("_t1_scratch", lambda: (_ for _ in ()).throw(
        RuntimeError("boom")), name="scratch")
    try:
        fn = get_helper("_t1_scratch")
        assert fn is not None
        with pytest.raises(HelperError):
            fn()
        assert get_helper("_t1_scratch") is None  # disabled after the raise
    finally:
        from deeplearning4j_tpu.ops.helpers import _HELPERS

        _HELPERS.pop("_t1_scratch", None)

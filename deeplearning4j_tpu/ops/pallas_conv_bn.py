"""Pallas conv + BN-statistics epilogue fusion — the CudnnConvolutionHelper/
CudnnBatchNormalizationHelper pair for the ResNet trunk.

Why: the round-9 profile (older than the code, not re-measured) showed
the train step bandwidth-bound, with 16.4 ms of a 48.8 ms step spent on
batch-norm statistics/normalization traffic over the residual trunk
(`convert_reduce_fusion` = 25.8 ms/step).
XLA materializes each conv output to HBM, then re-reads the full tensor
for the per-channel statistics reduction, then re-reads it AGAIN for the
normalize. This module closes one of those reads: the conv kernel computes
per-channel sum / sum-of-squares in f32 as an epilogue over each output
tile while it is still in VMEM, so the stats cost no extra HBM traffic at
all; a second fused normalize(+ReLU) kernel then performs the one
remaining read.

Two helper slots (ops/helpers.py), mirroring the reference's plugin pair
(CudnnConvolutionHelper.java:345, BatchNormalizationHelper.java:29):

- "conv2d":     `_conv2d_helper` — conv forward with the stats epilogue.
  The stats ride to the downstream BatchNormalization layer through a
  producer→consumer stash keyed by tensor identity: within one trace the
  conv's output object IS the BN layer's input object (compgraph passes
  activations through untouched), so the match is exact and anything in
  between (an activation, a residual add) breaks it safely.
- "batch_norm": `_bn_helper` — fused normalize from the stashed stats,
  with a deferred-ReLU hook: when the very next layer is a ReLU
  ActivationLayer, it swaps in the normalize+ReLU variant of the kernel
  and the plain-normalize pallas_call is dead-code-eliminated by XLA.

Scope (checked by the probes; everything else takes the XLA lowering,
like the cuDNN checkSupported fallback): NHWC, bf16 on real TPU,
training mode, bias-free identity-activation convs with SAME padding, no
dilation, and kernel/stride in {1x1 (stride 1 or 2), 3x3 (stride 1 or
2), 7x7 (stride 2)}. On the TPU the kxk kernel is narrower than in
interpret mode, because the chip's compiler refuses part of it
(`_ck_chip_refusal`): strided taps (3x3/s2, the 7x7/s2 stem), inputs
that do not fill the 128 lanes, and images too large to compile. Those
shapes are "unsupported" by shape; tests/test_tpu_compile.py compiles
what is left for the described chip. Structural support is necessary
but not sufficient: `conv_decision` then consults the per-instance
roofline (`analysis/costmodel.instance_roofline`) and DECLINES
compute-bound instances — an MXU-saturating conv gains nothing from the
stats epilogue and must never regress through the helper; only
memory-bound instances route to the kernel.

Backward is a hand-written custom_vjp pair: the conv pullback is the
standard pair of transposed XLA convolutions (jax.linear_transpose of the
reference lowering — already MXU-shaped; Pallas buys nothing there), and
the BN pullback reuses the fused-BN VJP structure of nn/layers/norm.py
(per-channel coefficients in the f32 accumulator dtype, every full-size
tensor in x.dtype). The per-channel reductions of that pullback (sum g,
sum g·x) and the dx normalization are themselves Pallas-fused here — one
reduce pass + one apply pass over the saved activations instead of
XLA's three separate re-reads — registered as a third helper slot
("bn_backward") consumed both by `bn_apply`'s VJP and by
nn/layers/norm.py's built-in `_bn_train` backward, behind the same
kill-switch/auto-disable machinery. The stats outputs are
stop_gradient'ed at the stash: the BN backward's dx is the TOTAL
derivative including the statistics paths (same composite as norm.py's
`_bn_train`), so routing any cotangent through the stats tensors as well
would double-count.
"""

from __future__ import annotations

import logging
import os
from collections import deque
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.helpers import (
    count_fallback_raised,
    interpret_mode,
)

logger = logging.getLogger("deeplearning4j_tpu")

# Interpret mode runs the kernels as a jaxpr interpreter on the CPU
# backend — the CPU-correctness/bench configuration (same pattern as
# pallas_lstm). Tests flip the module flag directly; bench flips it via
# set_interpret; DL4J_PALLAS_INTERPRET=1 forces it from the environment.
# Every reader goes through `_interpret()`, which refuses it on a TPU.
_INTERPRET = os.environ.get("DL4J_PALLAS_INTERPRET", "0") == "1"


def set_interpret(on: bool) -> None:
    """Run the Pallas kernels in interpret mode (CPU backend). Used by
    bench.py for the CPU-interpret helper A/B; tests set the module flag
    directly through their fixture."""
    global _INTERPRET
    _INTERPRET = bool(on)


def _interpret() -> bool:
    return interpret_mode(_INTERPRET)

_DIMS2D = ("NHWC", "HWIO", "NHWC")


# -- producer→consumer stashes ----------------------------------------------
#
# Entries are matched by `is` on the traced value, so they can only ever
# connect a conv to the BN (or a BN to the ReLU) that consumes that exact
# tensor inside the same trace. Bounded deques: unmatched entries (a conv
# whose consumer is not a BN, an abandoned trace) age out instead of
# accumulating tracer references.

_STATS_STASH: deque = deque(maxlen=8)
_RELU_STASH: deque = deque(maxlen=8)


def _stash_pop(dq: deque, x):
    """Remove and return the entry whose key tensor IS x. Removal is by
    index — deque.remove would compare entries with ==, which on traced
    arrays of unequal shapes raises instead of answering False."""
    for i, entry in enumerate(dq):
        if entry[0] is x:
            del dq[i]
            return entry
    return None


def _stash_stats(y, s1, s2) -> None:
    _STATS_STASH.append((y, s1, s2))


def take_stats(x):
    """(sum, sum_sq) f32 per-channel stats stashed for exactly this tensor,
    removing the entry; None when x is not a stashed conv output."""
    entry = _stash_pop(_STATS_STASH, x)
    return None if entry is None else (entry[1], entry[2])


def peek_stats(x) -> bool:
    return any(entry[0] is x for entry in _STATS_STASH)


def _stash_relu(y, thunk) -> None:
    _RELU_STASH.append((y, thunk))


def take_fused_relu(x):
    """The normalize+ReLU variant of a stashed BN output, or None. The
    plain-normalize pallas_call that produced x becomes dead code once its
    only consumer switches to the fused variant — XLA eliminates it."""
    entry = _stash_pop(_RELU_STASH, x)
    if entry is None:
        return None
    try:
        return entry[1]()
    except Exception as e:  # never let the fusion shortcut kill a layer
        logger.warning("fused BN+ReLU thunk failed (%s); applying "
                       "plain ReLU instead", e)
        count_fallback_raised("batch_norm", "bn_apply")
        return None


# -- tiling helpers ----------------------------------------------------------

def _row_tile(m: int, cap: int = 512) -> int:
    """Largest power-of-two row tile <= cap dividing m (ResNet row counts
    are highly 2-adic: N*H*W = 128*56*56 etc; tiny test shapes land on a
    smaller divisor, worst case 1)."""
    t = cap
    while t > 1 and m % t:
        t //= 2
    return t


def _dot_precision(dtype):
    """Precision of an in-kernel dot. bf16 operands take one MXU pass
    whatever the process-wide `jax_default_matmul_precision` asks, and
    the chip's compiler refuses "highest" on them ("Bad lhs type"), so
    they are pinned to DEFAULT; f32/f64 operands (interpret-mode parity
    tests) keep the configured precision."""
    return lax.Precision.DEFAULT if dtype == jnp.bfloat16 else None


def _acc_dtype(dtype):
    """f32 accumulators, or f64 when the whole check runs f64 (the
    gradient-check configuration) — matches nn/layers/norm.py."""
    return jnp.promote_types(dtype, jnp.float32)


# -- 1x1 conv (pointwise matmul) with stats epilogue -------------------------

def _mm_stats_kernel(x_ref, w_ref, y_ref, s1_ref, s2_ref):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        s1_ref[:] = jnp.zeros_like(s1_ref)
        s2_ref[:] = jnp.zeros_like(s2_ref)

    acc_dt = s1_ref.dtype
    y = jnp.dot(x_ref[:], w_ref[:], preferred_element_type=acc_dt,
                precision=_dot_precision(x_ref.dtype))
    yb = y.astype(y_ref.dtype)
    y_ref[:] = yb
    # Epilogue over the tile while it is still in VMEM. Statistics are of
    # the STORED (rounded) tensor — what the normalize will actually read
    # — not the f32 pre-rounding accumulator.
    yf = yb.astype(acc_dt)
    s1_ref[:] += jnp.sum(yf, axis=0, keepdims=True)
    s2_ref[:] += jnp.sum(yf * yf, axis=0, keepdims=True)


def _mm_stats_call(x2, w2):
    m, cin = x2.shape
    cout = w2.shape[1]
    acc = _acc_dtype(x2.dtype)
    # big-channel shapes get a smaller row tile so weights + double-buffered
    # row tiles stay inside VMEM (probe re-checks the same budget)
    tm = _row_tile(m, 128 if cin * cout >= 1024 * 1024 else 512)
    y2, s1, s2 = pl.pallas_call(
        _mm_stats_kernel,
        grid=(m // tm,),
        in_specs=[
            pl.BlockSpec((tm, cin), lambda t: (t, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((cin, cout), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((tm, cout), lambda t: (t, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, cout), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, cout), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, cout), x2.dtype),
            jax.ShapeDtypeStruct((1, cout), acc),
            jax.ShapeDtypeStruct((1, cout), acc),
        ],
        name="conv_bn_stats",
        interpret=_interpret(),
    )(x2, w2)
    return y2, s1, s2


# -- kxk strided SAME conv with stats epilogue -------------------------------

def _same_out_pad(in_sz: int, k: int, s: int):
    """(out_sz, pad_lo) of one spatial dim under XLA SAME padding (extra
    pad goes on the high side — must match the reference lowering the
    backward transposes and the tests compare against)."""
    out_sz = -(-in_sz // s)
    return out_sz, max((out_sz - 1) * s + k - in_sz, 0) // 2


def _conv_taps(h: int, w: int, kh: int, kw: int, sh: int, sw: int):
    """Static per-tap slice plan for a SAME kxk/s conv: for each kernel
    tap (a, b), the output range where the tap lands inside the image and
    the matching strided input origin. All values are Python ints, so the
    kernel below unrolls to kh*kw clipped dots with static slices."""
    ho, ph = _same_out_pad(h, kh, sh)
    wo, pw = _same_out_pad(w, kw, sw)
    rows = []
    for a in range(kh):
        o0 = max(0, -((a - ph) // sh)) if a < ph else 0
        o1 = min(ho, (h - 1 + ph - a) // sh + 1)
        if o1 > o0:
            rows.append((a, o0, o1, o0 * sh + a - ph))
    cols = []
    for b in range(kw):
        o0 = max(0, -((b - pw) // sw)) if b < pw else 0
        o1 = min(wo, (w - 1 + pw - b) // sw + 1)
        if o1 > o0:
            cols.append((b, o0, o1, o0 * sw + b - pw))
    taps = tuple((ra, rb) for ra in rows for rb in cols)
    return ho, wo, taps


def _ck_stats_kernel(x_ref, w_ref, y_ref, s1_ref, s2_ref, *, taps, sh, sw):
    n = pl.program_id(0)

    @pl.when(n == 0)
    def _():
        s1_ref[:] = jnp.zeros_like(s1_ref)
        s2_ref[:] = jnp.zeros_like(s2_ref)

    acc_dt = s1_ref.dtype
    ho, wo = y_ref.shape[1], y_ref.shape[2]
    cout = y_ref.shape[3]
    cin = x_ref.shape[3]
    acc = jnp.zeros((ho, wo, cout), acc_dt)
    x = x_ref[0]
    # kh*kw shifted whole-image dots accumulated in VMEM. The SAME-padding
    # halo is handled by clipping each tap to its valid output region
    # (static slices) instead of pre-padding the input — a jnp.pad outside
    # the kernel would materialize a full padded copy to HBM, spending the
    # very read the stats epilogue saves. Stride > 1 subsamples the input
    # rows/cols of each tap with a static strided slice.
    for (a, oh0, oh1, ih0), (b, ow0, ow1, iw0) in taps:
        ch, cw = oh1 - oh0, ow1 - ow0
        if sh == 1 and sw == 1:
            xs = x[ih0:ih0 + ch, iw0:iw0 + cw, :]
        else:
            xs = lax.slice(x, (ih0, iw0, 0),
                           (ih0 + (ch - 1) * sh + 1,
                            iw0 + (cw - 1) * sw + 1, cin),
                           (sh, sw, 1))
        part = lax.dot_general(
            xs, w_ref[a, b],
            (((2,), (0,)), ((), ())),
            preferred_element_type=acc_dt,
            precision=_dot_precision(x_ref.dtype),
        )
        # zero-extend the clipped partial back to (ho, wo) and add —
        # in-register pad; .at[...].add would capture index constants
        # the kernel tracer rejects
        acc = acc + lax.pad(
            part, jnp.asarray(0, acc_dt),
            ((oh0, ho - oh1, 0), (ow0, wo - ow1, 0), (0, 0, 0)))
    yb = acc.astype(y_ref.dtype)
    y_ref[0] = yb
    yf = yb.astype(acc_dt).reshape(ho * wo, cout)
    s1_ref[:] += jnp.sum(yf, axis=0, keepdims=True)
    s2_ref[:] += jnp.sum(yf * yf, axis=0, keepdims=True)


def _ck_stats_call(x, w, strides):
    n, h, wd, cin = x.shape
    kh, kw, _, cout = w.shape
    sh, sw = strides
    ho, wo, taps = _conv_taps(h, wd, kh, kw, sh, sw)
    acc = _acc_dtype(x.dtype)
    y, s1, s2 = pl.pallas_call(
        partial(_ck_stats_kernel, taps=taps, sh=sh, sw=sw),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, h, wd, cin), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((kh, kw, cin, cout), lambda i: (0, 0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, ho, wo, cout), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, cout), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, cout), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, ho, wo, cout), x.dtype),
            jax.ShapeDtypeStruct((1, cout), acc),
            jax.ShapeDtypeStruct((1, cout), acc),
        ],
        name="convk_bn_stats",
        interpret=_interpret(),
    )(x, w)
    return y, s1, s2


# -- fused conv + stats op (custom_vjp) --------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(2,))
def conv2d_bn_stats(x, w, strides):
    """NHWC conv (SAME, bias-free) returning (y, sum, sum_sq) where the
    per-channel f32 statistics are computed as a VMEM epilogue of the conv
    output tiles — zero extra HBM traffic for the reduction.

    x: [N,H,W,Cin]; w: [kh,kw,Cin,Cout] with (kh,kw)/(sh,sw) in
    {1x1/s1, 1x1/s2, 3x3/s1, 3x3/s2, 7x7/s2}; strides static.

    The statistics outputs carry NO gradient (see module docstring: the
    paired `bn_apply` backward computes the total dx including the stats
    paths). Consume them via the Helper SPI wiring or stop_gradient them.
    """
    y, s1, s2 = _conv_fwd_impl(x, w, strides)
    return y, s1, s2


def _conv_fwd_impl(x, w, strides):
    kh, kw = int(w.shape[0]), int(w.shape[1])
    cout = int(w.shape[3])
    if (kh, kw) == (1, 1):
        sh, sw = strides
        if (sh, sw) != (1, 1):
            # SAME 1x1/s: output pixel (i,j) samples x[i*s, j*s] exactly
            x = x[:, ::sh, ::sw, :]
        n, h, wd, cin = x.shape
        y2, s1, s2 = _mm_stats_call(x.reshape(n * h * wd, cin),
                                    w.reshape(cin, cout))
        return y2.reshape(n, h, wd, cout), s1[0], s2[0]
    # kxk SAME (stride 1 or 2): full image per grid step, halo clipped
    # and stride subsampled in-kernel
    y, s1, s2 = _ck_stats_call(x, w, strides)
    return y, s1[0], s2[0]


def _conv_fwd(x, w, strides):
    out = _conv_fwd_impl(x, w, strides)
    return out, (x, w)


def _conv_bwd(strides, res, cts):
    """Pullback = the two transposed convolutions of the reference XLA
    lowering (linear_transpose instantiates no forward pass). ds1/ds2 are
    structurally zero — the stats are stop_gradient'ed at the stash and
    bn_apply's dx is the total derivative — so they are dropped here."""
    x, w = res
    dy, _, _ = cts

    def conv_x(xx):
        return lax.conv_general_dilated(
            xx, w, window_strides=strides, padding="SAME",
            dimension_numbers=_DIMS2D)

    def conv_w(ww):
        return lax.conv_general_dilated(
            x, ww, window_strides=strides, padding="SAME",
            dimension_numbers=_DIMS2D)

    dx, = jax.linear_transpose(conv_x, x)(dy)
    dw, = jax.linear_transpose(conv_w, w)(dy)
    return dx, dw


conv2d_bn_stats.defvjp(_conv_fwd, _conv_bwd)


# -- fused normalize(+ReLU) consumer (custom_vjp) ----------------------------

def _norm_kernel_relu(x_ref, mb_ref, sc_ref, sh_ref, y_ref):
    xc = x_ref[:] - mb_ref[:]
    y = xc * sc_ref[:].astype(x_ref.dtype) + sh_ref[:].astype(x_ref.dtype)
    y_ref[:] = jnp.maximum(y, jnp.zeros_like(y))


def _norm_kernel(x_ref, mb_ref, sc_ref, sh_ref, y_ref):
    xc = x_ref[:] - mb_ref[:]
    y_ref[:] = xc * sc_ref[:].astype(x_ref.dtype) \
        + sh_ref[:].astype(x_ref.dtype)


def _norm_call(x2, mean_b, scale, shift, relu):
    """y = (x - mean_b)*scale + shift, one fused pass. Centered BEFORE the
    scale exactly like norm.py's `_bn_train`: x - bf16(mean) is exact near
    the mean (Sterbenz), so low-precision rounding applies to the
    deviation, not to mean*scale-sized intermediates."""
    m, c = x2.shape
    tm = _row_tile(m)
    return pl.pallas_call(
        _norm_kernel_relu if relu else _norm_kernel,
        grid=(m // tm,),
        in_specs=[
            pl.BlockSpec((tm, c), lambda t: (t, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda t: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda t: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda t: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tm, c), lambda t: (t, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, c), x2.dtype),
        name="bn_apply_relu" if relu else "bn_apply",
        interpret=_interpret(),
    )(x2, mean_b, scale, shift)


def _col_sums(x2, acc_dt):
    """Column sums of [n, c] with accumulator-dtype accumulation via a dot
    against ones — the MXU form norm.py's `_sum_to_f32` uses, generalized
    to f64 for the gradient-check configuration."""
    ones = jnp.ones((x2.shape[0],), x2.dtype)
    return lax.dot_general(ones, x2, (((0,), (0,)), ((), ())),
                           preferred_element_type=acc_dt)


# -- fused BN-backward epilogue ----------------------------------------------
#
# The fused-BN pullback needs two per-channel reductions over full-size
# tensors (sum g, sum g·x) and then one elementwise pass producing dx.
# XLA lowers the builtin form as three separate reductions/maps that each
# re-read the saved activation from HBM; these two kernels do it in one
# reduce pass (both sums per tile while g and x are in VMEM) plus one
# apply pass — the backward twin of the forward stats epilogue.

def _bnb_reduce_kernel(g_ref, x_ref, sg_ref, sgx_ref):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        sg_ref[:] = jnp.zeros_like(sg_ref)
        sgx_ref[:] = jnp.zeros_like(sgx_ref)

    acc_dt = sg_ref.dtype
    g = g_ref[:].astype(acc_dt)
    sg_ref[:] += jnp.sum(g, axis=0, keepdims=True)
    sgx_ref[:] += jnp.sum(g * x_ref[:].astype(acc_dt), axis=0,
                          keepdims=True)


def _bnb_apply_kernel(g_ref, x_ref, c1_ref, c3_ref, c0_ref, dx_ref):
    dt = dx_ref.dtype
    dx_ref[:] = (c1_ref[:].astype(dt) * g_ref[:]
                 - c3_ref[:].astype(dt) * x_ref[:]
                 + c0_ref[:].astype(dt))


def _bnb_reduce_call(g2, x2):
    m, c = g2.shape
    acc = _acc_dtype(g2.dtype)
    tm = _row_tile(m)
    return pl.pallas_call(
        _bnb_reduce_kernel,
        grid=(m // tm,),
        in_specs=[
            pl.BlockSpec((tm, c), lambda t: (t, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tm, c), lambda t: (t, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, c), lambda t: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda t: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, c), acc),
            jax.ShapeDtypeStruct((1, c), acc),
        ],
        name="bn_bwd_reduce",
        interpret=_interpret(),
    )(g2, x2)


def _bnb_apply_call(g2, x2, c1, c3, c0):
    m, c = g2.shape
    tm = _row_tile(m)
    return pl.pallas_call(
        _bnb_apply_kernel,
        grid=(m // tm,),
        in_specs=[
            pl.BlockSpec((tm, c), lambda t: (t, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tm, c), lambda t: (t, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda t: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda t: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda t: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tm, c), lambda t: (t, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, c), g2.dtype),
        name="bn_bwd_apply",
        interpret=_interpret(),
    )(g2, x2, c1, c3, c0)


def bn_backward_fused(g, x_for_dx, center, gamma, inv, n):
    """The fused-BN pullback's heavy lifting in two Pallas passes.

    g:        activation-dtype cotangent (already ReLU-gated if fused);
    x_for_dx: the tensor the dx formula is affine in — centered x for the
              bf16 path, raw x for the f32 path (norm.py `_bn_train_bwd`);
    center:   accumulator-dtype per-channel recentering constant — delta
              (mean's rounding error) for bf16, mean for f32 — so
              sum_gx = Σ g·x_for_dx − center·Σ g matches the builtin;
    gamma/inv: per-channel scale and rsqrt(var+eps); n: reduced elements.

    Returns (dx, dgamma, dbeta) with dx in g.dtype and dgamma/dbeta in
    the accumulator dtype (callers cast to the parameter dtype). The
    coefficient algebra is EXACTLY norm.py's `_bn_train_bwd`; only the
    reductions and the elementwise map are fused."""
    c = g.shape[-1]
    acc = _acc_dtype(g.dtype)
    g2 = g.reshape(n, c)
    x2 = x_for_dx.reshape(n, c)
    sg, sgx_raw = _bnb_reduce_call(g2, x2)
    sum_g = sg[0]
    sum_gx = sgx_raw[0] - center.astype(acc) * sum_g
    gamma_f = gamma.astype(acc)
    dgamma = inv * sum_gx
    dbeta = sum_g
    c1 = gamma_f * inv
    c3 = gamma_f * inv * inv * inv * sum_gx / n
    c0 = -(c1 * sum_g / n) + c3 * center.astype(acc)
    dx2 = _bnb_apply_call(g2, x2, c1[None, :], c3[None, :], c0[None, :])
    return dx2.reshape(g.shape), dgamma, dbeta


def _bn_backward_pieces(g, x, mean, inv, gamma, n):
    """(x_for_dx, center) for the dtype-appropriate recentering, then the
    fused backward if the "bn_backward" helper engages, else the builtin
    reductions — shared by `_bn_bwd` below and norm.py's `_bn_train_bwd`.
    Returns (dx, dgamma, dbeta) in (x.dtype, acc, acc)."""
    from deeplearning4j_tpu.ops.helpers import HelperError, get_helper

    c = x.shape[-1]
    acc = _acc_dtype(x.dtype)
    if x.dtype == jnp.bfloat16:
        mean_b = mean.astype(x.dtype)
        center = mean - mean_b.astype(acc)  # delta: mean's rounding error
        x_for_dx = x - jnp.broadcast_to(mean_b, x.shape)
    else:
        center = mean
        x_for_dx = x
    helper = get_helper("bn_backward", x_shape=tuple(x.shape),
                        dtype=x.dtype, training=True)
    if helper is not None:
        try:
            return helper(g, x_for_dx, center, gamma, inv, n)
        except HelperError:
            pass  # helper auto-disabled itself; builtin path below
    g2 = g.astype(acc) if x.dtype != jnp.bfloat16 else g
    g2 = g2.reshape(n, c)
    x2 = (x_for_dx.astype(acc)
          if x.dtype != jnp.bfloat16 else x_for_dx).reshape(n, c)
    if x.dtype == jnp.bfloat16:
        sum_g = _col_sums(g2, acc)
        sum_gx = _col_sums(g2 * x2, acc) - center * sum_g
    else:
        sum_g = jnp.sum(g2, axis=0)
        sum_gx = jnp.sum(g2 * x2, axis=0) - center * sum_g
    gamma_f = gamma.astype(acc)
    dgamma = inv * sum_gx
    dbeta = sum_g
    c1 = gamma_f * inv
    c3 = gamma_f * inv * inv * inv * sum_gx / n
    c0 = -(c1 * sum_g / n) + c3 * center
    dx = (c1.astype(x.dtype) * g - c3.astype(x.dtype) * x_for_dx
          + c0.astype(x.dtype))
    return dx, dgamma, dbeta


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def bn_apply(x, s1, s2, gamma, beta, eps, n, relu):
    """Training-mode batch norm from precomputed raw moments: one fused
    read of x (normalize + optional ReLU in a single Pallas pass) instead
    of XLA's reduce-then-normalize double read. Returns (y, mean, var)
    exactly like norm.py's `_bn_train`; mean/var feed the running-EMA
    state only. n = number of reduced elements (x.size / channels);
    eps/n/relu are static."""
    out, _ = _bn_fwd(x, s1, s2, gamma, beta, eps, n, relu)
    return out


def _bn_fwd(x, s1, s2, gamma, beta, eps, n, relu):
    acc = _acc_dtype(x.dtype)
    c = x.shape[-1]
    mean = s1.astype(acc) / n
    var = jnp.maximum(s2.astype(acc) / n - mean * mean, 0.0)
    inv = lax.rsqrt(var + eps)
    scale = gamma.astype(acc) * inv
    # centered application (norm.py's bf16 form): y = (x - bf16(mean))
    # * scale + (beta - delta*scale), with delta the mean's rounding error
    mean_b = mean.astype(x.dtype)
    delta = mean - mean_b.astype(acc)
    shift = beta.astype(acc) - delta * scale
    y2 = _norm_call(x.reshape(n, c), mean_b[None, :], scale[None, :],
                    shift[None, :], relu)
    y = y2.reshape(x.shape)
    return (y, mean, var), (x, gamma, mean, inv, y)


def _bn_bwd(eps, n, relu, res, cts):
    """The fused-BN VJP of nn/layers/norm.py (`_bn_train_bwd`), extended
    with the ReLU gate: per-channel coefficients in the accumulator dtype,
    every full-size tensor in x.dtype; bf16 uses the centered reduction
    (x - bf16(mean), exact by Sterbenz near the mean) so sum_gx never
    cancels catastrophically. mean/var cotangents are dropped — they feed
    the non-trainable running EMA, as in the reference."""
    g, _, _ = cts
    x, gamma, mean, inv, y = res
    g = g.astype(x.dtype)
    if relu:
        g = jnp.where(y > 0, g, jnp.zeros_like(g))
    c = x.shape[-1]
    dx, dgamma, dbeta = _bn_backward_pieces(g, x, mean, inv, gamma, n)
    # dx is the TOTAL derivative (elementwise + both statistics paths);
    # the raw-moment inputs therefore receive zero cotangent.
    zs = jnp.zeros((c,), _acc_dtype(x.dtype))
    return (dx, zs, zs, dgamma.astype(gamma.dtype),
            dbeta.astype(gamma.dtype))


bn_apply.defvjp(_bn_fwd, _bn_bwd)


# -- Helper SPI wiring -------------------------------------------------------

# What one kernel may take of the chip's fast memory. The v5e compiler
# allows a kernel 16 MiB of scoped VMEM by default and refuses above it
# ("Scoped allocation with size 19.03M and limit 16.00M", the 1x1 kernel
# at 2048->4096 compiled by itself for the described v5e:2x2; inside a
# larger program XLA may place the operands otherwise and let the same
# kernel pass); the estimates of `_conv_vmem_ok` are held 4 MiB under
# that limit.
_VMEM_BUDGET = 12 * 1024 * 1024

# The kxk kernel holds one whole image per grid step and unrolls its taps
# over it, so the chip compiler's time and memory grow with the image:
# 40x40x128 compiles in 14 s, 48x48x128 in 46 s, 56x56x128 was stopped
# after 40 minutes and 28 GB. Images above this many output rows are
# refused.
_CK_MAX_ROWS = 40 * 40

# "unsupported" reasons that are the chip compiler's verdict on a shape
# (tests/test_tpu_compile.py holds each to that compiler), as opposed to
# a conv the kernel family was never written for
CHIP_REFUSALS = ("strided_taps", "lane_alignment", "image_rows", "vmem")

# the structural whitelist: every ResNet-50 trunk conv is one of these
_KERNEL_STRIDES = {
    ((1, 1), (1, 1)): "conv1x1",
    ((1, 1), (2, 2)): "conv1x1s2",
    ((3, 3), (1, 1)): "conv3x3",
    ((3, 3), (2, 2)): "conv3x3s2",
    ((7, 7), (2, 2)): "conv7x7s2",
}


def conv_family(*, kernel=None, stride=None, **_):
    """Bounded kernel-family slug for the helper metrics labels: one of
    the five covered kernel/stride shapes, else "conv_other"."""
    if kernel is None or stride is None:
        return "conv_other"
    return _KERNEL_STRIDES.get((tuple(kernel), tuple(stride)), "conv_other")


def _conv_vmem_ok(kernel, stride, x_shape, n_in, n_out, itemsize) -> bool:
    """Estimate of the kernel's scoped VMEM against `_VMEM_BUDGET`, each
    form checked against what the described v5e compiler reports: the 1x1
    kernel takes the weights once plus double-buffered row tiles (19.03M
    reported, 19.0M estimated, at 2048->4096); the kxk kernel takes
    image, output and weights twice plus about seven image-sized f32
    temporaries of its unrolled taps (23.26M reported, 23.8M estimated,
    at 3x3 28x28x512->512)."""
    kh, kw = kernel
    if (kh, kw) == (1, 1):
        wgt = n_in * n_out * itemsize
        tm = 128 if n_in * n_out >= 1024 * 1024 else 512
        tiles = 2 * tm * (n_in + n_out) * itemsize
        return wgt + tiles <= _VMEM_BUDGET
    h, w = x_shape[1], x_shape[2]
    ho = -(-h // stride[0])
    wo = -(-w // stride[1])
    slab = h * w * n_in * itemsize  # one full input image
    out = ho * wo * n_out * itemsize
    accf = ho * wo * n_out * 4
    wgt = kh * kw * n_in * n_out * itemsize
    return 2 * (slab + out + wgt) + 7 * accf <= _VMEM_BUDGET


def _ck_chip_refusal(stride, x_shape, n_in):
    """Why the chip's compiler refuses this kxk instance, from its shape,
    or None. Each reason is a refusal seen when compiling for the
    described v5e:2x2 (interpret mode has none of these limits):
    stride > 1 needs a strided vector slice per tap ("'vector.
    extract_strided_slice' op expected strides to be confined to
    [1, 2)"); an input of fewer than 128 channels leaves the tap's
    (rows, cols, cin) -> (rows*cols, cin) reshape with partly filled
    lanes ("infer-vector-layout: unsupported shape cast"); and see
    `_CK_MAX_ROWS`."""
    if tuple(stride) != (1, 1):
        return "strided_taps"
    if n_in % 128:
        return "lane_alignment"
    ho = -(-x_shape[1] // stride[0])
    wo = -(-x_shape[2] // stride[1])
    if ho * wo > _CK_MAX_ROWS:
        return "image_rows"
    return None


def conv_decision(*, kernel, stride, dilation, same, has_bias, activation,
                  dtype, n_in, n_out, x_shape, training, planning=False,
                  **_):
    """Routing decision for the "conv2d" slot, in two stages:

    1. structural: the kernel must EXIST for the shape (bias-free SAME
       identity conv, kernel/stride in `_KERNEL_STRIDES`) and, on the
       TPU, COMPILE for it (channels that tile the 128-lane registers,
       no `_ck_chip_refusal`, the kernel inside the VMEM budget) —
       failures are "unsupported", the cuDNN checkSupported pattern,
       decided from the shape and never by catching the compiler;
    2. economic: the per-instance roofline verdict
       (analysis/costmodel.instance_roofline). The stats epilogue saves
       an HBM read — worth exactly nothing on an MXU-saturating conv, so
       compute-bound instances are "declined" and keep the XLA lowering:
       a compute-bound shape can never regress through the helper.

    Returns {"status": "covered"|"declined"|"unsupported", "reason",
    "family", "roofline"} — `cli perf`'s coverage table prints exactly
    this. planning=True models the TPU routing decision regardless of
    the local backend/interpret state (used by the coverage table and
    the T1 kernel-coverage smoke on CPU hosts)."""
    fam = conv_family(kernel=kernel, stride=stride)

    def uns(reason):
        return {"status": "unsupported", "reason": reason, "family": fam,
                "roofline": None}

    if not training:
        return uns("inference")
    if has_bias:
        return uns("bias")
    if not same:
        return uns("padding")
    if activation not in (None, "identity"):
        return uns("fused_activation")
    if tuple(dilation) != (1, 1):
        return uns("dilation")
    k, s = tuple(kernel), tuple(stride)
    if (k, s) not in _KERNEL_STRIDES:
        return uns("kernel_shape")
    if planning:
        pass  # model the TPU decision for any local backend/dtype
    elif _interpret():
        # CPU correctness/bench mode: any float dtype, tiny channels
        if not jnp.issubdtype(dtype, jnp.floating):
            return uns("dtype")
    else:
        if jax.default_backend() != "tpu":
            return uns("backend")
        if dtype != jnp.bfloat16:
            return uns("dtype")
    if planning or not _interpret():
        if k != (1, 1):
            refusal = _ck_chip_refusal(s, x_shape, n_in)
            if refusal is not None:
                return uns(refusal)
        # trunk channel counts tile the 128-lane registers cleanly
        if n_in % 64 or n_out % 64:
            return uns("channel_alignment")
        if not _conv_vmem_ok(k, s, x_shape, n_in, n_out,
                             jnp.dtype(dtype).itemsize):
            return uns("vmem")
    from deeplearning4j_tpu.analysis.costmodel import (
        conv_instance_cost,
        instance_roofline,
    )

    cost = conv_instance_cost(kernel=k, stride=s, x_shape=x_shape,
                              n_out=n_out,
                              itemsize=jnp.dtype(dtype).itemsize)
    rf = instance_roofline(cost["flops"], cost["bytes"])
    if rf["verdict"] == "compute-bound":
        return {"status": "declined", "reason": "compute_bound",
                "family": fam, "roofline": rf}
    return {"status": "covered", "reason": "memory_bound", "family": fam,
            "roofline": rf}


def conv_supported(*, kernel, stride, dilation, same, has_bias, activation,
                   dtype, n_in, n_out, x_shape, training, **_):
    """Probe for the "conv2d" slot — thin wrapper over `conv_decision`:
    engage the kernel only when the instance is structurally covered AND
    memory-bound on the roofline."""
    return conv_decision(
        kernel=kernel, stride=stride, dilation=dilation, same=same,
        has_bias=has_bias, activation=activation, dtype=dtype, n_in=n_in,
        n_out=n_out, x_shape=x_shape, training=training,
    )["status"] == "covered"


def bn_supported(*, x, training, **_):
    """Probe for the "batch_norm" slot: only engages when the input IS a
    stashed conv-epilogue output (identity match) — otherwise the built-in
    fused XLA path is already optimal (it needs the stats reduction
    anyway). The normalize pass is a pure streaming map (≈2 FLOP/byte),
    so the per-instance roofline consult can only say memory-bound; it
    runs anyway so the routing stays cost-model-driven by construction."""
    if not training or not hasattr(x, "ndim") or x.ndim != 4:
        return False
    if not _interpret():
        if jax.default_backend() != "tpu" or x.dtype != jnp.bfloat16:
            return False
    if not peek_stats(x):
        return False
    from deeplearning4j_tpu.analysis.costmodel import (
        bn_instance_cost,
        instance_roofline,
    )

    cost = bn_instance_cost(x_shape=tuple(x.shape),
                            itemsize=jnp.dtype(x.dtype).itemsize)
    return instance_roofline(cost["flops"],
                             cost["bytes"])["verdict"] == "memory-bound"


def bn_bwd_supported(*, x_shape, dtype, training, **_):
    """Probe for the "bn_backward" slot (the fused reduce+apply pullback).
    Same backend/dtype scope as the forward kernels; the roofline consult
    prices the pullback's traffic (read g and x twice, write dx once) —
    like the normalize it is structurally memory-bound, and the consult
    keeps that a checked fact rather than an assumption."""
    if not training or len(x_shape) < 2:
        return False
    if not _interpret():
        if jax.default_backend() != "tpu" or dtype != jnp.bfloat16:
            return False
        if x_shape[-1] % 64:
            return False
    elif not jnp.issubdtype(dtype, jnp.floating):
        return False
    from deeplearning4j_tpu.analysis.costmodel import (
        bn_instance_cost,
        instance_roofline,
    )

    cost = bn_instance_cost(x_shape=tuple(x_shape),
                            itemsize=jnp.dtype(dtype).itemsize,
                            n_reads=4, n_writes=1)
    return instance_roofline(cost["flops"],
                             cost["bytes"])["verdict"] == "memory-bound"


def _conv2d_helper(x, w, *, strides):
    y, s1, s2 = conv2d_bn_stats(x, w, tuple(int(s) for s in strides))
    # stop_gradient: the stats must never carry their own cotangent —
    # bn_apply's backward already accounts for them (module docstring)
    _stash_stats(y, lax.stop_gradient(s1), lax.stop_gradient(s2))
    return y


def _bn_helper(x, gamma, beta, eps):
    st = take_stats(x)
    if st is None:  # probe checked peek_stats; defensive
        raise RuntimeError("bn helper called without stashed conv stats")
    s1, s2 = st
    n = x.size // x.shape[-1]
    y, mean, var = bn_apply(x, s1, s2, gamma, beta, float(eps), n, False)
    # deferred ReLU: a downstream relu ActivationLayer swaps in the fused
    # variant; the plain-normalize call above then has no consumers and is
    # dead-code-eliminated at lowering
    _stash_relu(y, lambda: bn_apply(x, s1, s2, gamma, beta,
                                    float(eps), n, True)[0])
    return y, mean, var


def register():
    from deeplearning4j_tpu.ops.helpers import register_helper

    register_helper("conv2d", _conv2d_helper, conv_supported,
                    name="pallas_conv_bn_stats", family=conv_family)
    register_helper("batch_norm", _bn_helper, bn_supported,
                    name="pallas_fused_bn_apply",
                    family=lambda **_: "bn_apply")
    register_helper("bn_backward", bn_backward_fused, bn_bwd_supported,
                    name="pallas_fused_bn_bwd",
                    family=lambda **_: "bn_bwd")


register()

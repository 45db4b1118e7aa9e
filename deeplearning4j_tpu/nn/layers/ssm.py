"""The Mamba-2 mixer (config: Mamba2Layer).

    [z | xBC | dt] = u W_in
    xBC = silu(causal depthwise conv1d(xBC) + conv_b)      -> x, B, C
    dt  = softplus(dt + dt_bias),  A = -exp(A_log)          a head
    H_t = exp(dt_t A) H_(t-1) + dt_t x_t B_t^T,  y_t = H_t C_t + D x_t
    out = GroupRMSNorm(y * silu(z)) W_out

The recurrence runs in its chunked (state-space dual) form, `ssd_chunked`:
inside a chunk the masked, decay-weighted `C B^T` product, across chunks the
carried state; the tests hold it to the literal scan over time of the plain
reference (`benchmark/reference/nemotron_h.recurrence`). Decays, `dt` and the
carried state
are float32; the products take operands of the net's compute dtype and
accumulate in float32.

The two elementwise stages around the scan, `conv_silu` (taps + bias + SiLU)
and `gate_norm` (skip `D x`, gate `silu(z)`, group RMS norm), have
hand-written gradients (`jax.custom_vjp`). What they store: `xBC`, `z`, the
stages' outputs and the cotangents of all of these in the compute dtype; the
scan's `y`, its cotangent, every statistic and every parameter gradient in
float32. What they compute in: float32 (float64 where the net runs in it),
the group statistics as products with a 0/1 membership matrix at
`Precision.HIGHEST`. Their residuals are their stored inputs; every
full-size tensor keeps the lane-dense `[b, t, channels]` layout.
`mamba2_stage_lowering_total{stage, kind="fused_vjp"}` counts each stage
once per trace.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.layers.registry import LayerContext, register_layer
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.utils import metrics as _metrics


def mamba2_sizes(conf: L.Mamba2Layer):
    """(d_inner, width of B or of C, channels of the conv)."""
    d_inner = int(conf.n_heads) * int(conf.head_dim)
    bc = int(conf.n_groups) * int(conf.state_size)
    return d_inner, bc, d_inner + 2 * bc


def mamba2_init(key, conf: L.Mamba2Layer, dtype):
    """Matrices as the net's `weight_init` says; Mamba-2's own for the rest:
    `A_log = log(uniform(1, 16))`, `dt_bias` the inverse softplus of a `dt`
    log-uniform in [time_step_min, time_step_max] and floored, `D = 1`,
    norm weight 1, conv weight uniform in +-1/sqrt(k), conv bias 0."""
    H, k = int(conf.n_heads), int(conf.conv_kernel)
    if H % int(conf.n_groups):
        raise ValueError(f"n_heads {H} must be a multiple of n_groups "
                         f"{conf.n_groups}")
    d_inner, bc, conv_dim = mamba2_sizes(conf)
    n_in, n_out = int(conf.n_in), int(conf.n_out)
    ks = jax.random.split(key, 5)
    d_proj = 2 * d_inner + 2 * bc + H
    dt = jnp.exp(jax.random.uniform(ks[3], (H,), dtype)
                 * (math.log(conf.time_step_max)
                    - math.log(conf.time_step_min))
                 + math.log(conf.time_step_min))
    dt = jnp.maximum(dt, conf.time_step_floor)
    bound = 1.0 / math.sqrt(k)
    return {
        "W_in": init_weights(ks[0], (n_in, d_proj), n_in, d_proj,
                             conf.weight_init, conf.dist, dtype),
        "conv_W": jax.random.uniform(ks[1], (k, conv_dim), dtype, -bound,
                                     bound),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(ks[4], (H,), dtype, 1.0, 16.0)),
        "D": jnp.ones((H,), dtype),
        "norm_gamma": jnp.ones((d_inner,), dtype),
        "W_out": init_weights(ks[2], (d_inner, n_out), d_inner, n_out,
                              conf.weight_init, conf.dist, dtype),
    }


def mamba2_order(conf):
    return ("W_in", "conv_W", "conv_b", "dt_bias", "A_log", "D",
            "norm_gamma", "W_out")


def ssd_chunked(x, dt, A, B, C, chunk: int):
    """The recurrence in chunks of `chunk` positions. x: [b, t, H, P], dt:
    [b, t, H] (after softplus), A: [H] (negative), B, C: [b, t, G, N]; head
    `h` reads group `h // (H // G)`; x, B and C in the dtype the products
    should take their operands in, dt and A float32. Returns `y_t = H_t C_t`
    as float32 [b, t, H, P] (the `D x_t` term is the caller's).

    With `a = dt A` and `s` its running sum inside a chunk:
    - inside: `y_i += sum_(j<=i) (C_i . B_j) exp(s_i - s_j) dt_j x_j`;
    - a chunk's state: `S = sum_j exp(s_end - s_j) dt_j x_j B_j^T`, carried
      as `H_c = exp(s_end) H_(c-1) + S` by a scan over the chunks;
    - across: `y_i += exp(s_i) C_i . H_(c-1)`.
    A length that is no multiple of `chunk` is padded with `dt = 0`
    (decay 1, nothing added), and the padding is cut off again.

    The groups of heads that share a `B` and a `C` are taken one after the
    other (`lax.map`), each under `jax.checkpoint`: the masked decay
    matrices and the chunks' states of one group live at a time, a
    `1 / groups` of what the whole layer's would take."""
    b, t, H, P = x.shape
    G, N = B.shape[2:]
    r, Q = H // G, int(chunk)
    pad = (-t) % Q
    if pad:
        grow = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        x, dt, B, C = grow(x), grow(dt), grow(B), grow(C)
    c = (t + pad) // Q
    group_first = lambda a: jnp.moveaxis(a, 3, 0)
    y = lax.map(
        jax.checkpoint(_ssd_group),
        (group_first(x.reshape(b, c, Q, G, r, P)),
         group_first(dt.astype(jnp.float32).reshape(b, c, Q, G, r)),
         A.astype(jnp.float32).reshape(G, r),
         group_first(B.reshape(b, c, Q, G, N)),
         group_first(C.reshape(b, c, Q, G, N))))      # [G, b, c, Q, r, P]
    return jnp.moveaxis(y, 0, 3).reshape(b, t + pad, H, P)[:, :t]


def _ssd_group(args):
    """One group's heads. x: [b, c, Q, r, P], dt: [b, c, Q, r], A: [r], B
    and C: [b, c, Q, N] -> y: [b, c, Q, r, P] float32."""
    x, dt, A, B, C = args
    b, c, Q, r, P = x.shape
    N = B.shape[-1]
    cd = x.dtype
    s = jnp.cumsum(dt * A, axis=2)                      # [b, c, Q, r]
    s_h = jnp.moveaxis(s, 2, -1)                        # [b, c, r, Q]

    # inside a chunk
    cb = jnp.einsum("bcin,bcjn->bcij", C, B,
                    preferred_element_type=jnp.float32)
    lower = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(jnp.where(lower, s_h[..., :, None] - s_h[..., None, :],
                              -jnp.inf))                 # [b, c, r, Q, Q]
    weights = (cb[:, :, None] * decay).astype(cd)
    xf = x.astype(jnp.float32)
    y = jnp.einsum("bcrij,bcjrp->bcirp", weights,
                   (xf * dt[..., None]).astype(cd),
                   preferred_element_type=jnp.float32)

    # the chunks' states, carried
    to_end = jnp.moveaxis(jnp.exp(s_h[..., -1:] - s_h), -1, 2)   # [b,c,Q,r]
    states = jnp.einsum("bcjrp,bcjn->bcrpn",
                        (xf * (dt * to_end)[..., None]).astype(cd), B,
                        preferred_element_type=jnp.float32)
    chunk_decay = jnp.exp(s_h[..., -1])                 # [b, c, r]

    def carry(h, inp):
        state, d = inp
        return h * d[..., None, None] + state, h        # the state coming in

    _, h_in = lax.scan(carry, jnp.zeros((b, r, P, N), jnp.float32),
                       (jnp.moveaxis(states, 1, 0),
                        jnp.moveaxis(chunk_decay, 1, 0)))
    h_in = jnp.moveaxis(h_in, 0, 1).astype(cd)          # [b, c, r, P, N]

    # across chunks
    return y + jnp.einsum("bcin,bcrpn->bcirp", C, h_in,
                          preferred_element_type=jnp.float32) \
        * jnp.exp(s)[..., None]


# -- the two elementwise stages, each with its own backward pass ---------------
#
# Autodiff of these formulas wrote a float32 tensor for every tap and every
# view it took (four `f32[b, t, conv channels]` from one fusion, broadcasts of
# the group statistic, head-shaped `[b, t, H, P]` copies whose 64 lanes are a
# relayout on the chip): 31 GB a mixer where the stages' own inputs and
# outputs are 4 (PERF.md, PR 31). The backward passes below compute the
# float32 intermediates again from the stored inputs, as the inner
# `jax.checkpoint`s they replace did.

def _acc_dtype(*arrays):
    """float32, or float64 where the net itself runs in it."""
    return jnp.result_type(jnp.float32, *arrays)


def _taps(x, k, front):
    """The k views `x_(t + j - front)`, j = 0..k-1, of a [b, t, c] tensor,
    zeros outside it: `front = k - 1` looks back, `front = 0` ahead."""
    t = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (front, k - 1 - front), (0, 0)))
    return [xp[:, j:j + t] for j in range(k)]


def _silu_slope(a):
    """d silu(a) / da."""
    sig = jax.nn.sigmoid(a)
    return sig * (1 + a * (1 - sig))


def causal_depthwise_conv1d(x, w, b):
    """x: [b, t, c] as it is stored, w: [k, c] (tap `k - 1` meets the
    current position), b: [c] -> [b, t, c] float32:
    `y_t = b + sum_j w_j x_(t - (k-1) + j)`."""
    k = w.shape[0]
    acc = _acc_dtype(x, w)
    y = b.astype(acc)
    for j, tap in enumerate(_taps(x, k, k - 1)):
        y = y + w[j].astype(acc) * tap.astype(acc)
    return y


@jax.custom_vjp
def conv_silu(x, w, b):
    """`silu(causal_depthwise_conv1d(x, w, b))` in x's dtype, one pass
    forward; backward one pass that makes `dpre` (the one float32 full-size
    intermediate) with the tap and bias sums, and one that reads it."""
    return jax.nn.silu(causal_depthwise_conv1d(x, w, b)).astype(x.dtype)


def _conv_silu_fwd(x, w, b):
    return conv_silu(x, w, b), (x, w, b)


def _conv_silu_bwd(res, g):
    x, w, b = res
    k = w.shape[0]
    acc = _acc_dtype(x, w)
    dpre = g.astype(acc) * _silu_slope(causal_depthwise_conv1d(x, w, b))
    db = jnp.sum(dpre, axis=(0, 1))
    dw = jnp.stack([jnp.sum(dpre * tap.astype(acc), axis=(0, 1))
                    for tap in _taps(x, k, k - 1)])
    # x_s was read by tap k-1-i of position s + i
    dx = sum(w[k - 1 - i].astype(acc) * ahead
             for i, ahead in enumerate(_taps(dpre, k, 0)))
    return dx.astype(x.dtype), dw.astype(w.dtype), db.astype(b.dtype)


conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def _membership(c, groups, dtype):
    """[c, groups]: 1 where the channel is of the group."""
    return (jnp.arange(c)[:, None] // (c // groups)
            == jnp.arange(groups)).astype(dtype)


def _group_mean(a, groups):
    """[..., c] -> [..., groups]: each group's mean. A product with the
    membership matrix and no reshape: splitting the channel axis of a
    full-size tensor is a relayout on the chip."""
    c = a.shape[-1]
    return jnp.matmul(a, _membership(c, groups, a.dtype),
                      precision=lax.Precision.HIGHEST) * (groups / c)


def _to_channels(a, c):
    """[..., groups] -> [..., c]: each group's value at its channels (the
    chip's compiler fuses no broadcast across the reshape that would merge
    groups and channels, it does fuse this product)."""
    return jnp.matmul(a, _membership(c, a.shape[-1], a.dtype).T,
                      precision=lax.Precision.HIGHEST)


def _gated(y, x, z, D):
    """`v = (y + D x) silu(z)`, its factors and D at every channel, in the
    accumulator dtype."""
    acc = _acc_dtype(y, x, z, D)
    d = jnp.repeat(D.astype(acc), y.shape[-1] // D.shape[0])
    u = y.astype(acc) + d * x.astype(acc)
    s = jax.nn.silu(z.astype(acc))
    return u * s, u, s, d


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def gate_norm(y, x, z, D, gamma, eps, groups):
    """`rms_normalize((y + D x) silu(z), gamma, eps, groups)` in z's dtype.
    y: [b, t, c] float32 from the scan, x and z: [b, t, c] as they are
    stored, D: [heads] (a head is `c / heads` channels), gamma: [c]."""
    v, _, _, _ = _gated(y, x, z, D)
    r = lax.rsqrt(_group_mean(v * v, groups) + eps)
    return (v * _to_channels(r, v.shape[-1])
            * gamma.astype(v.dtype)).astype(z.dtype)


def _gate_norm_fwd(y, x, z, D, gamma, eps, groups):
    return gate_norm(y, x, z, D, gamma, eps, groups), (y, x, z, D, gamma)


def _gate_norm_bwd(eps, groups, res, g):
    """With `n = v r`: `dv = r (dn - n mean_g(dn n))`, and `mean_g(dn n)`
    is `r mean_g(dn v)`: both statistics are means over `v`, computed
    again from the stored inputs."""
    y, x, z, D, gamma = res
    v, u, s, d = _gated(y, x, z, D)
    acc, c = v.dtype, v.shape[-1]
    gf = g.astype(acc)
    dn = gf * gamma.astype(acc)
    r = lax.rsqrt(_group_mean(v * v, groups) + eps)
    r_c = _to_channels(r, c)
    dv = r_c * dn - v * _to_channels(r * r * r * _group_mean(dn * v, groups),
                                     c)
    du = dv * s
    dz = dv * u * _silu_slope(z.astype(acc))
    dgamma = jnp.sum(gf * v * r_c, axis=(0, 1))
    dD = jnp.sum(du * x.astype(acc), axis=(0, 1)) \
        .reshape(D.shape[0], -1).sum(1)
    return (du.astype(y.dtype), (du * d).astype(x.dtype), dz.astype(z.dtype),
            dD.astype(D.dtype), dgamma.astype(gamma.dtype))


gate_norm.defvjp(_gate_norm_fwd, _gate_norm_bwd)


def _count_stage(stage: str) -> None:
    """Trace-time, like conv._count_pool_lowering: one event per stage per
    trace."""
    _metrics.get_registry().counter(
        "mamba2_stage_lowering_total",
        "Mamba-2 elementwise stages traced, by backward lowering",
        ("stage", "kind")).labels(stage, "fused_vjp").inc()


def mamba2_forward(conf: L.Mamba2Layer, params, x, ctx: LayerContext):
    """x: [b, t, n_in] -> [b, t, n_out] in x's dtype."""
    if ctx.mask is not None:
        raise NotImplementedError(
            "Mamba2Layer takes no time mask: a masked position would still "
            "feed the carried state")
    bsz, t, _ = x.shape
    H, P = int(conf.n_heads), int(conf.head_dim)
    G, N = int(conf.n_groups), int(conf.state_size)
    d_inner, bc, conv_dim = mamba2_sizes(conf)
    cd = ctx.compute_dtype or x.dtype
    u = x.astype(cd)
    w_in = params["W_in"].astype(cd)
    mm = lambda a, w: jnp.matmul(a, w, preferred_element_type=jnp.float32)
    z_xbc = mm(u, w_in[:, :d_inner + conv_dim]).astype(cd)
    dt = jax.nn.softplus(mm(u, w_in[:, d_inner + conv_dim:])
                         + params["dt_bias"].astype(jnp.float32))
    z, xbc = z_xbc[..., :d_inner], z_xbc[..., d_inner:]
    _count_stage("conv_silu")
    xbc = conv_silu(xbc, params["conv_W"], params["conv_b"])
    xs = xbc[..., :d_inner]
    B = xbc[..., d_inner:d_inner + bc].reshape(bsz, t, G, N)
    C = xbc[..., d_inner + bc:].reshape(bsz, t, G, N)
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    with jax.named_scope("ssd_scan"):
        # the head-shaped views are the scan's own: around it a channel
        # stays a channel
        y = ssd_chunked(xs.reshape(bsz, t, H, P), dt, A, B, C,
                        int(conf.chunk_size)).reshape(bsz, t, d_inner)
    _count_stage("gate_norm")
    y = gate_norm(y, xs, z, params["D"], params["norm_gamma"],
                  conf.norm_eps, G)
    out = mm(y, params["W_out"].astype(cd))
    return out.astype(x.dtype), None


register_layer(L.Mamba2Layer, mamba2_init, mamba2_forward,
               order_fn=mamba2_order)

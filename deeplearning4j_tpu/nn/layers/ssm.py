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
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.layers.norm import rms_normalize
from deeplearning4j_tpu.nn.layers.registry import LayerContext, register_layer
from deeplearning4j_tpu.nn.weights import init_weights


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


def causal_depthwise_conv1d(x, w, b):
    """x: [b, t, c], w: [k, c] (tap `k - 1` meets the current position),
    b: [c] -> [b, t, c] float32: `y_t = b + sum_j w_j x_(t - (k-1) + j)`."""
    k, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    y = b.astype(jnp.float32)
    for j in range(k):
        y = y + w[j].astype(jnp.float32) * xp[:, j:j + t]
    return y


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
    # conv + silu and, below, gate + norm keep their narrow inputs for the
    # backward pass and compute their float32 intermediates again
    xbc = jax.checkpoint(lambda a, w, c: jax.nn.silu(
        causal_depthwise_conv1d(a, w, c)).astype(cd))(
            xbc, params["conv_W"], params["conv_b"])
    xs = xbc[..., :d_inner].reshape(bsz, t, H, P)
    B = xbc[..., d_inner:d_inner + bc].reshape(bsz, t, G, N)
    C = xbc[..., d_inner + bc:].reshape(bsz, t, G, N)
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    with jax.named_scope("ssd_scan"):
        y = ssd_chunked(xs, dt, A, B, C, int(conf.chunk_size))

    def gate_and_norm(y, xs, z, D, gamma):
        y = y + D.astype(jnp.float32)[:, None] * xs.astype(jnp.float32)
        y = y.reshape(bsz, t, d_inner) * jax.nn.silu(z.astype(jnp.float32))
        return rms_normalize(y, gamma, conf.norm_eps, G).astype(cd)

    y = jax.checkpoint(gate_and_norm)(y, xs, z, params["D"],
                                      params["norm_gamma"])
    out = mm(y, params["W_out"].astype(cd))
    return out.astype(x.dtype), None


register_layer(L.Mamba2Layer, mamba2_init, mamba2_forward,
               order_fn=mamba2_order)

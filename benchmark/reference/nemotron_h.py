"""Plain reference of the nemotron_h family's hybrid decoder as the
`nemotron3_nano_30b_a3b` configuration states it (source: the model's public
`config.json`; `model_type` `nemotron_h`). With `u = RMSNorm(x)` every block
is `x <- x + mixer(u)`, the mixer by the block's letter in
`hybrid_override_pattern`:

* `M`, Mamba-2: `[z | xBC | dt] = u W_in`; `xBC = silu(causal depthwise
  conv1d(xBC) + b)`, split into `x` (heads x head_dim), `B`, `C` (groups x
  state; head `h` reads group `h // (heads / groups)`); `dt = softplus(dt +
  dt_bias)`, `A = -exp(A_log)` a head; the recurrence a head, from `H_0 = 0`
  for each row: `H_t = exp(dt_t A) H_(t-1) + dt_t x_t B_t^T`, `y_t = H_t C_t
  + D x_t`; `out = GroupRMSNorm(y * silu(z)) W_out`. THE RECURRENCE IS THE
  LITERAL ONE, one position at a time (a scan over chunks of a checkpointed
  scan, so that its gradient fits): the independent statement that the
  program's chunked form is held to.
* `*`, attention: `q = u W_q`, `k = u W_k`, `v = u W_v`, causal
  `softmax(q k^T / sqrt(head_dim)) v` with each key-value head read by
  `heads / kv_heads` query heads, `out = o W_o`. No bias, no positional
  term: the family applies none.
* `E`, experts: `s = sigmoid(u W_r)` over all `router_width` outputs; the
  `num_experts_per_tok` largest are chosen; `w_i = routed_scaling_factor s_i
  / (sum of the chosen s)`; `out = sum over chosen i in experts_held of w_i
  W2_i relu(W1_i u)^2 + Ws2 relu(Ws1 u)^2`. A loop over the experts held,
  each applied to every token under a dense mask: no gather, no capacity.
  What the experts held elsewhere would add is left out.

After the last block a final RMSNorm, `logits = u W_head`, and the mean over
all positions of the next-token cross-entropy over the rows held.

Departures from the source, all under `assumed` in the configuration file
too: no `initializer_range` is published (normal 0.02 for matrices,
Mamba-2's own for `A_log`, `dt_bias`, `D`, conv; norm weights 1);
`e_score_correction_bias` is 0 and no parameter; `dt` is not clamped beyond
softplus; the residual stream is float32. Queries are taken in blocks
against their keys and every block of the net is recomputed in the backward
pass: neither changes any arithmetic.

`precision` other than "f32" rounds what a program of that compute type
holds in it: the operands of every matrix product (activations and weights)
and the activations handed between a mixer's parts, and on the way back the
cotangents of those. Router scores, `dt`, decays, the carried state, norms'
statistics, the softmax and the loss stay float32 in every mode, as the
configuration guarantees. The rounding is `lax.reduce_precision` (bf16: 8
exponent and 7 mantissa bits; "fp8": 4 and 3, with one scale a tensor so
that its largest value sits at 240, the format's largest): an operation of
its own that the compiler keeps, where it is free to drop a convert to a
narrow type and back (`plain.store`'s bf16 reads a gap of 0 on the chip).

Layer keys are the program's vertex names (`embed`, `b<i>_norm`,
`b<i>_mixer`, `final_norm`, `head`) and parameter names its own.
"""

from __future__ import annotations

import gc
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import plain

_HI = lax.Precision.HIGHEST
QUERY_BLOCK = 512      # queries whose scores are held at once
SCAN_CHUNK = 128       # positions of the literal scan recomputed together


def _sizes(config):
    H, P = config["mamba_num_heads"], config["mamba_head_dim"]
    G, N = config["n_groups"], config["ssm_state_size"]
    d_inner, bc = H * P, G * N
    return {"H": H, "P": P, "G": G, "N": N, "d_inner": d_inner, "bc": bc,
            "conv_dim": d_inner + 2 * bc, "k": config["conv_kernel"],
            "d": config["hidden_size"], "vocab": config["vocab_size"],
            "heads": config["num_attention_heads"],
            "kv": config["num_key_value_heads"], "hd": config["head_dim"],
            "width": config["moe_intermediate_size"],
            "shared": config["moe_shared_expert_intermediate_size"],
            "router": config.get("router_width",
                                 config["n_routed_experts"]),
            "held": list(config.get(
                "experts_held", range(config["n_routed_experts"]))),
            "top": config["num_experts_per_tok"],
            "eps": config["layer_norm_epsilon"]}


def _blocks(config):
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != config["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern and num_hidden_layers "
                         "disagree")
    return list(enumerate(pattern))


def layers(config):
    z = _sizes(config)
    d = z["d"]
    out = [{"kind": "embedding", "key": "embed", "rows": z["vocab"],
            "width": d}]
    dense = lambda key, n_in, n_out: {"kind": "dense", "key": key,
                                      "n_in": n_in, "n_out": n_out}
    for i, kind in _blocks(config):
        key = f"b{i}_mixer"
        if kind == "M":
            out += [dense(key, d, 2 * z["d_inner"] + 2 * z["bc"] + z["H"]),
                    {"kind": "depthwise_conv1d", "key": key,
                     "channels": z["conv_dim"], "k": z["k"]},
                    {"kind": "scan", "key": key, "heads": z["H"],
                     "head_dim": z["P"], "state": z["N"]},
                    dense(key, z["d_inner"], d)]
        elif kind == "*":
            out += [dense(key, d, z["heads"] * z["hd"]),
                    dense(key, d, z["kv"] * z["hd"]),
                    dense(key, d, z["kv"] * z["hd"]),
                    {"kind": "attention", "key": key, "n_heads": z["heads"],
                     "head_dim": z["hd"]},
                    dense(key, z["heads"] * z["hd"], d)]
        else:
            routed = {"kind": "experts", "key": key,
                      "experts_per_token": z["top"], "held": len(z["held"]),
                      "routed": z["router"]}
            out += [dense(key, d, z["router"]),
                    dict(routed, n_in=d, n_out=z["width"]),
                    dict(routed, n_in=z["width"], n_out=d),
                    dense(key, d, z["shared"]), dense(key, z["shared"], d)]
    out.append(dense("head", d, z["vocab"]))
    return out


def init_params(seed, config):
    """All weights from the seed in one jitted call, float32."""
    # This reference fills the chip. What its caller has dropped (the
    # program's net: `del` leaves it to the cycle collector, its step
    # functions point back at it) must be gone before these weights and the
    # optimizer's state beside them are made.
    gc.collect()
    z = _sizes(config)
    d, std = z["d"], 0.02
    blocks = _blocks(config)

    @jax.jit
    def make(key):
        count = [0]

        def normal(*shape):
            count[0] += 1
            return std * jax.random.normal(jax.random.fold_in(key, count[0]),
                                           shape, jnp.float32)

        def uniform(shape, lo, hi):
            count[0] += 1
            return jax.random.uniform(jax.random.fold_in(key, count[0]),
                                      shape, jnp.float32, lo, hi)

        params = {"embed": {"W": normal(z["vocab"], d)}}
        for i, kind in blocks:
            params[f"b{i}_norm"] = {"gamma": jnp.ones((d,), jnp.float32)}
            if kind == "M":
                lo, hi = config["time_step_min"], config["time_step_max"]
                dt = jnp.exp(uniform((z["H"],), math.log(lo), math.log(hi)))
                dt = jnp.maximum(dt, config["time_step_floor"])
                bound = 1.0 / math.sqrt(z["k"])
                p = {"W_in": normal(d, 2 * z["d_inner"] + 2 * z["bc"]
                                    + z["H"]),
                     "conv_W": uniform((z["k"], z["conv_dim"]), -bound,
                                       bound),
                     "conv_b": jnp.zeros((z["conv_dim"],), jnp.float32),
                     # the inverse of softplus, so that softplus gives dt
                     "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                     "A_log": jnp.log(uniform((z["H"],), 1.0, 16.0)),
                     "D": jnp.ones((z["H"],), jnp.float32),
                     "norm_gamma": jnp.ones((z["d_inner"],), jnp.float32),
                     "W_out": normal(z["d_inner"], d)}
            elif kind == "*":
                p = {"Wq": normal(d, z["heads"] * z["hd"]),
                     "Wk": normal(d, z["kv"] * z["hd"]),
                     "Wv": normal(d, z["kv"] * z["hd"]),
                     "Wo": normal(z["heads"] * z["hd"], d)}
            else:
                n = len(z["held"])
                p = {"W_router": normal(d, z["router"]),
                     "W1": normal(n, d, z["width"]),
                     "W2": normal(n, z["width"], d),
                     "Ws1": normal(d, z["shared"]),
                     "Ws2": normal(z["shared"], d)}
            params[f"b{i}_mixer"] = p
        params["final_norm"] = {"gamma": jnp.ones((d,), jnp.float32)}
        params["head"] = {"W": normal(d, z["vocab"])}
        return params

    return make(plain.seed_key(seed))


# -- the parts ------------------------------------------------------------------

_FP8_MAX = 240.0       # the largest value of 4 exponent and 3 mantissa bits


def _rounded(a, precision):
    if precision == "bf16":
        return lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / _FP8_MAX
    return lax.reduce_precision(a / scale, exponent_bits=4,
                                mantissa_bits=3) * scale


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _store(a, precision):
    return _rounded(a, precision)


_store.defvjp(lambda a, precision: (_rounded(a, precision), None),
              lambda precision, _, g: (_rounded(g, precision),))


def store(a, precision):
    """A value as a program of that compute type holds it: rounded on the
    way forward, its cotangent rounded on the way back."""
    if precision == "f32":
        return a
    if precision not in plain.PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}; one of "
                         f"{plain.PRECISIONS}")
    return _store(a, precision)


def _mm(a, w, precision):
    """A matrix product as a program of that compute type makes it: both
    operands in it, the sum in float32."""
    return jnp.matmul(store(a, precision), store(w, precision),
                      precision=_HI, preferred_element_type=jnp.float32)


def rms_norm(x, gamma, eps, groups=1):
    shape = x.shape
    if groups > 1:
        x = x.reshape(shape[:-1] + (groups, shape[-1] // groups))
    x = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return x.reshape(shape) * gamma


def causal_conv1d(x, w, b):
    """Depthwise over time: `y_t = b + sum_j w_j x_(t - (k - 1) + j)`,
    nothing from before the first position."""
    k, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return b + sum(w[j] * xp[:, j:j + t] for j in range(k))


def recurrence(x, dt, A, B, C, chunk=SCAN_CHUNK):
    """`H_t = exp(dt_t A) H_(t-1) + dt_t x_t B_t^T`, `y_t = H_t C_t`, from
    `H_0 = 0`, one position at a time. x: [b, t, H, P], dt: [b, t, H], A:
    [H], B, C: [b, t, H, N] (already a head's own). The scan over time is
    split into chunks whose inner scan is recomputed in the backward pass:
    the same steps in the same order."""
    b, t, H, P = x.shape
    N = B.shape[-1]

    def step(h, inp):
        x_t, dt_t, B_t, C_t = inp
        h = jnp.exp(dt_t * A)[..., None, None] * h \
            + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :]
        return h, jnp.sum(h * C_t[:, :, None, :], axis=-1)

    @jax.checkpoint
    def run_chunk(h, inputs):
        return lax.scan(step, h, inputs)

    pad = (-t) % chunk
    def chunks(a):   # [b, t, ...] -> [chunks, chunk, b, ...]
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((-1, chunk) + a.shape[1:])

    # padding comes after the last position and has dt = 0: it changes
    # nothing that is kept
    _, y = lax.scan(run_chunk, jnp.zeros((b, H, P, N), jnp.float32),
                    (chunks(x), chunks(dt), chunks(B), chunks(C)))
    y = y.reshape((-1,) + y.shape[2:])[:t]
    return jnp.moveaxis(y, 0, 1)


def mamba2(p, u, z, precision):
    b, t, _ = u.shape
    H, P, G, N = z["H"], z["P"], z["G"], z["N"]
    proj = _mm(u, p["W_in"], precision)
    split = z["d_inner"] + z["conv_dim"]
    gate = store(proj[..., :z["d_inner"]], precision)
    xbc = store(proj[..., z["d_inner"]:split], precision)
    dt = jax.nn.softplus(proj[..., split:] + p["dt_bias"])
    xbc = store(jax.nn.silu(causal_conv1d(xbc, p["conv_W"],
                                                p["conv_b"])), precision)
    x = xbc[..., :z["d_inner"]].reshape(b, t, H, P)
    own = lambda a: jnp.repeat(a.reshape(b, t, G, N), H // G, axis=2)
    B = own(xbc[..., z["d_inner"]:z["d_inner"] + z["bc"]])
    C = own(xbc[..., z["d_inner"] + z["bc"]:])
    y = recurrence(x, dt, -jnp.exp(p["A_log"]), B, C) \
        + p["D"][:, None] * x
    y = y.reshape(b, t, z["d_inner"]) * jax.nn.silu(gate)
    return _mm(rms_norm(y, p["norm_gamma"], z["eps"], G), p["W_out"],
               precision)


def attention(p, u, z, precision):
    b, t, _ = u.shape
    heads, kv, hd = z["heads"], z["kv"], z["hd"]
    q = store(_mm(u, p["Wq"], precision), precision
                    ).reshape(b, t, heads, hd)
    k = store(_mm(u, p["Wk"], precision), precision
                    ).reshape(b, t, kv, hd)
    v = store(_mm(u, p["Wv"], precision), precision
                    ).reshape(b, t, kv, hd)
    k, v = (jnp.repeat(a, heads // kv, axis=2) for a in (k, v))

    def attend(q_blk, start):
        s = jnp.einsum("bqhd,bshd->bhqs", q_blk, k, precision=_HI) \
            / math.sqrt(hd)
        seen = (start + jnp.arange(q_blk.shape[1]))[:, None] \
            >= jnp.arange(t)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqs,bshd->bqhd", store(probs, precision),
                          v, precision=_HI)

    o = jnp.concatenate([
        jax.checkpoint(attend, static_argnums=1)(q[:, s:s + QUERY_BLOCK], s)
        for s in range(0, t, QUERY_BLOCK)], axis=1)
    return _mm(o.reshape(b, t, heads * hd), p["Wo"], precision)


def relu2(a):
    return jnp.square(jax.nn.relu(a))


def route(p, u, z, scaling):
    """(chosen experts [..., top] and their weights) of every token."""
    s = jax.nn.sigmoid(jnp.matmul(u, p["W_router"], precision=_HI))
    top, idx = lax.top_k(s, z["top"])
    return idx, scaling * top / jnp.sum(top, axis=-1, keepdims=True)


def experts(p, u, z, config, precision, with_shared=True):
    """The part of the expert layer's result that the experts held here
    give (`experts_held`, in the order of the parameters' leading axis),
    plus the shared expert's."""
    idx, w = route(p, u, z, config["routed_scaling_factor"])
    out = jnp.zeros(u.shape, jnp.float32)
    for slot, expert in enumerate(z["held"]):
        weight = jnp.sum(jnp.where(idx == expert, w, 0.0), axis=-1)
        hidden = relu2(_mm(u, p["W1"][slot], precision))
        out = out + weight[..., None] * _mm(hidden, p["W2"][slot], precision)
    if with_shared:
        out = out + _mm(relu2(_mm(u, p["Ws1"], precision)), p["Ws2"],
                        precision)
    return out


def block(kind, norm, mixer, x, z, config, precision):
    u = rms_norm(x, norm["gamma"], z["eps"])
    if kind == "M":
        return x + mamba2(mixer, u, z, precision)
    if kind == "*":
        return x + attention(mixer, u, z, precision)
    return x + experts(mixer, u, z, config, precision)


def logits(params, x, config, precision="f32"):
    z = _sizes(config)
    h = plain.embedding(x, params["embed"]["W"])
    for i, kind in _blocks(config):
        run = jax.checkpoint(
            lambda norm, mixer, h, kind=kind: block(kind, norm, mixer, h, z,
                                                    config, precision))
        h = run(params[f"b{i}_norm"], params[f"b{i}_mixer"], h)
    u = rms_norm(h, params["final_norm"]["gamma"], z["eps"])
    return _mm(u, params["head"]["W"], precision)


def loss(params, x, y, config, precision="f32"):
    return plain.next_token_cross_entropy(
        logits(params, x, config, precision), y)

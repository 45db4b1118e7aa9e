"""Plain reference of the smallthinker family's decoder as the
`smallthinker_21b_a3b` configuration states it (source: the model's public
`config.json`; `model_type` of the family `smallthinker`). Layer `i`, on the
float32 residual stream `x`, with `win = sliding_window_layout[i]` and `r =
rope_layout[i]`:

    l = x W_router                         from x itself, before any norm
    h = RMSNorm(x)
    q, k, v = h Wq, h Wk, h Wv             no bias
    if r: q, k = rope(q), rope(k)          positions 0 .. T-1, whole head,
                                           dimension i paired with i + D/2
    key j is visible to query p iff j <= p and (not win or p - j < W)
    x = x + softmax(q k^T / sqrt(D)) v Wo  query head h reads kv head h // G
    u = RMSNorm(x)
    chosen = the k largest l;  w_e = exp(l_e) / sum over the chosen of exp(l)
    x = x + sum over chosen e in experts_held of
            w_e W2_e (relu(W1_e u) * (W3_e u))

then a final RMSNorm, `logits = u W_head` and the mean over all positions of
the next-token cross-entropy over the rows held. The experts are a loop over
those held, each applied to every token under a dense mask: no gather, no
capacity. What the experts held elsewhere would add is left out. The mask is
the literal band over all `T` keys; queries are taken in blocks, one after
another, and every sub-block is recomputed in the backward pass, which
changes no arithmetic.

Departures from the source, all under `assumed` in the configuration file
too: the router reads the un-normed input of the layer; the window counts
the query's own position; the "secondary experts" of the family's
description have no key in the config and are not built; initialisation is
normal(0, 0.02), norm weights 1; the residual stream is float32.

`precision` other than "f32" rounds what a program of that compute type
holds in it (`nemotron_h.store`: the operands of every matrix product, q, k
and v before and after the rotation, the softmax's output, and the
cotangents of those); router logits, the rotation's arithmetic, norms'
statistics, the softmax and the loss stay float32 in every mode.

Layer keys are the program's vertex names (`embed`, `b<i>_router`,
`b<i>_attn_norm`, `b<i>_attn`, `b<i>_ffn_norm`, `b<i>_experts`,
`final_norm`, `head`) and parameter names its own (`W1` gate, `W3` up, `W2`
down).

**The FLOP entry of a window layer.** `benchmark/flops.py` has one rule for
attention's two products, the full causal triangle `T (T + 1) / 2` pairs a
head. A window layer at `T > W` multiplies the band `W (W + 1) / 2 + (T - W)
W` pairs, so `layers(config)` lists its two products as a `dense` entry whose
`n_in n_out positions` is exactly `n_heads head_dim (W (W + 1) + 2 (T - W)
W)`: `n_in = n_heads head_dim W / T`, `n_out = 2 T - W + 1`, with `T` the
configuration's own `seq_len` (the entry holds for that length alone, and
the harness hands `flops.py` the traffic's, which the tests hold equal). A
configuration whose `T` does not divide `n_heads head_dim W` has no exact
entry and is refused. A `window_attention` rule in `flops.py` would replace
this (PERF.md section 7).
"""

from __future__ import annotations

import gc
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import plain
from benchmark.reference.nemotron_h import rms_norm, store

_HI = lax.Precision.HIGHEST
QUERY_BLOCK = 512      # queries whose scores are held at once


def _sizes(config):
    n = config["num_hidden_layers"]
    windows, ropes = config["sliding_window_layout"], config["rope_layout"]
    if len(windows) != n or len(ropes) != n:
        raise ValueError("sliding_window_layout, rope_layout and "
                         "num_hidden_layers disagree")
    return {"d": config["hidden_size"], "vocab": config["vocab_size"],
            "heads": config["num_attention_heads"],
            "kv": config["num_key_value_heads"], "hd": config["head_dim"],
            "window": config["sliding_window_size"],
            "theta": float(config["rope_theta"]),
            "width": config["moe_ffn_hidden_size"],
            "router": config.get("router_width",
                                 config["moe_num_primary_experts"]),
            "held": list(config.get(
                "experts_held", range(config["moe_num_primary_experts"]))),
            "top": config["moe_num_active_primary_experts"],
            "eps": config["rms_norm_eps"],
            "layers": list(zip(range(n), windows, ropes))}


def layers(config):
    z = _sizes(config)
    d, qd, kvd = z["d"], z["heads"] * z["hd"], z["kv"] * z["hd"]
    t = config.get("factory_args", {}).get("seq_len")
    out = [{"kind": "embedding", "key": "embed", "rows": z["vocab"],
            "width": d}]
    dense = lambda key, n_in, n_out: {"kind": "dense", "key": key,
                                      "n_in": n_in, "n_out": n_out}
    for i, win, _ in z["layers"]:
        key = f"b{i}_attn"
        out += [dense(f"b{i}_router", d, z["router"]), dense(key, d, qd),
                dense(key, d, kvd), dense(key, d, kvd)]
        if win and not t:
            raise ValueError("a window layer's FLOP entry needs the "
                             "configuration's factory_args.seq_len")
        if win and z["window"] < t:
            # the band's two products under the `dense` rule (see the
            # module's docstring): n_in n_out t == 2 qd band_pairs
            if (qd * z["window"]) % t:
                raise ValueError(
                    f"no exact `dense` entry for a window of {z['window']} "
                    f"over {t} positions at {qd} head dimensions")
            out.append(dict(dense(key, qd * z["window"] // t,
                                  2 * t - z["window"] + 1), band=True))
        else:
            out.append({"kind": "attention", "key": key,
                        "n_heads": z["heads"], "head_dim": z["hd"]})
        out.append(dense(key, qd, d))
        routed = {"kind": "experts", "key": f"b{i}_experts",
                  "experts_per_token": z["top"], "held": len(z["held"]),
                  "routed": z["router"]}
        out += [dict(routed, n_in=d, n_out=z["width"]),
                dict(routed, n_in=d, n_out=z["width"]),
                dict(routed, n_in=z["width"], n_out=d)]
    out.append(dense("head", d, z["vocab"]))
    return out


def init_params(seed, config):
    """All weights from the seed in one jitted call, float32."""
    # what the caller has dropped (the program's net: `del` leaves it to
    # the cycle collector) must be gone before these weights are made
    gc.collect()
    z = _sizes(config)
    d, std, n = z["d"], 0.02, len(z["held"])
    qd, kvd = z["heads"] * z["hd"], z["kv"] * z["hd"]

    @jax.jit
    def make(key):
        count = [0]

        def normal(*shape):
            count[0] += 1
            return std * jax.random.normal(jax.random.fold_in(key, count[0]),
                                           shape, jnp.float32)

        ones = lambda: {"gamma": jnp.ones((d,), jnp.float32)}
        params = {"embed": {"W": normal(z["vocab"], d)}}
        for i, _, _ in z["layers"]:
            params[f"b{i}_router"] = {"W": normal(d, z["router"])}
            params[f"b{i}_attn_norm"] = ones()
            params[f"b{i}_attn"] = {"Wq": normal(d, qd), "Wk": normal(d, kvd),
                                    "Wv": normal(d, kvd), "Wo": normal(qd, d)}
            params[f"b{i}_ffn_norm"] = ones()
            params[f"b{i}_experts"] = {"W1": normal(n, d, z["width"]),
                                       "W3": normal(n, d, z["width"]),
                                       "W2": normal(n, z["width"], d)}
        params["final_norm"] = ones()
        params["head"] = {"W": normal(d, z["vocab"])}
        return params

    return make(plain.seed_key(seed))


# -- the parts ------------------------------------------------------------------

def _mm(a, w, precision):
    """A matrix product as a program of that compute type makes it: both
    operands in it, the sum in float32."""
    return jnp.matmul(store(a, precision), store(w, precision),
                      precision=_HI, preferred_element_type=jnp.float32)


def rope(x, theta):
    """x: [b, t, heads, D] at positions 0 .. t-1: dimension `i` and `i +
    D/2` are rotated by the angle `p theta^(-2i/D)` (the half-split
    pairing, `x cos + rotate_half(x) sin`)."""
    t, D = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[:, None, :]
    rotated = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * cos + rotated * sin


def visible(t, window, start=0, rows=None):
    """The mask `[rows, t]` of the queries `start .. start + rows - 1`."""
    p = (start + jnp.arange(t if rows is None else rows))[:, None]
    p = jnp.minimum(p, t - 1)      # a row past the end sees what the last does
    j = jnp.arange(t)[None, :]
    seen = j <= p
    return seen if window is None else seen & (p - j < window)


def attention(p, h, z, window, rotary, precision):
    b, t, _ = h.shape
    heads, kv, hd = z["heads"], z["kv"], z["hd"]
    proj = lambda w, n: store(_mm(h, w, precision), precision
                              ).reshape(b, t, n, hd)
    q, k, v = proj(p["Wq"], heads), proj(p["Wk"], kv), proj(p["Wv"], kv)
    if rotary:
        q, k = (store(rope(a, z["theta"]), precision) for a in (q, k))
    k, v = (jnp.repeat(a, heads // kv, axis=2) for a in (k, v))

    @jax.checkpoint
    def attend(args):
        q_blk, start = args
        s = jnp.einsum("bqhd,bshd->bhqs", q_blk, k, precision=_HI) \
            / math.sqrt(hd)
        seen = visible(t, window, start, q_blk.shape[1])
        probs = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqs,bshd->bqhd", store(probs, precision), v,
                          precision=_HI)

    # the query blocks one after another (`lax.map`), so that one block's
    # scores live at once; rows added to fill the last block see what the
    # last position sees and are cut off again
    n = -(-t // QUERY_BLOCK)
    q = jnp.pad(q, ((0, 0), (0, n * QUERY_BLOCK - t), (0, 0), (0, 0)))
    blocks = jnp.moveaxis(q.reshape(b, n, QUERY_BLOCK, heads, hd), 1, 0)
    o = lax.map(attend, (blocks, jnp.arange(n) * QUERY_BLOCK))
    o = jnp.moveaxis(o, 0, 1).reshape(b, n * QUERY_BLOCK, heads, hd)[:, :t]
    return _mm(o.reshape(b, t, heads * hd), p["Wo"], precision)


def route(logits, top):
    """(chosen experts [..., top], their weights): the `top` largest logits
    and the softmax over them."""
    chosen, idx = lax.top_k(logits, top)
    return idx, jax.nn.softmax(chosen, axis=-1)


def experts(p, u, logits, z, precision):
    """The part of the expert layer's result that the experts held here
    give (`experts_held`, in the order of the parameters' leading axis)."""
    idx, w = route(logits, z["top"])
    out = jnp.zeros(u.shape, jnp.float32)
    for slot, expert in enumerate(z["held"]):
        weight = jnp.sum(jnp.where(idx == expert, w, 0.0), axis=-1)
        hidden = jax.nn.relu(_mm(u, p["W1"][slot], precision)) \
            * _mm(u, p["W3"][slot], precision)
        out = out + weight[..., None] * _mm(hidden, p["W2"][slot], precision)
    return out


def router_logits(p, x):
    return jnp.matmul(x, p["W"], precision=_HI)


def layer(params, i, window, rotary, x, z, precision):
    get = lambda name: params[f"b{i}_{name}"]
    logits = router_logits(get("router"), x)

    @jax.checkpoint
    def attend(norm, mixer, x):
        return x + attention(mixer, rms_norm(x, norm["gamma"], z["eps"]), z,
                             window, rotary, precision)

    @jax.checkpoint
    def feed(norm, mixer, x, logits):
        return x + experts(mixer, rms_norm(x, norm["gamma"], z["eps"]),
                           logits, z, precision)

    x = attend(get("attn_norm"), get("attn"), x)
    return feed(get("ffn_norm"), get("experts"), x, logits)


def logits(params, x, config, precision="f32"):
    z = _sizes(config)
    h = plain.embedding(x, params["embed"]["W"])
    for i, win, rotary in z["layers"]:
        h = layer(params, i, z["window"] if win else None, bool(rotary), h,
                  z, precision)
    u = rms_norm(h, params["final_norm"]["gamma"], z["eps"])
    return _mm(u, params["head"]["W"], precision)


def loss(params, x, y, config, precision="f32"):
    return plain.next_token_cross_entropy(
        logits(params, x, config, precision), y)

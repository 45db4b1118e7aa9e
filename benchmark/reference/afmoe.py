"""Plain reference of the AFMoE family's decoder (`model_type: afmoe`) as
the `trinity_mini_26b_a3b` configuration states it (source: the model's
public `config.json`; the mechanisms it has no key for are those of the
family's public implementation, `transformers`' `models/afmoe/
modeling_afmoe.py`). Before the first layer `x = embed(ids) * sqrt(d)`
(`mup_enabled`). Layer `i`, on the float32 residual stream `x`, with `win`
true where `layer_types[i]` is `sliding_attention`:

    h = RMSNorm_in(x)
    q = RMSNorm_q(h Wq), k = RMSNorm_k(h Wk)   per head over head_dim
    v = h Wv                                   no bias
    if win: q, k = rope(q), rope(k)            positions 0 .. T-1, whole
                                               head, dimension i paired with
                                               i + D/2; full layers: none
    key j is visible to query p iff j <= p and (not win or p - j < W)
    o = softmax(q k^T / sqrt(D)) v             query head h reads kv head
                                               h // G
    x = x + RMSNorm_post_attn((o * sigmoid(h Wg)) Wo)
    u = RMSNorm_pre_mlp(x)
    i < num_dense_layers:  m = Wd(silu(Wg u) * (Wu u))
    else:  s = sigmoid(u W_router)                         float32
           chosen = the k largest of s + b_select
           w_e = route_scale s_e / (sum over the chosen of s + 1e-20)
           m = sum over chosen e in experts_held of
                   w_e W2_e (silu(W1_e u) * (W3_e u))
               + Wd_s(silu(Wg_s u) * (Wu_s u))             the shared expert
    x = x + RMSNorm_post_mlp(m)

then a final RMSNorm, `logits = u W_head` and the mean over all positions of
the next-token cross-entropy over the rows held. The experts are a loop over
those held (`lax.scan`, `deepseek_v3.experts`), each applied to every token
under a dense mask: no gather, no capacity. What the experts held elsewhere
would add is left out. The band mask is the literal one over all `T` keys;
queries are taken in blocks, one after another, the head's loss in blocks of
positions, and every sub-block is recomputed in the backward pass, which
changes no arithmetic.

Departures from the source, all under `assumed` in the configuration file
too: initialisation is normal(0, 0.02) for every matrix and for `b_select`,
norm weights 1; `b_select` (the source's `expert_bias`) is a constant of the
checkpoint: the rule that moves it after a step from the experts' loads
(`load_balance_coeff`) is outside the gradient and is not applied; the
window counts the query's own position; the residual stream is float32.

`precision` other than "f32" rounds what a program of that compute type
holds in it (`nemotron_h.store`: the operands of every matrix product, q and
k after their norms and after the rotation, v, the softmax's output, and
the cotangents of those); the norms' statistics, the rotation's arithmetic,
the gate's sigmoid and product, router logits, the softmax and the loss stay
float32 in every mode.

Layer keys are the program's vertex names (`embed`, `b<i>_attn_norm`,
`b<i>_attn`, `b<i>_attn_post_norm`, `b<i>_ffn_norm`, `b<i>_mlp` or
`b<i>_experts` and `b<i>_shared`, `b<i>_ffn_post_norm`, `final_norm`,
`head`) and parameter names its own (`Wg` the output gate, `q_norm` and
`k_norm`; `W1` gate, `W3` up, `W2` down of the routed experts; `W_gate`,
`W_up`, `W_down` of a dense MLP).

**The FLOP entry of a window layer** follows `reference/smallthinker.py`:
at `T > W` the band's two products are one `dense` entry with `n_in =
n_heads head_dim W / T` and `n_out = 2 T - W + 1`, `T` the configuration's
own `seq_len`; a configuration whose `T` does not divide `n_heads head_dim
W` has no exact entry and is refused. The output gate's projection is one
more `dense` entry.
"""

from __future__ import annotations

import gc
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import plain
from benchmark.reference.deepseek_v3 import _mm, experts, gated_mlp
from benchmark.reference.nemotron_h import rms_norm, store
from benchmark.reference.smallthinker import rope, visible

_HI = lax.Precision.HIGHEST
QUERY_BLOCK = 256      # queries whose scores are held at once
HEAD_BLOCK = 1024      # positions whose logits are held at once


def _sizes(config):
    n = config["num_hidden_layers"]
    types = list(config["layer_types"])
    if len(types) != n:
        raise ValueError("layer_types and num_hidden_layers disagree")
    held = list(config.get("experts_held", range(config["num_experts"])))
    return {"d": config["hidden_size"], "vocab": config["vocab_size"],
            "dense_width": config["intermediate_size"],
            "heads": config["num_attention_heads"],
            "kv": config["num_key_value_heads"], "hd": config["head_dim"],
            "window": config["sliding_window"],
            "theta": float(config["rope_theta"]),
            "width": config["moe_intermediate_size"],
            "shared": config["num_shared_experts"]
            * config["moe_intermediate_size"],
            "router": config.get("router_width", config["num_experts"]),
            "held": held, "top": config["num_experts_per_tok"],
            "scaling": float(config["route_scale"]),
            "scale": math.sqrt(config["hidden_size"])
            if config.get("mup_enabled", True) else 1.0,
            "eps": config["rms_norm_eps"],
            "layers": [(i, i < config["num_dense_layers"],
                        kind == "sliding_attention")
                       for i, kind in enumerate(types)]}


def layers(config):
    z = _sizes(config)
    d, qd, kvd = z["d"], z["heads"] * z["hd"], z["kv"] * z["hd"]
    t = config.get("factory_args", {}).get("seq_len")
    out = [{"kind": "embedding", "key": "embed", "rows": z["vocab"],
            "width": d}]
    dense = lambda key, n_in, n_out: {"kind": "dense", "key": key,
                                      "n_in": n_in, "n_out": n_out}
    mlp = lambda key, width: [dense(key, d, width), dense(key, d, width),
                              dense(key, width, d)]
    for i, is_dense, win in z["layers"]:
        key = f"b{i}_attn"
        out += [dense(key, d, qd), dense(key, d, kvd), dense(key, d, kvd),
                dense(key, d, qd)]                    # the output gate's Wg
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
        if is_dense:
            out += mlp(f"b{i}_mlp", z["dense_width"])
            continue
        routed = {"kind": "experts", "key": f"b{i}_experts",
                  "experts_per_token": z["top"], "held": len(z["held"]),
                  "routed": z["router"]}
        out += [dense(f"b{i}_experts", d, z["router"]),
                dict(routed, n_in=d, n_out=z["width"]),
                dict(routed, n_in=d, n_out=z["width"]),
                dict(routed, n_in=z["width"], n_out=d)]
        if z["shared"]:
            out += mlp(f"b{i}_shared", z["shared"])
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

        ones = lambda width=d: jnp.ones((width,), jnp.float32)
        norm = lambda: {"gamma": ones()}
        mlp = lambda width: {"W_gate": normal(d, width),
                             "W_up": normal(d, width),
                             "W_down": normal(width, d)}
        params = {"embed": {"W": normal(z["vocab"], d)}}
        for i, is_dense, _ in z["layers"]:
            params[f"b{i}_attn_norm"] = norm()
            params[f"b{i}_attn"] = {
                "Wq": normal(d, qd), "Wk": normal(d, kvd),
                "Wv": normal(d, kvd), "Wo": normal(qd, d),
                "q_norm": ones(z["hd"]), "k_norm": ones(z["hd"]),
                "Wg": normal(d, qd)}
            params[f"b{i}_attn_post_norm"] = norm()
            params[f"b{i}_ffn_norm"] = norm()
            params[f"b{i}_ffn_post_norm"] = norm()
            if is_dense:
                params[f"b{i}_mlp"] = mlp(z["dense_width"])
                continue
            params[f"b{i}_experts"] = {
                "W_router": normal(d, z["router"]),
                "W1": normal(n, d, z["width"]),
                "W3": normal(n, d, z["width"]),
                "W2": normal(n, z["width"], d),
                "b_select": normal(z["router"])}
            if z["shared"]:
                params[f"b{i}_shared"] = mlp(z["shared"])
        params["final_norm"] = norm()
        params["head"] = {"W": normal(d, z["vocab"])}
        return params

    return make(plain.seed_key(seed))


# -- the parts ------------------------------------------------------------------

def attention(p, h, z, window, precision):
    """The attention sub-layer before its post-norm: normed query and key
    heads, rotated in a window layer (`window` not None), the band or the
    causal triangle, the output gated and projected."""
    b, t, _ = h.shape
    heads, kv, hd = z["heads"], z["kv"], z["hd"]
    keep = lambda a: store(a, precision)
    heads_of = lambda w, n: _mm(h, w, precision).reshape(b, t, n, hd)
    q = keep(rms_norm(heads_of(p["Wq"], heads), p["q_norm"], z["eps"]))
    k = keep(rms_norm(heads_of(p["Wk"], kv), p["k_norm"], z["eps"]))
    v = keep(heads_of(p["Wv"], kv))
    if window is not None:
        q, k = (keep(rope(a, z["theta"])) for a in (q, k))
    k, v = (jnp.repeat(a, heads // kv, axis=2) for a in (k, v))

    @jax.checkpoint
    def attend(args):
        q_blk, start = args
        s = jnp.einsum("bqhd,bshd->bhqs", q_blk, k, precision=_HI) \
            / math.sqrt(hd)
        seen = visible(t, window, start, q_blk.shape[1])
        probs = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqs,bshd->bqhd", keep(probs), v, precision=_HI)

    # the query blocks one after another (`lax.map`), so that one block's
    # scores live at once; rows added to fill the last block see what the
    # last position sees and are cut off again
    n = -(-t // QUERY_BLOCK)
    q = jnp.pad(q, ((0, 0), (0, n * QUERY_BLOCK - t), (0, 0), (0, 0)))
    blocks = jnp.moveaxis(q.reshape(b, n, QUERY_BLOCK, heads, hd), 1, 0)
    o = lax.map(attend, (blocks, jnp.arange(n) * QUERY_BLOCK))
    o = jnp.moveaxis(o, 0, 1).reshape(b, n * QUERY_BLOCK, heads, hd)[:, :t]
    gate = jax.nn.sigmoid(_mm(h, p["Wg"], precision)).reshape(b, t, heads, hd)
    return _mm((o * gate).reshape(b, t, heads * hd), p["Wo"], precision)


def layer(params, i, is_dense, window, x, z, precision):
    get = lambda name: params.get(f"b{i}_{name}")
    norm = lambda a, p: rms_norm(a, p["gamma"], z["eps"])

    @jax.checkpoint
    def attend(pre, mixer, post, x):
        return x + norm(attention(mixer, norm(x, pre), z, window, precision),
                        post)

    @jax.checkpoint
    def feed(pre, mixers, post, x):
        u = norm(x, pre)
        if is_dense:
            return x + norm(gated_mlp(mixers[0], u, precision), post)
        m = experts(mixers[0], u, z, precision)
        if mixers[1] is not None:
            m = m + gated_mlp(mixers[1], u, precision)
        return x + norm(m, post)

    x = attend(get("attn_norm"), get("attn"), get("attn_post_norm"), x)
    mixers = (get("mlp"),) if is_dense else (get("experts"), get("shared"))
    return feed(get("ffn_norm"), mixers, get("ffn_post_norm"), x)


def hidden(params, x, config, precision="f32"):
    """The final norm's output [b, t, d], float32."""
    z = _sizes(config)
    h = plain.embedding(x, params["embed"]["W"]) * z["scale"]
    for i, is_dense, win in z["layers"]:
        h = layer(params, i, is_dense, z["window"] if win else None, h, z,
                  precision)
    return rms_norm(h, params["final_norm"]["gamma"], z["eps"])


def logits(params, x, config, precision="f32"):
    return _mm(hidden(params, x, config, precision), params["head"]["W"],
               precision)


def loss(params, x, y, config, precision="f32"):
    """The mean next-token cross-entropy, `HEAD_BLOCK` positions of logits
    at a time where it divides the length (the whole length otherwise),
    each block recomputed in the backward pass."""
    u = hidden(params, x, config, precision)
    b, t, d = u.shape
    n = t // HEAD_BLOCK if t % HEAD_BLOCK == 0 else 1

    @jax.checkpoint
    def block(args):
        u_blk, y_blk = args
        z = _mm(u_blk, params["head"]["W"], precision)
        picked = jnp.take_along_axis(jax.nn.log_softmax(z), y_blk[..., None],
                                     axis=-1)
        return -jnp.sum(picked)

    split = lambda a: jnp.moveaxis(a.reshape((b, n, t // n) + a.shape[2:]),
                                   1, 0)
    return jnp.sum(lax.map(block, (split(u), split(y)))) / (b * t)

"""Plain reference of the DeepSeek-V3 family's decoder (`model_type:
deepseek_v3`) as the `kanana2_30b_a3b` configuration states it (source: the
model's public `config.json`). Layer `i`, on the float32 residual stream
`x`, with `H` heads, `n = qk_nope_head_dim`, `r = qk_rope_head_dim`:

    h  = RMSNorm(x)
    q  = h Wq                             [b, t, H, n + r] = [q_nope | q_rope]
    c  = h Wkv_a                          [b, t, kv_lora_rank + r]
                                          = [latent | k_rope], k_rope one head
    kv = RMSNorm(latent; kv_norm) Wkv_b   [b, t, H, n + v] = [k_nope | v]
    q_rope, k_rope <- rotary: positions 0 .. T-1, the adjacent pairs
                      (2j, 2j + 1) turned by p theta^(-2j / r)
    k  = [k_nope | k_rope for every head]
    x  = x + softmax_causal(q k^T / sqrt(n + r)) v Wo
    u  = RMSNorm(x)
    i < first_k_dense_replace:  x = x + Wd(silu(Wg u) * (Wu u))
    else:  s = sigmoid(u W_router)                     float32
           chosen = the k largest of s + b_select
           w_e = routed_scaling_factor s_e / (sum over the chosen of s + 1e-20)
           x = x + sum over chosen e in experts_held of
                       w_e W2_e (silu(W1_e u) * (W3_e u))
                 + Wd_s(silu(Wg_s u) * (Wu_s u))       the shared experts as
                                                       one MLP of their width

then a final RMSNorm, `logits = u W_head` and the mean over all positions of
the next-token cross-entropy over the rows held. The rotated pairs stay
where they were (interleaved); the program lays them apart, which the
products of q and k do not see. The experts are a loop over those held
(`lax.scan`), each applied to every token under a dense mask: no gather, no
capacity. What the experts held elsewhere would add is left out. Queries are
taken in blocks, one after another, and every sub-block is recomputed in the
backward pass, which changes no arithmetic.

Departures from the source, all under `assumed` in the configuration file
too: initialisation is normal(0, 0.02) for every matrix and for `b_select`,
norm weights 1; `b_select` (the source's `e_score_correction_bias`) is a
constant of the checkpoint: the rule that moves it after a step from the
experts' loads is outside the gradient and is not applied; no `mscale`
(`rope_scaling` is null); the residual stream is float32.

`precision` other than "f32" rounds what a program of that compute type
holds in it (`nemotron_h.store`: the operands of every matrix product, q, k
and v before and after the rotation, the softmax's output, and the
cotangents of those); the down-projection's result `c`, the latent's norm,
router logits, the rotation's arithmetic, norms' statistics, the softmax and
the loss stay float32 in every mode.

Layer keys are the program's vertex names (`embed`, `b<i>_attn_norm`,
`b<i>_attn`, `b<i>_ffn_norm`, `b<i>_mlp` or `b<i>_experts` and
`b<i>_shared`, `final_norm`, `head`) and parameter names its own (`W1` gate,
`W3` up, `W2` down of the routed experts; `W_gate`, `W_up`, `W_down` of a
dense MLP).

**The FLOP entries of an attention layer.** `benchmark/flops.py`'s
`attention` rule counts two products of `head_dim` a (query, key) pair.
Here the scores multiply `n + r` = 192 and the mix `v` = 128 a pair, so
`layers(config)` lists two `attention` entries a layer, each half a rule:
`head_dim` `(n + r) / 2` = 96 and `v / 2` = 64.
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
QUERY_BLOCK = 256      # queries whose scores are held at once


def _sizes(config):
    n = config["num_hidden_layers"]
    held = list(config.get("experts_held",
                           range(config["n_routed_experts"])))
    return {"d": config["hidden_size"], "vocab": config["vocab_size"],
            "dense_width": config["intermediate_size"],
            "heads": config["num_attention_heads"],
            "rank": config["kv_lora_rank"],
            "nope": config["qk_nope_head_dim"],
            "rot": config["qk_rope_head_dim"], "vd": config["v_head_dim"],
            "theta": float(config["rope_theta"]),
            "width": config["moe_intermediate_size"],
            "shared": config["n_shared_experts"]
            * config["moe_intermediate_size"],
            "router": config.get("router_width", config["n_routed_experts"]),
            "held": held, "top": config["num_experts_per_tok"],
            "scaling": float(config["routed_scaling_factor"]),
            "eps": config["rms_norm_eps"],
            "layers": [(i, i < config["first_k_dense_replace"])
                       for i in range(n)]}


def layers(config):
    z = _sizes(config)
    d, H = z["d"], z["heads"]
    qk, vd = z["nope"] + z["rot"], z["vd"]
    if qk % 2 or vd % 2:
        raise ValueError(f"no exact `attention` entries for heads of {qk} "
                         f"and {vd}")
    out = [{"kind": "embedding", "key": "embed", "rows": z["vocab"],
            "width": d}]
    dense = lambda key, n_in, n_out: {"kind": "dense", "key": key,
                                      "n_in": n_in, "n_out": n_out}
    mlp = lambda key, width: [dense(key, d, width), dense(key, d, width),
                              dense(key, width, d)]
    for i, is_dense in z["layers"]:
        key = f"b{i}_attn"
        out += [dense(key, d, H * qk), dense(key, d, z["rank"] + z["rot"]),
                dense(key, z["rank"], H * (z["nope"] + vd)),
                # the scores' product and the mix's, each half a rule
                {"kind": "attention", "key": key, "n_heads": H,
                 "head_dim": qk // 2},
                {"kind": "attention", "key": key, "n_heads": H,
                 "head_dim": vd // 2},
                dense(key, H * vd, d)]
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
    d, std, n, H = z["d"], 0.02, len(z["held"]), z["heads"]

    @jax.jit
    def make(key):
        count = [0]

        def normal(*shape):
            count[0] += 1
            return std * jax.random.normal(jax.random.fold_in(key, count[0]),
                                           shape, jnp.float32)

        ones = lambda width=d: jnp.ones((width,), jnp.float32)
        mlp = lambda width: {"W_gate": normal(d, width),
                             "W_up": normal(d, width),
                             "W_down": normal(width, d)}
        params = {"embed": {"W": normal(z["vocab"], d)}}
        for i, is_dense in z["layers"]:
            params[f"b{i}_attn_norm"] = {"gamma": ones()}
            params[f"b{i}_attn"] = {
                "Wq": normal(d, H * (z["nope"] + z["rot"])),
                "Wkv_a": normal(d, z["rank"] + z["rot"]),
                "kv_norm": ones(z["rank"]),
                "Wkv_b": normal(z["rank"], H * (z["nope"] + z["vd"])),
                "Wo": normal(H * z["vd"], d)}
            params[f"b{i}_ffn_norm"] = {"gamma": ones()}
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
        params["final_norm"] = {"gamma": ones()}
        params["head"] = {"W": normal(d, z["vocab"])}
        return params

    return make(plain.seed_key(seed))


# -- the parts ------------------------------------------------------------------

def _mm(a, w, precision):
    """A matrix product as a program of that compute type makes it: both
    operands in it, the sum in float32."""
    return jnp.matmul(store(a, precision), store(w, precision),
                      precision=_HI, preferred_element_type=jnp.float32)


def rope_pairs(x, theta):
    """x: [b, t, heads, r] at positions 0 .. t-1: the adjacent dimensions
    `2j` and `2j + 1` are one pair, turned by the angle `p theta^(-2j/r)`,
    and stay where they were."""
    t, r = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def latent_attention(p, h, z, precision):
    b, t, _ = h.shape
    H, nope, rot, vd, rank = z["heads"], z["nope"], z["rot"], z["vd"], \
        z["rank"]
    keep = lambda a: store(a, precision)
    q = keep(_mm(h, p["Wq"], precision)).reshape(b, t, H, nope + rot)
    c = _mm(h, p["Wkv_a"], precision)
    latent = rms_norm(c[..., :rank], p["kv_norm"], z["eps"])
    kv = keep(_mm(latent, p["Wkv_b"], precision)).reshape(b, t, H, nope + vd)
    k_rope = keep(rope_pairs(keep(c[..., rank:]).reshape(b, t, 1, rot),
                             z["theta"]))
    q = jnp.concatenate(
        [q[..., :nope], keep(rope_pairs(q[..., nope:], z["theta"]))], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, t, H, rot))], axis=-1)
    v = kv[..., nope:]

    @jax.checkpoint
    def attend(args):
        q_blk, start = args
        s = jnp.einsum("bqhd,bshd->bhqs", q_blk, k, precision=_HI) \
            / math.sqrt(nope + rot)
        pos = jnp.minimum(start + jnp.arange(q_blk.shape[1]), t - 1)
        seen = jnp.arange(t)[None, :] <= pos[:, None]
        probs = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqs,bshd->bqhd", keep(probs), v, precision=_HI)

    # the query blocks one after another (`lax.map`), so that one block's
    # scores live at once; rows added to fill the last block see what the
    # last position sees and are cut off again
    n = -(-t // QUERY_BLOCK)
    q = jnp.pad(q, ((0, 0), (0, n * QUERY_BLOCK - t), (0, 0), (0, 0)))
    blocks = jnp.moveaxis(q.reshape(b, n, QUERY_BLOCK, H, nope + rot), 1, 0)
    o = lax.map(attend, (blocks, jnp.arange(n) * QUERY_BLOCK))
    o = jnp.moveaxis(o, 0, 1).reshape(b, n * QUERY_BLOCK, H, vd)[:, :t]
    return _mm(o.reshape(b, t, H * vd), p["Wo"], precision)


def gated_mlp(p, u, precision):
    hidden = jax.nn.silu(_mm(u, p["W_gate"], precision)) \
        * _mm(u, p["W_up"], precision)
    return _mm(hidden, p["W_down"], precision)


def route(scores, bias, top, scaling):
    """(chosen experts [..., top], their weights): the `top` largest of
    `scores + bias`, weighted by their own scores over the chosen's sum."""
    _, idx = lax.top_k(scores + bias, top)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, scaling * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def experts(p, u, z, precision, held=None):
    """The part of the expert layer's result that the routed experts held
    here give (`experts_held`, or `held`, in the order of the parameters'
    leading axis): one expert after another (`lax.scan`, each recomputed in
    the backward pass), so that one expert's hidden rows live at once."""
    scores = jax.nn.sigmoid(jnp.matmul(u, p["W_router"], precision=_HI))
    idx, w = route(scores, p["b_select"], z["top"], z["scaling"])
    numbers = jnp.asarray(z["held"] if held is None else held, jnp.int32)

    @jax.checkpoint
    def add_expert(out, expert):
        w1, w3, w2, number = expert
        weight = jnp.sum(jnp.where(idx == number, w, 0.0), axis=-1)
        hidden = jax.nn.silu(_mm(u, w1, precision)) * _mm(u, w3, precision)
        return out + weight[..., None] * _mm(hidden, w2, precision), None

    out, _ = lax.scan(add_expert, jnp.zeros(u.shape, jnp.float32),
                      (p["W1"], p["W3"], p["W2"], numbers))
    return out


def layer(params, i, is_dense, x, z, precision):
    get = lambda name: params.get(f"b{i}_{name}")

    @jax.checkpoint
    def attend(norm, mixer, x):
        return x + latent_attention(
            mixer, rms_norm(x, norm["gamma"], z["eps"]), z, precision)

    @jax.checkpoint
    def feed(norm, mixers, x):
        u = rms_norm(x, norm["gamma"], z["eps"])
        if is_dense:
            return x + gated_mlp(mixers[0], u, precision)
        x = x + experts(mixers[0], u, z, precision)
        return x if mixers[1] is None \
            else x + gated_mlp(mixers[1], u, precision)

    x = attend(get("attn_norm"), get("attn"), x)
    mixers = (get("mlp"),) if is_dense else (get("experts"), get("shared"))
    return feed(get("ffn_norm"), mixers, x)


def logits(params, x, config, precision="f32"):
    z = _sizes(config)
    h = plain.embedding(x, params["embed"]["W"])
    for i, is_dense in z["layers"]:
        h = layer(params, i, is_dense, h, z, precision)
    u = rms_norm(h, params["final_norm"]["gamma"], z["eps"])
    return _mm(u, params["head"]["W"], precision)


def loss(params, x, y, config, precision="f32"):
    return plain.next_token_cross_entropy(
        logits(params, x, config, precision), y)

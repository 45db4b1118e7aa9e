"""Multi-head self-attention layer impl (config: SelfAttentionLayer).

Single-device forward uses parallel/sequence.full_attention; the SAME math
runs sequence-parallel over a mesh via ring_self_attention (parallel/
sequence.py) — tests prove block-ring == full. Time masking multiplies
attention scores' keys (masked keys unattendable) and zeroes masked
outputs, matching the framework's RNN masking semantics.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.layers.registry import LayerContext, register_layer
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.ops.activations import apply_activation
from deeplearning4j_tpu.parallel.sequence import full_attention


def attention_init(key, conf: L.SelfAttentionLayer, dtype):
    n_in, n_out = int(conf.n_in), int(conf.n_out)
    if n_out % conf.n_heads != 0:
        raise ValueError(
            f"n_out {n_out} must be divisible by n_heads {conf.n_heads}")
    ks = jax.random.split(key, 4)
    mk = lambda k, i, o: init_weights(k, (i, o), i, o, conf.weight_init,
                                      conf.dist, dtype)
    p = {
        "Wq": mk(ks[0], n_in, n_out),
        "Wk": mk(ks[1], n_in, n_out),
        "Wv": mk(ks[2], n_in, n_out),
        "Wo": mk(ks[3], n_out, n_out),
    }
    if conf.projection_bias:
        p["b"] = jnp.zeros((n_out,), dtype)
    return p


def attention_forward(conf: L.SelfAttentionLayer, params, x,
                      ctx: LayerContext):
    """x: [b, t, nIn] -> [b, t, nOut]."""
    B, T, _ = x.shape
    H = int(conf.n_heads)
    E = int(conf.n_out)
    D = E // H
    dt = x.dtype
    q = (x @ params["Wq"].astype(dt)).reshape(B, T, H, D)
    k = (x @ params["Wk"].astype(dt)).reshape(B, T, H, D)
    v = (x @ params["Wv"].astype(dt)).reshape(B, T, H, D)
    if ctx.mask is not None:
        # masked keys contribute nothing: push their scores to -inf by
        # zeroing v and biasing k is fragile — mask scores directly
        o = _masked_attention(q, k, v, ctx.mask.astype(dt), conf.causal)
    else:
        o = full_attention(q, k, v, causal=conf.causal)
    y = o.reshape(B, T, E) @ params["Wo"].astype(dt)
    if conf.projection_bias:
        y = y + params["b"].astype(dt)
    if ctx.mask is not None:
        y = y * ctx.mask.astype(dt)[..., None]
    return apply_activation(conf.activation or "identity", y,
                            key=ctx.rng, training=ctx.training), None


def _masked_attention(q, k, v, mask, causal):
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    neg = jnp.asarray(-1e30, s.dtype)
    s = jnp.where(mask[:, None, None, :] > 0, s, neg)
    if causal:
        T = q.shape[1]
        tri = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(tri, s, neg)
    a = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", a, v)


def attention_order(conf):
    return ("Wq", "Wk", "Wv", "Wo", "b") if conf.projection_bias else (
        "Wq", "Wk", "Wv", "Wo")


register_layer(L.SelfAttentionLayer, attention_init, attention_forward,
               order_fn=attention_order)


# -- grouped-query causal attention ---------------------------------------------

def gqa_init(key, conf: L.GroupedQueryAttentionLayer, dtype):
    n_in, n_out = int(conf.n_in), int(conf.n_out)
    H, KV, D = int(conf.n_heads), int(conf.n_kv_heads), int(conf.head_dim)
    if H % KV:
        raise ValueError(f"n_heads {H} must be a multiple of n_kv_heads {KV}")
    ks = jax.random.split(key, 4)
    mk = lambda k, i, o: init_weights(k, (i, o), i, o, conf.weight_init,
                                      conf.dist, dtype)
    return {"Wq": mk(ks[0], n_in, H * D), "Wk": mk(ks[1], n_in, KV * D),
            "Wv": mk(ks[2], n_in, KV * D), "Wo": mk(ks[3], H * D, n_out)}


def _attend_block(q, k, v, start: int, causal: bool):
    """One block of queries against the keys it may see. q: [b, tq, KV, G,
    D] (its first position is `start`), k/v: [b, tk, KV, D]; scores and
    softmax in float32, both products on operands of the inputs' dtype."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = start + jnp.arange(q.shape[1])[:, None]
        s = jnp.where(qpos >= jnp.arange(k.shape[1])[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


# queries whose scores are held at once: [b, H, 256, t] float32 is 0.54 GB
# at 4 x 32 heads x 4,096 keys
QUERY_BLOCK = 256


def grouped_query_attention(q, k, v, *, causal: bool):
    """q: [b, t, H, D], k/v: [b, t, KV, D] -> [b, t, H, D] float32; query
    head `h` reads key-value head `h // (H // KV)`. Queries are taken
    `QUERY_BLOCK` at a time against the keys up to the block's end (a
    causal layer never multiplies the blocks above the diagonal), each
    block under `jax.checkpoint` so that one block's scores live at once."""
    b, t, H, D = q.shape
    KV = k.shape[2]
    q = q.reshape(b, t, KV, H // KV, D)
    outs = []
    for start in range(0, t, QUERY_BLOCK):
        end = min(start + QUERY_BLOCK, t)
        seen = end if causal else t
        block = partial(_attend_block, start=start, causal=causal)
        if t > QUERY_BLOCK:
            block = jax.checkpoint(block)
        outs.append(block(q[:, start:end], k[:, :seen], v[:, :seen]))
    o = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    return o.reshape(b, t, H, D)


def gqa_forward(conf: L.GroupedQueryAttentionLayer, params, x,
                ctx: LayerContext):
    """x: [b, t, n_in] -> [b, t, n_out] in x's dtype; the products run in
    the net's compute dtype with float32 accumulation."""
    if ctx.mask is not None:
        raise NotImplementedError(
            "GroupedQueryAttentionLayer takes no time mask (packed or padded "
            "sequences): SelfAttentionLayer masks keys")
    B, T, _ = x.shape
    H, KV, D = int(conf.n_heads), int(conf.n_kv_heads), int(conf.head_dim)
    cd = ctx.compute_dtype or x.dtype
    u = x.astype(cd)
    proj = lambda name, heads: jnp.matmul(
        u, params[name].astype(cd), preferred_element_type=jnp.float32
    ).astype(cd).reshape(B, T, heads, D)
    o = grouped_query_attention(
        proj("Wq", H), proj("Wk", KV), proj("Wv", KV), causal=conf.causal)
    y = jnp.matmul(o.astype(cd).reshape(B, T, H * D), params["Wo"].astype(cd),
                   preferred_element_type=jnp.float32)
    return y.astype(x.dtype), None


register_layer(L.GroupedQueryAttentionLayer, gqa_init, gqa_forward,
               order_fn=lambda conf: ("Wq", "Wk", "Wv", "Wo"))

"""Attention layer impls (configs: SelfAttentionLayer,
GroupedQueryAttentionLayer, LatentAttentionLayer).

SelfAttentionLayer: single-device forward uses parallel/sequence.
full_attention; the SAME math runs sequence-parallel over a mesh via
ring_self_attention (parallel/sequence.py) — tests prove block-ring ==
full. Time masking multiplies attention scores' keys (masked keys
unattendable) and zeroes masked outputs, matching the framework's RNN
masking semantics.

GroupedQueryAttentionLayer (causal, full or windowed, optional rotary
positions, no time mask): projections and `rope` are XLA's; the inner part
(scores, band mask, softmax, mix: `grouped_query_attention`) is one
algorithm with two lowerings, chosen by what the trace can observe. The
fused kernel (ops/pallas_attention.py, helper slot "gqa_attention") serves
a one-device program on a TPU with bf16 operands, a causal layer, value
heads of whole lanes (128), query/key heads of whole half-lanes and a
sequence that is a multiple of 128 (up to 16,384 positions at heads of 128:
the backward keeps a key-value head's `dk` and `dv` in VMEM); no `[queries,
keys]` array reaches HBM there. Everything else — the CPU,
float32 operands, other head sizes or lengths, a program partitioned over
a mesh — takes the built-in blocked XLA lowering below (`QUERY_BLOCK`
queries at a time under `jax.checkpoint`, the blocks past the window one
scanned body). `helper_hit_total` / `helper_fallback_total{op=
"gqa_attention"}` say which ran, once a layer a trace.

LatentAttentionLayer (the DeepSeek family's low-rank key-value attention):
its own projections, latent norm and partial rotation around the same
`grouped_query_attention`, with a group of one and queries and keys wider
(`qk_nope + qk_rope`) than values.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.layers.norm import rms_normalize
from deeplearning4j_tpu.nn.layers.registry import LayerContext, register_layer
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.ops.activations import apply_activation
from deeplearning4j_tpu.ops.helpers import HelperError, get_helper
from deeplearning4j_tpu.parallel.sequence import full_attention
from deeplearning4j_tpu.utils import metrics as _metrics


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


# Keys above which a block's softmax takes its row maximum in a pass of its
# own. `jax.nn.softmax` lets the chip's compiler fuse the maximum with the
# subtraction; from 5,376 keys on it finds no tiling for that fusion (its
# cost estimate overflows) and the one it falls back to took 41 ms for a
# block of 256 queries on 8,192 keys where 4,352 keys take 1.1 (PERF.md, PR
# 32). Blocks of fewer keys keep the program they had. Since PR 33 this
# guards the built-in lowering alone: where the fused kernel serves a
# layer, no block's softmax is XLA's.
WIDE_KEYS = 5120


def _softmax_max_apart(s):
    """softmax over the last axis, float32, the maximum behind an
    `optimization_barrier` so that it stays a reduction of its own."""
    m = jax.lax.optimization_barrier(jax.lax.stop_gradient(
        jnp.max(s, axis=-1, keepdims=True)))
    e = jnp.exp(s - m)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def _attend_block(q, k, v, start: int, causal: bool, key_start: int = 0,
                  window=None):
    """One block of queries against the keys it may see. q: [b, tq, KV, G,
    D] (its first position is `start`), k/v: [b, tk, KV, D] (their first
    position is `key_start`); scores, mask and softmax in float32, both
    products on operands of the inputs' dtype. With `window` a query sees
    the `window` keys that end at its own position."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = start + jnp.arange(q.shape[1])[:, None]
        if window is None:    # the mask as it was traced before windows
            seen = qpos >= jnp.arange(k.shape[1])[None, :]
        else:
            kpos = key_start + jnp.arange(k.shape[1])[None, :]
            seen = (qpos >= kpos) & (qpos - kpos < window)
        s = jnp.where(seen, s, -jnp.inf)
    p = _softmax_max_apart(s) if k.shape[1] > WIDE_KEYS \
        else jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


# queries whose scores are held at once: [b, H, 256, t] float32 is 0.54 GB
# at 4 x 32 heads x 4,096 keys
QUERY_BLOCK = 256


def _key_start(start: int, window, block: int) -> int:
    """First key a query block at `start` is multiplied with: the start of
    the key block that holds the oldest key its first query sees."""
    if window is None:
        return 0
    return max(0, (start - int(window) + 1) // block * block)


def key_block_pairs(t: int, window, block: int = None, causal: bool = True):
    """(multiplied, skipped): the (query block, key block) pairs over `t`
    positions that the blocked product multiplies, and those under the
    diagonal that a window lets a causal layer leave out."""
    block = block or QUERY_BLOCK
    if not causal:    # every block of queries meets every key
        return (-(-t // block)) ** 2, 0
    multiplied = skipped = 0
    for start in range(0, t, block):
        first = _key_start(start, window, block)
        skipped += first // block
        multiplied += -(-(min(start + block, t) - first) // block)
    return multiplied, skipped


def _count_lowering(t: int, *, window, positions: str,
                    causal: bool = True) -> None:
    """Trace-time, like conv._count_pool_lowering: the layer once a trace,
    and the key blocks its product multiplies or leaves out."""
    reg = _metrics.get_registry()
    reg.counter(
        "attention_lowering_total",
        "grouped-query and latent attention layers traced, by what a query "
        "sees (window or full) and the positional term (rope over the whole "
        "head, rope_partial over a slice of it, or none)",
        ("kind", "positions")).labels(
            "full" if window is None else "window", positions).inc()
    pairs = reg.counter(
        "attention_key_blocks_total",
        "(query block, key block) pairs of the traced attention layers' "
        "blocked products: multiplied, or under the diagonal and skipped "
        "because they lie wholly before the window", ("state",))
    multiplied, skipped = key_block_pairs(t, window, causal=causal)
    pairs.labels("multiplied").inc(multiplied)
    pairs.labels("skipped").inc(skipped)


def grouped_query_attention(q, k, v, *, causal: bool, window=None):
    """q: [b, t, H, D], k: [b, t, KV, D], v: [b, t, KV, Dv] -> [b, t, H,
    Dv] float32; query head `h` reads key-value head `h // (H // KV)`, the
    scores are scaled by `D ** -0.5`. The fused kernel where its probe
    takes the shapes (ops/pallas_attention.py), else the blocked XLA
    lowering."""
    if window is not None and not causal:
        raise ValueError("a window is a causal layer's")
    helper = get_helper("gqa_attention", q_shape=tuple(q.shape),
                        dtype=q.dtype, causal=causal, window=window,
                        v_head_dim=int(v.shape[-1]))
    if helper is not None:
        try:
            return helper(q, k, v, causal=causal, window=window)
        except HelperError:
            pass    # disabled and logged: the built-in lowering below
    return _blocked_attention(q, k, v, causal=causal, window=window)


def _blocked_attention(q, k, v, *, causal: bool, window=None):
    """The built-in lowering. Queries are taken `QUERY_BLOCK` at a time
    against the keys up to the block's end (a causal layer never multiplies
    the blocks above the diagonal, a window layer none that lie wholly
    before the window either), each block under `jax.checkpoint` so that
    one block's scores live at once. Past the window every whole block of a
    window layer meets the same number of keys: those blocks are one
    scanned body, traced and compiled once."""
    b, t, H, D = q.shape
    KV = k.shape[2]
    q = q.reshape(b, t, KV, H // KV, D)
    blocks = [(start, min(start + QUERY_BLOCK, t))
              for start in range(0, t, QUERY_BLOCK)]
    # past the window every whole block sees `back` keys behind its own
    steady = []
    if window is not None:
        back = -((1 - int(window)) // QUERY_BLOCK) * QUERY_BLOCK
        steady = [(s, e) for s, e in blocks
                  if s >= back and e - s == QUERY_BLOCK]
        steady = steady if len(steady) > 1 else []
    outs = []
    for start, end in blocks:
        if steady and start == steady[0][0]:
            outs.append(_steady_blocks(q, k, v, steady, back, int(window)))
        if (start, end) in steady:
            continue
        first = _key_start(start, window, QUERY_BLOCK)
        seen = end if causal else t
        block = partial(_attend_block, start=start, causal=causal,
                        key_start=first, window=window)
        if t > QUERY_BLOCK:
            block = jax.checkpoint(block)
        outs.append(block(q[:, start:end], k[:, first:seen],
                          v[:, first:seen]))
    o = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    return o.reshape(b, t, H, v.shape[-1])


def _steady_blocks(q, k, v, steady, back: int, window: int):
    """The query blocks `steady` (consecutive, whole, each `back` positions
    behind its first key) as one `lax.map` over blocks: the body slices its
    keys from `k`/`v` where the block's window starts, and its mask is the
    same for every block."""
    b, _, KV, G, D = q.shape
    n, lo = len(steady), steady[0][0]
    keys = back + QUERY_BLOCK

    @jax.checkpoint
    def body(args):
        q_blk, key_start = args
        k_blk = jax.lax.dynamic_slice_in_dim(k, key_start, keys, axis=1)
        v_blk = jax.lax.dynamic_slice_in_dim(v, key_start, keys, axis=1)
        # positions relative to the first key: the mask does not move
        return _attend_block(q_blk, k_blk, v_blk, start=back, causal=True,
                             key_start=0, window=window)

    q_blocks = jnp.moveaxis(
        q[:, lo:lo + n * QUERY_BLOCK].reshape(b, n, QUERY_BLOCK, KV, G, D),
        1, 0)
    starts = jnp.arange(n, dtype=jnp.int32) * QUERY_BLOCK + (lo - back)
    out = jax.lax.map(body, (q_blocks, starts))
    return jnp.moveaxis(out, 0, 1).reshape(b, n * QUERY_BLOCK, KV, G,
                                           v.shape[-1])


def rope(x, theta: float, *, start: int = 0, interleaved: bool = False):
    """Rotary positions: x [b, t, heads, D] at positions `0 .. t - 1`. Over
    the whole head, dimension `i` paired with `i + D / 2` (`rotate_half`),
    angle `p * theta ** (-2 i / D)`. With `start` only the dimensions from
    `start` on are rotated (`D` is then their number) and the others pass
    through; with `interleaved` the pairs are the adjacent dimensions `(2 j,
    2 j + 1)` and come back apart, the rotated `2 j` in the first half and
    the rotated `2 j + 1` in the second: a permutation of the head's
    dimensions that queries and keys share, so their products do not see
    it. Computed in float32, returned in x's dtype."""
    if start:
        return jnp.concatenate(
            [x[..., :start],
             rope(x[..., start:], theta, interleaved=interleaved)], axis=-1)
    t, D = x.shape[1], x.shape[-1]
    half = D // 2
    inv_freq = 1.0 / (float(theta) ** (
        jnp.arange(half, dtype=jnp.float32) * 2.0 / D))
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = (xf[..., 0::2], xf[..., 1::2]) if interleaved \
        else (xf[..., :half], xf[..., half:])
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def gqa_forward(conf: L.GroupedQueryAttentionLayer, params, x,
                ctx: LayerContext):
    """x: [b, t, n_in] -> [b, t, n_out] in x's dtype; the products run in
    the net's compute dtype with float32 accumulation, the rotation in
    float32."""
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
    q, k, v = proj("Wq", H), proj("Wk", KV), proj("Wv", KV)
    if conf.rope_theta is not None:
        with jax.named_scope("rope"):
            q, k = rope(q, conf.rope_theta), rope(k, conf.rope_theta)
    with jax.named_scope("full_attention" if conf.window is None
                         else "window_attention"):
        o = grouped_query_attention(q, k, v, causal=conf.causal,
                                    window=conf.window)
    _count_lowering(T, window=conf.window, causal=conf.causal,
                    positions="none" if conf.rope_theta is None else "rope")
    y = jnp.matmul(o.astype(cd).reshape(B, T, H * D), params["Wo"].astype(cd),
                   preferred_element_type=jnp.float32)
    return y.astype(x.dtype), None


register_layer(L.GroupedQueryAttentionLayer, gqa_init, gqa_forward,
               order_fn=lambda conf: ("Wq", "Wk", "Wv", "Wo"))


# -- latent (low-rank key-value) causal attention -------------------------------

def latent_init(key, conf: L.LatentAttentionLayer, dtype):
    n_in, n_out, H = int(conf.n_in), int(conf.n_out), int(conf.n_heads)
    nope, rot = int(conf.qk_nope_head_dim), int(conf.qk_rope_head_dim)
    dv, rank = int(conf.v_head_dim), int(conf.kv_lora_rank)
    if rot % 2:
        raise ValueError(f"qk_rope_head_dim {rot} is not made of pairs")
    ks = jax.random.split(key, 4)
    mk = lambda k, i, o: init_weights(k, (i, o), i, o, conf.weight_init,
                                      conf.dist, dtype)
    return {"Wq": mk(ks[0], n_in, H * (nope + rot)),
            "Wkv_a": mk(ks[1], n_in, rank + rot),
            "kv_norm": jnp.ones((rank,), dtype),
            "Wkv_b": mk(ks[2], rank, H * (nope + dv)),
            "Wo": mk(ks[3], H * dv, n_out)}


def latent_forward(conf: L.LatentAttentionLayer, params, x,
                   ctx: LayerContext):
    """x: [b, t, n_in] -> [b, t, n_out] in x's dtype; the products run in
    the net's compute dtype with float32 accumulation, the latent's norm and
    the rotation in float32. The inner part is `grouped_query_attention`
    with a group of one: queries and keys of `qk_nope + qk_rope`, values of
    `v_head_dim`."""
    if ctx.mask is not None:
        raise NotImplementedError(
            "LatentAttentionLayer takes no time mask (packed or padded "
            "sequences): SelfAttentionLayer masks keys")
    B, T, _ = x.shape
    H, nope, rot = int(conf.n_heads), int(conf.qk_nope_head_dim), \
        int(conf.qk_rope_head_dim)
    dv, rank = int(conf.v_head_dim), int(conf.kv_lora_rank)
    cd = ctx.compute_dtype or x.dtype
    mm = lambda a, name: jnp.matmul(a, params[name].astype(cd),
                                    preferred_element_type=jnp.float32)
    u = x.astype(cd)
    q = mm(u, "Wq").astype(cd).reshape(B, T, H, nope + rot)
    with jax.named_scope("latent_kv"):
        c = mm(u, "Wkv_a")                           # float32 [b, t, rank + rot]
        latent = rms_normalize(c[..., :rank], params["kv_norm"], conf.eps)
        kv = mm(latent.astype(cd), "Wkv_b").astype(cd).reshape(
            B, T, H, nope + dv)
        k_rope = c[..., rank:].astype(cd).reshape(B, T, 1, rot)
    if conf.rope_theta is not None:
        with jax.named_scope("rope"):
            q = rope(q, conf.rope_theta, start=nope, interleaved=True)
            k_rope = rope(k_rope, conf.rope_theta, interleaved=True)
    with jax.named_scope("latent_kv"):
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (B, T, H, rot))],
            axis=-1)
        v = kv[..., nope:]
    with jax.named_scope("latent_attention"):
        o = grouped_query_attention(q, k, v, causal=True)
    _count_lowering(T, window=None, positions="none"
                    if conf.rope_theta is None else "rope_partial")
    y = mm(o.astype(cd).reshape(B, T, H * dv), "Wo")
    return y.astype(x.dtype), None


register_layer(L.LatentAttentionLayer, latent_init, latent_forward,
               order_fn=lambda conf: ("Wq", "Wkv_a", "kv_norm", "Wkv_b",
                                      "Wo"))

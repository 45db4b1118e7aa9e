"""What the smallthinker family forced on the program: a causal window and
rotary positions in `GroupedQueryAttentionLayer`, gated softmax-routed
experts and a router wired from another vertex in `SparseExpertsLayer`, and
`models/smallthinker.py`, at tiny sizes on the CPU: hidden 64, 4 query heads
on 2 key-value heads, a window of 8 under sequences of 32, 16 routed experts
of which 8 are held. The plain reference is
`benchmark/reference/smallthinker.py`, which imports nothing of the
program."""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import smallthinker as ref  # noqa: E402
from deeplearning4j_tpu.data.dataset import DataSet  # noqa: E402
from deeplearning4j_tpu.data.iterators import ListDataSetIterator  # noqa: E402
from deeplearning4j_tpu.models.smallthinker import (  # noqa: E402
    smallthinker_conf,
    tiny_smallthinker_conf,
)
from deeplearning4j_tpu.nn.compgraph import ComputationGraph  # noqa: E402
from deeplearning4j_tpu.nn.conf import layers as L  # noqa: E402
from deeplearning4j_tpu.nn.conf.graph import (  # noqa: E402
    ComputationGraphConfiguration,
)
from deeplearning4j_tpu.nn.layers import attention as A  # noqa: E402
from deeplearning4j_tpu.nn.layers import experts as X  # noqa: E402
from deeplearning4j_tpu.nn.layers.registry import (  # noqa: E402
    LayerContext,
    forward_layer,
    init_layer_params,
    init_layer_state,
)
from deeplearning4j_tpu.utils.metrics import get_registry  # noqa: E402

# the tiny preset as the reference reads a configuration
TINY = {
    "num_hidden_layers": 4, "sliding_window_layout": [0, 1, 1, 1],
    "rope_layout": [0, 1, 1, 1], "hidden_size": 64, "vocab_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window_size": 8, "rope_theta": 1.5e6,
    "moe_num_primary_experts": 8, "router_width": 16,
    "experts_held": list(range(8)), "moe_num_active_primary_experts": 3,
    "moe_ffn_hidden_size": 48, "rms_norm_eps": 1e-6,
    "factory_args": {"seq_len": 32},
}
SEQ, BATCH = 32, 4


def _tokens(seed=0, batch=BATCH, seq=SEQ, vocab=128):
    ids = np.random.default_rng(seed).integers(0, vocab, (batch, seq + 1),
                                               dtype=np.int32)
    return np.ascontiguousarray(ids[:, :-1]), np.ascontiguousarray(ids[:, 1:])


def _net(precision="f32", seed=3, **kw):
    net = ComputationGraph(tiny_smallthinker_conf(
        precision=precision, seq_len=SEQ, **kw)).init()
    weights = ref.init_params(seed, TINY)
    net.params_list = [dict(weights[name]) for name in
                       net.layer_vertex_names]
    return net, weights


def _rel(a, b):
    return float(jnp.linalg.norm((a - b).ravel())
                 / jnp.maximum(jnp.linalg.norm(b.ravel()), 1e-30))


# -- the whole net against the plain reference ------------------------------------

# f32: both sides float32 at HIGHEST; what is left is the order of the sums
# (blocked and banded attention against the literal mask, grouped experts
# against the masked loop). bf16: as for the nemotron_h preset
# (tests/test_nemotron_h.py), with the router's own leaves the noisiest: their
# gradient passes through the softmax over three chosen logits alone.
@pytest.mark.parametrize("precision,loss_tol,grad_tol", [
    ("f32", 2e-6, 2e-4), ("bf16", 3e-4, 0.5)])
def test_fits_first_step_against_the_reference(precision, loss_tol, grad_tol):
    """Loss and every gradient leaf of `fit()`'s first step (read back from
    Adam's first moment, as the harness reads it), at a size where the
    window (8) is shorter than the sequence (32) and the router's input (the
    layer's input) differs from the experts' (the normed output of the
    attention block)."""
    net, weights = _net(precision)
    x, y = _tokens(1)
    net.fit(ListDataSetIterator(DataSet(x, y), BATCH))
    got = float(net._score)
    want, want_grads = jax.value_and_grad(ref.loss)(weights, x, y, TINY,
                                                    "f32")
    assert abs(got - float(want)) / float(want) < loss_tol
    moments = dict(zip(net.layer_vertex_names, net.upd_state))
    assert set(moments) == set(want_grads)
    for layer, leaves in want_grads.items():
        assert set(moments[layer]) == set(leaves)
        for name, g in leaves.items():
            mine = moments[layer][name]["m"] / (1.0 - 0.9)
            assert mine.shape == g.shape
            assert _rel(mine, g) < grad_tol, f"{layer}/{name}"


def test_the_references_layers_hold_the_programs_names_and_shapes():
    _, weights = _net()
    fresh = ComputationGraph(tiny_smallthinker_conf(seq_len=SEQ)).init()
    for name, mine in zip(fresh.layer_vertex_names, fresh.params_list):
        assert set(mine) == set(weights[name]), name
        for leaf, a in mine.items():
            assert a.shape == weights[name][leaf].shape, (name, leaf)
    assert set(weights) == set(fresh.layer_vertex_names)
    # one period: a full layer without positions, three window layers with
    kinds = [(c.window, c.rope_theta) for c in fresh._layer_confs
             if isinstance(c, L.GroupedQueryAttentionLayer)]
    assert kinds == [(None, None)] + [(8, 1.5e6)] * 3


def test_training_lowers_the_loss_and_the_books_are_kept():
    net, _ = _net()
    x, y = _tokens(2)
    first = None
    for _ in range(6):
        net.fit(ListDataSetIterator(DataSet(x, y), BATCH))
        first = first if first is not None else float(net._score)
    assert float(net._score) < first - 0.05
    values = get_registry().scalar_values()
    assert values["experts_overflow_total"] == 0
    assert values['experts_assignments_total{held="1"}'] > 0
    assert 0 < values["experts_buffer_fill"] <= 1


# -- the window -------------------------------------------------------------------

def _dense_attention(q, k, v, window):
    """softmax(q k^T / sqrt(D)) v under the literal band mask."""
    b, t, H, D = q.shape
    g = H // k.shape[2]
    k, v = (jnp.repeat(a, g, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bshd->bhqs", q, k) / np.sqrt(D)
    p, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = (j <= p) if window is None else (j <= p) & (p - j < window)
    return jnp.einsum("bhqs,bshd->bqhd",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)


def _qkv(t, seed=0, b=2, H=4, KV=2, D=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, t, H, D)),
            jax.random.normal(ks[1], (b, t, KV, D)),
            jax.random.normal(ks[2], (b, t, KV, D)))


@pytest.mark.parametrize("t,window,block", [
    (37, 8, 4),      # T no multiple of the block; the window two blocks
    (37, 7, 4),      # a window that is no multiple of the block
    (40, 8, 8), (29, 5, 16), (64, 16, 4), (23, 1, 4), (16, 64, 4)])
def test_window_attention_against_a_dense_masked_softmax(t, window, block,
                                                         monkeypatch):
    monkeypatch.setattr(A, "QUERY_BLOCK", block)
    q, k, v = _qkv(t)
    want = _dense_attention(q, k, v, window)
    got = A.grouped_query_attention(q, k, v, causal=True, window=window)
    assert got.shape == want.shape and _rel(got, want) < 1e-5
    # and its gradients, through the scanned blocks and the checkpoints
    f = lambda fn: lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))
    got_g = jax.grad(f(lambda *a: A.grouped_query_attention(
        *a, causal=True, window=window)), argnums=(0, 1, 2))(q, k, v)
    want_g = jax.grad(f(lambda *a: _dense_attention(*a, window)),
                      argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got_g, want_g):
        assert _rel(a, b) < 1e-5


@pytest.mark.parametrize("block", [4, 5, 64])
def test_another_query_block_does_not_change_a_window_layer(block,
                                                            monkeypatch):
    q, k, v = _qkv(37, seed=1)
    monkeypatch.setattr(A, "QUERY_BLOCK", 256)
    want = A.grouped_query_attention(q, k, v, causal=True, window=9)
    monkeypatch.setattr(A, "QUERY_BLOCK", block)
    got = A.grouped_query_attention(q, k, v, causal=True, window=9)
    assert _rel(got, want) < 1e-5


def test_a_window_layer_never_multiplies_what_lies_before_the_window(
        monkeypatch):
    """Keys that lie wholly before every window may be NaN: no product
    touches them. The steady blocks are one scanned body."""
    monkeypatch.setattr(A, "QUERY_BLOCK", 4)
    q, k, v = _qkv(32)
    jaxpr = str(jax.make_jaxpr(lambda *a: A.grouped_query_attention(
        *a, causal=True, window=8))(q, k, v))
    assert jaxpr.count("scan[") == 1
    # blocks 0, 1 see keys from 0; blocks 2..7 are the scan's
    assert jaxpr.count("checkpoint[") + jaxpr.count("remat") >= 3
    got = A.grouped_query_attention(q[:, 16:], k[:, 16:], v[:, 16:],
                                    causal=True, window=8)
    poisoned = A.grouped_query_attention(
        q, k.at[:, :4].set(jnp.nan), v.at[:, :4].set(jnp.nan), causal=True,
        window=8)
    assert bool(jnp.all(jnp.isfinite(poisoned[:, 12:])))
    # positions 24.. see only keys 17..: the same from the cut sequence
    assert _rel(poisoned[:, 24:], got[:, 8:]) < 1e-5
    with pytest.raises(ValueError, match="causal"):
        A.grouped_query_attention(q, k, v, causal=False, window=8)


@pytest.mark.parametrize("window", [None, 12])
def test_the_wide_key_softmax_is_the_softmax(window, monkeypatch):
    """Blocks of more than `WIDE_KEYS` keys take their row maximum in a pass
    of its own (the chip's compiler finds no tiling for the fused form from
    5,376 keys on): the same value and gradients, and no block of the
    Nemotron cell's 4,096 keys is wide."""
    assert 4096 + 256 <= A.WIDE_KEYS < 5376
    monkeypatch.setattr(A, "QUERY_BLOCK", 8)
    q, k, v = _qkv(40, seed=2)
    f = lambda q, k, v: jnp.sum(jnp.sin(A.grouped_query_attention(
        q, k, v, causal=True, window=window)))
    want, want_g = jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setattr(A, "WIDE_KEYS", 16)
    jaxpr = str(jax.make_jaxpr(f)(q, k, v))
    assert "optimization_barrier" in jaxpr
    got, got_g = jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    for a, b in zip(got_g, want_g):
        assert _rel(a, b) < 1e-5
    s = jax.random.normal(jax.random.PRNGKey(3), (3, 5, 33)) * 20.0
    s = s.at[..., 20:].set(-jnp.inf)
    assert jnp.allclose(A._softmax_max_apart(s), jax.nn.softmax(s, axis=-1),
                        atol=1e-6)


@pytest.mark.parametrize("t,window,block,multiplied,skipped", [
    (8192, 4096, 256, 408, 120),   # the cell's window layer: 16 x 17 / 2 + 16 x 17
    (8192, None, 256, 528, 0),     # its full layer: 32 x 33 / 2
    (4096, 4096, 256, 136, 0),     # at the window's length a window layer is a full one
    (32, 8, 4, 8 * 3 - 3, 15), (37, 8, 4, 10 * 3 - 3, 28)])
def test_key_block_pairs_by_hand(t, window, block, multiplied, skipped):
    assert A.key_block_pairs(t, window, block) == (multiplied, skipped)
    # against the band mask itself: a block pair is multiplied iff it holds
    # a visible (query, key) pair or lies between such pairs of its row
    if t <= 64:
        mask = np.asarray(ref.visible(t, window))
        n = -(-t // block)
        rows = [[mask[i * block:(i + 1) * block, j * block:(j + 1) * block
                      ].any() for j in range(n)] for i in range(n)]
        assert sum(map(sum, rows)) == multiplied
        assert sum(row.index(True) for row in rows) == skipped


def _lowering_counts():
    values = get_registry().scalar_values()
    return {k: v for k, v in values.items()
            if k.startswith(("attention_lowering_total",
                             "attention_key_blocks_total"))}


def test_the_two_counters_read_the_layers_and_their_block_pairs(monkeypatch):
    """One full layer without positions and three window layers with, once
    a layer a trace, and the block pairs by hand: at blocks of 4 over 32
    positions the full layer multiplies 8 x 9 / 2 = 36 pairs, a window
    layer (8) 21 and skips 15."""
    monkeypatch.setattr(A, "QUERY_BLOCK", 4)
    net, _ = _net()
    before = _lowering_counts()
    x, y = _tokens(3)
    net.fit(ListDataSetIterator(DataSet(x, y), BATCH))
    after = _lowering_counts()
    delta = {k: after[k] - before.get(k, 0.0) for k in after}
    delta = {k: v for k, v in delta.items() if v}
    assert delta == {
        'attention_lowering_total{kind="full",positions="none"}': 1,
        'attention_lowering_total{kind="window",positions="rope"}': 3,
        'attention_key_blocks_total{state="multiplied"}': 36 + 3 * 21,
        'attention_key_blocks_total{state="skipped"}': 3 * 15}


# -- rotary positions ----------------------------------------------------------------

def test_rotary_against_the_complex_number_formula():
    """Dimension i and i + D/2 of a head are the real and imaginary part of
    one complex number, turned by exp(1j p theta^(-2i/D))."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 11, 3, 16))
    theta = 1.5e6
    z = np.asarray(x[..., :8]) + 1j * np.asarray(x[..., 8:])
    angle = np.arange(11)[:, None] * theta ** (-np.arange(8) * 2.0 / 16)
    turned = z * np.exp(1j * angle)[None, :, None, :]
    want = np.concatenate([turned.real, turned.imag], axis=-1)
    assert _rel(A.rope(x, theta), jnp.asarray(want)) < 1e-5
    assert _rel(ref.rope(x, theta), jnp.asarray(want)) < 1e-5
    # position 0 is not turned; the rotation keeps every pair's length
    assert jnp.allclose(A.rope(x, theta)[:, 0], x[:, 0], atol=1e-6)
    assert jnp.allclose(jnp.linalg.norm(A.rope(x, theta), axis=-1),
                        jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    # computed in float32, handed on in the input's type
    assert A.rope(x.astype(jnp.bfloat16), theta).dtype == jnp.bfloat16


def _attention_layer(**kw):
    conf = L.GroupedQueryAttentionLayer(
        n_in=32, n_out=32, n_heads=4, n_kv_heads=2, head_dim=8,
        weight_init="xavier", **kw)
    return conf, init_layer_params(jax.random.PRNGKey(1), conf, jnp.float32)


def test_a_layer_without_positions_is_what_it_was_and_rotary_is_relative():
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 32))
    plain, params = _attention_layer()
    rotary, _ = _attention_layer(rope_theta=1e4)
    got, _ = forward_layer(plain, params, x, LayerContext())
    # the layer of PR 28, written out: no positional term, all keys before
    q, k, v = (jnp.matmul(x, params[n]).reshape(2, 12, -1, 8)
               for n in ("Wq", "Wk", "Wv"))
    want = _dense_attention(q, k, v, None).reshape(2, 12, 32) @ params["Wo"]
    assert _rel(got, want) < 1e-5
    turned, _ = forward_layer(rotary, params, x, LayerContext())
    assert _rel(turned, want) > 1e-2          # the rotation is applied
    # and to nothing but q and k: scores depend on p - j alone, so a layer
    # fed the same token at every position is blind to it
    same = jnp.broadcast_to(x[:, :1], x.shape)
    a, _ = forward_layer(plain, params, same, LayerContext())
    b, _ = forward_layer(rotary, params, same, LayerContext())
    assert _rel(b, a) < 1e-5


@pytest.mark.parametrize("kw", [{"window": 4}, {"rope_theta": 1e4}])
def test_a_time_mask_is_still_refused(kw):
    conf, params = _attention_layer(**kw)
    with pytest.raises(NotImplementedError, match="time mask"):
        forward_layer(conf, params, jnp.ones((2, 6, 32)),
                      LayerContext(mask=jnp.ones((2, 6))))


# -- the experts -------------------------------------------------------------------

def _gated_layer(held, factor=8.0, router_input=True, seed=3):
    conf = L.SparseExpertsLayer(
        n_in=32, n_out=32, router_width=16, experts_held=held,
        experts_per_token=3, width=24, activation="relu", gated=True,
        score="softmax", router_input=router_input, capacity_factor=factor,
        weight_init="xavier")
    return conf, init_layer_params(jax.random.PRNGKey(seed), conf,
                                   jnp.float32)


def _reference_sizes(held):
    return ref._sizes(dict(TINY, hidden_size=32, moe_ffn_hidden_size=24,
                           experts_held=held,
                           moe_num_primary_experts=len(held)))


def test_the_router_reads_another_input_than_the_experts():
    """The logits come from the layer's input `x`, the experts read `u`: the
    layer is the reference's `experts(u; x W_router)`, value and gradients
    down to the router's own weight, and is not what a router on `u` gives."""
    held = list(range(8))
    conf, params = _gated_layer(held)
    assert "W_router" not in params and conf.n_inputs() == 2
    router = L.ExpertRouterLayer(n_in=32, n_out=16, weight_init="xavier")
    w = init_layer_params(jax.random.PRNGKey(4), router, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 32))
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 24, 32))
    z = _reference_sizes(held)

    def mine(p, w, x, u):
        logits, _ = forward_layer(router, w, x, LayerContext())
        assert logits.dtype == jnp.float32
        return jnp.sum(jnp.sin(forward_layer(
            conf, p, u, LayerContext(extra_inputs=(logits,)))[0]))

    def theirs(p, w, x, u):
        return jnp.sum(jnp.sin(ref.experts(
            p, u, ref.router_logits(w, x), z, "f32")))

    got, got_g = jax.value_and_grad(mine, argnums=(0, 1, 2, 3))(
        params, w, x, u)
    want, want_g = jax.value_and_grad(theirs, argnums=(0, 1, 2, 3))(
        params, w, x, u)
    assert abs(float(got) - float(want)) < 1e-4 * abs(float(want)) + 1e-4
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        assert _rel(a, b) < 1e-4
    assert abs(float(mine(params, w, u, u)) - float(want)) > 1e-2


def _skewed(skew, factor):
    """Logits under which the routing is as skewed as asked: every token
    the same experts, one held expert idle, none of the held chosen."""
    held = [9, 2, 3, 12, 5, 0, 15, 7] if skew == "held_in_any_order" \
        else list(range(8))
    conf, params = _gated_layer(held, factor)
    u = jax.random.normal(jax.random.PRNGKey(11), (2, 48, 32))
    logits = jax.random.normal(jax.random.PRNGKey(12), (2, 48, 16))
    if skew == "same_experts":
        logits = jnp.broadcast_to(logits[:1, :1], logits.shape)
    unchosen = {"one_held_idle": [3], "none_held_chosen": held}.get(skew, [])
    logits = logits.at[..., jnp.asarray(unchosen, jnp.int32)].set(-30.0)
    return conf, params, u, logits


@pytest.mark.parametrize("factor", [8.0, 0.25])
@pytest.mark.parametrize("skew", ["uniform", "same_experts", "one_held_idle",
                                  "none_held_chosen", "held_in_any_order"])
def test_gated_experts_are_the_reference_on_either_path(skew, factor,
                                                        monkeypatch):
    """Value and gradients against the plain reference's masked loop, with
    buffers that hold everything (8.0: the grouped path) and buffers that
    do not (0.25: the exact path): the two paths are one layer."""
    monkeypatch.setattr(X, "_ROWS", 8)
    conf, params, u, logits = _skewed(skew, factor)
    held = conf.held()
    z = _reference_sizes(held)
    state = init_layer_state(conf, jnp.float32)
    _, books = forward_layer(conf, params, u, LayerContext(
        state=state, extra_inputs=(logits,)))
    loads = np.asarray(books["routed"])[held]
    cap = X.expert_capacity(conf, 96)
    assert int(books["overflow"]) == np.maximum(loads - cap, 0).sum()
    assert (int(books["overflow"]) > 0) == (factor < 1 and loads.sum() > 0)
    mine = lambda p, a, l: jnp.sum(jnp.sin(forward_layer(
        conf, p, a, LayerContext(extra_inputs=(l,)))[0]))
    theirs = lambda p, a, l: jnp.sum(jnp.sin(ref.experts(p, a, l, z, "f32")))
    got, got_g = jax.value_and_grad(mine, argnums=(0, 1, 2))(
        params, u, logits)
    want, want_g = jax.value_and_grad(theirs, argnums=(0, 1, 2))(
        params, u, logits)
    assert abs(float(got) - float(want)) < 1e-4 * abs(float(want)) + 1e-4
    for k in want_g[0]:
        assert jnp.linalg.norm(got_g[0][k] - want_g[0][k]) \
            < 1e-4 * jnp.linalg.norm(want_g[0][k]) + 1e-5, k
    for a, b in zip(got_g[1:], want_g[1:]):
        assert jnp.linalg.norm(a - b) < 1e-4 * jnp.linalg.norm(b) + 1e-5


def test_softmax_weights_are_the_softmax_over_the_chosen():
    conf, _ = _gated_layer([0, 1])
    logits = 30.0 * jax.random.normal(jax.random.PRNGKey(2), (10, 16))
    scores = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    idx, w = X.route(conf, scores)
    chosen, want_idx = jax.lax.top_k(logits, 3)
    assert jnp.array_equal(idx, want_idx)
    assert jnp.allclose(w, jax.nn.softmax(chosen, axis=-1), atol=1e-6)


def test_an_unknown_score_and_a_missing_router_are_refused():
    bad = L.SparseExpertsLayer(n_in=8, n_out=8, router_width=4, width=4,
                               score="tanh", weight_init="xavier")
    with pytest.raises(ValueError, match="score"):
        init_layer_params(jax.random.PRNGKey(0), bad, jnp.float32)
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration

    gb = NeuralNetConfiguration.builder().graph_builder().add_inputs("a")
    with pytest.raises(ValueError, match="takes 2 inputs"):
        gb.add_layer("e", L.SparseExpertsLayer(
            n_out=8, router_width=4, width=4, router_input=True), "a")


# -- the graph, the factory, the serde ---------------------------------------------

def test_recomputation_keeps_the_routers_edge_and_changes_nothing():
    """Two runs a layer, the router's logits crossing into the second from
    outside both; with and without recomputation the same loss and
    gradients."""
    net, weights = _net()
    plain, _ = _net(recompute=False)
    runs = net._recompute_runs()
    assert len(runs) == 8
    second = [r for r in runs.values() if r["names"][1] == "b1_experts"][0]
    assert second["inputs"] == ["b1_attn_add", "b1_router"]
    assert second["exits"] == ["b1_ffn_add"]
    x, y = _tokens(4)

    def loss_and_grads(n):
        f = lambda params: n._loss(params, n.state_list, [jnp.asarray(x)],
                                   [jnp.asarray(y)], None, None, None)[0]
        return jax.value_and_grad(f)(n.params_list)

    a, ga = loss_and_grads(net)
    b, gb = loss_and_grads(plain)
    assert abs(float(a) - float(b)) < 1e-6
    for u, v in zip(jax.tree_util.tree_leaves(ga),
                    jax.tree_util.tree_leaves(gb)):
        assert _rel(u, v) < 1e-5


def test_serde_round_trip_of_the_new_fields():
    conf = tiny_smallthinker_conf(seq_len=SEQ)
    back = ComputationGraphConfiguration.from_json(conf.to_json())
    assert back.to_json() == conf.to_json()
    attn = back.vertices["b1_attn"].layer
    assert (attn.window, attn.rope_theta) == (8, 1.5e6)
    full = back.vertices["b0_attn"].layer
    assert (full.window, full.rope_theta) == (None, None)
    experts = back.vertices["b2_experts"].layer
    assert (experts.gated, experts.score, experts.router_input) \
        == (True, "softmax", True)
    assert back.vertex_inputs["b2_experts"] == ["b2_ffn_norm", "b2_router"]
    assert isinstance(back.vertices["b2_router"].layer, L.ExpertRouterLayer)
    assert back.recompute == conf.recompute and len(back.recompute) == 8
    # the defaults are PR 28's layers
    old = L.SparseExpertsLayer(n_out=8, router_width=4, width=4)
    assert (old.gated, old.score, old.router_input, old.n_inputs()) \
        == (False, "sigmoid", False, 1)


def test_layer_scopes_name_the_router_and_both_kinds_of_attention():
    net, _ = _net()
    x, y = _tokens(5)
    text = net._build_train_step().lower(
        net.params_list, net.state_list, net.upd_state,
        ([jnp.asarray(x)], [jnp.asarray(y)], [None], [None]),
        jnp.float32(1e-3), jnp.float32(0.0), jax.random.PRNGKey(0)
    ).as_text(debug_info=True)
    for scope in ("Lb0_router_expertrouter", "Lb0_attn_groupedqueryattention/"
                  "full_attention", "Lb1_attn_groupedqueryattention/rope",
                  "Lb1_attn_groupedqueryattention/window_attention",
                  "Lb1_experts_sparseexperts/router",
                  "Lb1_experts_sparseexperts/experts"):
        assert scope in text, scope
    assert "Lb0_attn_groupedqueryattention/rope" not in text
    assert "Lb0_attn_groupedqueryattention/window_attention" not in text


def test_the_factory_refuses_what_it_cannot_build():
    with pytest.raises(ValueError, match="each of the 4 layers"):
        smallthinker_conf(sliding_window_layout=(0, 1), seq_len=8)
    with pytest.raises(ValueError, match="experts_held"):
        smallthinker_conf(experts_held=[0, 1], seq_len=8)
    with pytest.raises(ValueError, match="softmax"):
        smallthinker_conf(norm_topk_prob=False, seq_len=8)

"""What the AFMoE family (Trinity-Mini) forced on the program: per-head
query/key RMS norms and a sigmoid output gate in
`GroupedQueryAttentionLayer`, a norm after each sub-layer before its residual
add, the embedding's `sqrt(hidden_size)` and `models/afmoe.py`, at tiny
sizes on the CPU: one dense window layer and four expert layers (window,
full, window, window), hidden 64, 4 query heads on 2 key-value heads of 16,
a window of 8 under a sequence of 24, 16 routed experts of which 8 are held,
3 a token, 1 shared. The plain reference is `benchmark/reference/afmoe.py`,
which imports nothing of the program."""

from __future__ import annotations

import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import afmoe as ref  # noqa: E402
from deeplearning4j_tpu.data.dataset import DataSet  # noqa: E402
from deeplearning4j_tpu.data.iterators import ListDataSetIterator  # noqa: E402
from deeplearning4j_tpu.models.afmoe import (  # noqa: E402
    afmoe_conf,
    tiny_afmoe_conf,
)
from deeplearning4j_tpu.nn.compgraph import ComputationGraph  # noqa: E402
from deeplearning4j_tpu.nn.conf import layers as L  # noqa: E402
from deeplearning4j_tpu.nn.layers import attention as A  # noqa: E402
from deeplearning4j_tpu.nn.layers.registry import (  # noqa: E402
    LayerContext,
    forward_layer,
    init_layer_params,
)
from deeplearning4j_tpu.utils.metrics import get_registry  # noqa: E402

S, F = "sliding_attention", "full_attention"
# the tiny preset as the reference reads a configuration
TINY = {
    "num_hidden_layers": 5, "layer_types": [S, S, F, S, S],
    "num_dense_layers": 1, "hidden_size": 64, "vocab_size": 128,
    "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 8,
    "rope_theta": 1e4, "num_experts": 8, "router_width": 16,
    "experts_held": list(range(8)), "num_experts_per_tok": 3,
    "num_shared_experts": 1, "moe_intermediate_size": 48,
    "route_scale": 2.826, "rms_norm_eps": 1e-5, "mup_enabled": True,
}
SEQ, BATCH = 24, 4


def _tokens(seed=0, batch=BATCH, seq=SEQ, vocab=128):
    ids = np.random.default_rng(seed).integers(0, vocab, (batch, seq + 1),
                                               dtype=np.int32)
    return np.ascontiguousarray(ids[:, :-1]), np.ascontiguousarray(ids[:, 1:])


def _net(precision="f32", seed=3, **kw):
    net = ComputationGraph(tiny_afmoe_conf(
        precision=precision, seq_len=SEQ, **kw)).init()
    weights = ref.init_params(seed, TINY)
    net.params_list = [dict(weights[name]) for name in
                       net.layer_vertex_names]
    return net, weights


def _rel(a, b):
    return float(jnp.linalg.norm((jnp.asarray(a) - b).ravel())
                 / jnp.maximum(jnp.linalg.norm(jnp.asarray(b).ravel()), 1e-30))


# -- the whole net against the plain reference ------------------------------------

def test_the_net_against_the_reference_and_then_it_trains():
    """float32 on both sides at HIGHEST: the net's output (the softmax of
    its logits) against the softmax of the reference's logits, then the
    loss and every gradient leaf of `fit()`'s first step (read back from
    Adam's first moment, as the harness reads it; the selection bias gets
    no gradient on either side); four more steps lower the loss and leave
    the bias bit for bit where it was."""
    net, weights = _net(learning_rate=1e-2)
    x, y = _tokens(1)
    # the reference jitted: op by op it takes seconds on the CPU
    logits = jax.jit(lambda w, x: ref.logits(w, x, TINY))
    want_probs = jax.nn.softmax(logits(weights, x), axis=-1)
    assert _rel(net.output(x), want_probs) < 1e-5
    net.fit(ListDataSetIterator(DataSet(x, y), BATCH))
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda w, x, y: ref.loss(w, x, y, TINY)))(weights, x, y)
    first = float(net._score)
    assert abs(first - float(want)) / float(want) < 2e-6
    moments = dict(zip(net.layer_vertex_names, net.upd_state))
    assert set(moments) == set(want_grads)
    for layer, leaves in want_grads.items():
        assert set(moments[layer]) == set(leaves)
        for name, g in leaves.items():
            mine = moments[layer][name]["m"] / (1.0 - 0.9)
            assert mine.shape == g.shape
            if name == "b_select":
                assert not np.asarray(g).any() and not np.asarray(mine).any()
            else:
                assert _rel(mine, g) < 2e-4, f"{layer}/{name}"
    for _ in range(4):
        net.fit(ListDataSetIterator(DataSet(x, y), BATCH))
    assert float(net._score) < first - 0.05
    index = net.layer_vertex_names.index("b1_experts")
    assert np.asarray(net.params_list[index]["b_select"]).tobytes() \
        == np.asarray(weights["b1_experts"]["b_select"]).tobytes()
    assert get_registry().scalar_values()["experts_overflow_total"] == 0


def test_the_graph_holds_the_sandwich_and_the_references_names():
    _, weights = _net()
    fresh = ComputationGraph(tiny_afmoe_conf(seq_len=SEQ)).init()
    assert set(weights) == set(fresh.layer_vertex_names)
    for name, mine in zip(fresh.layer_vertex_names, fresh.params_list):
        assert set(mine) == set(weights[name]), name
        for leaf, a in mine.items():
            assert a.shape == weights[name][leaf].shape, (name, leaf)
    conf = fresh.conf
    assert conf.vertex_inputs["embed_scale"] == ["embed"]
    assert conf.vertices["embed_scale"].scale == pytest.approx(8.0)
    # the post-norms sit between each sub-layer and its add; an expert
    # layer's post-norm reads the sum of its routed and shared parts
    assert conf.vertex_inputs["b0_attn_post_norm"] == ["b0_attn"]
    assert conf.vertex_inputs["b0_attn_add"] == ["embed_scale",
                                                 "b0_attn_post_norm"]
    assert conf.vertex_inputs["b0_ffn_post_norm"] == ["b0_mlp"]
    assert conf.vertex_inputs["b2_ffn_sum"] == ["b2_experts", "b2_shared"]
    assert conf.vertex_inputs["b2_ffn_post_norm"] == ["b2_ffn_sum"]
    assert conf.vertex_inputs["b2_ffn_add"] == ["b2_attn_add",
                                                "b2_ffn_post_norm"]
    assert len(conf.recompute) == 10
    attn = [lc for lc in fresh._layer_confs
            if isinstance(lc, L.GroupedQueryAttentionLayer)]
    assert [(lc.window, lc.rope_theta) for lc in attn] == [
        (8, 1e4), (8, 1e4), (None, None), (8, 1e4), (8, 1e4)]
    assert all(lc.qk_norm and lc.output_gate and lc.eps == 1e-5
               for lc in attn)
    assert "b0_experts" not in weights and "b0_mlp" in weights


# -- the share --------------------------------------------------------------------

def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """One expert layer as a deployment of 2 chips holds it (experts 0-7
    and 8-15, the router, its bias and the shared expert on both): the two
    program layers' routed parts, with the shared expert counted once, are
    the uncut reference's mixer (all 16 held), and through the post-norm
    and the residual its layer's output."""
    d, width = 64, 48
    cut = dict(TINY, num_experts=16, experts_held=list(range(16)))
    z = ref._sizes(cut)
    whole = ref.init_params(5, dict(cut, num_hidden_layers=2,
                                    layer_types=[S, S]))
    p, shared = whole["b1_experts"], whole["b1_shared"]
    gamma = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(8), (d,))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 24, d))
    u = jax.random.normal(jax.random.PRNGKey(7), (2, 24, d))
    routed = jax.jit(lambda p, u: ref.experts(p, u, z, "f32"))(p, u)
    mixer = routed + jax.jit(ref.gated_mlp, static_argnums=2)(shared, u,
                                                              "f32")
    want = x + ref.rms_norm(mixer, gamma, 1e-5)

    parts = []
    for held in (list(range(8)), list(range(8, 16))):
        conf = L.SparseExpertsLayer(
            n_in=d, n_out=d, router_width=16, experts_held=held,
            experts_per_token=3, width=width, activation="silu", gated=True,
            score="sigmoid", select_bias=True, scaling=2.826)
        mine = {"W_router": p["W_router"], "b_select": p["b_select"],
                **{k: p[k][jnp.asarray(held)] for k in ("W1", "W2", "W3")}}
        part = jax.jit(lambda p, u: forward_layer(
            conf, p, u, LayerContext())[0])(mine, u)
        parts.append(part)
        assert _rel(part, routed) > 0.1          # a share alone is not it
    mlp = L.GatedMLPLayer(n_in=d, n_out=d, width=width, activation="silu")
    every_chip, _ = forward_layer(mlp, shared, u, LayerContext())
    summed = parts[0] + parts[1] + every_chip
    assert _rel(summed, mixer) < 1e-5
    norm = L.RMSNorm(n_in=d, eps=1e-5)
    out, _ = forward_layer(norm, {"gamma": gamma}, summed, LayerContext())
    assert _rel(x + out, want) < 1e-6


# -- the attention layer's two new parts --------------------------------------------

def _layer(**kw):
    return L.GroupedQueryAttentionLayer(
        n_in=32, n_out=32, n_heads=4, n_kv_heads=2, head_dim=8,
        weight_init="xavier", **kw)


def _by_hand(p, x, *, norm=False, gate=False, eps=1e-5):
    """The layer's formula in numpy, float64, without positions: each head
    of q and k over its own root mean square times its weight, the causal
    softmax, the output times sigmoid(x Wg), then Wo."""
    x = np.asarray(x, np.float64)
    w = {k: np.asarray(v, np.float64) for k, v in p.items()}
    b, t, _ = x.shape
    q = (x @ w["Wq"]).reshape(b, t, 4, 8)
    k = (x @ w["Wk"]).reshape(b, t, 2, 8)
    v = (x @ w["Wv"]).reshape(b, t, 2, 8)
    if norm:
        rms = lambda a: np.sqrt(np.mean(a * a, axis=-1, keepdims=True) + eps)
        q, k = q / rms(q) * w["q_norm"], k / rms(k) * w["k_norm"]
    k, v = np.repeat(k, 2, axis=2), np.repeat(v, 2, axis=2)
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(8)
    s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
    e = np.exp(s - s.max(-1, keepdims=True))
    o = np.einsum("bhqk,bkhd->bqhd", e / e.sum(-1, keepdims=True), v)
    o = o.reshape(b, t, 32)
    if gate:
        o = o / (1.0 + np.exp(-(x @ w["Wg"])))
    return o @ w["Wo"]


@pytest.mark.parametrize("part", ["qk_norm", "output_gate"])
def test_each_new_part_is_its_formula(part):
    on = {"qk_norm": {"qk_norm": True, "eps": 1e-5},
          "output_gate": {"output_gate": True}}[part]
    conf = _layer(**on)
    key = jax.random.PRNGKey(0)
    p = init_layer_params(key, conf, jnp.float32)
    plain = init_layer_params(key, _layer(), jnp.float32)
    # the parent's leaves are drawn as they were, the new ones behind them
    for k in plain:
        assert jnp.array_equal(p[k], plain[k]), k
    new = {"qk_norm": ("q_norm", "k_norm"), "output_gate": ("Wg",)}[part]
    assert A.gqa_order(conf) == ("Wq", "Wk", "Wv", "Wo") + new
    assert A.gqa_order(_layer()) == ("Wq", "Wk", "Wv", "Wo")
    if part == "qk_norm":
        assert all(jnp.array_equal(p[k], jnp.ones((8,))) for k in new)
        p = dict(p, q_norm=p["q_norm"] * jnp.linspace(0.5, 1.5, 8),
                 k_norm=p["k_norm"] * jnp.linspace(1.5, 0.5, 8))
    else:
        assert p["Wg"].shape == (32, 32)
    # inputs far from unit scale, where a norm shows
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(1), (2, 10, 32))
    got, _ = forward_layer(conf, p, x, LayerContext())
    want = _by_hand(p, x, norm=part == "qk_norm", gate=part == "output_gate")
    assert _rel(got, want) < 1e-5
    # and the part is there: without it the layer is another function
    assert _rel(got, _by_hand(p, x)) > 1e-2


def test_the_norm_comes_before_the_rotation(monkeypatch):
    """With rotary positions the heads are normed first: the rotation keeps
    a head's root mean square, so normed-then-rotated q and k have unit RMS
    (at weights 1) at every position."""
    conf = _layer(qk_norm=True, rope_theta=1e4)
    p = init_layer_params(jax.random.PRNGKey(2), conf, jnp.float32)
    x = 5.0 * jax.random.normal(jax.random.PRNGKey(3), (1, 12, 32))
    seen = {}

    def spy(q, k, v, **kw):
        seen["q"], seen["k"] = q, k
        return A._blocked_attention(q, k, v, **kw)

    monkeypatch.setattr(A, "grouped_query_attention", spy)
    forward_layer(conf, p, x, LayerContext())
    for a in (seen["q"], seen["k"]):
        rms = jnp.sqrt(jnp.mean(jnp.square(a), axis=-1))
        assert jnp.allclose(rms, 1.0, atol=1e-4)


def _counts():
    values = get_registry().scalar_values()
    return {k: v for k, v in values.items() if k.startswith((
        "attention_parts_total", "attention_lowering_total",
        "attention_key_blocks_total"))}


def test_one_trace_counts_the_parts_and_the_key_blocks(monkeypatch):
    monkeypatch.setattr(A, "QUERY_BLOCK", 4)
    net, _ = _net()
    x, _ = _tokens(5)
    before = _counts()
    # the forward pass as the train step runs it (recomputed runs and all)
    forward = lambda p, s, x: net._forward(p, s, [x], training=True,
                                           rng=None)[0]["final_norm"]
    text = jax.jit(forward).lower(net.params_list, net.state_list,
                                  jnp.asarray(x)).as_text(debug_info=True)
    for scope in ("Lb0_attn_groupedqueryattention/qk_norm",
                  "Lb0_attn_groupedqueryattention/output_gate",
                  "Lb0_attn_groupedqueryattention/rope",
                  "Lb0_attn_groupedqueryattention/window_attention",
                  "Lb2_attn_groupedqueryattention/full_attention",
                  "Lb0_attn_post_norm_rmsnorm", "Lb1_ffn_post_norm_rmsnorm",
                  "Lb1_ffn_sum_elementwise", "Lembed_scale_scale"):
        assert scope in text, scope
    after = _counts()
    delta = {k: after[k] - before.get(k, 0.0) for k in after}
    # five layers, once a trace; at blocks of 4 over 24 positions a full
    # layer multiplies 6 x 7 / 2 = 21 pairs, a window of 8 multiplies 15
    # and skips 6 (`key_block_pairs`)
    assert {k: v for k, v in delta.items() if v} == {
        'attention_parts_total{part="qk_norm"}': 5,
        'attention_parts_total{part="output_gate"}': 5,
        'attention_lowering_total{kind="window",positions="rope"}': 4,
        'attention_lowering_total{kind="full",positions="none"}': 1,
        'attention_key_blocks_total{state="multiplied"}': 21 + 4 * 15,
        'attention_key_blocks_total{state="skipped"}': 4 * 6}
    assert A.key_block_pairs(24, 8, 4) == (15, 6)


def test_serde_round_trip_of_the_layer_and_the_graph():
    from deeplearning4j_tpu.nn.conf.graph import (
        ComputationGraphConfiguration,
    )
    from deeplearning4j_tpu.nn.conf.serde import (
        config_from_dict,
        config_to_dict,
    )

    layer = _layer(qk_norm=True, output_gate=True, eps=1e-5, window=4,
                   rope_theta=1e4)
    back = config_from_dict(config_to_dict(layer))
    assert type(back) is type(layer) and back == layer
    # the defaults are the layers the other cells hold
    assert (_layer().qk_norm, _layer().output_gate) == (False, False)
    conf = tiny_afmoe_conf(seq_len=SEQ)
    again = ComputationGraphConfiguration.from_json(conf.to_json())
    assert again.to_json() == conf.to_json()
    assert again.recompute == conf.recompute
    assert again.vertices["embed_scale"].scale == pytest.approx(8.0)


# -- what the factory refuses ---------------------------------------------------------

@pytest.mark.parametrize("kw,match", [
    ({"n_group": 8}, "n_group"), ({"topk_group": 2}, "topk_group"),
    ({"num_expert_groups": 4}, "num_expert_groups"),
    ({"num_limited_groups": 2}, "num_limited_groups"),
    ({"score_func": "softmax"}, "score_func"),
    ({"route_norm": False}, "route_norm"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"layer_types": [S, "linear_attention", F, S, S]}, "linear_attention"),
    ({"layer_types": [S, S]}, "do not say"),
    ({"experts_held": [0, 1]}, "experts_held"),
])
def test_the_factory_refuses_what_it_cannot_build(kw, match):
    with pytest.raises(ValueError, match=match):
        tiny_afmoe_conf(**kw)


def test_without_mup_there_is_no_scale_and_without_shared_no_sum():
    conf = afmoe_conf(num_hidden_layers=2, layer_types=[S, F],
                      num_dense_layers=0, hidden_size=32, vocab_size=64,
                      num_attention_heads=2, num_key_value_heads=1,
                      head_dim=16, sliding_window=4, num_experts=8,
                      num_experts_per_tok=2, num_shared_experts=0,
                      moe_intermediate_size=16, mup_enabled=False, seq_len=8)
    assert "embed_scale" not in conf.vertices
    assert conf.vertex_inputs["b0_attn_norm"] == ["embed"]
    assert "b0_ffn_sum" not in conf.vertices
    assert conf.vertex_inputs["b1_ffn_post_norm"] == ["b1_experts"]


# -- the other decoders' step programs are what they were -----------------------------

# recorded from the parent of this change, in the suite's configuration
# (`jax_default_matmul_precision` "highest"): the whole train step's jaxpr at
# batch 2 x 32 positions, addresses stripped, of the presets whose attention
# layer gained the two optional parts (off in them)
_PARENT_JAXPRS = {
    "tiny_smallthinker": ("b4a334831641d89a", 8718),
    "tiny_deepseek_v3": ("faaa0598ec267cd6", 7213),
    "tiny_nemotron_h": ("bf9b45104e7a82c0", 6508),
}


@pytest.mark.parametrize("name", sorted(_PARENT_JAXPRS))
def test_the_other_decoders_step_programs_are_what_they_were(name):
    from deeplearning4j_tpu.analysis.costmodel import train_step_args
    from deeplearning4j_tpu.models.deepseek_v3 import tiny_deepseek_v3_conf
    from deeplearning4j_tpu.models.nemotron_h import tiny_nemotron_h_conf
    from deeplearning4j_tpu.models.smallthinker import tiny_smallthinker_conf

    conf = {"tiny_smallthinker": lambda: tiny_smallthinker_conf(seq_len=32),
            "tiny_deepseek_v3": lambda: tiny_deepseek_v3_conf(seq_len=32),
            "tiny_nemotron_h": tiny_nemotron_h_conf}[name]()
    step, args = train_step_args(ComputationGraph(conf).init(),
                                 batch_size=2, timesteps=32)
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(step)(*args)))
    digest = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert (digest, text.count("\n")) == _PARENT_JAXPRS[name]

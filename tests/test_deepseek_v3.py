"""What the DeepSeek-V3 family (kanana-2-30b-a3b) forced on the program:
`LatentAttentionLayer` (low-rank keys and values, queries and keys wider
than values, a rotary slice of every head with adjacent pairs),
`GatedMLPLayer`, selection on `score + bias` in `SparseExpertsLayer`, and
`models/deepseek_v3.py`, at tiny sizes on the CPU: one dense layer and two
expert layers, hidden 64, 4 heads of 16 + 8 and 16, a latent of 32, 16
routed experts of which 8 are held, 3 a token, 2 shared. The plain reference
is `benchmark/reference/deepseek_v3.py`, which imports nothing of the
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

from benchmark.reference import deepseek_v3 as ref  # noqa: E402
from benchmark.reference import plain  # noqa: E402
from deeplearning4j_tpu.data.dataset import DataSet  # noqa: E402
from deeplearning4j_tpu.data.iterators import ListDataSetIterator  # noqa: E402
from deeplearning4j_tpu.models.deepseek_v3 import (  # noqa: E402
    deepseek_v3_conf,
    tiny_deepseek_v3_conf,
)
from deeplearning4j_tpu.nn.compgraph import ComputationGraph  # noqa: E402
from deeplearning4j_tpu.nn.conf import layers as L  # noqa: E402
from deeplearning4j_tpu.nn.conf.graph import (  # noqa: E402
    ComputationGraphConfiguration,
)
from deeplearning4j_tpu.nn.conf.serde import (  # noqa: E402
    config_from_dict,
    config_to_dict,
)
from deeplearning4j_tpu.nn.layers import attention as A  # noqa: E402
from deeplearning4j_tpu.nn.layers import experts as X  # noqa: E402
from deeplearning4j_tpu.nn.layers.registry import (  # noqa: E402
    LayerContext,
    forward_layer,
    init_layer_params,
)
from deeplearning4j_tpu.utils.metrics import get_registry  # noqa: E402

# the tiny preset as the reference reads a configuration
TINY = {
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 64,
    "vocab_size": 128, "intermediate_size": 96, "num_attention_heads": 4,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "rope_theta": 1e6, "n_routed_experts": 8,
    "router_width": 16, "experts_held": list(range(8)),
    "num_experts_per_tok": 3, "n_shared_experts": 2,
    "moe_intermediate_size": 48, "routed_scaling_factor": 2.448,
    "rms_norm_eps": 1e-6,
}
ADAM = {"name": "adam", "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
        "epsilon": 1e-8}
SEQ, BATCH = 32, 4


def _tokens(seed=0, batch=BATCH, seq=SEQ, vocab=128):
    ids = np.random.default_rng(seed).integers(0, vocab, (batch, seq + 1),
                                               dtype=np.int32)
    return np.ascontiguousarray(ids[:, :-1]), np.ascontiguousarray(ids[:, 1:])


def _net(precision="f32", seed=3, **kw):
    net = ComputationGraph(tiny_deepseek_v3_conf(
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
# (blocked attention against the literal mask, grouped experts against the
# masked loop). bf16: as for the other two decoder presets, the router's own
# leaves the noisiest.
@pytest.mark.parametrize("precision,loss_tol,grad_tol", [
    ("f32", 2e-6, 2e-4), ("bf16", 3e-4, 0.5)])
def test_fits_first_step_against_the_reference(precision, loss_tol, grad_tol):
    """Loss and every gradient leaf of `fit()`'s first step (read back from
    Adam's first moment, as the harness reads it); the selection bias gets
    no gradient on either side."""
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
            if name == "b_select":
                assert not np.asarray(g).any() and not np.asarray(mine).any()
            else:
                assert _rel(mine, g) < grad_tol, f"{layer}/{name}"


def test_three_adam_steps_through_fit_against_plain_first_steps():
    """The harness's comparison at a tiny size: three optimizer steps of
    `fit()`, one batch a call, against `plain.first_steps` of the reference
    from the same weights: every step's loss, and how far each leaf moved."""
    net, weights = _net(learning_rate=1e-3)
    batches = [_tokens(10 + i) for i in range(3)]
    start = {name: {k: np.asarray(v) for k, v in leaves.items()}
             for name, leaves in zip(net.layer_vertex_names, net.params_list)}
    losses = []
    for x, y in batches:
        net.fit(ListDataSetIterator(DataSet(x, y), BATCH))
        losses.append(float(net._score))
    want = plain.first_steps(lambda p, x, y: ref.loss(p, x, y, TINY, "f32"),
                             weights, batches, ADAM)
    for got, ref_loss in zip(losses, want["losses"]):
        assert abs(got - ref_loss) / ref_loss < 1e-5
    for name, leaves in zip(net.layer_vertex_names, net.params_list):
        for k, v in leaves.items():
            moved = float(np.linalg.norm(np.asarray(v) - start[name][k]))
            ref_moved = want["delta_norms"][f"{name}/{k}"]
            if k == "b_select":
                assert moved == 0.0 and ref_moved == 0.0
            elif want["grad_norms"][f"{name}/{k}"] > 1e-6:
                assert abs(moved - ref_moved) < 0.05 * ref_moved + 1e-7, \
                    (name, k)


def test_the_selection_bias_is_bit_identical_after_the_steps():
    """No gradient reaches `b_select` (the choice is a set of integers), so
    Adam's moments for it stay 0 and no step moves it, bit for bit, while
    everything around it trains."""
    net, _ = _net(learning_rate=1e-2)
    index = {n: i for i, n in enumerate(net.layer_vertex_names)}
    before = {n: np.asarray(net.params_list[index[n]]["b_select"]).copy()
              for n in ("b1_experts", "b2_experts")}
    router = np.asarray(net.params_list[index["b1_experts"]]["W_router"]
                        ).copy()
    assert all(np.any(b != 0) for b in before.values())
    for i in range(5):
        x, y = _tokens(20 + i)
        net.fit(ListDataSetIterator(DataSet(x, y), BATCH))
    for n, was in before.items():
        now = np.asarray(net.params_list[index[n]]["b_select"])
        assert now.dtype == np.float32
        assert now.tobytes() == was.tobytes()
        state = net.upd_state[index[n]]["b_select"]
        assert not np.asarray(state["m"]).any()
        assert not np.asarray(state["v"]).any()
    assert np.any(np.asarray(
        net.params_list[index["b1_experts"]]["W_router"]) != router)


def test_the_references_layers_hold_the_programs_names_and_shapes():
    _, weights = _net()
    fresh = ComputationGraph(tiny_deepseek_v3_conf(seq_len=SEQ)).init()
    for name, mine in zip(fresh.layer_vertex_names, fresh.params_list):
        assert set(mine) == set(weights[name]), name
        for leaf, a in mine.items():
            assert a.shape == weights[name][leaf].shape, (name, leaf)
    assert set(weights) == set(fresh.layer_vertex_names)
    # one dense layer, then expert layers with their shared MLP beside them
    assert "b0_mlp" in weights and "b0_experts" not in weights
    assert {"b1_experts", "b1_shared", "b2_experts", "b2_shared"} \
        <= set(weights)
    assert weights["b1_shared"]["W_gate"].shape == (64, 2 * 48)
    # a fresh net draws the bias from the seed like every weight
    index = fresh.layer_vertex_names.index("b1_experts")
    assert np.asarray(fresh.params_list[index]["b_select"]).std() > 0


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


# -- the share --------------------------------------------------------------------

def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """One expert layer as a deployment of 2 chips holds it (8 of the 16
    routed experts each, the router, its bias and the shared MLP on both):
    the two program layers' parts, with the shared MLP and the residual
    counted once, are what the uncut reference (all 16 held) gives."""
    d, width = 64, 48
    z = ref._sizes(dict(TINY, n_routed_experts=16,
                        experts_held=list(range(16))))
    whole = ref.init_params(5, dict(TINY, num_hidden_layers=2,
                                    n_routed_experts=16,
                                    experts_held=list(range(16))))
    p, shared = whole["b1_experts"], whole["b1_shared"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 24, d))
    u = jax.random.normal(jax.random.PRNGKey(7), (2, 24, d))
    routed = ref.experts(p, u, z, "f32")
    want = x + routed + ref.gated_mlp(shared, u, "f32")

    parts = []
    for held in (list(range(8)), list(range(8, 16))):
        conf = L.SparseExpertsLayer(
            n_in=d, n_out=d, router_width=16, experts_held=held,
            experts_per_token=3, width=width, activation="silu", gated=True,
            score="sigmoid", select_bias=True, scaling=2.448)
        mine = {"W_router": p["W_router"], "b_select": p["b_select"],
                **{k: p[k][jnp.asarray(held)] for k in ("W1", "W2", "W3")}}
        part, _ = forward_layer(conf, mine, u, LayerContext())
        parts.append(part)
        # a share alone is not the layer
        assert _rel(part, routed) > 0.1
        # and it is the reference's own share
        assert _rel(part, ref.experts(mine, u, z, "f32", held=held)) < 1e-5
    mlp = L.GatedMLPLayer(n_in=d, n_out=d, width=2 * width,
                          activation="silu")
    every_chip, _ = forward_layer(mlp, shared, u, LayerContext())
    assert _rel(parts[0] + parts[1], routed) < 1e-5
    assert _rel(parts[0] + parts[1] + every_chip, want - x) < 1e-5
    assert _rel(x + parts[0] + parts[1] + every_chip, want) < 1e-6


# -- selection on score + bias ----------------------------------------------------

def _bias_layer(**kw):
    return L.SparseExpertsLayer(
        n_in=32, n_out=32, router_width=16, experts_held=list(range(8)),
        experts_per_token=3, width=24, activation="silu", gated=True,
        score="sigmoid", scaling=2.448, weight_init="xavier", **kw)


def test_selection_reads_the_bias_and_the_weights_do_not():
    conf = _bias_layer(select_bias=True)
    scores = jax.nn.sigmoid(
        jax.random.normal(jax.random.PRNGKey(0), (40, 16)))
    plain_idx, plain_w = X.route(conf, scores)
    # a planted bias: expert 13 is lifted over everything, expert of the
    # largest score is pushed under everything
    top = int(jnp.argmax(scores[0]))
    b = jnp.zeros((16,)).at[13].set(2.0)
    idx, w = X.route(conf, scores, b)
    assert bool(jnp.all(jnp.any(idx == 13, axis=-1)))
    differs = [set(map(int, a)) != set(map(int, c))
               for a, c in zip(np.asarray(idx), np.asarray(plain_idx))]
    assert sum(differs) > 20
    # the weights are the scores' own at the chosen, over their sum, scaled:
    # the bias is in none of them
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    assert jnp.allclose(
        w, 2.448 * chosen / jnp.sum(chosen, axis=-1, keepdims=True),
        rtol=1e-6)
    assert jnp.allclose(jnp.sum(w, axis=-1), 2.448, rtol=1e-5)
    # against the reference's rule
    ref_idx, ref_w = ref.route(scores, b, 3, 2.448)
    assert jnp.array_equal(idx, ref_idx) and jnp.allclose(w, ref_w)
    # a bias of nought chooses what the scores choose
    zero_idx, zero_w = X.route(conf, scores, jnp.zeros((16,)))
    assert jnp.array_equal(zero_idx, plain_idx)
    assert jnp.allclose(zero_w, plain_w, rtol=1e-6)
    pushed = jnp.zeros((16,)).at[top].set(-2.0)
    assert top not in set(map(int, X.route(conf, scores, pushed)[0][0]))


def test_only_a_layer_that_asks_for_it_holds_the_bias():
    key = jax.random.PRNGKey(0)
    without = init_layer_params(key, _bias_layer(), jnp.float32)
    with_bias = init_layer_params(key, _bias_layer(select_bias=True),
                                  jnp.float32)
    assert "b_select" not in without
    assert set(with_bias) == set(without) | {"b_select"}
    assert with_bias["b_select"].shape == (16,)
    assert with_bias["b_select"].dtype == jnp.float32
    # the other leaves are drawn as they were
    for k in without:
        assert jnp.array_equal(without[k], with_bias[k]), k
    assert X.experts_order(_bias_layer(select_bias=True)) == (
        "W_router", "W1", "W2", "W3", "b_select")
    # the layer's value moves with a planted bias, its weights' rule not
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 32))
    conf = _bias_layer(select_bias=True)
    a, _ = forward_layer(conf, with_bias, u, LayerContext())
    moved = dict(with_bias, b_select=with_bias["b_select"].at[3].set(5.0))
    b, _ = forward_layer(conf, moved, u, LayerContext())
    assert _rel(b, a) > 1e-3
    grads = jax.grad(lambda p: jnp.sum(jnp.sin(forward_layer(
        conf, p, u, LayerContext())[0])))(with_bias)
    assert not np.asarray(grads["b_select"]).any()
    assert np.asarray(grads["W_router"]).any()


# -- the partial, interleaved rotation ------------------------------------------------

def test_partial_interleaved_rotary_against_the_complex_number_formula():
    """Dimensions `2j` and `2j + 1` of the rotary slice are the real and
    imaginary part of one complex number, turned by `exp(1j p theta^(-2j /
    r))`; the program hands the real parts back in front of the imaginary
    ones, the reference leaves them where they were, and the slice before
    `start` passes through."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 11, 3, 24))
    theta, start, r = 1e6, 16, 8
    z = np.asarray(x[..., start::2]) + 1j * np.asarray(x[..., start + 1::2])
    angle = np.arange(11)[:, None] * theta ** (-np.arange(0, r, 2) / r)
    turned = z * np.exp(1j * angle)[None, :, None, :]
    got = A.rope(x, theta, start=start, interleaved=True)
    assert got.shape == x.shape
    assert jnp.array_equal(got[..., :start], x[..., :start])
    apart = np.concatenate([turned.real, turned.imag], axis=-1)
    assert _rel(got[..., start:], jnp.asarray(apart)) < 1e-5
    in_place = np.stack([turned.real, turned.imag], axis=-1).reshape(
        2, 11, 3, r)
    assert _rel(ref.rope_pairs(x[..., start:], theta),
                jnp.asarray(in_place)) < 1e-5
    # the two layouts are one permutation: products of q and k do not see it
    y = jax.random.normal(jax.random.PRNGKey(1), (2, 11, 3, 24))
    dots = lambda f: jnp.einsum("bqhd,bshd->bhqs", f(x), f(y))
    mine = dots(lambda a: A.rope(a, theta, start=start, interleaved=True))
    theirs = dots(lambda a: jnp.concatenate(
        [a[..., :start], ref.rope_pairs(a[..., start:], theta)], axis=-1))
    assert _rel(mine, theirs) < 1e-5
    # position 0 is not turned; lengths are kept; the input's type comes back
    assert jnp.allclose(got[:, 0, :, start:],
                        jnp.concatenate([x[:, 0, :, start::2],
                                         x[:, 0, :, start + 1::2]], -1),
                        atol=1e-6)
    assert jnp.allclose(jnp.linalg.norm(got, axis=-1),
                        jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    assert A.rope(x.astype(jnp.bfloat16), theta, start=start,
                  interleaved=True).dtype == jnp.bfloat16
    # the whole-head half-split form is what it was
    whole = A.rope(x, theta)
    half = np.asarray(x[..., :12]) + 1j * np.asarray(x[..., 12:])
    ang = np.arange(11)[:, None] * theta ** (-np.arange(12) * 2.0 / 24)
    t2 = half * np.exp(1j * ang)[None, :, None, :]
    assert _rel(whole, jnp.asarray(np.concatenate([t2.real, t2.imag], -1))) \
        < 1e-5


# -- the latent layer ----------------------------------------------------------------

def _latent(**kw):
    sizes = dict(n_in=32, n_out=32, n_heads=4, qk_nope_head_dim=8,
                 qk_rope_head_dim=4, v_head_dim=8, kv_lora_rank=16,
                 weight_init="xavier")
    sizes.update(kw)
    conf = L.LatentAttentionLayer(**sizes)
    return conf, init_layer_params(jax.random.PRNGKey(1), conf, jnp.float32)


def test_a_full_rank_latent_without_rotation_is_a_multi_head_layer():
    """With the latent as wide as its input and no rotation the layer is a
    bias-free multi-head attention whose key and value projections are the
    products `Wkv_a Wkv_b`: shown on rows of unit RMS through an orthogonal
    down-projection, which keeps a row's RMS, so that the latent's norm
    between the two factors is the identity."""
    d, H, nope, rot, vd = 32, 4, 8, 4, 8
    conf, params = _latent(kv_lora_rank=d, rope_theta=None)
    # an orthogonal down-projection keeps a row's RMS, so the latent's norm
    # of a unit-RMS row is the identity
    ortho = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(2),
                                            (d, d)))[0]
    params = dict(params, Wkv_a=jnp.concatenate(
        [ortho, params["Wkv_a"][:, d:]], axis=1))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, d))
    x = x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + 1e-6)
    got, _ = forward_layer(conf, params, x, LayerContext())

    kv = (ortho @ params["Wkv_b"]).reshape(d, H, nope + vd)
    wk = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(
            params["Wkv_a"][:, None, d:], (d, H, rot))], axis=-1)
    q = (x @ params["Wq"]).reshape(2, 12, H, nope + rot)
    k = jnp.einsum("btd,dhe->bthe", x, wk)
    v = jnp.einsum("btd,dhe->bthe", x, kv[..., nope:])
    s = jnp.einsum("bqhd,bshd->bhqs", q, k) / np.sqrt(nope + rot)
    seen = jnp.tril(jnp.ones((12, 12), bool))
    o = jnp.einsum("bhqs,bshd->bqhd",
                   jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)
    want = o.reshape(2, 12, H * vd) @ params["Wo"]
    assert _rel(got, want) < 1e-4
    # the same products as a GroupedQueryAttentionLayer of equal head sizes
    # would make: the inner part is the shared one, a group of one
    inner = A.grouped_query_attention(q, k, v, causal=True)
    assert inner.shape == (2, 12, H, vd) and _rel(inner, o) < 1e-5


def test_the_latent_layer_against_the_reference_and_its_scale():
    conf, params = _latent()
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 20, 32))
    z = {"heads": 4, "nope": 8, "rot": 4, "vd": 8, "rank": 16,
         "theta": 1e4, "eps": 1e-6}
    mine = lambda p, x: jnp.sum(jnp.sin(forward_layer(
        conf, p, x, LayerContext())[0]))
    theirs = lambda p, x: jnp.sum(jnp.sin(ref.latent_attention(
        p, x, z, "f32")))
    got, got_g = jax.value_and_grad(mine, argnums=(0, 1))(params, x)
    want, want_g = jax.value_and_grad(theirs, argnums=(0, 1))(params, x)
    assert abs(float(got) - float(want)) < 1e-4 * abs(float(want)) + 1e-4
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        assert _rel(a, b) < 1e-4
    # the rotation is relative: the same token at every position is blind
    # to it, and the layer without it is another layer on other input
    plain_conf, _ = _latent(rope_theta=None)
    same = jnp.broadcast_to(x[:, :1], x.shape)
    a, _ = forward_layer(conf, params, same, LayerContext())
    b, _ = forward_layer(plain_conf, params, same, LayerContext())
    assert _rel(a, b) < 1e-5
    c, _ = forward_layer(plain_conf, params, x, LayerContext())
    assert _rel(forward_layer(conf, params, x, LayerContext())[0], c) > 1e-3


@pytest.mark.parametrize("kind", ["latent", "experts_with_bias"])
def test_a_time_mask_is_refused(kind):
    conf, params = _latent()
    if kind == "latent":
        with pytest.raises(NotImplementedError, match="time mask"):
            forward_layer(conf, params, jnp.ones((2, 6, 32)),
                          LayerContext(mask=jnp.ones((2, 6))))
    else:
        experts = _bias_layer(select_bias=True)
        p = init_layer_params(jax.random.PRNGKey(0), experts, jnp.float32)
        with pytest.raises(NotImplementedError, match="time mask"):
            forward_layer(experts, p, jnp.ones((2, 6, 32)),
                          LayerContext(mask=jnp.ones((2, 6))))


def test_the_gated_mlp_is_its_formula():
    conf = L.GatedMLPLayer(n_in=16, n_out=16, width=24, activation="silu",
                           weight_init="xavier")
    p = init_layer_params(jax.random.PRNGKey(0), conf, jnp.float32)
    assert set(p) == {"W_gate", "W_up", "W_down"}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 16))
    got, state = forward_layer(conf, p, x, LayerContext())
    want = (jax.nn.silu(x @ p["W_gate"]) * (x @ p["W_up"])) @ p["W_down"]
    assert state is None and _rel(got, want) < 1e-5
    assert _rel(got, ref.gated_mlp(p, x, "f32")) < 1e-5
    # bf16 products on a float32 stream hand float32 back
    low, _ = forward_layer(conf, p, x,
                           LayerContext(compute_dtype=jnp.bfloat16))
    assert low.dtype == jnp.float32 and 1e-4 < _rel(low, want) < 3e-2


# -- the graph, the factory, the serde, the scopes -------------------------------------

@pytest.mark.parametrize("conf", [
    L.LatentAttentionLayer(n_in=8, n_out=8, n_heads=2, qk_nope_head_dim=4,
                           qk_rope_head_dim=2, v_head_dim=4, kv_lora_rank=6,
                           rope_theta=1e6, eps=1e-6),
    L.GatedMLPLayer(n_in=8, n_out=8, width=12, activation="silu"),
    L.SparseExpertsLayer(n_in=8, n_out=8, router_width=4, width=4,
                         gated=True, select_bias=True, scaling=2.448),
], ids=["latent_attention", "gated_mlp", "sparse_experts_with_bias"])
def test_serde_round_trip_of_the_new_and_changed_configs(conf):
    back = config_from_dict(config_to_dict(conf))
    assert type(back) is type(conf) and back == conf


def test_serde_round_trip_of_the_graph():
    conf = tiny_deepseek_v3_conf(seq_len=SEQ)
    back = ComputationGraphConfiguration.from_json(conf.to_json())
    assert back.to_json() == conf.to_json()
    attn = back.vertices["b1_attn"].layer
    assert isinstance(attn, L.LatentAttentionLayer)
    assert (attn.n_heads, attn.qk_nope_head_dim, attn.qk_rope_head_dim,
            attn.v_head_dim, attn.kv_lora_rank, attn.rope_theta) \
        == (4, 16, 8, 16, 32, 1e6)
    assert isinstance(back.vertices["b0_mlp"].layer, L.GatedMLPLayer)
    experts = back.vertices["b2_experts"].layer
    assert (experts.gated, experts.score, experts.select_bias,
            experts.scaling, experts.router_input) \
        == (True, "sigmoid", True, 2.448, False)
    assert back.vertex_inputs["b2_ffn_add"] == [
        "b2_attn_add", "b2_experts", "b2_shared"]
    assert back.vertex_inputs["b0_ffn_add"] == ["b0_attn_add", "b0_mlp"]
    assert back.recompute == conf.recompute and len(back.recompute) == 6
    # the defaults are the layers the other cells hold
    old = L.SparseExpertsLayer(n_out=8, router_width=4, width=4)
    assert old.select_bias is False


def test_recomputation_changes_nothing():
    net, _ = _net()
    plain_net, _ = _net(recompute=False)
    runs = net._recompute_runs()
    assert len(runs) == 6
    second = [r for r in runs.values() if "b1_experts" in r["names"]][0]
    assert second["names"] == ["b1_ffn_norm", "b1_experts", "b1_shared",
                               "b1_ffn_add"]
    assert second["inputs"] == ["b1_attn_add"]
    assert second["exits"] == ["b1_ffn_add"]
    x, y = _tokens(4)

    def loss_and_grads(n):
        f = lambda params: n._loss(params, n.state_list, [jnp.asarray(x)],
                                   [jnp.asarray(y)], None, None, None)[0]
        return jax.value_and_grad(f)(n.params_list)

    a, ga = loss_and_grads(net)
    b, gb = loss_and_grads(plain_net)
    assert abs(float(a) - float(b)) < 1e-6
    for u, v in zip(jax.tree_util.tree_leaves(ga),
                    jax.tree_util.tree_leaves(gb)):
        assert _rel(u, v) < 1e-5 or not np.asarray(v).any()


def _lowering_counts():
    values = get_registry().scalar_values()
    return {k: v for k, v in values.items()
            if k.startswith(("attention_lowering_total",
                             "attention_key_blocks_total"))}


def test_layer_scopes_and_counters_name_the_new_kinds(monkeypatch):
    monkeypatch.setattr(A, "QUERY_BLOCK", 4)
    net, _ = _net()
    x, y = _tokens(5)
    before = _lowering_counts()
    text = net._build_train_step().lower(
        net.params_list, net.state_list, net.upd_state,
        ([jnp.asarray(x)], [jnp.asarray(y)], [None], [None]),
        jnp.float32(1e-3), jnp.float32(0.0), jax.random.PRNGKey(0)
    ).as_text(debug_info=True)
    for scope in ("Lb0_attn_latentattention/rope",
                  "Lb0_attn_latentattention/latent_kv",
                  "Lb0_attn_latentattention/latent_attention",
                  "Lb0_mlp_gatedmlp", "Lb1_shared_gatedmlp",
                  "Lb1_experts_sparseexperts/router",
                  "Lb1_experts_sparseexperts/experts",
                  "Lb2_attn_latentattention/latent_attention"):
        assert scope in text, scope
    assert "groupedqueryattention" not in text
    assert "shared_expert" not in text       # that is the Nemotron layer's
    after = _lowering_counts()
    delta = {k: after[k] - before.get(k, 0.0) for k in after}
    # three layers, once a trace; at blocks of 4 over 32 positions a full
    # layer multiplies 8 x 9 / 2 = 36 pairs
    assert {k: v for k, v in delta.items() if v} == {
        'attention_lowering_total{kind="full",positions="rope_partial"}': 3,
        'attention_key_blocks_total{state="multiplied"}': 3 * 36}


def test_the_factory_refuses_what_it_cannot_build():
    with pytest.raises(ValueError, match="q_lora_rank"):
        deepseek_v3_conf(q_lora_rank=1536, seq_len=8)
    with pytest.raises(ValueError, match="group-limited"):
        deepseek_v3_conf(n_group=8, topk_group=4, seq_len=8)
    with pytest.raises(ValueError, match="rope_interleave"):
        deepseek_v3_conf(rope_interleave=False, seq_len=8)
    with pytest.raises(ValueError, match="rope_scaling"):
        deepseek_v3_conf(rope_scaling={"type": "yarn", "factor": 40},
                         seq_len=8)
    with pytest.raises(ValueError, match="experts_held"):
        deepseek_v3_conf(experts_held=[0, 1], seq_len=8)
    with pytest.raises(ValueError, match="sigmoid"):
        deepseek_v3_conf(scoring_func="softmax", seq_len=8)
    with pytest.raises(ValueError, match="moe_layer_freq"):
        deepseek_v3_conf(moe_layer_freq=2, seq_len=8)
    with pytest.raises(ValueError, match="pairs"):
        init_layer_params(jax.random.PRNGKey(0), L.LatentAttentionLayer(
            n_in=8, n_out=8, qk_rope_head_dim=3), jnp.float32)

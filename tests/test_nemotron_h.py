"""The decoder-block vocabulary (token embedding, RMS norm, grouped-query
attention, the Mamba-2 mixer, the sparse-expert layer), integer-label
cross-entropy, recomputation and the int32 feed, at tiny sizes on the CPU:
hidden 64, 2 key-value heads, 16 routed experts of which 8 are held, state
16, chunk 8, sequences of 32. The plain reference is
`benchmark/reference/nemotron_h.py`, which imports nothing of the program."""

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

from benchmark.reference import nemotron_h as ref  # noqa: E402
from deeplearning4j_tpu.data.dataset import DataSet  # noqa: E402
from deeplearning4j_tpu.data.iterators import ListDataSetIterator  # noqa: E402
from deeplearning4j_tpu.models.nemotron_h import (  # noqa: E402
    nemotron_h_conf,
    tiny_nemotron_h_conf,
)
from deeplearning4j_tpu.nn.compgraph import ComputationGraph  # noqa: E402
from deeplearning4j_tpu.nn.conf import layers as L  # noqa: E402
from deeplearning4j_tpu.nn.layers import experts as X  # noqa: E402
from deeplearning4j_tpu.nn.layers import ssm  # noqa: E402
from deeplearning4j_tpu.nn.layers.registry import (  # noqa: E402
    LayerContext,
    forward_layer,
    init_layer_params,
    init_layer_state,
)
from deeplearning4j_tpu.ops.losses import (  # noqa: E402
    LossFunction,
    loss_value,
    sparse_head_loss,
)
from deeplearning4j_tpu.utils.metrics import get_registry  # noqa: E402

# the tiny preset as the reference reads a configuration
TINY = {
    "num_hidden_layers": 4, "hybrid_override_pattern": "ME*M",
    "hidden_size": 64, "vocab_size": 128,
    "mamba_num_heads": 8, "mamba_head_dim": 16, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "chunk_size": 8,
    "time_step_min": 1e-3, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "n_routed_experts": 8, "router_width": 16,
    "experts_held": list(range(8)), "num_experts_per_tok": 3,
    "moe_intermediate_size": 48, "moe_shared_expert_intermediate_size": 96,
    "routed_scaling_factor": 2.5, "layer_norm_epsilon": 1e-5,
}
SEQ, BATCH = 32, 4


def _tokens(seed=0, batch=BATCH, seq=SEQ, vocab=128):
    ids = np.random.default_rng(seed).integers(0, vocab, (batch, seq + 1),
                                               dtype=np.int32)
    return np.ascontiguousarray(ids[:, :-1]), np.ascontiguousarray(ids[:, 1:])


def _net(precision="f32", seed=3, **kw):
    net = ComputationGraph(tiny_nemotron_h_conf(precision=precision,
                                                **kw)).init()
    weights = ref.init_params(seed, TINY)
    net.params_list = [dict(weights[name]) for name in
                       net.layer_vertex_names]
    return net, weights


def _program_loss_and_grads(net, x, y):
    def loss(params):
        return net._loss(params, net.state_list, [jnp.asarray(x)],
                         [jnp.asarray(y)], None, None, None)[0]

    value, grads = jax.value_and_grad(loss)(net.params_list)
    return value, dict(zip(net.layer_vertex_names, grads))


def _rel(a, b):
    return float(jnp.linalg.norm((a - b).ravel())
                 / jnp.maximum(jnp.linalg.norm(b.ravel()), 1e-30))


# -- the whole net against the plain reference ------------------------------------

# f32: both sides are float32 at HIGHEST on the CPU; what is left is the order
# of the sums (chunked scan against the literal one, grouped experts against
# the masked loop). bf16: the program rounds the operands of every matrix
# product to 8 bits of mantissa (relative 2**-9 an operand), and a leaf's
# gradient is a sum over thousands of such products: the reference's own bf16
# witness reads 2e-3 on the worst leaf here, so 3e-2 is the cell's order of
# tolerance and a precision below bf16 (fp8: 2**-4) is ten times outside it.
# This test is stricter than the cell's comparison, the norm of each leaf's
# DIFFERENCE and not the difference of norms: an expert's W1 sums over the two
# dozen tokens it meets here, and a token whose third and fourth router scores
# lie within round-off goes to another expert, so its worst leaf reads 0.08.
@pytest.mark.parametrize("precision,loss_tol,grad_tol", [
    ("f32", 2e-6, 2e-4), ("bf16", 2e-4, 0.15)])
def test_loss_and_every_gradient_leaf_against_the_reference(
        precision, loss_tol, grad_tol):
    net, weights = _net(precision)
    x, y = _tokens(1)
    got, got_grads = _program_loss_and_grads(net, x, y)
    want, want_grads = jax.value_and_grad(ref.loss)(weights, x, y, TINY,
                                                    "f32")
    assert abs(float(got) - float(want)) / float(want) < loss_tol
    assert set(got_grads) == set(want_grads)
    for layer, leaves in want_grads.items():
        assert set(got_grads[layer]) == set(leaves)
        for name, g in leaves.items():
            assert got_grads[layer][name].shape == g.shape
            assert _rel(got_grads[layer][name], g) < grad_tol, \
                f"{layer}/{name}"


def test_the_references_layers_hold_the_programs_names_and_shapes():
    net, weights = _net()
    fresh = ComputationGraph(tiny_nemotron_h_conf()).init()
    for name, mine in zip(fresh.layer_vertex_names, fresh.params_list):
        assert set(mine) == set(weights[name]), name
        for leaf, a in mine.items():
            assert a.shape == weights[name][leaf].shape, (name, leaf)
    assert set(weights) == set(fresh.layer_vertex_names)


# -- the chunked scan against the literal recurrence ------------------------------

def _scan_inputs(t, seed=0, b=2, H=4, P=8, G=2, N=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, t, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, H)) - 1.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.0))
    B = jax.random.normal(ks[3], (b, t, G, N))
    C = jax.random.normal(ks[4], (b, t, G, N))
    return x, dt, A, B, C


def _literal(x, dt, A, B, C, chunk=8):
    """The plain reference's recurrence, one position at a time; it takes B
    and C a head."""
    H, G = x.shape[2], B.shape[2]
    return ref.recurrence(x, dt, A, jnp.repeat(B, H // G, axis=2),
                          jnp.repeat(C, H // G, axis=2), chunk=chunk)


@pytest.mark.parametrize("t,chunk", [(32, 8), (29, 8), (5, 8), (16, 16),
                                     (33, 4)],
                         ids=["multiple", "not_a_multiple", "under_a_chunk",
                              "one_chunk", "one_over"])
def test_chunked_scan_against_the_literal_recurrence(t, chunk):
    x, dt, A, B, C = _scan_inputs(t)
    want = _literal(x, dt, A, B, C)
    got = ssm.ssd_chunked(x, dt, A, B, C, chunk)
    assert got.shape == want.shape == x.shape
    assert _rel(got, want) < 1e-5
    # the reference's chunks only bound what its backward pass recomputes
    assert _rel(_literal(x, dt, A, B, C, chunk=t + 3), want) < 1e-6


def test_chunked_scan_gradients_against_the_literal_recurrence():
    x, dt, A, B, C = _scan_inputs(19, seed=4)
    f = lambda fn: jax.grad(
        lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2, 3, 4))
    want = f(_literal)(x, dt, A, B, C)
    got = f(lambda *a: ssm.ssd_chunked(*a, 8))(x, dt, A, B, C)
    for g, w in zip(got, want):
        assert _rel(g, w) < 1e-4


# -- the mixer's two elementwise stages, gradients written by hand -----------------

# (batch, positions, channels, conv width) and (batch, positions, heads, head
# size, groups): positions under the conv's width + 1 and over it, nothing a
# multiple of 8 or 128, one group, one batch row
CONV_CASES = {
    "t29_k4": (2, 29, 24, 4), "t5_k4": (2, 5, 24, 4), "t29_k2": (2, 29, 24, 2),
    "t5_k2_batch1": (1, 5, 40, 2), "t3_under_k4": (1, 3, 24, 4),
    "c136_over_a_lane_tile": (2, 29, 136, 4)}
GATE_CASES = {
    "g1": (2, 29, 6, 4, 1), "g3": (2, 29, 6, 4, 3), "g8": (2, 29, 8, 5, 8),
    "g3_t5_batch1": (1, 5, 6, 4, 3), "g8_one_head_a_group": (2, 5, 8, 3, 8),
    "c136_over_a_lane_tile": (1, 29, 17, 8, 1)}


def _conv_stage(shape, dtype=jnp.float32):
    b, t, c, k = shape
    ks = jax.random.split(jax.random.PRNGKey(b + t + c + k), 3)
    args = (jax.random.normal(ks[0], (b, t, c)).astype(dtype),
            jax.random.normal(ks[1], (k, c)) * 0.5,
            jax.random.normal(ks[2], (c,)) * 0.1)
    literal = lambda x, w, c: jax.nn.silu(ref.causal_conv1d(
        x.astype(jnp.float32), w, c)).astype(x.dtype)
    return ssm.conv_silu, literal, args


def _gate_stage(shape, dtype=jnp.float32):
    from deeplearning4j_tpu.nn.layers.norm import rms_normalize

    b, t, H, P, G = shape
    ks = jax.random.split(jax.random.PRNGKey(b + t + H + P + G), 5)
    c = H * P
    args = (jax.random.normal(ks[0], (b, t, c)),
            jax.random.normal(ks[1], (b, t, c)).astype(dtype),
            jax.random.normal(ks[2], (b, t, c)).astype(dtype),
            1.0 + 0.3 * jax.random.normal(ks[3], (H,)),
            1.0 + 0.3 * jax.random.normal(ks[4], (c,)))

    def literal(y, x, z, D, gamma):   # the head-shaped view autodiff took
        y = y.reshape(b, t, H, P) + D[:, None] * x.reshape(
            b, t, H, P).astype(jnp.float32)
        v = y.reshape(b, t, c) * jax.nn.silu(z.astype(jnp.float32))
        return rms_normalize(v, gamma, 1e-5, G).astype(z.dtype)

    stage = lambda *a: ssm.gate_norm(*a, 1e-5, G)
    return stage, literal, args


STAGE_CASES = {**{f"conv_silu-{k}": (_conv_stage, v)
                  for k, v in CONV_CASES.items()},
               **{f"gate_norm-{k}": (_gate_stage, v)
                  for k, v in GATE_CASES.items()}}


@pytest.mark.parametrize("case", sorted(STAGE_CASES))
def test_a_stage_and_every_gradient_against_autodiff_of_its_formula(case):
    make, shape = STAGE_CASES[case]
    stage, literal, args = make(shape)
    want, pull_want = jax.vjp(literal, *args)
    got, pull_got = jax.vjp(stage, *args)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert _rel(got, want) < 1e-6
    ct = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    for g, w in zip(pull_got(ct), pull_want(ct)):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert _rel(g, w) < 2e-5, case


def _float_operands_of_arithmetic(jaxpr, found):
    """Dtypes that the arithmetic of a jaxpr (everything but moving,
    slicing and converting) takes its floating operands in."""
    moving = {"convert_element_type", "pad", "slice", "reshape", "squeeze",
              "broadcast_in_dim", "concatenate", "transpose", "copy",
              "select_n", "iota", "eq", "jit", "pjit", "custom_vjp_call",
              "custom_jvp_call"}
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _float_operands_of_arithmetic(sub, found)
        if eqn.primitive.name not in moving:
            found |= {str(v.aval.dtype) for v in eqn.invars
                      if hasattr(v.aval, "dtype")
                      and jnp.issubdtype(v.aval.dtype, jnp.floating)}
    return found


@pytest.mark.parametrize("make,shape", [(_conv_stage, CONV_CASES["t29_k4"]),
                                        (_gate_stage, GATE_CASES["g3"])],
                         ids=["conv_silu", "gate_norm"])
def test_a_stage_stores_what_it_stored_and_computes_in_float32(make, shape):
    """bf16 in, bf16 out and bf16 cotangents for what is stored in bf16
    (`xBC`, `z`, `x`); float32 for the scan's `y`, its cotangent and every
    parameter gradient; no arithmetic on bf16 operands inside."""
    stage, literal, args = make(shape, jnp.bfloat16)
    out, pull = jax.vjp(stage, *args)
    assert out.dtype == jnp.bfloat16
    grads = pull(jnp.ones_like(out))
    assert [g.dtype for g in grads] == [a.dtype for a in args]
    stored = [a.dtype for a in args if a.ndim == 3]
    assert stored in ([jnp.bfloat16],
                      [jnp.float32, jnp.bfloat16, jnp.bfloat16])
    assert all(a.dtype == jnp.float32 for a in args if a.ndim < 3)
    both = jax.make_jaxpr(lambda *a: jax.vjp(stage, *a)[1](
        jnp.ones_like(out)))(*args)
    assert _float_operands_of_arithmetic(both.jaxpr, set()) == {"float32"}
    # and the rounding of the stored tensors is all that separates it from
    # the formula
    want, pull_want = jax.vjp(literal, *args)
    assert _rel(out.astype(jnp.float32), want.astype(jnp.float32)) < 1e-2
    for g, w in zip(grads, pull_want(jnp.ones_like(out))):
        assert _rel(g.astype(jnp.float32), w.astype(jnp.float32)) < 2e-2


def _stage_counts():
    values = get_registry().scalar_values()
    return tuple(int(values.get(
        f'mamba2_stage_lowering_total{{stage="{stage}",kind="fused_vjp"}}',
        0)) for stage in ("conv_silu", "gate_norm"))


def test_each_stage_is_counted_once_a_mixer_a_trace():
    """The cell's nine blocks (four of them mixers) at test width count 4
    and 4, recomputed or not; a net without mixers counts nothing."""
    from deeplearning4j_tpu.analysis.costmodel import train_step_args
    from deeplearning4j_tpu.models.vgg16 import vgg16_conf
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    def traced(net):
        before = _stage_counts()
        step, args = train_step_args(net, batch_size=2)
        jax.make_jaxpr(step)(*args)
        return tuple(a - b for a, b in zip(_stage_counts(), before))

    cell = ComputationGraph(tiny_nemotron_h_conf(
        hybrid_override_pattern="MEMEM*EME")).init()
    assert traced(cell) == (4, 4)
    vgg = MultiLayerNetwork(vgg16_conf(1000, 32, "bf16")).init()
    assert traced(vgg) == (0, 0)


def test_a_position_never_reads_a_later_one():
    """Causality of the mixer, the conv included: changing the tokens from
    position 20 on leaves the first 20 outputs as they were."""
    conf = L.Mamba2Layer(n_in=16, n_out=16, n_heads=4, head_dim=8,
                         state_size=8, n_groups=2, chunk_size=8,
                         weight_init="xavier")
    params = init_layer_params(jax.random.PRNGKey(0), conf, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 16))
    x2 = x.at[:, 20:].set(0.5)
    y, _ = forward_layer(conf, params, x, LayerContext())
    y2, _ = forward_layer(conf, params, x2, LayerContext())
    assert jnp.allclose(y[:, :20], y2[:, :20], atol=1e-6)
    assert not jnp.allclose(y[:, 20:], y2[:, 20:], atol=1e-3)


# -- the share of the experts ------------------------------------------------------

def _expert_layer(held, seed=0, factor=8.0, **kw):
    conf = L.SparseExpertsLayer(**dict(dict(
        n_in=32, n_out=32, router_width=16, experts_held=held,
        experts_per_token=3, width=24, shared_width=40, scaling=2.5,
        activation="relu2", capacity_factor=factor, weight_init="xavier"),
        **kw))
    return conf, init_layer_params(jax.random.PRNGKey(seed), conf,
                                   jnp.float32)


def _share_of(whole, held):
    """The leaves of the layer that holds every expert, cut to `held`."""
    rows = jnp.asarray(held)
    return {k: v[rows] if k in ("W1", "W2", "W3") else v
            for k, v in whole.items()}


@pytest.mark.parametrize("score,gated", [
    ("sigmoid", False), ("sigmoid", True), ("softmax", False),
    ("softmax", True)])
def test_the_shares_add_up_to_the_uncut_layer(score, gated):
    """16 routed experts, 8 held by each of two chips: the routed parts that
    both shares give, with the shared expert counted once, add up to what
    the layer that holds all 16 gives, under either score function and
    for two-matrix and gated experts alike."""
    kind = dict(score=score, gated=gated)
    whole_conf, whole = _expert_layer(list(range(16)), **kind)
    assert ("W3" in whole) == gated
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 32))
    want, _ = forward_layer(whole_conf, whole, x, LayerContext())
    shared = (X.apply_activation("relu2", x @ whole["Ws1"])) @ whole["Ws2"]
    total = -shared          # both shares compute it: count it once
    for held in (list(range(8)), list(range(8, 16))):
        conf, _ = _expert_layer(held, **kind)
        part, _ = forward_layer(conf, _share_of(whole, held), x,
                                LayerContext())
        total = total + part
    assert _rel(total, want) < 1e-5
    if (score, gated) == ("softmax", True):
        _the_gated_softmax_shares_from_their_reference()
    if (score, gated) != ("sigmoid", False):
        return
    # and the same from the plain reference, its shares by configuration
    config = dict(TINY, hidden_size=32, moe_intermediate_size=24,
                  moe_shared_expert_intermediate_size=40)
    z = lambda held: ref._sizes(dict(config, experts_held=held,
                                     n_routed_experts=len(held)))
    theirs = ref.experts(whole, x, z(list(range(16))), config, "f32")
    assert _rel(theirs, want) < 1e-5
    halves = sum(ref.experts(
        dict(whole, W1=whole["W1"][jnp.asarray(h)],
             W2=whole["W2"][jnp.asarray(h)]), x, z(h), config, "f32",
        with_shared=(h[0] == 0)) for h in (list(range(8)),
                                           list(range(8, 16))))
    assert _rel(halves, want) < 1e-5


def _the_gated_softmax_shares_from_their_reference():
    """The eight shares of a gated, softmax-routed layer without a shared
    expert (64 routed experts, 8 a chip, the logits from another input than
    the experts read) add up to the uncut layer, in the program and in
    `benchmark/reference/smallthinker.py` alike."""
    from benchmark.reference import smallthinker as st

    kind = dict(router_width=64, experts_per_token=6, shared_width=0,
                scaling=1.0, activation="relu", score="softmax", gated=True,
                router_input=True)
    whole_conf, whole = _expert_layer(list(range(64)), **kind)
    assert set(whole) == {"W1", "W2", "W3"}
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 32))
    logits = jax.random.normal(jax.random.PRNGKey(6), (2, 24, 64))
    ctx = LayerContext(extra_inputs=(logits,))
    want, _ = forward_layer(whole_conf, whole, u, ctx)
    config = {"num_hidden_layers": 0, "sliding_window_layout": [],
              "rope_layout": [], "hidden_size": 32, "vocab_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "sliding_window_size": 8, "rope_theta": 1e4,
              "moe_ffn_hidden_size": 24, "router_width": 64,
              "moe_num_active_primary_experts": 6, "rms_norm_eps": 1e-6}
    z = lambda held: st._sizes(dict(config, experts_held=held,
                                    moe_num_primary_experts=len(held)))
    assert _rel(st.experts(whole, u, logits, z(list(range(64))), "f32"),
                want) < 1e-5
    mine = theirs = 0.0
    for chip in range(8):
        held = list(range(8 * chip, 8 * chip + 8))
        conf, _ = _expert_layer(held, **kind)
        mine = mine + forward_layer(conf, _share_of(whole, held), u, ctx)[0]
        theirs = theirs + st.experts(_share_of(whole, held), u, logits,
                                     z(held), "f32")
    assert _rel(mine, want) < 1e-5 and _rel(theirs, want) < 1e-5


def test_router_weights_are_normalised_over_all_the_chosen():
    conf, params = _expert_layer([0, 1])
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(2),
                                              (10, 16)))
    idx, w = X.route(conf, scores)
    assert idx.shape == w.shape == (10, 3)
    assert jnp.allclose(jnp.sum(w, axis=-1), 2.5, atol=1e-5)
    assert jnp.all(jnp.take_along_axis(scores, idx, 1)[:, :1]
                   == jnp.max(scores, axis=1, keepdims=True))


def test_an_overflowing_capacity_is_counted_and_nothing_is_dropped():
    """Every token sent to the same experts, a buffer of a quarter of the
    uniform mean: the step takes the exact path, its result and gradients
    are the roomy buffer's, and the books say how far the load went."""
    tight, params = _expert_layer(list(range(8)), factor=0.25)
    roomy, _ = _expert_layer(list(range(8)), factor=8.0)
    state = init_layer_state(tight, jnp.float32)
    assert X.expert_capacity(tight, 256) == 128
    assert X.expert_capacity(roomy, 256) == 256     # never more than tokens
    x = jax.random.normal(jax.random.PRNGKey(7), (4, 64, 32))
    same = x.at[:, :, :].set(x[:1, :1])
    for inputs in (same, x):
        f = lambda conf: lambda p, a: jnp.sum(jnp.sin(forward_layer(
            conf, p, a, LayerContext(state=state))[0]))
        want, want_g = jax.value_and_grad(f(roomy), argnums=(0, 1))(
            params, inputs)
        got, got_g = jax.value_and_grad(f(tight), argnums=(0, 1))(
            params, inputs)
        assert abs(float(got) - float(want)) < 1e-3 * abs(float(want)) + 1e-4
        for k in want_g[0]:
            assert _rel(got_g[0][k], want_g[0][k]) < 1e-4, k
        assert _rel(got_g[1], want_g[1]) < 1e-4
    _, books = forward_layer(tight, params, same, LayerContext(state=state))
    assert int(books["routed"].sum()) == 256 * 3
    held = int(books["routed"][:8].sum())
    assert held > 0 and int(books["peak"]) == 256
    assert int(books["overflow"]) == held - 128 * (held // 256) > 0
    assert int(books["rows"]) == 128
    _, books = forward_layer(roomy, params, x, LayerContext(state=state))
    assert int(books["overflow"]) == 0 and 0 < int(books["peak"]) <= 256
    # the books add up over steps: counts summed, loads at their largest
    _, twice = forward_layer(tight, params, same, LayerContext(state=books))
    assert int(twice["routed"].sum()) == 2 * 256 * 3
    assert int(twice["overflow"]) == held - 128 * (held // 256)
    assert int(twice["peak"]) == 256 and int(twice["rows"]) == 256


def _skewed(skew):
    """The layer's weights and tokens under one routing: feature 0 of every
    token is 5 and the router's row 0 is 0, or -10 for an expert that no
    token is to choose. 16 routed experts, 8 held."""
    held = [9, 2, 3, 12, 5, 0, 15, 7] if skew == "held_in_any_order" \
        else list(range(8))
    conf, params = _expert_layer(held, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 48, 32))
    x = x.at[..., 0].set(5.0)
    if skew == "same_experts":
        x = x.at[:, :, :].set(x[:1, :1])
    unchosen = {"one_held_idle": [3], "none_held_chosen": held}.get(skew, [])
    router = params["W_router"].at[0].set(0.0).at[
        0, jnp.asarray(unchosen, jnp.int32)].set(-10.0)
    return conf, dict(params, W_router=router), x


@pytest.mark.parametrize("factor", [8.0, 0.25])
@pytest.mark.parametrize("skew", ["uniform", "same_experts", "one_held_idle",
                                  "none_held_chosen", "held_in_any_order"])
def test_the_layer_is_the_reference_under_any_skew(skew, factor, monkeypatch):
    """Value and gradients against the plain reference's masked loop, with
    buffers that hold everything (8.0: the grouped path) and buffers that
    do not (0.25: the exact path), whatever the routing sends the held
    experts and in whatever order they are held."""
    monkeypatch.setattr(X, "_ROWS", 8)
    conf, params, x = _skewed(skew)
    conf.capacity_factor = factor
    held = conf.held()
    config = dict(TINY, hidden_size=32, moe_intermediate_size=24,
                  moe_shared_expert_intermediate_size=40, experts_held=held)
    z = ref._sizes(config)
    state = init_layer_state(conf, jnp.float32)
    _, books = forward_layer(conf, params, x, LayerContext(state=state))
    loads = np.asarray(books["routed"])[held]
    assert {"uniform": loads.min() > 0, "held_in_any_order": loads.min() > 0,
            "same_experts": 0 < (loads > 0).sum() <= 3,
            "one_held_idle": loads[3] == 0 and loads.sum() > 0,
            "none_held_chosen": loads.sum() == 0}[skew]
    cap = X.expert_capacity(conf, 96)
    assert cap == (96 if factor == 8.0 else 8) == int(books["rows"])
    assert int(books["peak"]) == loads.max()
    assert int(books["overflow"]) == np.maximum(loads - cap, 0).sum()
    assert (int(books["overflow"]) > 0) == (factor < 1 and loads.sum() > 0)
    mine = lambda p, a: jnp.sum(jnp.sin(forward_layer(
        conf, p, a, LayerContext())[0]))
    theirs = lambda p, a: jnp.sum(jnp.sin(ref.experts(p, a, z, config,
                                                      "f32")))
    got, got_g = jax.value_and_grad(mine, argnums=(0, 1))(params, x)
    want, want_g = jax.value_and_grad(theirs, argnums=(0, 1))(params, x)
    assert abs(float(got) - float(want)) < 1e-4 * abs(float(want)) + 1e-4
    for k in want_g[0]:
        assert jnp.linalg.norm(got_g[0][k] - want_g[0][k]) \
            < 1e-4 * jnp.linalg.norm(want_g[0][k]) + 1e-5, k
    assert _rel(got_g[1], want_g[1]) < 1e-4


def test_the_books_reach_the_registry_at_the_end_of_fit():
    net, _ = _net()
    x, y = _tokens(2)
    before = get_registry().scalar_values()
    net.fit(ListDataSetIterator(DataSet(x, y), BATCH), epochs=2)
    after = get_registry().scalar_values()
    moved = lambda k: after.get(k, 0.0) - before.get(k, 0.0)
    total = moved('experts_assignments_total{held="1"}') \
        + moved('experts_assignments_total{held="0"}')
    assert total == 2 * BATCH * SEQ * 3      # one expert layer, two steps
    assert moved('experts_assignments_total{held="1"}') > 0
    assert moved("experts_overflow_total") == 0
    assert after["experts_load_max_over_mean"] >= 1.0
    # how near the fullest held expert came to its buffer's 128 rows
    assert 0.0 < after["experts_buffer_fill"] <= 1.0
    assert after["experts_buffer_fill"] == after["experts_peak_load"] / 128
    # published means zeroed: the next fit counts from nought
    (slot,), = net._book_slots.values()
    assert int(net.state_list[slot]["routed"].sum()) == 0


def test_a_net_without_expert_layers_publishes_nothing():
    from deeplearning4j_tpu.models.resnet import tiny_resnet_conf

    net = ComputationGraph(tiny_resnet_conf()).init()
    assert net._publish_layer_books() is None
    assert net._book_slots == {}
    assert net._publish_layer_books() is None


# -- attention ----------------------------------------------------------------------

def test_grouped_query_attention_with_all_heads_is_self_attention():
    old = L.SelfAttentionLayer(n_in=24, n_out=32, n_heads=4, causal=True,
                               projection_bias=False, activation="identity",
                               weight_init="xavier")
    new = L.GroupedQueryAttentionLayer(n_in=24, n_out=32, n_heads=4,
                                       n_kv_heads=4, head_dim=8, causal=True,
                                       weight_init="xavier")
    params = init_layer_params(jax.random.PRNGKey(0), old, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 24))
    want, _ = forward_layer(old, params, x, LayerContext())
    got, _ = forward_layer(new, params, x, LayerContext())
    assert _rel(got, want) < 1e-5


@pytest.mark.parametrize("query_block", [4, 5, 64])
def test_query_blocks_do_not_change_the_result(query_block, monkeypatch):
    from deeplearning4j_tpu.nn.layers import attention

    conf = L.GroupedQueryAttentionLayer(
        n_in=16, n_out=16, n_heads=4, n_kv_heads=2, head_dim=8,
        weight_init="xavier")
    params = init_layer_params(jax.random.PRNGKey(0), conf, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 13, 16))
    f = lambda p: jnp.sum(jnp.sin(forward_layer(
        conf, p, x, LayerContext())[0]))
    want, want_g = jax.value_and_grad(f)(params)     # 13 queries: one block
    monkeypatch.setattr(attention, "QUERY_BLOCK", query_block)
    got, got_g = jax.value_and_grad(f)(params)
    assert abs(float(got) - float(want)) < 1e-4
    for k in want_g:
        assert _rel(got_g[k], want_g[k]) < 1e-5
    # key-value head j serves query heads 2j and 2j + 1: against the
    # reference's attention with repeated heads
    z = {"heads": 4, "kv": 2, "hd": 8}
    theirs = ref.attention(params, x, z, "f32")
    assert _rel(forward_layer(conf, params, x, LayerContext())[0],
                theirs) < 1e-5


@pytest.mark.parametrize("conf", [
    L.Mamba2Layer(n_in=16, n_out=16, n_heads=4, head_dim=8, state_size=8,
                  n_groups=2, chunk_size=8, weight_init="xavier"),
    L.SparseExpertsLayer(n_in=16, n_out=16, router_width=4, width=8,
                         activation="relu2", weight_init="xavier"),
], ids=["mamba2", "sparseexperts"])
def test_a_time_mask_is_refused_not_ignored(conf):
    params = init_layer_params(jax.random.PRNGKey(0), conf, jnp.float32)
    with pytest.raises(NotImplementedError, match="mask"):
        forward_layer(conf, params, jnp.ones((2, 8, 16)),
                      LayerContext(mask=jnp.ones((2, 8))))


# -- norm, activation, loss ---------------------------------------------------------

def test_rms_norm_and_its_groups():
    conf = L.RMSNorm(n_in=12, eps=1e-5)
    params = {"gamma": jnp.arange(1.0, 13.0)}
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 12)) * 4.0
    y, _ = forward_layer(conf, params, x, LayerContext())
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) \
        * params["gamma"]
    assert jnp.allclose(y, want, atol=1e-5)
    from deeplearning4j_tpu.nn.layers.norm import rms_normalize

    grouped = rms_normalize(x, params["gamma"], 1e-5, groups=3)  # the mixer's
    xg = x.reshape(3, 5, 3, 4)
    want = (xg / jnp.sqrt(jnp.mean(xg * xg, -1, keepdims=True) + 1e-5)
            ).reshape(3, 5, 12) * params["gamma"]
    assert jnp.allclose(grouped, want, atol=1e-5)
    # statistics in float32 whatever the input: a bf16 input comes back bf16
    assert forward_layer(conf, params, x.astype(jnp.bfloat16),
                         LayerContext())[0].dtype == jnp.bfloat16


def test_relu2_is_registered():
    from deeplearning4j_tpu.ops.activations import apply_activation

    x = jnp.asarray([-2.0, 0.0, 3.0])
    assert jnp.array_equal(apply_activation("relu2", x),
                           jnp.asarray([0.0, 0.0, 9.0]))


def test_sparse_mcxent_is_mcxent_on_the_one_hot():
    assert LossFunction.SPARSE_MCXENT == "sparse_mcxent"
    key = jax.random.PRNGKey(0)
    z = jax.random.normal(key, (6, 10)) * 3.0
    y = jax.random.randint(jax.random.PRNGKey(1), (6,), 0, 10)
    sparse = loss_value("sparse_mcxent", y, z, "softmax")
    dense = loss_value("mcxent", jax.nn.one_hot(y, 10), z, "softmax")
    assert jnp.allclose(sparse, dense, atol=1e-6)
    # over a sequence it is the mean over time where mcxent is the sum
    zt = jax.random.normal(key, (3, 7, 10))
    yt = jax.random.randint(jax.random.PRNGKey(2), (3, 7), 0, 10)
    sparse = loss_value("sparse_mcxent", yt, zt, "softmax")
    dense = loss_value("mcxent", jax.nn.one_hot(yt, 10), zt, "softmax")
    assert sparse.shape == (3,)
    assert jnp.allclose(sparse, dense / 7.0, atol=1e-6)
    # a mask leaves positions out of the mean
    mask = jnp.asarray([[1.0] * 7, [1.0] * 3 + [0.0] * 4, [0.0] * 7])
    masked = loss_value("sparse_mcxent", yt, zt, "softmax", mask)
    per = -jnp.take_along_axis(jax.nn.log_softmax(zt), yt[..., None],
                               -1)[..., 0]
    assert jnp.allclose(masked[1], jnp.mean(per[1, :3]), atol=1e-6)
    assert float(masked[2]) == 0.0
    with pytest.raises(TypeError, match="integer labels"):
        loss_value("sparse_mcxent", jax.nn.one_hot(y, 10), z, "softmax")


@pytest.mark.parametrize("rows_block", [1, 2, 4])
def test_the_head_in_blocks_of_rows_is_the_head_at_once(rows_block):
    feats = jax.random.normal(jax.random.PRNGKey(0), (4, 6, 8))
    params = {"W": jax.random.normal(jax.random.PRNGKey(1), (8, 11))}
    y = jax.random.randint(jax.random.PRNGKey(2), (4, 6), 0, 11)
    whole = lambda p, f: jnp.mean(loss_value(
        "sparse_mcxent", y, jnp.einsum("bti,io->bto", f, p["W"]), "softmax"))
    blocks = lambda p, f: jnp.mean(sparse_head_loss(
        f, p, y, rows_block=rows_block))
    want, want_g = jax.value_and_grad(whole, argnums=(0, 1))(params, feats)
    got, got_g = jax.value_and_grad(blocks, argnums=(0, 1))(params, feats)
    assert abs(float(got) - float(want)) < 1e-6
    assert _rel(got_g[0]["W"], want_g[0]["W"]) < 1e-5
    assert _rel(got_g[1], want_g[1]) < 1e-5
    with pytest.raises(ValueError, match="does not divide"):
        sparse_head_loss(feats, params, y, rows_block=3)


def test_the_sequential_engine_refuses_a_head_in_blocks():
    """`head_rows_block` is ComputationGraph's: MultiLayerNetwork says so
    rather than taking the whole batch's logits in silence."""
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder().list()
            .layer(L.EmbeddingSequenceLayer(n_in=11, n_out=8))
            .layer(L.RnnOutputLayer(n_in=8, n_out=11, activation="softmax",
                                    loss="sparse_mcxent", has_bias=False,
                                    head_rows_block=1))
            .build())
    with pytest.raises(ValueError, match="ComputationGraph only"):
        MultiLayerNetwork(conf)


# -- recomputation -------------------------------------------------------------------

def test_recomputation_changes_neither_loss_nor_gradients():
    on, _ = _net(recompute=True)
    off, _ = _net(recompute=False)
    assert on.conf.recompute == [[f"b{i}_norm", f"b{i}_mixer", f"b{i}_add"]
                                 for i in range(4)]
    assert off.conf.recompute is None
    x, y = _tokens(3)
    a, ga = _program_loss_and_grads(on, x, y)
    b, gb = _program_loss_and_grads(off, x, y)
    assert abs(float(a) - float(b)) < 1e-6
    for layer in ga:
        for name in ga[layer]:
            assert _rel(ga[layer][name], gb[layer][name]) < 1e-5

    def text(net):
        return str(jax.make_jaxpr(lambda p: net._loss(
            p, net.state_list, [jnp.asarray(x)], [jnp.asarray(y)], None,
            None, None)[0])(net.params_list))

    # the head's blocks of rows and the attention's query blocks are
    # checkpointed either way; the blocks' runs are what `recompute` adds
    count = lambda net: len(re.findall(r"\b(?:remat2?|checkpoint)\[",
                                       text(net)))
    assert count(on) >= count(off) + 4


def test_a_recompute_run_that_is_no_run_is_refused():
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration

    conf = tiny_nemotron_h_conf(recompute=False)
    conf.recompute = [["b0_norm", "b0_add"]]       # skips the mixer
    with pytest.raises(ValueError, match="not consecutive"):
        ComputationGraph(conf).init()._recompute_runs()
    conf.recompute = [["final_norm", "head"]]
    with pytest.raises(ValueError, match="output vertex"):
        ComputationGraph(conf).init()._recompute_runs()
    gb = NeuralNetConfiguration.builder().graph_builder().add_inputs("in")
    with pytest.raises(ValueError, match="not vertices"):
        gb.recompute("nowhere")


# recorded from the parent of PR 28 (commit a8caab7) in this container, under
# the suite's `jax_default_matmul_precision` "highest": the whole train
# step's jaxpr, addresses stripped, of presets that predate the
# recompute option, the integer pass-through of `cast_input`, the bias-free
# RnnOutputLayer and `LayerContext.compute_dtype`
_PARENT_JAXPRS = {
    "tiny_resnet": ("1b813e1331c3a034", 1795),
    "tiny_resnet_bf16": ("28ff657301598c75", 2484),
    "vgg16_32": ("5525cfe594d23c67", 2165),
    "char_lstm": ("8510a47a41b1083b", 459),
    # taken on the parent of PR 32, before attention learnt a window and
    # rotary positions and the experts a gate, a softmax and a wired router
    # (ad8e472ef9d657ac, 6470 lines). PR 36 gave the expert layers a fifth
    # book, `tiles`: 38 lines more. With the book's lines taken out of
    # `experts_forward` the digest is still the parent's (checked by hand in
    # PR 36: the op slot's probe declines on the CPU and adds no equation)
    "tiny_nemotron_h": ("bf9b45104e7a82c0", 6508),
}


def _preset(name):
    from deeplearning4j_tpu.models import charlstm
    from deeplearning4j_tpu.models.resnet import tiny_resnet_conf
    from deeplearning4j_tpu.models.vgg16 import vgg16_conf
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    if name.startswith("tiny_resnet"):
        conf = tiny_resnet_conf(
            precision="bf16" if name.endswith("bf16") else "f32")
        return ComputationGraph(conf).init(), {"batch_size": 2}
    if name == "tiny_nemotron_h":
        return ComputationGraph(tiny_nemotron_h_conf()).init(), \
            {"batch_size": 2, "timesteps": 32}
    if name == "vgg16_32":
        return MultiLayerNetwork(vgg16_conf(
            num_classes=10, image_size=32, precision="bf16")).init(), \
            {"batch_size": 2}
    return MultiLayerNetwork(charlstm.char_lstm_conf(
        vocab_size=11, hidden=8, layers=1)).init(), \
        {"batch_size": 2, "timesteps": 5}


@pytest.mark.parametrize("name", sorted(_PARENT_JAXPRS))
def test_existing_presets_step_programs_are_what_they_were(name):
    from deeplearning4j_tpu.analysis.costmodel import train_step_args

    net, kw = _preset(name)
    step, args = train_step_args(net, **kw)
    text = str(jax.make_jaxpr(step)(*args))
    if name != "tiny_nemotron_h":    # the decoder recomputes its blocks
        assert "checkpoint" not in text and "remat" not in text
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    digest = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert (digest, text.count("\n")) == _PARENT_JAXPRS[name]


# -- the feed --------------------------------------------------------------------------

def test_integer_inputs_pass_the_precision_policy_uncast():
    from deeplearning4j_tpu.common.dtypes import tpu_policy

    policy = tpu_policy()
    ids = jnp.asarray([[16383, 257, 0]], jnp.int32)
    assert policy.cast_input(ids) is ids
    # what the cast did before: bf16 holds 8 bits of mantissa
    assert int(ids.astype(jnp.bfloat16)[0, 0]) == 16384
    assert int(ids.astype(jnp.bfloat16)[0, 1]) == 256
    images = jnp.ones((1, 2, 2, 3), jnp.float32)
    assert policy.cast_input(images).dtype == jnp.bfloat16
    assert policy.cast_input(images.astype(jnp.bfloat16)).dtype \
        == jnp.bfloat16


def test_the_embedding_refuses_ids_that_were_cast():
    conf = L.EmbeddingSequenceLayer(n_in=10, n_out=4, weight_init="xavier")
    params = init_layer_params(jax.random.PRNGKey(0), conf, jnp.float32)
    out, _ = forward_layer(conf, params, jnp.asarray([[1, 9], [0, 3]]),
                           LayerContext())
    assert out.shape == (2, 2, 4)
    assert jnp.array_equal(out[0, 1], params["W"][9])
    with pytest.raises(TypeError, match="integer ids"):
        forward_layer(conf, params, jnp.ones((2, 2), jnp.bfloat16),
                      LayerContext())


def test_fit_on_an_int32_pool_keeps_dtypes_and_counts_rows(monkeypatch):
    """Through `async_prefetch=True` (the staged pipeline) under the bf16
    policy: what reaches the step is int32 on both sides, ids above 256
    included, and `fit_examples_total` counts sequences."""
    net, _ = _net("bf16")
    seen = []
    real = net._fit_step

    def spy(xs, ys, *a, **kw):
        seen.append((xs[0].dtype, ys[0].dtype, int(jnp.max(xs[0]))))
        return real(xs, ys, *a, **kw)

    monkeypatch.setattr(net, "_fit_step", spy)
    x, y = _tokens(5)
    x[0, 0] = 127
    before = get_registry().scalar_values().get("fit_examples_total", 0.0)
    net.fit(ListDataSetIterator(DataSet(x, y), BATCH), epochs=3,
            async_prefetch=True)
    after = get_registry().scalar_values()["fit_examples_total"]
    assert after - before == 3 * BATCH
    assert seen == [(jnp.int32, jnp.int32, 127)] * 3
    assert np.isfinite(float(net._score))


def test_training_lowers_the_loss():
    net, _ = _net()
    x, y = _tokens(6)
    it = ListDataSetIterator(DataSet(x, y), BATCH)
    net.fit(it, epochs=1)
    first = float(net._score)
    net.fit(it, epochs=15)
    assert float(net._score) < first - 0.05


# -- configuration: serde, shapes, doctor ------------------------------------------------

def test_serde_round_trip_and_shapes():
    from deeplearning4j_tpu.nn.conf import ComputationGraphConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import RecurrentInput
    from deeplearning4j_tpu.analysis import shapeflow

    conf = tiny_nemotron_h_conf()
    back = ComputationGraphConfiguration.from_json(conf.to_json())
    assert back.to_json() == conf.to_json()
    assert back.recompute == conf.recompute
    kinds = {type(v.layer).__name__ for v in back.vertices.values()
             if hasattr(v, "layer")}
    assert kinds == {"EmbeddingSequenceLayer", "RMSNorm", "Mamba2Layer",
                     "SparseExpertsLayer", "GroupedQueryAttentionLayer",
                     "RnnOutputLayer"}
    # n_in inferred from the InputType: the vocabulary for the embedding
    assert back.vertices["embed"].layer.n_in == 128
    assert back.vertices["b0_norm"].layer.n_in == 64
    assert back.vertices["b1_mixer"].layer.n_in == 64
    types = shapeflow.propagate_types(conf)
    assert types["embed"] == RecurrentInput(64, SEQ)
    assert types["b2_mixer"] == RecurrentInput(64, SEQ)
    assert types["head"] == RecurrentInput(128, SEQ)
    a = ComputationGraph(conf).init()
    b = ComputationGraph(back).init()
    for p, q in zip(a.params_list, b.params_list):
        for k in p:
            assert jnp.array_equal(p[k], q[k])


def test_doctor_is_clean_and_the_cost_model_traces_the_step():
    from deeplearning4j_tpu.analysis.costmodel import train_step_cost

    net, _ = _net()
    findings = net.doctor(batch_size=2)
    assert [f for f in findings if f.severity == "error"] == [], findings
    cm = train_step_cost(net, batch_size=2)
    assert cm.param_bytes == 4 * net.num_params()
    assert cm.flops_total > 0


def test_layer_scopes_are_underscore_free_kinds():
    from deeplearning4j_tpu.nn.multilayer import layer_scope

    conf = tiny_nemotron_h_conf()
    scopes = {layer_scope(n, v.layer) for n, v in conf.vertices.items()
              if hasattr(v, "layer")}
    assert {"Lembed_embeddingsequence", "Lb0_norm_rmsnorm",
            "Lb0_mixer_mamba2", "Lb1_mixer_sparseexperts",
            "Lb2_mixer_groupedqueryattention", "Lhead_rnnoutput"} <= scopes
    net, _ = _net()
    x, y = _tokens(7)
    text = net._build_train_step().lower(
        net.params_list, net.state_list, net.upd_state,
        ([jnp.asarray(x)], [jnp.asarray(y)], None, None),
        jnp.float32(1e-3), jnp.float32(0.0),
        jax.random.PRNGKey(0)).as_text(debug_info=True)
    for scope in ("Lb0_mixer_mamba2/ssd_scan", "Lb1_mixer_sparseexperts/"
                  "experts", "Lb1_mixer_sparseexperts/router",
                  "Lb1_mixer_sparseexperts/shared_expert",
                  "loss)/Lhead_rnnoutput"):
        assert scope in text, scope


def test_the_factory_refuses_what_it_cannot_build():
    with pytest.raises(ValueError, match="blocks are of"):
        nemotron_h_conf(hybrid_override_pattern="MXM")
    with pytest.raises(ValueError, match="experts held here"):
        tiny_nemotron_h_conf(experts_held=[0, 1, 2])
    with pytest.raises(ValueError, match="distinct experts"):
        ComputationGraph(tiny_nemotron_h_conf(
            experts_held=[0, 1, 2, 3, 4, 5, 6, 99])).init()

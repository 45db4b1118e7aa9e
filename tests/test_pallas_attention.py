"""The fused grouped-query attention kernel (`ops/pallas_attention.py`) in
interpret mode on the CPU, at 512 positions, heads of 128 and tiles of 128:
against the built-in blocked lowering and against a dense masked softmax in
float32, values and gradients; through the layer under `jax.checkpoint`;
its probe as a pure function of backend, shapes and dtype; and the SPI's
counters for one trace of a SmallThinker test net."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import ListDataSetIterator
from deeplearning4j_tpu.models.smallthinker import tiny_smallthinker_conf
from deeplearning4j_tpu.nn.compgraph import ComputationGraph
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.layers import attention as A
from deeplearning4j_tpu.nn.layers.registry import LayerContext
from deeplearning4j_tpu.ops import pallas_attention as P
from deeplearning4j_tpu.ops.helpers import (
    get_helper,
    helper_books,
    helper_enabled,
    partitioned_program,
    register_helper,
)
from deeplearning4j_tpu.utils.metrics import get_registry

BF16 = jnp.bfloat16
T, D = 512, 128
MASKS = {"full": None, "window_half": 256, "window_wide": 600}
THETA = 1.5e6


@pytest.fixture
def interpreted(monkeypatch):
    """The kernel through the Pallas interpreter, at tiles of 128."""
    monkeypatch.setattr(P, "_INTERPRET", True)
    monkeypatch.setattr(P, "BLOCKS", (128,))


def _dense(q, k, v, window):
    """Masked softmax over all keys at once, float32 at HIGHEST."""
    group = q.shape[2] // k.shape[2]
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") \
        * q.shape[-1] ** -0.5
    qpos = jnp.arange(q.shape[1])[:, None]
    kpos = jnp.arange(k.shape[1])[None, :]
    seen = qpos >= kpos
    if window is not None:
        seen &= qpos - kpos < window
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")


def _inputs(group: int):
    b, kv = (2, 2) if group == 7 else (1, 2)
    ks = jax.random.split(jax.random.PRNGKey(group), 4)
    q = jax.random.normal(ks[0], (b, T, kv * group, D), BF16)
    k = jax.random.normal(ks[1], (b, T, kv, D), BF16)
    v = jax.random.normal(ks[2], (b, T, kv, D), BF16)
    w = jax.random.normal(ks[3], (b, T, kv * group, D), jnp.float32)
    return q, k, v, w


@functools.lru_cache(maxsize=None)
def _readings(mask: str, group: int, rotary: bool):
    """(output, dq, dk, dv) in float32 of the fused kernel, the built-in
    lowering and the dense form, on one set of inputs."""
    window = MASKS[mask]
    q, k, v, w = _inputs(group)

    def reading(attend):
        def out(q, k, v):
            if rotary:
                q, k = A.rope(q, THETA), A.rope(k, THETA)
            return attend(q, k, v)
        o, pull = jax.vjp(out, q, k, v)     # the gradients of sum(o * w)
        return tuple(np.asarray(x, np.float32) for x in (o,) + pull(w))

    was = P._INTERPRET, P.BLOCKS
    P._INTERPRET, P.BLOCKS = True, (128,)
    try:
        fused = reading(lambda q, k, v: P.gqa_attention(
            q, k, v, causal=True, window=window))
    finally:
        P._INTERPRET, P.BLOCKS = was
    return {
        "fused": fused,
        "builtin": reading(lambda q, k, v: A._blocked_attention(
            q, k, v, causal=True, window=window)),
        "dense": reading(lambda q, k, v: _dense(q, k, v, window)),
    }


@pytest.mark.parametrize("against", ["builtin", "dense"])
@pytest.mark.parametrize("rotary", [False, True], ids=["plain", "rope"])
@pytest.mark.parametrize("group", [7, 16])
@pytest.mark.parametrize("mask", sorted(MASKS))
def test_fused_kernel_agrees(mask, group, rotary, against):
    """Output and the gradients of q, k and v within bf16 rounding of the
    built-in lowering and of the dense float32 form (which the built-in
    lowering itself meets to 0.2-0.4% in norm)."""
    readings = _readings(mask, group, rotary)
    for name, got, want in zip(("o", "dq", "dk", "dv"), readings["fused"],
                               readings[against]):
        assert got.shape == want.shape
        gap = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert gap < 8e-3, (name, gap)
    assert np.abs(readings["fused"][0] - readings[against][0]).max() < 0.05


@pytest.mark.parametrize("t,window,block", [
    (512, None, 128), (512, 256, 128), (512, 600, 128), (1024, 100, 256),
    (1024, 257, 128), (8192, 4096, 1024), (8192, None, 512)])
def test_band_pairs_are_the_tiles_with_a_visible_key(t, window, block):
    """Against the band itself: a tile is walked iff it holds a visible
    (query, key) pair, masked iff it also holds one that is not, and each
    query block's pairs are marked first and last."""
    qi, kj, flags = P.band_pairs(t, window, block)
    n = t // block
    starts = np.arange(n) * block
    # per tile, from its corners: rows q0..q1, columns k0..k1
    q0, k0 = starts[:, None], starts[None, :]
    q1, k1 = q0 + block - 1, k0 + block - 1
    any_seen = (q1 >= k0) & (True if window is None else q0 - k1 < window)
    all_seen = (q0 >= k1) & (True if window is None else q1 - k0 < window)
    walked = np.zeros((n, n), bool)
    walked[qi, kj] = True
    assert (walked == any_seen).all()
    edge = np.zeros((n, n), bool)
    edge[qi, kj] = flags & P._EDGE != 0
    assert (edge == (any_seen & ~all_seen)).all()
    assert len(qi) == any_seen.sum()
    for i in range(n):
        mine = np.flatnonzero(qi == i)
        assert (np.diff(mine) == 1).all() and (np.diff(kj[mine]) == 1).all()
        first, last = flags[mine] & P._FIRST, flags[mine] & P._LAST
        assert first[0] and not first[1:].any()
        assert last[-1] and not last[:-1].any()


def test_band_pairs_count_what_the_layers_counter_counts():
    """At the built-in lowering's block the pairs are the counter's own:
    `attention_key_blocks_total` keeps its definition."""
    for t, window in ((8192, 4096), (8192, None), (4096, None)):
        qi, _, _ = P.band_pairs(t, window, A.QUERY_BLOCK)
        assert len(qi) == A.key_block_pairs(t, window)[0]


@pytest.mark.parametrize("kind", [
    {}, {"window": 256, "rope_theta": THETA}], ids=["full", "window_rope"])
def test_the_layer_under_checkpoint(kind, interpreted, monkeypatch):
    """`gqa_forward` under `jax.checkpoint`, as `GraphBuilder.recompute`
    runs a block: the `custom_vjp`'s forward runs again in the backward
    pass, and loss and gradients meet the built-in lowering's."""
    conf = L.GroupedQueryAttentionLayer(
        n_in=256, n_out=256, n_heads=4, n_kv_heads=2, head_dim=D,
        weight_init="xavier", **kind)
    ctx = LayerContext(training=True, compute_dtype=BF16)
    params = A.gqa_init(jax.random.PRNGKey(0), conf, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, 256), jnp.float32)

    def loss(params, x):
        layer = jax.checkpoint(lambda p, x: A.gqa_forward(conf, p, x, ctx)[0])
        return jnp.sum(jnp.square(x + layer(params, x)))

    before = helper_books()
    fused = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    moved = helper_books(before)
    assert moved["hits"] == {"full" if not kind else "window": 1}
    assert not moved["fallbacks"]
    monkeypatch.setattr(P, "_INTERPRET", False)     # the CPU declines
    builtin = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    assert helper_books(before)["fallbacks"]["unsupported"]
    for got, want in zip(jax.tree.leaves(fused), jax.tree.leaves(builtin)):
        gap = jnp.linalg.norm(got - want) / jnp.linalg.norm(want)
        assert gap < 8e-3, gap


# -- the probe ---------------------------------------------------------------------

def _ask(**ctx):
    """`get_helper` for the slot, and what its counters booked."""
    before = helper_books()
    helper = get_helper("gqa_attention", **ctx)
    return helper, helper_books(before)


SMALLTHINKER = dict(q_shape=(2, 8192, 28, 128), dtype=BF16, causal=True)
NEMOTRON = dict(q_shape=(4, 4096, 32, 128), dtype=BF16, causal=True,
                window=None)


@pytest.mark.parametrize("ctx,family", [
    (dict(SMALLTHINKER, window=4096), "window"),
    (dict(SMALLTHINKER, window=None), "full"),
    (NEMOTRON, "full")], ids=["smallthinker_window", "smallthinker_full",
                              "nemotron_full"])
def test_probe_takes_both_cells_shapes(ctx, family, monkeypatch):
    """What the two decoder cells trace: on a TPU (here: the interpreter's
    stand-in for one) the slot answers, under the layer's family."""
    monkeypatch.setattr(P, "_INTERPRET", True)
    assert P.supported(**ctx) is True
    helper, moved = _ask(**ctx)
    assert helper is not None
    assert moved == {"hits": {family: 1}, "auto_disable": {},
                     "fallbacks": {}}
    assert P._block(ctx["q_shape"][1]) == 1024


@pytest.mark.parametrize("change", [
    {"q_shape": (2, 8192, 28, 64)},          # half a lane of head
    {"q_shape": (2, 8192 + 64, 28, 128)},    # no tile divides the sequence
    {"q_shape": (2, 32768, 28, 128)},        # dk, dv pass the VMEM they get
    {"dtype": jnp.float32},
    {"causal": False, "window": None},
], ids=["head_dim_64", "ragged_seq", "seq_32768", "float32", "not_causal"])
def test_probe_declines_by_shape_and_dtype(change, monkeypatch):
    monkeypatch.setattr(P, "_INTERPRET", True)
    ctx = {**SMALLTHINKER, "window": 4096, **change}
    assert P.supported(**ctx) is False
    helper, moved = _ask(**ctx)
    family = "full" if ctx["window"] is None else "window"
    assert helper is None
    assert moved == {"hits": {}, "auto_disable": {},
                     "fallbacks": {"unsupported": {family: 1}}}


def test_probe_declines_the_cpu_without_the_interpreter():
    assert jax.default_backend() == "cpu" and not P._INTERPRET
    helper, moved = _ask(**NEMOTRON)
    assert helper is None
    assert moved["fallbacks"] == {"unsupported": {"full": 1}}


def test_slot_declines_inside_a_partitioned_program(monkeypatch):
    """Under a four-chip mesh the kernel is an opaque call the partitioner
    cannot split: the SPI declines before the probe is asked."""
    monkeypatch.setattr(P, "_INTERPRET", True)
    with partitioned_program(4):
        helper, moved = _ask(**NEMOTRON)
    assert helper is None
    assert moved["fallbacks"] == {"partitioned_program": {"full": 1}}


def test_a_raising_kernel_is_disabled_and_the_builtin_lowering_runs(
        interpreted):
    def exploding(*a, **k):
        raise RuntimeError("lowering failed")

    q, k, v, _ = _inputs(7)
    try:
        register_helper("gqa_attention", exploding, P.supported,
                        name="exploding_attention",
                        family=lambda *, window, **_: "full")
        got = A.grouped_query_attention(q, k, v, causal=True)
        assert helper_enabled("gqa_attention") is False
    finally:
        P.register()
    assert helper_enabled("gqa_attention") is True
    want = A._blocked_attention(q, k, v, causal=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- one trace of a SmallThinker test net ------------------------------------------

def _attention_counters():
    values = get_registry().scalar_values()
    return {k: v for k, v in values.items()
            if k.startswith(("attention_lowering_total",
                             "attention_key_blocks_total"))}


def _fit_once(seq=256):
    """One period (a full layer without positions, three window layers
    with rotary) at heads of 128 and bf16 products, one `fit()` step."""
    net = ComputationGraph(tiny_smallthinker_conf(
        precision="bf16", seq_len=seq, head_dim=D,
        sliding_window_size=128)).init()
    x = np.random.default_rng(0).integers(0, 128, (2, seq)).astype(np.int32)
    was = _attention_counters()
    net.fit(ListDataSetIterator(DataSet(x, np.roll(x, -1, axis=1)), 2))
    now = _attention_counters()
    return {k: now[k] - was.get(k, 0.0) for k in now}


def test_one_trace_counts_one_full_and_three_window_hits(monkeypatch):
    """With the helper on, the slot is hit once a layer a trace, by family;
    the layer's own counters read what they read on the built-in lowering,
    whichever runs."""
    def attention_only(books):
        # the net's expert layers ask a slot of their own (families
        # "gated" / "two_matrix", tests/test_pallas_experts.py)
        mine = lambda fams: {f: n for f, n in fams.items()
                             if f in ("full", "window")}
        fallbacks = {r: mine(fams) for r, fams in books["fallbacks"].items()}
        return {"hits": mine(books["hits"]),
                "auto_disable": mine(books["auto_disable"]),
                "fallbacks": {r: f for r, f in fallbacks.items() if f}}

    builtin_before = helper_books()
    builtin = _fit_once()
    moved = attention_only(helper_books(builtin_before))
    assert moved["hits"] == {}
    assert moved["fallbacks"] == {"unsupported": {"full": 1, "window": 3}}
    monkeypatch.setattr(P, "_INTERPRET", True)
    monkeypatch.setattr(P, "BLOCKS", (128,))
    fused_before = helper_books()
    fused = _fit_once()
    moved = attention_only(helper_books(fused_before))
    assert moved["hits"] == {"full": 1, "window": 3}
    assert moved["fallbacks"] == {} and moved["auto_disable"] == {}
    assert fused == builtin
    assert fused['attention_lowering_total{kind="full",positions="none"}'] == 1
    assert fused['attention_lowering_total{kind="window",positions="rope"}'] \
        == 3
    assert fused['attention_key_blocks_total{state="multiplied"}'] == 4


# -- two head sizes: queries and keys wider than values (latent attention) ----------

TWO_SIZES = {"latent_192_128": (192, 128), "half_lane_64_128": (64, 128),
             "lanes_256_128": (256, 128)}


def _two_size_inputs(case: str):
    """q, k, v and an output cotangent with queries and keys of `dqk` and
    values of `dv`, four heads on two key-value heads."""
    dqk, dv = TWO_SIZES[case]
    ks = jax.random.split(jax.random.PRNGKey(dqk), 4)
    return (jax.random.normal(ks[0], (1, 256, 4, dqk), BF16),
            jax.random.normal(ks[1], (1, 256, 2, dqk), BF16),
            jax.random.normal(ks[2], (1, 256, 2, dv), BF16),
            jax.random.normal(ks[3], (1, 256, 4, dv), jnp.float32))


@functools.lru_cache(maxsize=None)
def _two_size_readings(case: str):
    """(output, dq, dk, dv) of the fused kernel, the built-in lowering and
    the dense float32 form on those inputs."""
    q, k, v, w = _two_size_inputs(case)

    def reading(attend):
        o, pull = jax.vjp(attend, q, k, v)
        return tuple(np.asarray(x, np.float32) for x in (o,) + pull(w))

    was = P._INTERPRET, P.BLOCKS
    P._INTERPRET, P.BLOCKS = True, (128,)
    try:
        fused = reading(lambda q, k, v: P.gqa_attention(
            q, k, v, causal=True, window=None))
    finally:
        P._INTERPRET, P.BLOCKS = was
    return {
        "fused": fused,
        "builtin": reading(lambda q, k, v: A._blocked_attention(
            q, k, v, causal=True, window=None)),
        "dense": reading(lambda q, k, v: _dense(q, k, v, None)),
    }


@pytest.mark.parametrize("against", ["builtin", "dense"])
@pytest.mark.parametrize("case", sorted(TWO_SIZES))
def test_fused_kernel_with_two_head_sizes_agrees(case, against):
    """Queries and keys of another width than values, and a width that is
    not whole lanes (192, 64): the output is `[b, t, H, Dv]`, `dq` and `dk`
    are as wide as q and k, and all four meet the built-in lowering and the
    dense float32 form (whose scale is `Dqk ** -0.5`, the true width)."""
    dqk, dv = TWO_SIZES[case]
    readings = _two_size_readings(case)
    shapes = [(1, 256, 4, dv), (1, 256, 4, dqk), (1, 256, 2, dqk),
              (1, 256, 2, dv)]
    for name, got, want, shape in zip(
            ("o", "dq", "dk", "dv"), readings["fused"], readings[against],
            shapes):
        assert got.shape == want.shape == shape, name
        gap = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert gap < 8e-3, (name, gap)


def test_the_scale_is_the_true_widths_and_padding_must_pass_it_in(
        interpreted):
    """The kernel multiplies the scale it is handed, and the helper hands
    it `q.shape[-1] ** -0.5` of what the layer gave it: zero-padded
    operands under the same scale are the same attention, under the padded
    width's own scale they are another one."""
    dqk = 192
    readings = _two_size_readings("latent_192_128")
    q, k, v, _ = _two_size_inputs("latent_192_128")
    heads_first = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    pad = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, 256 - dqk)))
    padded = lambda scale: np.asarray(heads_first(P._attend(
        heads_first(pad(q)), heads_first(pad(k)), heads_first(v), None, 128,
        scale)), np.float32)
    same = padded(dqk ** -0.5)
    assert np.linalg.norm(same - readings["fused"][0]) \
        < 1e-5 * np.linalg.norm(same)
    other = padded(256 ** -0.5)
    assert np.linalg.norm(other - readings["fused"][0]) \
        > 1e-2 * np.linalg.norm(same)


LATENT = dict(q_shape=(2, 8192, 32, 192), dtype=BF16, causal=True,
              window=None, v_head_dim=128)


def test_probe_takes_the_latent_layers_shapes(monkeypatch):
    """What the latent-attention cell traces: queries and keys of 192,
    values of 128, a group of one."""
    monkeypatch.setattr(P, "_INTERPRET", True)
    assert P.supported(**LATENT) is True
    helper, moved = _ask(**LATENT)
    assert helper is not None
    assert moved == {"hits": {"full": 1}, "auto_disable": {},
                     "fallbacks": {}}
    # a call that names no value width has the queries' (the older layers)
    assert P.supported(**SMALLTHINKER, window=None) is True


@pytest.mark.parametrize("change", [
    {"v_head_dim": 64},                       # values of half a lane
    {"v_head_dim": 192},
    {"q_shape": (2, 8192, 32, 96)},           # not whole half-lanes
    {"q_shape": (2, 16384, 32, 192)},         # dk, dv pass the VMEM they get
], ids=["v_64", "v_192", "qk_96", "seq_16384"])
def test_probe_declines_two_sizes_by_shape(change, monkeypatch):
    monkeypatch.setattr(P, "_INTERPRET", True)
    ctx = {**LATENT, **change}
    assert P.supported(**ctx) is False
    helper, moved = _ask(**ctx)
    assert helper is None
    assert moved["fallbacks"] == {"unsupported": {"full": 1}}


def test_a_latent_layer_runs_the_kernel_under_checkpoint(interpreted):
    """`LatentAttentionLayer` in bf16 at 256 positions, heads of 128 + 64
    and 128: one `full` hit a trace, the same value and gradients as the
    built-in lowering gives within bf16 rounding."""
    conf = L.LatentAttentionLayer(
        n_in=64, n_out=64, n_heads=2, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=32,
        rope_theta=1e6, weight_init="xavier")
    params = A.latent_init(jax.random.PRNGKey(0), conf, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 64))
    ctx = LayerContext(compute_dtype=BF16)
    loss = lambda p, x: jnp.sum(jnp.sin(jax.checkpoint(
        lambda p, x: A.latent_forward(conf, p, x, ctx)[0])(p, x)))
    before = helper_books()
    got, got_g = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    moved = helper_books(before)
    assert moved["hits"] == {"full": 1} and not moved["fallbacks"]
    P._INTERPRET = False            # the CPU then declines: the built-in
    try:
        want, want_g = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    finally:
        P._INTERPRET = True
    assert abs(float(got) - float(want)) < 2e-2 * abs(float(want)) + 0.5
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        assert a.shape == b.shape
        assert float(jnp.linalg.norm(a - b)) \
            < 2e-2 * float(jnp.linalg.norm(b)) + 1e-4

"""The fused grouped-experts kernels (`ops/pallas_experts.py`) in interpret
mode on the CPU, at tiles of 128 rows: against the built-in three einsums
and against a plain float32 form, values and every gradient leaf, with the
experts' loads on a tile's edges and NaN planted in the rows nobody was sent
to; through the layer under every skew of the routing, against the built-in
grouped path and the three families' plain references; the probe as a pure
function of backend, shapes and dtype; the op slot's counters for one trace
of the tiny nets; and the layer's fifth book, `tiles`."""

from __future__ import annotations

import contextlib
import copy
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import deepseek_v3 as ref_latent  # noqa: E402
from benchmark.reference import nemotron_h as ref_nemotron  # noqa: E402
from benchmark.reference import smallthinker as ref_small  # noqa: E402
from deeplearning4j_tpu.data.dataset import DataSet  # noqa: E402
from deeplearning4j_tpu.data.iterators import ListDataSetIterator  # noqa: E402
from deeplearning4j_tpu.models.deepseek_v3 import (  # noqa: E402
    tiny_deepseek_v3_conf,
)
from deeplearning4j_tpu.models.smallthinker import (  # noqa: E402
    tiny_smallthinker_conf,
)
from deeplearning4j_tpu.nn.compgraph import ComputationGraph  # noqa: E402
from deeplearning4j_tpu.nn.conf import layers as L  # noqa: E402
from deeplearning4j_tpu.nn.layers import experts as X  # noqa: E402
from deeplearning4j_tpu.nn.layers.registry import (  # noqa: E402
    LayerContext,
    forward_layer,
    init_layer_params,
    init_layer_state,
)
from deeplearning4j_tpu.ops import pallas_experts as P  # noqa: E402
from deeplearning4j_tpu.ops.activations import apply_activation  # noqa: E402
from deeplearning4j_tpu.ops.helpers import (  # noqa: E402
    get_helper,
    helper_books,
    helper_enabled,
    partitioned_program,
    register_helper,
)
from deeplearning4j_tpu.utils.metrics import get_registry  # noqa: E402

BF16 = jnp.bfloat16
TILE, CAP, D, WIDTH = 128, 384, 256, 128
FAMILIES = {"gated_silu": (True, "silu"), "gated_relu": (True, "relu"),
            "two_matrix_relu2": (False, "relu2")}
# one held expert on each edge of a tile: nobody, one row, a whole tile, a
# tile and a row, the whole buffer
LOADS = {"edges": (0, 1, TILE, TILE + 1, CAP),
         "edges_from_the_full_end": (CAP, TILE + 1, TILE, 1, 0),
         "nobody": (0, 0, 0, 0, 0),
         "every_buffer_full": (CAP,) * 5}
LEAVES = ("out", "rows", "w1", "w3", "w2", "slot_w")


@contextlib.contextmanager
def _interpreter(on: bool = True):
    """The kernels through the Pallas interpreter at tiles of 128 rows, or
    (off) the plain CPU, where the probe declines."""
    was = P._INTERPRET, P.ROW_TILES
    P._INTERPRET, P.ROW_TILES = on, (TILE,)
    try:
        yield
    finally:
        P._INTERPRET, P.ROW_TILES = was


@pytest.fixture
def interpreted():
    with _interpreter():
        yield


def _rel(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.linalg.norm(got - want)
                 / max(float(np.linalg.norm(want)), 1e-30))


# -- the kernels against the three einsums and a plain float32 form -----------

def _inputs(family: str, loads):
    """The buffers as the layer hands them over, the rows nobody was sent to
    at 0 (and their weights at 0, as `slot_w` has them), and an output
    cotangent."""
    gated, _ = FAMILIES[family]
    held = len(loads)
    ks = jax.random.split(jax.random.PRNGKey(len(family)), 6)
    filled = jnp.arange(CAP)[None, :] < jnp.asarray(loads)[:, None]
    rows = jnp.where(filled[:, :, None],
                     jax.random.normal(ks[0], (held, CAP, D)), 0.0)
    w1 = jax.random.normal(ks[1], (held, D, WIDTH)) * D ** -0.5
    w3 = jax.random.normal(ks[2], (held, D, WIDTH)) * D ** -0.5
    w2 = jax.random.normal(ks[3], (held, WIDTH, D)) * WIDTH ** -0.5
    slot_w = jnp.where(filled, jax.random.uniform(
        ks[4], (held, CAP), minval=0.1, maxval=0.9), 0.0).reshape(-1)
    ct = jax.random.normal(ks[5], (held, CAP, D), jnp.float32)
    cast = lambda a: a.astype(BF16)
    return (cast(rows), cast(w1), cast(w3) if gated else None, cast(w2),
            slot_w, ct, filled)


def _einsums(rows, w1, w3, w2, slot_w, activation, dtype):
    """`experts.grouped()`'s products in `dtype`: bf16 is the built-in
    lowering word for word, float32 the plain form (full precision, no
    rounding of `hidden`)."""
    prec = "highest" if dtype == jnp.float32 else None
    mm = lambda spec, a, b: jnp.einsum(
        spec, a.astype(dtype), b.astype(dtype), precision=prec,
        preferred_element_type=jnp.float32)
    hidden = apply_activation(activation, mm("ecd,edf->ecf", rows, w1))
    if w3 is not None:
        hidden = hidden * mm("ecd,edf->ecf", rows, w3)
    out = mm("ecf,efd->ecd", hidden.astype(dtype), w2)
    return out * slot_w.reshape(out.shape[0], out.shape[1], 1)


@functools.lru_cache(maxsize=None)
def _readings(family: str, loads_name: str):
    """(out, d_rows, dW1, dW3, dW2, d_slot_w) in float32 of the kernels,
    of the built-in einsums and of the plain float32 form; the kernels read
    rows with NaN planted where nobody was sent."""
    _, activation = FAMILIES[family]
    loads = LOADS[loads_name]
    rows, w1, w3, w2, slot_w, ct, filled = _inputs(family, loads)
    planted = jnp.where(filled[:, :, None], rows, jnp.nan)
    count = jnp.asarray(loads, jnp.int32)

    def reading(fn, rows):
        out, pull = jax.vjp(fn, rows, w1, w3, w2, slot_w)
        grads = pull(ct)
        # the layer's `slot_w` is a `where(filled, ...)`: what reaches an
        # unfilled slot's weight goes nowhere
        d_slot_w = jnp.where(filled.reshape(-1), grads[4], 0.0)
        return {name: None if g is None else np.asarray(g, np.float32)
                for name, g in zip(LEAVES, (out,) + grads[:4] + (d_slot_w,))}

    with _interpreter():
        kernels = reading(lambda r, a, b, c, s: P.grouped_experts(
            r, a, b, c, s, count, activation=activation), planted)
    return {"kernels": kernels, "filled": np.asarray(filled),
            "builtin": reading(lambda *a: _einsums(*a, activation, BF16),
                               rows),
            "float32": reading(lambda *a: _einsums(*a, activation,
                                                   jnp.float32), rows)}


@pytest.mark.parametrize("against", ["builtin", "float32"])
@pytest.mark.parametrize("loads", sorted(LOADS))
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_kernels_agree_in_value_and_every_gradient_leaf(family, loads,
                                                        against):
    """Within bf16 rounding of the built-in einsums (the forward to the
    bit: same operands, same rounding points) and of the plain float32
    form, whatever the loads."""
    readings = _readings(family, loads)
    got, want = readings["kernels"], readings[against]
    for name in LEAVES:
        if want[name] is None:
            assert got[name] is None and name == "w3"
            continue
        assert got[name].shape == want[name].shape
        assert np.isfinite(got[name]).all(), name
        if not want[name].any():
            assert not got[name].any(), name
        else:
            assert _rel(got[name], want[name]) < 1.2e-2, name
    if against == "builtin":
        assert _rel(got["out"], want["out"]) < 1e-6


@pytest.mark.parametrize("loads", sorted(LOADS))
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_rows_nobody_was_sent_to_come_out_exactly_zero(family, loads):
    """Skipped tiles are written, never left as they were, and nothing an
    unfilled row holds (NaN here) reaches the output, `d_rows`, `d_slot_w`
    or a weight gradient; an expert nobody was sent to gets zero weight
    gradients."""
    readings = _readings(family, loads)
    got, filled = readings["kernels"], readings["filled"]
    assert (got["out"][~filled] == 0).all()
    assert (got["rows"][~filled] == 0).all()
    assert (got["slot_w"][~filled.reshape(-1)] == 0).all()
    idle = np.flatnonzero(np.asarray(LOADS[loads]) == 0)
    for name in ("w1", "w3", "w2"):
        if got[name] is not None:
            assert np.isfinite(got[name]).all()
            assert (got[name][idle] == 0).all(), name


def test_the_walk_pins_skipped_tiles_and_idle_experts():
    """`last`: an expert's last multiplied tile, where a skipped tile's
    blocks stay; `wsel`: an idle expert keeps its predecessor's matrices;
    a load past the buffer (a step of the exact path) is held to it."""
    count, last, wsel = P._walk(jnp.asarray([0, 1, 128, 129, 384, 0, 999]),
                                3, 128)
    assert count.tolist() == [0, 1, 128, 129, 384, 0, 384]
    assert last.tolist() == [0, 0, 0, 1, 2, 0, 2]
    assert wsel.tolist() == [0, 1, 2, 3, 4, 4, 6]


# -- through the layer, under any skew of the routing ---------------------------

SKEWS = ("uniform", "same_experts", "one_held_idle", "none_held_chosen")
HELD = list(range(8))


def _layer(family: str, skew: str):
    """A sparse-expert layer of whole lanes (hidden 128, experts 128 wide,
    16 routed, 8 held, 3 a token, buffers of two tiles) in bf16 products on
    a float32 stream, its parameters and 256 tokens under one routing:
    feature 0 of every token is 5 and the router's row 0 is 0, or -10 for
    an expert that no token is to choose."""
    gated, activation = FAMILIES[family]
    kind = {"gated_silu": dict(score="sigmoid", select_bias=True,
                               scaling=2.448),
            "gated_relu": dict(score="softmax", router_input=True),
            "two_matrix_relu2": dict(score="sigmoid", scaling=2.5)}[family]
    conf = L.SparseExpertsLayer(
        n_in=128, n_out=128, width=128, router_width=16, experts_held=HELD,
        experts_per_token=3, shared_width=0, capacity_factor=8.0,
        activation=activation, gated=gated, weight_init="xavier", **kind)
    params = init_layer_params(jax.random.PRNGKey(3), conf, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(11), (4, 64, 128))
    x = x.at[..., 0].set(5.0)
    if skew == "same_experts":
        x = x.at[:, :, :].set(x[:1, :1])
    unchosen = {"one_held_idle": [3], "none_held_chosen": HELD}.get(skew, [])
    row0 = jnp.zeros((16,)).at[jnp.asarray(unchosen, jnp.int32)].set(-10.0)
    if skew == "same_experts":     # held ones, whatever the seed
        row0 = row0.at[jnp.asarray([1, 4, 6])].set(2.0)
    if conf.router_input:
        router = jax.random.normal(jax.random.PRNGKey(5), (128, 16)) * 0.1
        logits = X.router_logits(x, router.at[0].set(row0))
    else:
        params = dict(params, W_router=params["W_router"].at[0].set(row0))
        logits = None
    return conf, params, x, logits


def _reference(family: str, params, x, logits):
    """The family's plain reference in float32 on the layer's parameters."""
    if family == "gated_relu":
        return ref_small.experts(params, x, logits, {"top": 3, "held": HELD},
                                 "f32")
    if family == "gated_silu":
        return ref_latent.experts(
            params, x, {"top": 3, "held": HELD, "scaling": 2.448}, "f32")
    return ref_nemotron.experts(
        params, x, {"top": 3, "held": HELD},
        {"routed_scaling_factor": 2.5}, "f32", with_shared=False)


@functools.lru_cache(maxsize=None)
def _layer_readings(family: str, skew: str):
    conf, params, x, logits = _layer(family, skew)
    weight = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    extra = () if logits is None else (logits,)

    def through_layer(p, a):
        ctx = LayerContext(compute_dtype=BF16, extra_inputs=extra,
                           state=init_layer_state(conf, jnp.float32))
        y, books = jax.checkpoint(
            lambda p, a: forward_layer(conf, p, a, ctx))(p, a)
        return jnp.sum(y * weight), (y, books)

    def reading(fn):
        (_, (y, books)), grads = jax.value_and_grad(
            fn, argnums=(0, 1), has_aux=True)(params, x)
        leaves = dict(grads[0], x=grads[1], y=y)
        leaves.pop("b_select", None)       # no gradient reaches it
        return {k: np.asarray(v, np.float32) for k, v in leaves.items()}, \
            books

    builtin_before = helper_books()
    builtin, books = reading(through_layer)
    assert helper_books(builtin_before)["hits"] == {}
    with _interpreter():
        kernel_before = helper_books()
        kernels, kernel_books = reading(through_layer)
        moved = helper_books(kernel_before)
    reference, _ = reading(lambda p, a: (
        jnp.sum(_reference(family, p, a, logits) * weight),
        (_reference(family, p, a, logits), None)))
    return {"kernels": kernels, "builtin": builtin, "reference": reference,
            "books": books, "kernel_books": kernel_books, "moved": moved}


@pytest.mark.parametrize("against", ["builtin", "reference"])
@pytest.mark.parametrize("skew", SKEWS)
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_layer_on_the_kernels_under_any_skew(family, skew, against):
    """The grouped branch of the `lax.cond` on the kernels, recomputed
    under `jax.checkpoint`: value and the gradient of every parameter and of
    the input within bf16 rounding of the same layer on the three einsums
    and of the family's plain float32 reference, whatever the routing sends
    the held experts."""
    readings = _layer_readings(family, skew)
    assert readings["moved"]["hits"] == {
        "two_matrix" if family.startswith("two") else "gated": 1}
    assert readings["moved"]["fallbacks"] == {}
    books = readings["books"]
    loads = np.asarray(books["routed"])[HELD]
    assert int(books["overflow"]) == 0 and int(books["rows"]) == 256
    assert {"uniform": loads.min() > 0,
            "same_experts": set(loads.tolist()) <= {0, 256}
            and 0 < (loads > 0).sum() <= 3,
            "one_held_idle": loads[3] == 0 and loads.sum() > 0,
            "none_held_chosen": loads.sum() == 0}[skew]
    for name, b in readings["kernel_books"].items():
        np.testing.assert_array_equal(np.asarray(b), np.asarray(books[name]))
    got, want = readings["kernels"], readings[against]
    assert set(got) == set(want)
    for name in sorted(want):
        assert np.isfinite(got[name]).all(), name
        if not want[name].any():
            assert not got[name].any(), name
            continue
        # against float32 the bound is what the three einsums themselves
        # keep (a relu's step flips with the operands' rounding)
        bound = 2e-2 if against == "builtin" else max(2e-2, 1.5 * _rel(
            readings["builtin"][name], want[name]))
        assert _rel(got[name], want[name]) < bound, name


def test_an_overflowing_step_leaves_the_kernels_idle(interpreted):
    """A step that sends a held expert more than its buffer takes the exact
    branch: the kernels are traced beside it and never run, and the result
    is the roomy buffer's."""
    conf, params, x, _ = _layer("two_matrix_relu2", "same_experts")
    tight = copy.copy(conf)
    tight.capacity_factor = 2.0
    assert X.expert_capacity(tight, 256) == 128
    ctx = lambda c: LayerContext(compute_dtype=BF16,
                                 state=init_layer_state(c, jnp.float32))
    want, _ = forward_layer(conf, params, x, ctx(conf))
    got, books = forward_layer(tight, params, x, ctx(tight))
    assert int(books["overflow"]) > 0
    # the buffers hold 128 rows each, all filled; the load beyond is booked
    # as overflow, not as tiles
    assert np.asarray(books["tiles"]).sum() == 8
    assert _rel(got, want) < 2e-2


# -- the probe -----------------------------------------------------------------------

KANANA = dict(rows_shape=(16, 6144, 2048), width=768, dtype=BF16, gated=True,
              activation="silu")
SMALLTHINKER = dict(rows_shape=(8, 16384, 2560), width=768, dtype=BF16,
                    gated=True, activation="relu")
NEMOTRON = dict(rows_shape=(8, 6144, 2688), width=1856, dtype=BF16,
                gated=False, activation="relu2")


def _ask(**ctx):
    before = helper_books()
    helper = get_helper("grouped_experts", **ctx)
    return helper, helper_books(before)


@pytest.mark.parametrize("ctx,family,tile", [
    (KANANA, "gated", 256), (SMALLTHINKER, "gated", 256),
    (dict(NEMOTRON, width=1792), "two_matrix", 128)],
    ids=["kanana", "smallthinker", "two_matrix_of_whole_lanes"])
def test_probe_takes_the_gated_cells_shapes(ctx, family, tile, monkeypatch):
    monkeypatch.setattr(P, "_INTERPRET", True)
    helper, moved = _ask(**ctx)
    assert helper is not None
    assert moved["hits"] == {family: 1} and moved["fallbacks"] == {}
    _, cap, d = ctx["rows_shape"]
    assert P._tile(cap, d, ctx["width"], ctx["gated"]) == tile
    assert P.vmem_bytes(tile, d, ctx["width"], ctx["gated"]) < P.VMEM_LIMIT


@pytest.mark.parametrize("change", [
    dict(dtype=jnp.float32), dict(width=1856),
    dict(rows_shape=(16, 6144, 2000)), dict(rows_shape=(16, 6144 + 64, 2048)),
    dict(rows_shape=(16, 6144, 7168), width=2048)],
    ids=["float32", "width_of_14_and_a_half_lanes", "inputs_not_whole_lanes",
         "rows_no_tile_divides", "matrices_past_the_vmem_limit"])
def test_probe_declines_by_shape_and_dtype(change, monkeypatch):
    monkeypatch.setattr(P, "_INTERPRET", True)
    helper, moved = _ask(**dict(KANANA, **change))
    assert helper is None
    assert moved["fallbacks"] == {"unsupported": {"gated": 1}}


def test_probe_declines_the_nemotron_cells_width(monkeypatch):
    """1,856 is 14.5 lanes: the cell keeps the three einsums."""
    monkeypatch.setattr(P, "_INTERPRET", True)
    helper, moved = _ask(**NEMOTRON)
    assert helper is None
    assert moved["fallbacks"] == {"unsupported": {"two_matrix": 1}}


def test_probe_declines_the_cpu_without_the_interpreter():
    assert jax.default_backend() == "cpu" and not P._INTERPRET
    helper, moved = _ask(**KANANA)
    assert helper is None
    assert moved["fallbacks"] == {"unsupported": {"gated": 1}}


def test_slot_declines_inside_a_partitioned_program(monkeypatch):
    """Under a four-chip mesh the kernels are opaque calls the partitioner
    cannot split: the SPI declines before the probe is asked."""
    monkeypatch.setattr(P, "_INTERPRET", True)
    with partitioned_program(4):
        helper, moved = _ask(**KANANA)
    assert helper is None
    assert moved["fallbacks"] == {"partitioned_program": {"gated": 1}}


def test_a_raising_kernel_is_disabled_and_the_three_einsums_run(interpreted):
    def exploding(*a, **k):
        raise RuntimeError("lowering failed")

    conf, params, x, _ = _layer("two_matrix_relu2", "uniform")
    ctx = LayerContext(compute_dtype=BF16)
    try:
        register_helper("grouped_experts", exploding, P.supported,
                        name="exploding_experts",
                        family=lambda **_: "two_matrix")
        got, _ = forward_layer(conf, params, x, ctx)
        assert helper_enabled("grouped_experts") is False
    finally:
        P.register()
    assert helper_enabled("grouped_experts") is True
    with _interpreter(False):
        want, _ = forward_layer(conf, params, x, ctx)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- one trace of the tiny nets --------------------------------------------------------

NETS = {
    # four expert layers, each gated relu on a wired router
    "smallthinker": (lambda: tiny_smallthinker_conf(
        precision="bf16", seq_len=64, hidden_size=128,
        moe_ffn_hidden_size=128), 4),
    # one dense layer, then two expert layers, each gated silu beside a
    # shared MLP
    "latent": (lambda: tiny_deepseek_v3_conf(
        precision="bf16", seq_len=64, hidden_size=128,
        moe_intermediate_size=128), 2),
}


def _tile_counters():
    values = get_registry().scalar_values()
    return {state: values.get(
        'experts_row_tiles_total{state="%s"}' % state, 0.0)
        for state in ("filled", "empty")}


def _fit_once(conf):
    net = ComputationGraph(conf()).init()
    x = np.random.default_rng(0).integers(0, 128, (2, 64)).astype(np.int32)
    was = _tile_counters()
    net.fit(ListDataSetIterator(DataSet(x, np.roll(x, -1, axis=1)), 2),
            epochs=2)
    now = _tile_counters()
    return {k: now[k] - was[k] for k in now}, float(net._score)


@pytest.mark.parametrize("name", sorted(NETS))
def test_one_trace_counts_one_hit_a_layer(name, monkeypatch):
    """With the helper on, the slot is hit once an expert layer a trace; on
    the plain CPU it falls back as `unsupported` as often. The tiles'
    counter reads the routing, whichever lowering runs: two steps of a
    layer's 8 buffers of 128 rows, filled + empty."""
    conf, layers = NETS[name]
    before = helper_books()
    builtin_tiles, builtin_score = _fit_once(conf)
    moved = helper_books(before)
    assert moved["hits"].get("gated", 0) == 0
    assert moved["fallbacks"]["unsupported"]["gated"] == layers
    monkeypatch.setattr(P, "_INTERPRET", True)
    monkeypatch.setattr(P, "ROW_TILES", (TILE,))
    before = helper_books()
    kernel_tiles, kernel_score = _fit_once(conf)
    moved = helper_books(before)
    assert moved["hits"]["gated"] == layers
    assert "gated" not in moved["fallbacks"].get("unsupported", {})
    assert moved["auto_disable"] == {}
    assert kernel_tiles["filled"] + kernel_tiles["empty"] == 2 * layers * 8
    assert kernel_tiles["filled"] > 0
    assert abs(kernel_score - builtin_score) < 2e-2 * abs(builtin_score)
    assert builtin_tiles["filled"] + builtin_tiles["empty"] == 2 * layers * 8


# -- the fifth book ------------------------------------------------------------------------

def test_the_tiles_book_adds_up_over_two_steps(monkeypatch):
    """`tiles` = (filled, empty) tiles of `_ROWS` rows over the held
    experts' buffers: filled is the sum of `ceil(load / _ROWS)` by the
    routing, filled + empty the buffers' tiles, and a second step's are
    added to the first's."""
    monkeypatch.setattr(X, "_ROWS", 8)
    conf = L.SparseExpertsLayer(
        n_in=32, n_out=32, width=24, router_width=16,
        experts_held=[9, 2, 3, 12, 5, 0, 15, 7], experts_per_token=3,
        shared_width=0, capacity_factor=2.0, activation="relu2",
        weight_init="xavier")
    params = init_layer_params(jax.random.PRNGKey(3), conf, jnp.float32)
    state = init_layer_state(conf, jnp.float32)
    assert state["tiles"].shape == (2,) and state["tiles"].dtype == jnp.int32
    total = np.zeros((2,), np.int64)
    for step in range(2):
        x = jax.random.normal(jax.random.PRNGKey(20 + step), (2, 48, 32))
        _, state = forward_layer(conf, params, x, LayerContext(state=state))
        once = forward_layer(conf, params, x, LayerContext(
            state=init_layer_state(conf, jnp.float32)))[1]
        cap = int(once["rows"])
        assert cap == X.expert_capacity(conf, 96) == 40
        loads = np.minimum(np.asarray(once["routed"])[conf.held()], cap)
        filled = int(np.ceil(loads / 8).sum())
        assert np.asarray(once["tiles"]).tolist() == [
            filled, 8 * (cap // 8) - filled]
        total += np.asarray(once["tiles"])
    assert np.asarray(state["tiles"]).tolist() == total.tolist()
    assert total.sum() == 2 * 8 * 5
    before = _tile_counters()
    out = X.publish_expert_books([conf], [jax.device_get(state)])
    after = _tile_counters()
    assert out["tiles_filled"] == total[0] and out["tiles_empty"] == total[1]
    assert after["filled"] - before["filled"] == total[0]
    assert after["empty"] - before["empty"] == total[1]

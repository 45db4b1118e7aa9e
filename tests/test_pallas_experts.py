"""The fused grouped-experts kernels (`ops/pallas_experts.py`) in interpret
mode on the CPU, at tiles of 128 rows: against the built-in three einsums
and against a plain float32 form, values and every gradient leaf, with the
experts' loads on a tile's edges and NaN planted in the rows nobody was sent
to, at widths of whole lanes and at widths of whole 8-row blocks that are
not; dispatch and combine against the gather and the scatter-add, value
and gradient, with NaN where a kernel writes nothing and planted past every
load; through the layer under every skew of the routing, against the built-in grouped path
and the three families' plain references; the probe as a pure function of
backend, shapes and dtype; the op slot's counters and the five kernels'
scopes for one trace of the tiny nets; the layer's fifth book, `tiles`; and
the kernels as lowered for a TPU at the whole-lane cells' shapes, pinned to
the parent's."""

from __future__ import annotations

import contextlib
import copy
import functools
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

from benchmark.reference import deepseek_v3 as ref_latent  # noqa: E402
from benchmark.reference import nemotron_h as ref_nemotron  # noqa: E402
from benchmark.reference import smallthinker as ref_small  # noqa: E402
from deeplearning4j_tpu.data.dataset import DataSet  # noqa: E402
from deeplearning4j_tpu.data.iterators import ListDataSetIterator  # noqa: E402
from deeplearning4j_tpu.models.afmoe import tiny_afmoe_conf  # noqa: E402
from deeplearning4j_tpu.models.deepseek_v3 import (  # noqa: E402
    tiny_deepseek_v3_conf,
)
from deeplearning4j_tpu.models.nemotron_h import (  # noqa: E402
    tiny_nemotron_h_conf,
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
TILE, CAP = 128, 384
FAMILIES = {"gated_silu": (True, "silu"), "gated_relu": (True, "relu"),
            "two_matrix_relu2": (False, "relu2")}
# (n_in, width): whole lanes; widths of whole 8-row blocks but not whole
# lanes (the Nemotron cell's 1,856 is 14.5 lanes); an `n_in` of whole lanes
# but not whole 512-column slices (the Nemotron cell's 2,688)
SHAPES = {"whole_lanes": (256, 128), "width_192": (256, 192),
          "width_320_n_in_640": (640, 320)}
# one held expert on each edge of a tile: nobody, one row, a whole tile, a
# tile and a row, the whole buffer
LOADS = {"edges": (0, 1, TILE, TILE + 1, CAP),
         "edges_from_the_full_end": (CAP, TILE + 1, TILE, 1, 0),
         "nobody": (0, 0, 0, 0, 0),
         "every_buffer_full": (CAP,) * 5}
LEAVES = ("out", "rows", "w1", "w3", "w2", "slot_w")
# every family under every load at whole lanes; the ragged shapes for both
# families on a tile's edges
CASES = [(f, lo, "whole_lanes") for f in sorted(FAMILIES)
         for lo in sorted(LOADS)] + [
    (f, lo, shape) for shape in ("width_192", "width_320_n_in_640")
    for f in ("gated_silu", "two_matrix_relu2")
    for lo in ("edges", "edges_from_the_full_end")]
CASE_IDS = ["-".join(c) for c in CASES]


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

def _inputs(family: str, loads, shape: str):
    """The buffers as the layer hands them over, the rows nobody was sent to
    at 0 (and their weights at 0, as `slot_w` has them), and an output
    cotangent."""
    gated, _ = FAMILIES[family]
    d, width = SHAPES[shape]
    held = len(loads)
    ks = jax.random.split(jax.random.PRNGKey(len(family)), 6)
    filled = jnp.arange(CAP)[None, :] < jnp.asarray(loads)[:, None]
    rows = jnp.where(filled[:, :, None],
                     jax.random.normal(ks[0], (held, CAP, d)), 0.0)
    w1 = jax.random.normal(ks[1], (held, d, width)) * d ** -0.5
    w3 = jax.random.normal(ks[2], (held, d, width)) * d ** -0.5
    w2 = jax.random.normal(ks[3], (held, width, d)) * width ** -0.5
    slot_w = jnp.where(filled, jax.random.uniform(
        ks[4], (held, CAP), minval=0.1, maxval=0.9), 0.0).reshape(-1)
    ct = jax.random.normal(ks[5], (held, CAP, d), jnp.float32)
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
def _readings(family: str, loads_name: str, shape: str):
    """(out, d_rows, dW1, dW3, dW2, d_slot_w) in float32 of the kernels,
    of the built-in einsums and of the plain float32 form; the kernels read
    rows with NaN planted where nobody was sent."""
    _, activation = FAMILIES[family]
    loads = LOADS[loads_name]
    rows, w1, w3, w2, slot_w, ct, filled = _inputs(family, loads, shape)
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
@pytest.mark.parametrize("family,loads,shape", CASES, ids=CASE_IDS)
def test_kernels_agree_in_value_and_every_gradient_leaf(family, loads, shape,
                                                        against):
    """Within bf16 rounding of the built-in einsums (the forward to the
    bit: same operands, same rounding points) and of the plain float32
    form, whatever the loads and whether or not the widths are whole
    lanes."""
    readings = _readings(family, loads, shape)
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


@pytest.mark.parametrize("family,loads,shape", CASES, ids=CASE_IDS)
def test_rows_nobody_was_sent_to_come_out_exactly_zero(family, loads, shape):
    """Skipped tiles are written, never left as they were, and nothing an
    unfilled row holds (NaN here) reaches the output, `d_rows`, `d_slot_w`
    or a weight gradient; an expert nobody was sent to gets zero weight
    gradients."""
    readings = _readings(family, loads, shape)
    got, filled = readings["kernels"], readings["filled"]
    assert (got["out"][~filled] == 0).all()
    assert (got["rows"][~filled] == 0).all()
    assert (got["slot_w"][~filled.reshape(-1)] == 0).all()
    idle = np.flatnonzero(np.asarray(LOADS[loads]) == 0)
    for name in ("w1", "w3", "w2"):
        if got[name] is not None:
            assert np.isfinite(got[name]).all()
            assert (got[name][idle] == 0).all(), name


# -- rows in and out of the buffers: dispatch and combine ------------------------

MOVE_TOKENS, MOVE_HELD = 300, 8
# each held expert's load (eight experts but where said) in a buffer of two
# tiles of `TILE` rows, and n_in
MOVES = {
    "an_idle_expert": ((0, 200, 128, 77, 0, 5, 256, 1), 256),
    "partly_filled_tiles": ((1, 129, 255, 3, 127, 130, 64, 200), 256),
    "full_buffers": ((2 * TILE,) * MOVE_HELD, 256),
    "every_assignment_foreign": ((0,) * MOVE_HELD, 256),
    # every held expert sent the same tokens: each token held eight times
    "tokens_held_eight_times": ((150,) * MOVE_HELD, 256),
    # runs of consecutive tokens
    "tokens_in_runs": ((256, 200, 37, 9, 0, 130, 8, 16), 256),
    # 5 x 256 slots, not whole blocks of 1,024 in SMEM: padded to two
    "slots_not_whole_smem_blocks": ((256, 0, 129, 200, 1), 256),
    # loads past the buffer: its rows are kept, the rest have no slot
    "loads_past_the_buffer": ((300, 10, 0, 256, 999, 1, 2, 3), 256),
    # the Nemotron cell's n_in, 21 lanes
    "n_in_2688": ((200, 60, 0, 129, 1, 256, 31, 128), 2688),
}


def _moves(case: str, dtype):
    """x in token order, the slots' tokens (sorted in each expert, as the
    router's running sum fills them; NaN-free 0 past a load), the loads,
    and what is defined past each load: the rest of the tile it ends in."""
    loads, d = MOVES[case]
    cap = 2 * TILE
    rng = np.random.default_rng(sum(loads) + d)
    shared = np.sort(rng.choice(MOVE_TOKENS, cap, replace=False))
    slot_token = np.zeros((len(loads), cap), np.int32)
    for e, n in enumerate(loads):
        kept = min(n, cap)
        if case == "tokens_in_runs":
            start = rng.integers(0, MOVE_TOKENS - kept + 1)
            slot_token[e, :kept] = np.arange(start, start + kept)
        else:
            slot_token[e, :kept] = shared[:kept] if case.startswith(
                "tokens_held") else np.sort(rng.choice(
                    MOVE_TOKENS, kept, replace=False))
    ks = jax.random.split(jax.random.PRNGKey(d), 4)
    kept = np.minimum(loads, cap)
    row = np.arange(cap)[None, :]
    filled = row < kept[:, None]
    tile_end = -(-kept // TILE) * TILE
    return {"x": jax.random.normal(ks[0], (MOVE_TOKENS, d)).astype(dtype),
            "rows": jax.random.normal(ks[1], (len(loads), cap, d)).astype(
                dtype),
            "dy": jax.random.normal(ks[2], (MOVE_TOKENS, d), jnp.float32),
            "slot_token": jnp.asarray(slot_token.reshape(-1)),
            "count": jnp.asarray(loads, jnp.int32), "filled": filled,
            "zeroed": ~filled & (row < tile_end[:, None]),
            "undefined": row >= tile_end[:, None]}


@pytest.mark.parametrize("dtype", [BF16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("case", sorted(MOVES))
def test_rows_move_in_and_out_of_the_buffers(case, dtype, interpreted):
    """`experts_dispatch` against `x[slot_token]` and `experts_combine`
    against `zeros.at[slot_token].add(rows)`, value and gradient, each the
    other's transpose. The interpreter leaves NaN in what a kernel does not
    write: the tiles past a load come out NaN (never written), and NaN
    planted past each load in the rows and cotangents handed in reaches no
    output and no gradient."""
    m = _moves(case, dtype)
    x, st, count, filled = m["x"], m["slot_token"], m["count"], m["filled"]
    held, cap, d = m["rows"].shape
    flat = filled.reshape(-1)
    st_np = np.asarray(st)
    planted = lambda a: jnp.where(jnp.asarray(filled)[:, :, None], a, jnp.nan)

    def into_tokens(rows):
        """The plain sum in float32 of the kept rows into token order."""
        rows = np.asarray(rows, np.float32).reshape(held * cap, d)
        out = np.zeros((MOVE_TOKENS, d), np.float32)
        np.add.at(out, st_np[flat], rows[flat])
        return out

    rows, pull = jax.vjp(
        lambda a: P.experts_dispatch(a, st, count, tile=TILE), x)
    rows = np.asarray(rows, np.float32)
    want = np.asarray(x, np.float32)[st_np].reshape(held, cap, d)
    assert rows.shape == (held, cap, d)
    np.testing.assert_array_equal(rows[filled], want[filled])
    assert (rows[m["zeroed"]] == 0).all()
    assert np.isnan(rows[m["undefined"]]).all()
    # the gradient: every slot's cotangent summed into its token, float32,
    # rounded once to x's dtype
    d_rows = planted(m["rows"])
    (dx,) = pull(d_rows.astype(dtype))
    assert dx.dtype == dtype and np.isfinite(np.asarray(dx, np.float32)).all()
    want_dx = into_tokens(jnp.where(jnp.isnan(d_rows), 0.0, d_rows))
    np.testing.assert_array_equal(np.asarray(dx, np.float32), np.asarray(
        jnp.asarray(want_dx).astype(dtype), np.float32))

    y, pull = jax.vjp(lambda r: P.experts_combine(
        r, st, count, tokens=MOVE_TOKENS, tile=TILE), planted(m["rows"]))
    y = np.asarray(y)
    assert y.dtype == np.float32 and np.isfinite(y).all()
    want_y = into_tokens(jnp.where(jnp.asarray(filled)[:, :, None],
                                   m["rows"], 0))
    assert _rel(y, want_y) < 1e-6 if want_y.any() else not y.any()
    untouched = np.setdiff1d(np.arange(MOVE_TOKENS), st_np[flat])
    assert (y[untouched] == 0).all()
    # the gradient: each kept slot reads its token's cotangent
    (d_rows,) = pull(m["dy"])
    assert d_rows.dtype == dtype and d_rows.shape == (held, cap, d)
    d_rows = np.asarray(d_rows, np.float32)
    want = np.asarray(m["dy"].astype(dtype), np.float32)[st_np].reshape(
        held, cap, d)
    np.testing.assert_array_equal(d_rows[filled], want[filled])
    assert (d_rows[m["zeroed"]] == 0).all()


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
    (dict(NEMOTRON, width=1792), "two_matrix", 128),
    (NEMOTRON, "two_matrix", 128),
    (dict(KANANA, rows_shape=(3, 768, 2048)), "gated", 256)],
    ids=["kanana", "smallthinker", "two_matrix_of_whole_lanes", "nemotron",
         "slots_not_whole_smem_blocks"])
def test_probe_takes_the_decoder_cells_shapes(ctx, family, tile, monkeypatch):
    """1,856 is 14.5 lanes: its blocks take the whole width, and VMEM is
    reckoned at 15 lanes; 256 rows of it would not fit. 3 x 768 slots are
    not whole blocks of 1,024: dispatch and combine pad them."""
    monkeypatch.setattr(P, "_INTERPRET", True)
    helper, moved = _ask(**ctx)
    assert helper is not None
    assert moved["hits"] == {family: 1} and moved["fallbacks"] == {}
    _, cap, d = ctx["rows_shape"]
    assert P._tile(cap, d, ctx["width"], ctx["gated"]) == tile
    assert P.vmem_bytes(tile, d, ctx["width"], ctx["gated"]) < P.VMEM_LIMIT
    assert P.vmem_bytes(tile, d, ctx["width"], ctx["gated"]) == P.vmem_bytes(
        tile, d, -(-ctx["width"] // 128) * 128, ctx["gated"])


@pytest.mark.parametrize("change", [
    dict(dtype=jnp.float32), dict(width=1852),
    dict(rows_shape=(16, 6144, 2000)), dict(rows_shape=(16, 6144 + 64, 2048)),
    dict(rows_shape=(16, 6144, 7168), width=2048)],
    ids=["float32", "width_not_whole_8_row_blocks", "inputs_not_whole_lanes",
         "rows_no_tile_divides", "matrices_past_the_vmem_limit"])
def test_probe_declines_by_shape_and_dtype(change, monkeypatch):
    monkeypatch.setattr(P, "_INTERPRET", True)
    helper, moved = _ask(**dict(KANANA, **change))
    assert helper is None
    assert moved["fallbacks"] == {"unsupported": {"gated": 1}}


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
        moe_ffn_hidden_size=128), "gated", 4),
    # one dense layer, then two expert layers, each gated silu beside a
    # shared MLP
    "latent": (lambda: tiny_deepseek_v3_conf(
        precision="bf16", seq_len=64, hidden_size=128,
        moe_intermediate_size=128), "gated", 2),
    # one two-matrix relu2 expert layer 1.5 lanes wide between the mixers
    "nemotron": (lambda: tiny_nemotron_h_conf(
        precision="bf16", seq_len=64, hidden_size=128,
        moe_intermediate_size=192), "two_matrix", 1),
    # one dense layer, then four expert layers, each gated silu beside a
    # shared expert and summed with it before a post-norm
    "trinity": (lambda: tiny_afmoe_conf(
        precision="bf16", seq_len=64, hidden_size=128,
        moe_intermediate_size=128), "gated", 4),
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
    conf, family, layers = NETS[name]
    before = helper_books()
    builtin_tiles, builtin_score = _fit_once(conf)
    moved = helper_books(before)
    assert moved["hits"].get(family, 0) == 0
    assert moved["fallbacks"]["unsupported"][family] == layers
    monkeypatch.setattr(P, "_INTERPRET", True)
    monkeypatch.setattr(P, "ROW_TILES", (TILE,))
    before = helper_books()
    kernel_tiles, kernel_score = _fit_once(conf)
    moved = helper_books(before)
    assert moved["hits"][family] == layers
    assert family not in moved["fallbacks"].get("unsupported", {})
    assert moved["auto_disable"] == {}
    assert kernel_tiles["filled"] + kernel_tiles["empty"] == 2 * layers * 8
    assert kernel_tiles["filled"] > 0
    assert abs(kernel_score - builtin_score) < 2e-2 * abs(builtin_score)
    assert builtin_tiles["filled"] + builtin_tiles["empty"] == 2 * layers * 8


FIVE_KERNELS = ("experts_dispatch", "experts_fwd", "experts_bwd_rows",
                "experts_bwd_weights", "experts_combine")


def _kernel_calls(jaxpr, stack: str = ""):
    """(name, name stack) of every `pallas_call` in a jaxpr, its inner
    jaxprs' stacks joined to their equation's."""
    from jax.extend import core as jex

    calls = []
    for eqn in jaxpr.eqns:
        here = "/".join(s for s in (stack, str(eqn.source_info.name_stack))
                        if s)
        if eqn.primitive.name == "pallas_call":
            calls.append((eqn.params["name"], here))
            continue
        for p in eqn.params.values():
            for inner in p if isinstance(p, (list, tuple)) else [p]:
                if isinstance(inner, jex.ClosedJaxpr):
                    inner = inner.jaxpr
                if isinstance(inner, jex.Jaxpr):
                    calls += _kernel_calls(inner, here)
    return calls


@pytest.mark.parametrize("name", sorted(NETS))
def test_the_tiny_nets_move_rows_on_the_kernels(name, monkeypatch):
    """Loss and every gradient leaf of a tiny decoder on the five kernels
    within bf16 rounding of the gather, einsums and scatter-add; the slot
    hit once an expert layer and no fallback; dispatch and combine inside
    the layer's `experts` scope, forward, recomputed and backward. The plain
    CPU keeps the built-in lowering (its jaxpr is pinned in
    tests/test_afmoe.py)."""
    conf, family, layers = NETS[name]
    x = np.random.default_rng(0).integers(0, 128, (2, 64)).astype(np.int32)

    def step_of(net):
        """The loss and its gradients: a net of its own for each lowering,
        since a recomputed block keeps the trace it made first."""
        return jax.value_and_grad(lambda p: net._loss(
            p, net.state_list, [jnp.asarray(x)],
            [jnp.asarray(np.roll(x, -1, axis=1))], None, None,
            jax.random.PRNGKey(0)), has_aux=True)

    params = ComputationGraph(conf()).init().params_list
    step = step_of(ComputationGraph(conf()).init())
    (builtin, _), builtin_grads = step(params)
    assert not _kernel_calls(jax.make_jaxpr(step)(params).jaxpr)
    monkeypatch.setattr(P, "_INTERPRET", True)
    monkeypatch.setattr(P, "ROW_TILES", (TILE,))
    step = step_of(ComputationGraph(conf()).init())
    before = helper_books()
    (kernels, _), kernel_grads = step(params)
    moved = helper_books(before)
    assert moved["hits"][family] == layers
    assert family not in moved["fallbacks"].get("unsupported", {})
    assert moved["auto_disable"] == {}
    assert abs(float(kernels) - float(builtin)) < 1e-3 * abs(float(builtin))
    for got, want in zip(jax.tree_util.tree_leaves(kernel_grads),
                         jax.tree_util.tree_leaves(builtin_grads)):
        assert np.isfinite(np.asarray(got, np.float32)).all()
        if np.asarray(want, np.float32).any():
            assert _rel(got, want) < 2e-2
    calls = _kernel_calls(jax.make_jaxpr(step)(params).jaxpr)
    assert {n for n, _ in calls} >= set(FIVE_KERNELS)
    for kernel in ("experts_dispatch", "experts_combine"):
        stacks = [s for n, s in calls if n == kernel]
        assert len(stacks) >= 2 * layers
        assert all(re.search(r"L\w+_sparseexperts\)?/experts/", s)
                   for s in stacks), stacks


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


# -- the whole-lane cells' kernels are the parent's --------------------------------

# recorded from the parent of PR 38 (commit 50fc802) in this container: the
# three kernels' Mosaic bodies without locations, their names and the rest
# of their custom calls' configuration, at the gated cells' shapes
_PARENT_KERNELS = {
    "kanana": (KANANA, {"experts_fwd": "c12d1f0c4894e98f",
                        "experts_bwd_rows": "4b5a09ec8110dcde",
                        "experts_bwd_weights": "80ec36218df72d34"}),
    "smallthinker": (SMALLTHINKER, {"experts_fwd": "ad2b9135abea2eb8",
                                    "experts_bwd_rows": "f153402a4a8b99b2",
                                    "experts_bwd_weights": "e3116e9f6929a42b"}),
}


def _kernel_digests(ctx):
    """{kernel name: digest} of the three kernels as they are lowered for a
    TPU (no chip needed) at a probe's shapes: forward and backward as a
    recomputed block runs them."""
    import base64
    import hashlib
    import json
    import re

    from jax._src.lib.mlir import ir

    n_held, cap, d = ctx["rows_shape"]
    width, gated = ctx["width"], ctx["gated"]

    def grads(rows, w1, w3, w2, slot_w, count, ct):
        run = jax.checkpoint(lambda r, a, b, c, s: P.grouped_experts(
            r, a, b, c, s, count, activation=ctx["activation"]))
        out, pull = jax.vjp(run, rows, w1, w3, w2, slot_w)
        return (out,) + pull(ct)

    arg = jax.ShapeDtypeStruct
    text = jax.jit(grads).trace(
        arg((n_held, cap, d), BF16), arg((n_held, d, width), BF16),
        arg((n_held, d, width), BF16) if gated else None,
        arg((n_held, width, d), BF16), arg((n_held * cap,), jnp.float32),
        arg((n_held,), jnp.int32), arg((n_held, cap, d), jnp.float32),
    ).lower(lowering_platforms=("tpu",)).as_text()
    digests = {}
    for call in re.findall(r"stablehlo.custom_call @tpu_custom_call\(.*", text):
        name = re.search(r'kernel_name = "(\w+)"', call).group(1)
        config = json.loads(re.sub(
            r"\\([0-9A-Fa-f]{2})", lambda m: chr(int(m.group(1), 16)),
            re.search(r'backend_config = "((?:[^"\\]|\\.)*)"', call).group(1)))
        body = base64.b64decode(config["custom_call_config"].pop("body"))
        with ir.Context() as mlir:
            mlir.allow_unregistered_dialects = True
            asm = ir.Module.parse(body).operation.get_asm(
                enable_debug_info=False)
        digests[name] = hashlib.sha256(
            (asm + json.dumps(config, sort_keys=True)).encode()
        ).hexdigest()[:16]
    return digests


@pytest.mark.parametrize("cell", sorted(_PARENT_KERNELS))
def test_whole_lane_cells_lower_the_parents_kernels(cell):
    """Widths of whole lanes and `n_in` of whole 512-column slices take no
    ragged branch: the kernels lowered for a TPU are the parent's."""
    ctx, want = _PARENT_KERNELS[cell]
    assert _kernel_digests(ctx) == want

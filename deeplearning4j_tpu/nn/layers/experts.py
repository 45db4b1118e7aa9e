"""The sparse-expert feed-forward (config: SparseExpertsLayer).

    l = u W_router                       over all `router_width` experts, f32
                                         (or the vertex's second input:
                                         `router_input`, an ExpertRouterLayer)
    s = sigmoid(l) | softmax(l)          `score`
    chosen = the `experts_per_token` largest s (`select_bias`: largest
             s + b_select);  w_i = scaling s_i / sum of the chosen s
    out = sum over chosen i held here of w_i W2_i act(W1_i u)
          (`gated`: w_i W2_i (act(W1_i u) * (W3_i u)))
          + Ws2 act(Ws1 u)               the shared expert, every token

The layer is told which experts it holds (`experts_held`) and computes their
part of the result; what the experts held elsewhere would add is left out.
Assignments to held experts are gathered into one buffer `[held, capacity,
n_in]`, multiplied as one grouped product over the held experts and added
back with their weights. `capacity` is fixed from the configuration, so
every shape is static. No assignment is ever dropped: a step in which some
held expert is sent more than `capacity` tokens takes the exact path instead
(`lax.cond`): every held expert applied to every token under a dense mask,
`tokens / capacity` times the grouped path's products. Such steps are
counted, never silent. The layer's books are layer state (`routed`: tokens
sent to each of the `router_width` experts; `overflow`: assignments beyond
the buffer, each served by the exact path; `peak`: the fullest held expert's
load in one step, of the `rows` its buffer has; `tiles`), carried like batch norm's
running statistics and published by `publish_expert_books`, the kind's
publish hook (the net calls it where utils/devprof already blocks, and at
the end of `fit()`).

The grouped path stays inside the conditional, beside the exact path, and
the buffers stay one per held expert: on the chip the same path outside the
`lax.cond` ran 27 ms a step slower (the compiler then fuses the optimizer's
update into the weight-gradient products and lays the buffers out worse),
and one buffer shared by the held experts under `lax.ragged_dot` won only at
half the rows, which the fullest layer fills to 86% (PERF.md, PR 29). What
fills, multiplies and empties the buffers is one algorithm with two
lowerings, asked for once a layer a trace at the op slot `grouped_experts`
(ops/helpers): on a one-device TPU program with bf16 products, `n_in` of
whole lanes, `width` of whole 8-row blocks (the Nemotron cell's 1,856 is
14.5 lanes) and one expert's matrices inside the kernels' VMEM, the five
kernels of ops/pallas_experts.py, which follow each held expert's load
(`count`: rows past it are never moved and row tiles past it never
multiplied, the float32 hidden arrays stay on the chip; PERF.md §6); else
the gather, the three einsums and the scatter-add over every row, which
only this fallback keeps. The probe declines any other shape or compute
dtype, the CPU and a partitioned program;
`helper_hit_total` / `helper_fallback_total{op="grouped_experts"}` say
which ran. The fifth book, `tiles`, counts the buffers' tiles of 128 rows
that a step's routing filled and left empty, whichever lowering runs
(`experts_row_tiles_total{state}`).
"""

from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.layers.registry import LayerContext, register_layer
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.ops.activations import apply_activation
from deeplearning4j_tpu.ops.helpers import HelperError, get_helper
from deeplearning4j_tpu.utils import metrics as _metrics

_ROWS = 128   # a held expert's capacity is a multiple of this many rows
SCORES = ("sigmoid", "softmax")


def expert_capacity(conf: L.SparseExpertsLayer, tokens: int) -> int:
    """Rows of one held expert's buffer: `capacity_factor` times the uniform
    mean `tokens * experts_per_token / router_width`, rounded up to a
    multiple of 128 (never more than `tokens`: an expert meets a token
    once)."""
    mean = tokens * int(conf.experts_per_token) / int(conf.router_width)
    rows = int(math.ceil(float(conf.capacity_factor) * mean / _ROWS)) * _ROWS
    return max(1, min(rows, tokens))


def experts_init(key, conf: L.SparseExpertsLayer, dtype):
    n_in, width = int(conf.n_in), int(conf.width)
    if int(conf.n_out) != n_in:
        raise ValueError(f"SparseExpertsLayer: n_out {conf.n_out} is not "
                         f"n_in {n_in} (the experts map back to their input)")
    held = conf.held()
    if len(set(held)) != len(held) or not all(
            0 <= e < int(conf.router_width) for e in held):
        raise ValueError(f"experts_held {held} are not distinct experts "
                         f"below router_width {conf.router_width}")
    if conf.score not in SCORES:
        raise ValueError(f"SparseExpertsLayer: score {conf.score!r} is not "
                         f"one of {SCORES}")
    ks = jax.random.split(key, 6)
    mk = lambda k, shape, i, o: init_weights(
        k, shape, i, o, conf.weight_init, conf.dist, dtype)
    p = {"W1": mk(ks[1], (len(held), n_in, width), n_in, width),
         "W2": mk(ks[2], (len(held), width, n_in), width, n_in)}
    if not conf.router_input:
        p["W_router"] = mk(ks[0], (n_in, int(conf.router_width)), n_in,
                           int(conf.router_width))
    if conf.gated:
        p["W3"] = mk(ks[5], (len(held), n_in, width), n_in, width)
    if conf.select_bias:
        # a weight of the checkpoint like any other, from the seed; ks[0]
        # is the router's, which such a layer may not hold
        p["b_select"] = init_weights(
            jax.random.fold_in(ks[0], 1), (int(conf.router_width),), n_in,
            int(conf.router_width), conf.weight_init, conf.dist, jnp.float32)
    if conf.shared_width:
        sw = int(conf.shared_width)
        p["Ws1"] = mk(ks[3], (n_in, sw), n_in, sw)
        p["Ws2"] = mk(ks[4], (sw, n_in), sw, n_in)
    return p


def experts_order(conf):
    return (() if conf.router_input else ("W_router",)) + ("W1", "W2") + (
        ("W3",) if conf.gated else ()) + (
        ("b_select",) if conf.select_bias else ()) + (
        ("Ws1", "Ws2") if conf.shared_width else ())


def experts_state(conf: L.SparseExpertsLayer, dtype):
    """The books since they were last published. int32: a count passes
    2**31 only after 131,072 unpublished steps of 16,384 tokens."""
    return {"routed": jnp.zeros((int(conf.router_width),), jnp.int32),
            "overflow": jnp.zeros((), jnp.int32),
            "peak": jnp.zeros((), jnp.int32),
            "rows": jnp.zeros((), jnp.int32),
            "tiles": jnp.zeros((2,), jnp.int32)}


def route(conf: L.SparseExpertsLayer, scores, b_select=None):
    """scores: [tokens, router_width] float32 -> (chosen experts [tokens, k]
    int32, their weights [tokens, k] float32): the k largest scores,
    normalised over the k chosen (held here or not) and scaled. With
    `b_select` [router_width] the k largest of `scores + b_select` are
    chosen and the weights are the scores' own at those experts, over
    their sum and the family's `1e-20`; nothing differentiable reads
    `b_select`."""
    if b_select is None:
        top, idx = jax.lax.top_k(scores, int(conf.experts_per_token))
        return idx.astype(jnp.int32), \
            float(conf.scaling) * top / jnp.sum(top, axis=-1, keepdims=True)
    _, idx = jax.lax.top_k(scores + b_select.astype(scores.dtype),
                           int(conf.experts_per_token))
    top = jnp.take_along_axis(scores, idx, axis=-1)
    return idx.astype(jnp.int32), float(conf.scaling) * top / (
        jnp.sum(top, axis=-1, keepdims=True) + 1e-20)


def router_logits(x, w):
    """`x W` in float32 at full precision, whatever the net computes in."""
    return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def experts_forward(conf: L.SparseExpertsLayer, params, x, ctx: LayerContext):
    """x: [b, t, n_in] -> [b, t, n_in] in x's dtype, and the books."""
    if ctx.mask is not None:
        raise NotImplementedError(
            "SparseExpertsLayer takes no time mask: a masked token would "
            "still take a row of an expert's buffer")
    shape = x.shape
    d = shape[-1]
    tokens = int(np.prod(shape[:-1]))
    cd = ctx.compute_dtype or x.dtype
    act = lambda a: apply_activation(conf.activation, a)
    mm = lambda a, w: jnp.matmul(a, w, preferred_element_type=jnp.float32)
    xf = x.reshape(tokens, d)
    u = xf.astype(cd)
    held = conf.held()
    n_held, k = len(held), int(conf.experts_per_token)
    cap = expert_capacity(conf, tokens)

    with jax.named_scope("router"):
        # the router reads the layer's input as it came (float32 on a
        # float32 residual stream) at full precision: near-ties among the
        # k largest would otherwise flip with the operands' rounding
        if conf.router_input:
            logits = ctx.extra_inputs[0].reshape(
                tokens, int(conf.router_width)).astype(jnp.float32)
        else:
            logits = router_logits(xf, params["W_router"])
        if conf.score == "softmax":
            # over the chosen it is the softmax of their logits; the row's
            # largest taken out first, so that no exponential overflows
            scores = jnp.exp(logits - jax.lax.stop_gradient(
                jnp.max(logits, axis=-1, keepdims=True)))
        else:
            scores = jax.nn.sigmoid(logits)
        idx, w = route(conf, scores, params.get("b_select"))
        # where each assignment goes: the slot of its expert here (none:
        # held elsewhere) and its rank among that expert's assignments.
        # By comparison and running sum, not by table look-up: a gather or
        # a scatter over the 98,304 assignments of the Nemotron cell takes
        # the chip 0.5-0.8 ms, an elementwise pass a few microseconds
        flat = idx.reshape(-1)                                     # [T k]
        onehot = flat[:, None] == jnp.asarray(held, jnp.int32)
        mine = jnp.any(onehot, axis=1)
        local = jnp.sum(jnp.where(onehot, jnp.arange(
            n_held, dtype=jnp.int32), 0), axis=1)
        rank = jnp.sum(jnp.where(onehot, jnp.cumsum(
            onehot.astype(jnp.int32), axis=0) - 1, 0), axis=1)
        kept = mine & (rank < cap)
        # dropped and foreign assignments all land in one spare row. One
        # scatter says which assignment fills each slot, as its token and
        # which of the token's k choices it is, packed into one integer
        # (shifts: the chip divides integers slowly); its weight follows
        # by one gather
        dest = jnp.where(kept, local * cap + rank, n_held * cap)
        bits = max(1, (k - 1).bit_length())
        label = (jnp.arange(tokens, dtype=jnp.int32)[:, None] << bits) \
            | jnp.arange(k, dtype=jnp.int32)
        slot = jnp.full((n_held * cap + 1,), -1, jnp.int32).at[dest].set(
            label.reshape(-1))[:-1]
        filled = slot >= 0
        slot = jnp.maximum(slot, 0)
        slot_token = slot >> bits
        slot_w = jnp.where(filled, w.reshape(-1)[
            slot_token * k + (slot & ((1 << bits) - 1))], 0.0)
        overflow = jnp.sum(mine & (rank >= cap), dtype=jnp.int32)
        books = {
            "routed": jnp.sum(flat[:, None] == jnp.arange(
                int(conf.router_width), dtype=jnp.int32), axis=0,
                dtype=jnp.int32),
            "overflow": overflow}
        # each held expert's load: its assignments fill its rows from 0
        count = jnp.sum(onehot, axis=0, dtype=jnp.int32)
        books["peak"] = jnp.max(count)
        books["rows"] = jnp.asarray(cap, jnp.int32)
        # whatever multiplies the buffers: the tiles of `_ROWS` rows that
        # hold an assignment, and those that hold none
        filled_tiles = jnp.sum((jnp.minimum(count, cap) + _ROWS - 1) // _ROWS)
        books["tiles"] = jnp.stack([
            filled_tiles, n_held * (-(-cap // _ROWS)) - filled_tiles])

    w1, w2 = params["W1"].astype(cd), params["W2"].astype(cd)
    w3 = params["W3"].astype(cd) if conf.gated else None

    helper = get_helper("grouped_experts", rows_shape=(n_held, cap, d),
                        width=int(conf.width), dtype=cd, gated=bool(conf.gated),
                        activation=conf.activation)

    def products(rows):
        hidden = act(jnp.einsum("ecd,edf->ecf", rows, w1,
                                preferred_element_type=jnp.float32))
        if conf.gated:
            hidden = hidden * jnp.einsum("ecd,edf->ecf", rows, w3,
                                         preferred_element_type=jnp.float32)
        hidden = hidden.astype(cd)
        out_rows = jnp.einsum("ecf,efd->ecd", hidden, w2,
                              preferred_element_type=jnp.float32)
        return out_rows.reshape(n_held * cap, d) * slot_w[:, None]

    def grouped():
        if helper is not None:
            try:
                return helper(u, slot_token, count, w1, w3, w2, slot_w,
                              activation=conf.activation)
            except HelperError:
                pass    # disabled and logged: gather, einsums, scatter-add
        out_rows = products(u[slot_token].reshape(n_held, cap, d))
        return jnp.zeros((tokens, d), jnp.float32).at[slot_token].add(
            out_rows)

    def exact():
        # every held expert over every token, weighted by the router's
        # weight where the token chose it and by nought elsewhere
        def one(y, expert):
            w1_e, w2_e, number = expert[:3]
            weight = jnp.sum(jnp.where(idx == number, w, 0.0), axis=-1)

            def hidden():
                h = act(mm(u, w1_e))
                return (h * mm(u, expert[3]) if conf.gated else h).astype(cd)

            return y + weight[:, None] * mm(hidden(), w2_e), None

        y, _ = jax.lax.scan(jax.checkpoint(one),
                            jnp.zeros((tokens, d), jnp.float32),
                            (w1, w2, jnp.asarray(held, jnp.int32))
                            + ((w3,) if conf.gated else ()))
        return y

    with jax.named_scope("experts"):
        y = jax.lax.cond(overflow > 0, exact, grouped)

    if conf.shared_width:
        with jax.named_scope("shared_expert"):
            y = y + mm(act(mm(u, params["Ws1"].astype(cd))).astype(cd),
                       params["Ws2"].astype(cd))

    state = ctx.state
    if state is not None:
        books = {"routed": state["routed"] + books["routed"],
                 "overflow": state["overflow"] + books["overflow"],
                 "peak": jnp.maximum(state["peak"], books["peak"]),
                 "rows": jnp.maximum(state["rows"], books["rows"]),
                 "tiles": state["tiles"] + books["tiles"]}
    return y.reshape(shape).astype(x.dtype), books


# -- the books, published -------------------------------------------------------

def _instruments():
    reg = _metrics.get_registry()
    return {
        "assignments": reg.counter(
            "experts_assignments_total",
            "token-to-expert assignments routed by the sparse-expert "
            "layers, by whether the expert is held here", ("held",)),
        "overflow": reg.counter(
            "experts_overflow_total",
            "assignments to a held expert beyond its buffer's capacity: "
            "none is dropped, their steps took the exact dense path "
            "(tokens / capacity times the grouped products)").labels(),
        "peak": reg.gauge(
            "experts_peak_load",
            "assignments to the fullest held expert in one step, the "
            "worst layer's and step's since the books were last published "
            "(above the buffer's rows that step took the exact path)"
            ).labels(),
        "fill": reg.gauge(
            "experts_buffer_fill",
            "experts_peak_load over the rows of one held expert's buffer, "
            "the worst layer's and step's since the books were last "
            "published: how near the layer came to the exact path (above "
            "1 it took it)").labels(),
        "tiles": reg.counter(
            "experts_row_tiles_total",
            "tiles of 128 rows of the held experts' buffers, by whether a "
            "step's routing put an assignment into them (filled) or none "
            "(empty): what a lowering that follows each expert's load "
            "multiplies, and what it may skip", ("state",)),
        "load": reg.gauge(
            "experts_load_max_over_mean",
            "the fullest held expert's assignments over the mean of the "
            "held ones, over all sparse-expert layers, since the books "
            "were last published").labels(),
    }


def publish_expert_books(confs, books) -> Dict[str, float]:
    """The publish hook of the kind (`register_layer(publish_fn=)`): host
    copies of the sparse-expert layers' books since they were last
    published go to the registry. The net calls it where the fit loop
    already blocks (devprof's sampled steps, the end of `fit()`), never on
    a plain step, and zeroes the books afterwards."""
    held_n = foreign_n = overflow = peak = 0
    tiles = np.zeros((2,), np.int64)
    fill = 0.0
    loads: List[np.ndarray] = []
    for conf, b in zip(confs, books):
        peak = max(peak, int(b["peak"]))
        fill = max(fill, int(b["peak"]) / max(int(b["rows"]), 1))
        routed = np.asarray(b["routed"], np.int64)
        mine = routed[conf.held()]
        held_n += int(mine.sum())
        foreign_n += int(routed.sum() - mine.sum())
        overflow += int(b["overflow"])
        tiles += np.asarray(b["tiles"], np.int64)
        loads.append(mine)
    ins = _instruments()
    ins["assignments"].labels("1").inc(held_n)
    ins["assignments"].labels("0").inc(foreign_n)
    ins["overflow"].inc(overflow)
    ins["tiles"].labels("filled").inc(int(tiles[0]))
    ins["tiles"].labels("empty").inc(int(tiles[1]))
    out = {"held": held_n, "foreign": foreign_n, "overflow": overflow,
           "peak": peak, "fill": fill, "tiles_filled": int(tiles[0]),
           "tiles_empty": int(tiles[1])}
    ins["peak"].set(peak)
    ins["fill"].set(fill)
    if held_n:
        ratio = max(float(m.max()) / max(float(m.mean()), 1e-30)
                    for m in loads if m.sum())
        ins["load"].set(ratio)
        out["load_max_over_mean"] = ratio
    if overflow:
        import logging

        logging.getLogger("deeplearning4j_tpu").warning(
            "SparseExpertsLayer: %d assignments beyond a held expert's "
            "buffer (experts_overflow_total; the fullest expert was sent "
            "%.2f times its rows, experts_buffer_fill); none was dropped, "
            "their steps took the exact dense path. A larger "
            "capacity_factor makes such steps rarer", overflow, fill)
    return out


register_layer(L.SparseExpertsLayer, experts_init, experts_forward,
               order_fn=experts_order, state_fn=experts_state,
               publish_fn=publish_expert_books)


# -- the router as a vertex of its own ------------------------------------------

def router_init(key, conf: L.ExpertRouterLayer, dtype):
    n_in, n_out = int(conf.n_in), int(conf.n_out)
    return {"W": init_weights(key, (n_in, n_out), n_in, n_out,
                              conf.weight_init, conf.dist, dtype)}


def router_forward(conf: L.ExpertRouterLayer, params, x, ctx: LayerContext):
    """x: [b, t, n_in] -> the logits [b, t, n_out] in float32."""
    return router_logits(x, params["W"]), None


register_layer(L.ExpertRouterLayer, router_init, router_forward,
               order_fn=lambda conf: ("W",))

"""Fused grouped experts — the held experts' products, activation, gate,
rounding and slot weights in Pallas kernels that follow each expert's load,
forward and backward.

Why: the built-in lowering (nn/layers/experts.grouped) multiplies every row
of every held expert's buffer, and between its three einsums holds the
float32 hidden arrays `[held, capacity, width]` in HBM. A buffer has room
for `capacity_factor` times the mean load, so over a run a quarter of the
rows or fewer hold an assignment (PERF.md, PR 28-35): the products ran near
the MXU's peak on rows nobody was sent to. Here the grid walks (held expert,
row tile) and reads one scalar-prefetched `count[e]`, the expert's load this
step (the slots fill from row 0): a tile whose first row is at or past it is
not multiplied, fetches nothing (its block indices stay where the last
multiplied tile left them) and writes zeros; inside the one partly filled
tile the rows at or past `count[e]` are masked by their index, so that
nothing an unfilled row holds reaches the output or a weight gradient. The
hidden arrays live in VMEM only.

Same mathematics and rounding points as `grouped()`: operands of the
compute dtype, float32 accumulation, the activation and the gate in
float32, `hidden` rounded once to the compute dtype, `out = (hidden W2) *
slot_w` in float32.

Three kernels under one `custom_vjp`, whose residuals are its inputs (so a
recomputed block runs no second `experts_fwd` unless something reads its
result): `experts_fwd`; `experts_bwd_rows`, which recomputes `hidden` in
the tile and makes `d_rows`, `d_slot_w` and `dW2` (the last in a float32
block that stays in VMEM over an expert's tiles) and hands the
pre-activations' cotangents on in the compute dtype; `experts_bwd_weights`,
which reduces `rows^T d_pre` over the expert's multiplied tiles to `dW1`
and `dW3`. One kernel for all of it would keep an expert's three matrices
and their three float32 gradients resident: 59 MB at 2,560 x 768 before a
tile is fetched.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.activations import apply_activation
from deeplearning4j_tpu.ops.helpers import interpret_mode

_INTERPRET = False  # flipped by tests on CPU; read through _interpret()

# Rows of one tile: the largest of these that divides a buffer's rows and
# whose kernels fit `VMEM_LIMIT`. On the chip at both decoder cells' shapes
# the three kernels run at 87-94% of the MXU's peak over full buffers at
# 256 and at 128; at 512 `experts_bwd_rows` is a sixth slower at 2,560 x
# 768, and at a mean load of 768 rows a tile of 512 multiplies 256 spare
# rows an expert (PERF.md, PR 36).
ROW_TILES = (256, 128)

LANES = 128
COLUMNS = 512   # of an `n_in`-wide product taken at a time (`_chunks`)
VMEM_LIMIT = 64 * 1024 * 1024

_NN = (((1,), (0,)), ((), ()))   # a @ b
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _interpret() -> bool:
    return interpret_mode(_INTERPRET)


def _dot(a, b, dims=_NN):
    """A product of two operands of the compute dtype, accumulated in
    float32. The precision is spelled out: a process-wide
    `jax_default_matmul_precision` of "highest" would ask Mosaic for a
    float32 product of bf16 operands, which it refuses."""
    return jax.lax.dot_general(a, b, dims,
                               precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.float32)


def _lanes(row):
    """A `[1, tile]` row of per-row scalars as `[tile, LANES]`: each row's
    scalar along its lanes (`_wide` repeats it to a block's width)."""
    return jnp.broadcast_to(row, (LANES, row.shape[1])).T


def _wide(lanes, width: int):
    return jnp.tile(lanes, (1, width // LANES))


def _as_row(column):
    """`[tile, 1]` per-row scalars as one `[1, tile]` row."""
    return jnp.broadcast_to(column, (column.shape[0], LANES)).T[:1]


def _chunks(n: int):
    """Slices of `COLUMNS` along an axis of `n` (whole lanes): the kernels
    take an `n_in`-wide product a slice at a time, so that no float32 array
    of `[tile, n_in]` or `[width, n_in]` is live beside the blocks."""
    step = next(c for c in (COLUMNS, 256, LANES) if n % c == 0)
    return [slice(c, c + step) for c in range(0, n, step)]


def _by_load(count_ref, rows_ref, skipped, step):
    """One grid step of (expert, row tile): `skipped()` where the tile's
    first row is at or past the expert's load, else `step(keep)` with `keep
    [tile, 1]` the rows below it (all of them but in the one partly filled
    tile)."""
    tile = rows_ref.shape[0]
    n = count_ref[pl.program_id(0)] - pl.program_id(1) * tile
    pl.when(n <= 0)(skipped)

    @pl.when(n > 0)
    def _():
        step(jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0) < n)


def _hidden(x, w1_ref, w3_ref, activation: str, keep):
    """(`hidden` in float32 with the rows past the load at 0, and for the
    backward: the activation's pull-back, the activation's value and the
    gate's pre-activation)."""
    a, pull = jax.vjp(lambda pre: apply_activation(activation, pre),
                      _dot(x, w1_ref[...]))
    pre3 = None if w3_ref is None else _dot(x, w3_ref[...])
    h = jnp.where(keep, a if pre3 is None else a * pre3, 0.0)
    return h, pull, a, pre3


def _fwd_kernel(count_ref, last_ref, wsel_ref, rows_ref, sw_ref, *refs,
                activation, gated):
    w1_ref, w3_ref, w2_ref, out_ref = refs if gated else (
        refs[0], None, refs[1], refs[2])

    def skipped():
        out_ref[...] = jnp.zeros_like(out_ref)

    def step(keep):
        x = rows_ref[...]
        h = _hidden(x, w1_ref, w3_ref, activation, keep)[0].astype(x.dtype)
        sw = _lanes(sw_ref[...])
        for c in _chunks(out_ref.shape[1]):
            out = _dot(h, w2_ref[:, c])
            out_ref[:, c] = out * _wide(sw, out.shape[1])

    _by_load(count_ref, rows_ref, skipped, step)


def _bwd_rows_kernel(count_ref, last_ref, wsel_ref, rows_ref, sw_ref, do_ref,
                     *refs, activation, gated):
    if gated:
        (w1_ref, w3_ref, w2_ref, dr_ref, dsw_ref, dw2_ref, dp1_ref,
         dp3_ref) = refs
    else:
        w1_ref, w2_ref, dr_ref, dsw_ref, dw2_ref, dp1_ref = refs
        w3_ref = dp3_ref = None

    @pl.when(pl.program_id(1) == 0)
    def _():
        dw2_ref[...] = jnp.zeros_like(dw2_ref)

    def skipped():
        dr_ref[...] = jnp.zeros_like(dr_ref)
        dsw_ref[...] = jnp.zeros_like(dsw_ref)

    def step(keep):
        x = rows_ref[...]
        cd = x.dtype
        hf, pull, a, pre3 = _hidden(x, w1_ref, w3_ref, activation, keep)
        h = hf.astype(cd)
        sw = _lanes(sw_ref[...])
        # out = (h W2) * slot_w: the unweighted pull-back serves `d_slot_w`
        # and, weighted, `d_hidden`
        g = jnp.zeros_like(hf)
        for c in _chunks(do_ref.shape[1]):
            do = do_ref[:, c]
            g += _dot(do.astype(cd), w2_ref[:, c], _NT)
            dw2_ref[:, c] += _dot(
                h, (do * _wide(sw, do.shape[1])).astype(cd), _TN)
        dsw_ref[...] = _as_row(jnp.sum(h.astype(jnp.float32) * g, axis=-1,
                                       keepdims=True))
        dh = g * _wide(sw, g.shape[1])
        cotangents = [pull(dh if pre3 is None else dh * pre3)[0]] + (
            [] if pre3 is None else [dh * a])
        dp = [jnp.where(keep, c, 0.0).astype(cd) for c in cotangents]
        dp1_ref[...] = dp[0]
        if pre3 is not None:
            dp3_ref[...] = dp[1]
        for c in _chunks(dr_ref.shape[1]):
            dr = _dot(dp[0], w1_ref[c, :], _NT)
            if pre3 is not None:
                dr += _dot(dp[1], w3_ref[c, :], _NT)
            dr_ref[:, c] = dr.astype(dr_ref.dtype)

    _by_load(count_ref, rows_ref, skipped, step)


def _bwd_weights_kernel(count_ref, last_ref, rows_ref, *refs, gated):
    dp_refs, dw_refs = (refs[:2], refs[2:]) if gated else (refs[:1], refs[1:])

    @pl.when(pl.program_id(1) == 0)
    def _():
        for dw_ref in dw_refs:
            dw_ref[...] = jnp.zeros_like(dw_ref)

    def step(keep):
        for c in _chunks(rows_ref.shape[1]):
            x = rows_ref[:, c]
            x = jnp.where(keep, x, jnp.zeros_like(x))
            for dp_ref, dw_ref in zip(dp_refs, dw_refs):
                dw_ref[c, :] += _dot(x, dp_ref[...], _TN)

    _by_load(count_ref, rows_ref, lambda: None, step)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _walk(count, n_tiles: int, tile: int):
    """The scalar-prefetched vectors, one entry a held expert: its load,
    its last multiplied tile (a skipped tile's blocks stay there, so the
    pipeline fetches nothing for it) and the expert whose matrices are
    resident while its tiles are walked (an expert nobody was sent to keeps
    its predecessor's)."""
    count = jnp.minimum(count.astype(jnp.int32), n_tiles * tile)
    last = jnp.maximum((count + tile - 1) // tile - 1, 0)
    experts = jnp.arange(count.shape[0], dtype=jnp.int32)
    wsel = jnp.maximum(jax.lax.cummax(jnp.where(count > 0, experts, -1)), 0)
    return count, last, wsel


def _rows_spec(tile: int, cols: int, pinned: bool = True):
    """A row tile `[tile, cols]` of a buffer. Pinned: a tile past the
    expert's load keeps the last multiplied tile's block, so that nothing
    is fetched (an input) or written back (an output) for it. Not pinned:
    an output every tile of which is written."""
    if pinned:
        return pl.BlockSpec((None, tile, cols), lambda e, i, c, last, *_: (
            e, jnp.minimum(i, last[e]), 0))
    return pl.BlockSpec((None, tile, cols), lambda e, i, *_: (e, i, 0))


def _scalars_spec(tile: int, pinned: bool = True):
    """One `[1, tile]` row of per-row scalars, pinned or written alike."""
    if pinned:
        return pl.BlockSpec((None, 1, tile), lambda e, i, c, last, *_: (
            e, 0, jnp.minimum(i, last[e])))
    return pl.BlockSpec((None, 1, tile), lambda e, i, *_: (e, 0, i))


def _expert_spec(shape, selected: bool):
    """An expert's matrix (selected: `wsel`'s, so that an idle expert
    fetches none) or its float32 gradient. It changes once in `cap // tile`
    steps: one buffer. With two, and with float32 temporaries as wide as
    `n_in` in the body, the chip copied every block in and out on every
    step, skipped ones too (33 us a step at 2,048 x 768; PERF.md, PR 36)."""
    index = (lambda e, i, c, last, wsel: (wsel[e], 0, 0)) if selected \
        else (lambda e, i, *_: (e, 0, 0))
    return pl.BlockSpec((None,) + tuple(shape), index,
                        pipeline_mode=pl.Buffered(1))


def _forward(rows, w1, w3, w2, sw, count, activation, tile):
    n_held, cap, d = rows.shape
    gated = w3 is not None
    weights = [w1, w3, w2] if gated else [w1, w2]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, activation=activation, gated=gated),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n_held, cap // tile),
            in_specs=[_rows_spec(tile, d), _scalars_spec(tile)]
            + [_expert_spec(w.shape[1:], True) for w in weights],
            out_specs=_rows_spec(tile, d, pinned=False)),
        out_shape=jax.ShapeDtypeStruct((n_held, cap, d), jnp.float32),
        compiler_params=_params(), name="experts_fwd",
        interpret=_interpret(),
    )(*_walk(count, cap // tile, tile), rows, sw, *weights)


def _backward(rows, w1, w3, w2, sw, count, do, activation, tile):
    n_held, cap, d = rows.shape
    width = w1.shape[2]
    gated = w3 is not None
    cd = rows.dtype
    weights = [w1, w3, w2] if gated else [w1, w2]
    walk = _walk(count, cap // tile, tile)
    n_dp = 2 if gated else 1
    pre_cotangent = jax.ShapeDtypeStruct((n_held, cap, width), cd)
    d_rows, d_sw, dw2, *dp = pl.pallas_call(
        functools.partial(_bwd_rows_kernel, activation=activation,
                          gated=gated),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n_held, cap // tile),
            in_specs=[_rows_spec(tile, d), _scalars_spec(tile),
                      _rows_spec(tile, d)]
            + [_expert_spec(w.shape[1:], True) for w in weights],
            out_specs=[_rows_spec(tile, d, pinned=False),
                       _scalars_spec(tile, pinned=False),
                       _expert_spec(w2.shape[1:], False)]
            + [_rows_spec(tile, width)] * n_dp),
        out_shape=[jax.ShapeDtypeStruct(rows.shape, cd),
                   jax.ShapeDtypeStruct(sw.shape, jnp.float32),
                   jax.ShapeDtypeStruct(w2.shape, jnp.float32)]
        + [pre_cotangent] * n_dp,
        compiler_params=_params(), name="experts_bwd_rows",
        interpret=_interpret(),
    )(*walk, rows, sw, do, *weights)
    dw = pl.pallas_call(
        functools.partial(_bwd_weights_kernel, gated=gated),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_held, cap // tile),
            in_specs=[_rows_spec(tile, d)] + [_rows_spec(tile, width)] * n_dp,
            out_specs=[_expert_spec(w1.shape[1:], False)] * n_dp),
        out_shape=[jax.ShapeDtypeStruct(w1.shape, jnp.float32)] * n_dp,
        compiler_params=_params(), name="experts_bwd_weights",
        interpret=_interpret(),
    )(*walk[:2], rows, *dp)
    return (d_rows, dw[0].astype(w1.dtype),
            dw[1].astype(w3.dtype) if gated else None, dw2.astype(w2.dtype),
            d_sw, None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _experts(rows, w1, w3, w2, sw, count, activation, tile):
    return _forward(rows, w1, w3, w2, sw, count, activation, tile)


def _experts_fwd(rows, w1, w3, w2, sw, count, activation, tile):
    return (_forward(rows, w1, w3, w2, sw, count, activation, tile),
            (rows, w1, w3, w2, sw, count))


def _experts_bwd(activation, tile, res, do):
    return _backward(*res, do, activation, tile)


_experts.defvjp(_experts_fwd, _experts_bwd)


def grouped_experts(rows, w1, w3, w2, slot_w, count, *, activation: str):
    """`experts.grouped()`'s products: rows `[held, cap, n_in]`, w1 (and w3,
    or None) `[held, n_in, width]`, w2 `[held, width, n_in]`, all of the
    compute dtype; slot_w `[held * cap]` float32, 0 at an unfilled slot;
    count `[held]` int32, each expert's load, whose assignments fill its
    rows from 0 -> `(act(rows w1) [* (rows w3)]) w2 * slot_w` as `[held,
    cap, n_in]` float32, exactly 0 in every row at or past `count`."""
    n_held, cap, d = rows.shape
    tile = _tile(cap, d, w1.shape[2], w3 is not None)
    return _experts(rows, w1, w3, w2, slot_w.reshape(n_held, 1, cap), count,
                    str(activation), tile)


def vmem_bytes(tile: int, d: int, width: int, gated: bool) -> int:
    """What the largest of the three kernels, `experts_bwd_rows`, asks of
    VMEM at bf16 operands: the expert's matrices and the float32 `dW2`
    block (one buffer each), the row tiles in and out (two each) and the
    float32 arrays of the body (eight of `[tile, width]`, and per slice of
    `COLUMNS` the cotangent's, the product's and `dW2`'s)."""
    n_w = 3 if gated else 2
    resident = n_w * d * width * 2 + d * width * 4
    tiles = 2 * tile * d * (2 + 4 + 2) + 2 * (n_w - 1) * tile * width * 2
    body = tile * width * 4 * 8 + COLUMNS * 4 * (4 * tile + width)
    return resident + tiles + body


def _tile(cap: int, d: int, width: int, gated: bool) -> Optional[int]:
    return next((n for n in ROW_TILES if cap % n == 0
                 and vmem_bytes(n, d, width, gated) <= VMEM_LIMIT), None)


def supported(*, rows_shape, width, dtype, gated, **_):
    """A pure function of backend, shapes and dtype: a TPU (or the
    interpreter in a CPU test), bf16 operands, inputs and hidden width of
    whole lanes, a buffer some row tile divides, and that tile's blocks
    with one expert's matrices inside `VMEM_LIMIT`."""
    _, cap, d = rows_shape
    if not (_interpret() or jax.default_backend() == "tpu"):
        return False
    if jnp.dtype(dtype) != jnp.bfloat16:
        return False
    if d % LANES or width % LANES:
        return False
    return _tile(cap, d, width, gated) is not None


def register():
    from deeplearning4j_tpu.ops.helpers import register_helper

    register_helper(
        "grouped_experts", grouped_experts, supported,
        name="pallas_grouped_experts",
        family=lambda *, gated, **_: "gated" if gated else "two_matrix")


register()

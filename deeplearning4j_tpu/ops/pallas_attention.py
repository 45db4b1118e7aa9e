"""Fused grouped-query causal attention — scores, band mask, softmax and
mix in one Pallas kernel with an online softmax, forward and backward.

Why: the built-in lowering (nn/layers/attention.grouped_query_attention)
holds one block of 256 queries' float32 scores `[b, KV, G, 256, keys]` in
HBM and passes over them six times a block (scores, maximum, exponential,
sum and divide, cast; again recomputed; again as cotangents): 440 of the
664 ms the SmallThinker cell's attention layers took were those passes
(PERF.md, PR 32). Here a `[block, block]` tile of scores lives in
VMEM only: per query block the kernel keeps a running maximum, a running
sum and a float32 accumulator (the online softmax) and writes `o` and one
log-sum-exp a query; the backward recomputes each tile's probabilities
from that log-sum-exp.

Same mathematics and precision as `attention._attend_block`: scores
accumulate in float32 from operands of the inputs' dtype, the
`1 / sqrt(head_dim)` scale multiplies the float32 scores (no rounding of
`q`), mask and softmax in float32, the probabilities are cast to `v`'s
dtype for the mix, accumulation in float32. A query at `qpos` sees the key
at `kpos` when `qpos >= kpos` and, with a window, `qpos - kpos < window`.

Band-aware: the grid's last axis walks a table of the (query block, key
block) pairs that hold a visible key, made from `t`, `window` and the
block size at trace time and handed to the kernel as scalar prefetch; a
pair above the diagonal or wholly before the window is never fetched, and
only the pairs the band's edge crosses compute a mask.

Two kernels under one `custom_vjp`, both walking the pairs by query
block: `gqa_fwd`, and `gqa_bwd`, which makes `dq`, `dk` and `dv` from one
recomputation of a tile's probabilities (five products a tile).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.helpers import interpret_mode

_INTERPRET = False  # flipped by tests on CPU; read through _interpret()

# Rows of queries and of keys in a square tile of scores: the largest of
# these that divides the sequence. On the chip, forward + recomputed
# forward + backward of a SmallThinker window layer took 29.6 ms at 1,024
# and at 512, its full layer 34.2 / 36.1, 45-55 at 256 queries (PERF.md, PR
# 33); tests take 128.
BLOCKS = (1024, 512, 256, 128)

LANES = 128
# a masked score: finite, so that a row whose first tile is wholly masked
# reads exp(0) there and is wiped by the next tile's exp(-huge) = 0
MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)
VMEM_LIMIT = 64 * 1024 * 1024
# of which the backward may keep resident: one key-value head's `dk` and
# `dv`, each a float32 scratch and a double-buffered output block of [t, D]
RESIDENT_LIMIT = 32 * 1024 * 1024

_NN = (((1,), (0,)), ((), ()))   # a @ b
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_FIRST, _LAST, _EDGE = 1, 2, 4


def _interpret() -> bool:
    return interpret_mode(_INTERPRET)


def band_pairs(t: int, window: Optional[int], block: int):
    """The (query block, key block) pairs of `block` x `block` positions
    that hold a visible key, as three int32 arrays `(qi, kj, flags)` in the
    order the grid walks them: by query block, keys ascending. Flags:
    `_FIRST` / `_LAST` pair of its query block, `_EDGE` when the band's
    edge crosses the tile, so that it needs a mask."""
    pairs = []
    for i in range(t // block):
        q0, q1 = i * block, (i + 1) * block - 1
        lo = 0 if window is None else max(0, q0 - window + 1) // block
        for j in range(lo, i + 1):
            k0, k1 = j * block, (j + 1) * block - 1
            whole = k1 <= q0 and (window is None or q1 - k0 < window)
            pairs.append((i, j, 0 if whole else _EDGE))
    qi, kj, flags = (np.array(c, np.int32) for c in zip(*pairs))
    change = np.flatnonzero(np.diff(qi)) + 1
    flags[np.concatenate([[0], change])] |= _FIRST
    flags[np.concatenate([change - 1, [len(qi) - 1]])] |= _LAST
    return qi, kj, flags


def _dot(a, b, dims=_NN):
    """A product of two operands of the inputs' dtype, accumulated in
    float32. The precision is spelled out: a process-wide
    `jax_default_matmul_precision` of "highest" would ask Mosaic for a
    float32 product of bf16 operands, which it refuses."""
    return jax.lax.dot_general(a, b, dims,
                               precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.float32)


def _mask(s, i, j, window, q_axis: int):
    """The scores of tile (query block `i`, key block `j`) with what no
    query sees at `MASK_VALUE`; queries run along `q_axis` of `s`."""
    block = s.shape[0]
    qpos = i * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    kpos = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                1 - q_axis)
    seen = qpos >= kpos
    if window is not None:
        seen &= qpos - kpos < window
    return jnp.where(seen, s, MASK_VALUE)


def _tile_lanes(x, n: int):
    """[rows, LANES] lane-replicated statistics against `n` columns."""
    return jnp.tile(x, (1, n // LANES))


def _either(flag, step):
    """`step(masked)` with the mask only where the band's edge crosses
    the tile."""
    pl.when(flag & _EDGE != 0)(functools.partial(step, True))
    pl.when(flag & _EDGE == 0)(functools.partial(step, False))


def _fwd_kernel(qi_ref, kj_ref, fl_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, window):
    p = pl.program_id(2)
    i, j, flag = qi_ref[p], kj_ref[p], fl_ref[p]

    @pl.when(flag & _FIRST != 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, MASK_VALUE)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked):
        s = _dot(q_ref[...], k_ref[...], _NT)
        if masked:
            s = _mask(s, i, j, window, q_axis=0)
        m_prev = m_scr[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        e = jnp.exp((s - _tile_lanes(m_next, s.shape[1])) * scale)
        alpha = jnp.exp((m_prev - m_next) * scale)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(e, axis=-1, keepdims=True)
        m_scr[...] = m_next
        acc_scr[...] = (
            _tile_lanes(alpha, acc_scr.shape[1]) * acc_scr[...]
            + _dot(e.astype(v_ref.dtype), v_ref[...]))

    _either(flag, step)

    @pl.when(flag & _LAST != 0)
    def _():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] * _tile_lanes(1.0 / l, acc_scr.shape[1])
                      ).astype(o_ref.dtype)
        # lane-replicated [bq, LANES] -> one row [1, bq], as the backward
        # reads it (keys in rows there)
        lse_ref[...] = (m_scr[...] * scale + jnp.log(l)).T[:1]


def _bwd_kernel(qi_ref, kj_ref, fl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                di_ref, dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *,
                scale, window, group):
    g, p = pl.program_id(2), pl.program_id(3)
    i, j, flag = qi_ref[p], kj_ref[p], fl_ref[p]
    block = k_ref.shape[0]

    @pl.when((p == 0) & (g == 0))
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(flag & _FIRST != 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def step(masked):
        # keys in rows: the per-query statistics are row vectors [1, bq]
        q, k, do = q_ref[...], k_ref[...], do_ref[...]
        s = _dot(k, q, _NT)
        if masked:
            s = _mask(s, i, j, window, q_axis=1)
        prob = jnp.exp(s * scale - lse_ref[...])
        keys = pl.ds(pl.multiple_of(j * block, block), block)
        dv_scr[keys, :] += _dot(prob.astype(do.dtype), do)
        ds = prob * (_dot(v_ref[...], do, _NT) - di_ref[...])
        dk_scr[keys, :] += _dot(ds.astype(q.dtype), q)
        dq_scr[...] += _dot(ds.T.astype(k.dtype), k)

    _either(flag, step)

    @pl.when(flag & _LAST != 0)
    def _():
        dq_ref[...] = (dq_scr[...] * scale).astype(dq_ref.dtype)

    @pl.when((p == pl.num_programs(3) - 1) & (g == group - 1))
    def _():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _params(n_axes: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel")
        + ("arbitrary",) * (n_axes - 2),
        vmem_limit_bytes=VMEM_LIMIT)


def _forward(q, k, v, window, block, scale):
    """q [b, H, t, D], k [b, KV, t, D], v [b, KV, t, Dv] -> o [b, H, t, Dv]
    float32 and the log-sum-exp of each query's scaled scores [b, H, 1,
    t]."""
    b, H, t, D = q.shape
    Dv = v.shape[-1]
    G = H // k.shape[1]
    qi, kj, flags = band_pairs(t, window, block)
    rows = lambda width, index: pl.BlockSpec((None, None, block, width), index)
    of_query = lambda b_, h, p, qi, kj, fl: (b_, h, qi[p], 0)
    of_key = lambda b_, h, p, qi, kj, fl: (b_, h // G, kj[p], 0)
    row_spec = pl.BlockSpec((None, None, 1, block),
                            lambda b_, h, p, qi, kj, fl: (b_, h, 0, qi[p]))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, H, len(qi)),
            in_specs=[rows(D, of_query), rows(D, of_key), rows(Dv, of_key)],
            out_specs=[rows(Dv, of_query), row_spec],
            scratch_shapes=[pltpu.VMEM((block, LANES), jnp.float32),
                            pltpu.VMEM((block, LANES), jnp.float32),
                            pltpu.VMEM((block, Dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, H, t, Dv), jnp.float32),
                   jax.ShapeDtypeStruct((b, H, 1, t), jnp.float32)],
        compiler_params=_params(3), name="gqa_fwd", interpret=_interpret(),
    )(qi, kj, flags, q, k, v)


def _backward(q, k, v, o, lse, do, window, block, scale):
    """One kernel for `dq`, `dk` and `dv`, query-major over the pairs of one
    query head after another: `dq` accumulates over a query block's keys in
    a `[block, D]` scratch, `dk` and `dv` over all the queries of the `G`
    heads that share the key-value head in a `[t, D]` and a `[t, Dv]`
    float32 scratch that stay in VMEM (8 MB at 8,192 positions of 128)."""
    b, H, t, D = q.shape
    Dv = v.shape[-1]
    KV = k.shape[1]
    G = H // KV
    di = jnp.sum(o * do, axis=-1)                       # [b, H, t] float32
    qi, kj, flags = band_pairs(t, window, block)
    rows = lambda width, index: pl.BlockSpec((None, None, block, width), index)
    of_query = lambda b_, h, g, p, qi, kj, fl: (b_, h * G + g, qi[p], 0)
    of_key = lambda b_, h, g, p, qi, kj, fl: (b_, h, kj[p], 0)
    row_spec = pl.BlockSpec(
        (None, None, 1, block),
        lambda b_, h, g, p, qi, kj, fl: (b_, h * G + g, 0, qi[p]))
    whole = lambda width: pl.BlockSpec(
        (None, None, t, width), lambda b_, h, g, p, qi, kj, fl: (b_, h, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, window=window, group=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, KV, G, len(qi)),
            in_specs=[rows(D, of_query), rows(D, of_key), rows(Dv, of_key),
                      rows(Dv, of_query), row_spec, row_spec],
            out_specs=[rows(D, of_query), whole(D), whole(Dv)],
            scratch_shapes=[pltpu.VMEM((block, D), jnp.float32),
                            pltpu.VMEM((t, D), jnp.float32),
                            pltpu.VMEM((t, Dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=_params(4), name="gqa_bwd", interpret=_interpret(),
    )(qi, kj, flags, q, k, v, do.astype(v.dtype), lse, di[:, :, None, :])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attend(q, k, v, window, block, scale):
    return _forward(q, k, v, window, block, scale)[0]


def _attend_fwd(q, k, v, window, block, scale):
    o, lse = _forward(q, k, v, window, block, scale)
    return o, (q, k, v, o, lse)


def _attend_bwd(window, block, scale, res, do):
    return _backward(*res, do, window, block, scale)


_attend.defvjp(_attend_fwd, _attend_bwd)


def gqa_attention(q, k, v, *, causal: bool, window=None):
    """`attention.grouped_query_attention`'s contract: q [b, t, H, D], k
    [b, t, KV, D], v [b, t, KV, Dv] -> [b, t, H, Dv] float32, query head
    `h` on key-value head `h // (H // KV)`, the scores scaled by `D **
    -0.5` of the width the layer handed over. The kernels take heads in
    front of positions; the transposes are XLA's."""
    assert causal, "the probe declines a layer that is not causal"
    heads_first = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    o = _attend(heads_first(q), heads_first(k), heads_first(v),
                None if window is None else int(window), _block(q.shape[1]),
                q.shape[-1] ** -0.5)
    return heads_first(o)


def _block(t: int) -> Optional[int]:
    return next((n for n in BLOCKS if t % n == 0), None)


def supported(*, q_shape, dtype, causal, v_head_dim=None, **_):
    """A pure function of backend, shapes and dtype: a TPU (or the
    interpreter in a CPU test), a causal layer, bf16 operands, value heads
    of whole lanes and query/key heads of whole half-lanes (a block's last
    dimension is then the whole head: 192 beside 128 is the latent layer's),
    a sequence the smallest block divides and whose `dk` and `dv` the
    backward can keep in VMEM (16,384 positions at 128 + 128, 12,288 at
    192 + 128)."""
    _, t, _, head_dim = q_shape
    v_head_dim = head_dim if v_head_dim is None else v_head_dim
    if not (_interpret() or jax.default_backend() == "tpu"):
        return False
    if not causal or jnp.dtype(dtype) != jnp.bfloat16:
        return False
    if head_dim % (LANES // 2) or v_head_dim % LANES or _block(t) is None:
        return False
    # each a float32 scratch and a double-buffered bf16 output block
    return t * (head_dim + v_head_dim) * (4 + 2 * 2) <= RESIDENT_LIMIT


def register():
    from deeplearning4j_tpu.ops.helpers import register_helper

    register_helper(
        "gqa_attention", gqa_attention, supported, name="pallas_gqa_attention",
        family=lambda *, window, **_: "full" if window is None else "window")


register()

"""Sequence/context parallelism: ring attention over a mesh axis.

NEW capability beyond the reference (SURVEY §5 "long-context: absent" —
DL4J's only long-sequence tool is truncated BPTT). For sequences too long
for one chip's HBM, the sequence axis is sharded over the mesh and
attention runs as a RING: each device holds one query block permanently
and passes its key/value block around the "seq" axis with ppermute,
accumulating attention with the online-softmax (flash-style) update so
the full [T, T] score matrix never materializes. After `p` hops every
query block has attended to every kv block; communication rides ICI
neighbor links (the pattern of Ring Attention, Liu et al.; blockwise
streaming softmax, Rabe & Staats).

All functions here are written to run under `shard_map` over a Mesh axis
named ``axis_name`` — see ``ring_self_attention`` for the user-facing
entry and tests/test_sequence_parallel.py for the 8-device CPU-mesh
equivalence proof vs single-device full attention.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

SEQ_AXIS = "seq"


def _block_attend(q, k, v, *, scale, causal, q_start, kv_start):
    """Scores of one (q-block, kv-block) pair + unnormalized streaming
    stats. q: [B, Tq, H, D]; k/v: [B, Tk, H, D]. Returns (m, l, o):
    running max [B, H, Tq], sum-exp [B, H, Tq], weighted values
    [B, Tq, H, D]."""
    # Softmax statistics live in at-least-f32 (flash convention): the
    # QK^T and PV dots keep bf16 operands on the MXU but accumulate f32
    # via preferred_element_type, so bf16 long-context inputs never
    # accumulate softmax mass in bf16 across ring hops. f64 inputs (the
    # gradient-check harness) keep full f64 statistics.
    acc_dt = jnp.promote_types(q.dtype, jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=acc_dt) * scale
    if causal:
        Tq, Tk = q.shape[1], k.shape[1]
        qpos = q_start + jnp.arange(Tq)[:, None]
        kpos = kv_start + jnp.arange(Tk)[None, :]
        s = jnp.where(qpos >= kpos, s, -jnp.inf)
    m = jnp.max(s, axis=-1)                          # [B, H, Tq] f32
    # fully-masked rows (causal, kv block entirely in the future) produce
    # -inf max; exp(-inf - -inf) would be NaN — clamp those rows
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = jnp.sum(p, axis=-1)                          # [B, H, Tq] f32
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=acc_dt)
    return m_safe, l, o


def _merge(acc, new):
    """Online-softmax merge of two partial attention states."""
    m_a, l_a, o_a = acc
    m_n, l_n, o_n = new
    m = jnp.maximum(m_a, m_n)
    ca = jnp.exp(m_a - m)
    cn = jnp.exp(m_n - m)
    l = l_a * ca + l_n * cn
    o = (o_a * jnp.moveaxis(ca, 1, -1)[..., None]
         + o_n * jnp.moveaxis(cn, 1, -1)[..., None])
    return m, l, o


def ring_attention_sharded(q, k, v, *, axis_name: str = SEQ_AXIS,
                           causal: bool = False):
    """The shard_map body: q/k/v are LOCAL sequence blocks
    [B, T_local, H, D]; the kv block rotates around ``axis_name``.

    Device i keeps its queries; at hop s it holds kv block (i - s) mod p.
    Online-softmax accumulation makes the result exactly equal (up to
    float re-association) to full attention over the gathered sequence.
    """
    p = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    t_local = q.shape[1]
    acc_dt = jnp.promote_types(q.dtype, jnp.float32)
    scale = jnp.sqrt(jnp.asarray(q.shape[-1], acc_dt)) ** -1
    q_start = idx * t_local

    B, T, H, D = q.shape
    # accumulators are at-least-f32 regardless of q.dtype — see _block_attend
    acc = (
        jnp.full((B, H, T), -jnp.inf, acc_dt),
        jnp.zeros((B, H, T), acc_dt),
        jnp.zeros((B, T, H, D), acc_dt),
    )
    # the accumulator becomes device-varying after the first hop; mark the
    # (device-constant) init accordingly for shard_map's axis typing
    if hasattr(lax, "pcast"):
        acc = jax.tree_util.tree_map(
            lambda a: lax.pcast(a, (axis_name,), to="varying"), acc)
    elif hasattr(lax, "pvary"):  # pre-0.9 jax
        acc = jax.tree_util.tree_map(
            lambda a: lax.pvary(a, (axis_name,)), acc)
    # static unroll over the (small, known) ring size: lets XLA overlap
    # each hop's permute with the previous hop's attention, and skips the
    # final rotation whose result nobody reads
    perm = [(j, (j + 1) % p) for j in range(p)]
    k_cur, v_cur = k, v
    for s in range(p):
        kv_owner = (idx - s) % p                # whose block we hold now
        new = _block_attend(q, k_cur, v_cur, scale=scale, causal=causal,
                            q_start=q_start, kv_start=kv_owner * t_local)
        acc = _merge(acc, new)
        if s < p - 1:  # last hop: kv would never be read again
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
    m, l, o = acc
    l = jnp.maximum(l, 1e-20)
    out = o / jnp.moveaxis(l, 1, -1)[..., None]
    return out.astype(q.dtype)


def full_attention(q, k, v, *, causal: bool = False):
    """Single-device reference: ordinary softmax attention
    ([B, T, H, D] inputs, head-batched)."""
    acc_dt = jnp.promote_types(q.dtype, jnp.float32)
    scale = jnp.sqrt(jnp.asarray(q.shape[-1], acc_dt)) ** -1
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=acc_dt) * scale
    if causal:
        T = q.shape[1]
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", a.astype(v.dtype), v,
                     preferred_element_type=acc_dt)
    return out.astype(q.dtype)


def ring_self_attention(x, wq, wk, wv, wo, *, mesh: Mesh,
                        n_heads: int, causal: bool = False,
                        axis_name: str = SEQ_AXIS):
    """Sequence-parallel multi-head self-attention over a Mesh.

    x: [B, T, E] with T divisible by the ``axis_name`` mesh size. The
    projections are computed on the local block (no communication); only
    k/v blocks travel the ring."""
    E = x.shape[-1]
    D = E // n_heads

    def body(xb):
        B, Tl = xb.shape[0], xb.shape[1]
        q = (xb @ wq).reshape(B, Tl, n_heads, D)
        k = (xb @ wk).reshape(B, Tl, n_heads, D)
        v = (xb @ wv).reshape(B, Tl, n_heads, D)
        o = ring_attention_sharded(q, k, v, axis_name=axis_name,
                                   causal=causal)
        return o.reshape(B, Tl, E) @ wo

    spec_x = PartitionSpec(None, axis_name, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_x,),
        out_specs=spec_x,
    )(x)

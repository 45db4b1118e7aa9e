"""The Mamba-2 chunked scan (`nn/layers/ssm.ssd_chunked`) as two Pallas
kernels under one `custom_vjp`: `ssd_fwd` and `ssd_bwd`.

Why: the XLA body writes each head's float32 `[Q, Q]` decay and weight
matrices of every chunk to HBM, carries the chunks' states by a scan, and
moves whole tensors between the channel layout and a group-first one
around its `lax.map` over the groups: about 94 ms of the Nemotron cell's
step for 5 ms of the recurrence's own operations and bytes (PERF.md).
Here those matrices live in VMEM only.

Both kernels walk a grid (batch, group, chunk): batch and group parallel,
the chunk axis sequential. A step takes one group's `r` heads over one
chunk of `Q` positions: `x` (and `y`, `dx`, `dy`) as one lane-dense
`[Q, r P]` block of the channel layout, `B` and `C` as `[Q, N]` blocks,
`dt` and the chunk's running sum `s` of `dt A` as `[r, Q]` rows. `x` and
`dy` are turned to `[r P, Q]` in VMEM once a step, so a head is `P`
sublane-aligned rows and a sum over its channels is a sum over sublanes;
`y` and `dx` are made so and turned back once. `C B^T` is taken once for
the group; each head's masked decay `exp(s_i - s_j)` and its weights are
made in VMEM, and nothing of size `Q x Q` reaches HBM.

- `ssd_fwd` walks the chunks forward and carries the group's state
  `[r P, N]` float32 in a VMEM scratch. It writes `y`; called by the
  backward pass, it writes the state that comes into each chunk instead
  (float32 `[b, G, c, r P, N]`, 268 MB a Nemotron mixer).
- `ssd_bwd` walks the chunks in reverse and carries the state's cotangent
  `[r P, N]` float32 the same way. From the inputs and those states it
  recomputes `C B^T`, the decays and the weights of a chunk, and writes
  `dx`, `dB` and `dC` (summed over the group's heads), the direct part of
  `ddt` and the cotangent of `s`. The running sum itself is XLA's, outside
  the `custom_vjp`, so autodiff takes `ds` on to `dt` and `A`.

The `custom_vjp` keeps only its inputs: the backward pass makes the states
with a forward sweep of its own (on a TPU v5e 9.68 ms a Nemotron mixer for
forward, forward again and backward, against 10.31 with the states
written by the forward), so a forward pass writes `y` alone and the states
live only while their mixer's backward pass runs.

Same mathematics and precision as the XLA body: products take operands of
`x`'s dtype (a cotangent is cast to it for its product) and accumulate in
float32; decays, `dt`, `s` and the carried states are float32; `y` is
float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.helpers import interpret_mode
from deeplearning4j_tpu.ops.pallas_experts import (LANES, VMEM_LIMIT, _NN,
                                                   _NT, _TN, _dot)

_INTERPRET = False  # flipped by tests on CPU; read through _interpret()

# what a grid step may hold in VMEM, by the estimate of `_vmem_bytes`
STEP_LIMIT = 32 * 1024 * 1024


def _interpret() -> bool:
    return interpret_mode(_INTERPRET)


def _chunk_terms(dt_ref, s_ref, b_ref, c_ref):
    """What both kernels make of a chunk before its heads, from `dt` and `s`
    as `[r, Q]` rows: `C B^T`, the causal mask, `s` as rows and as `[Q, r]`
    columns, and as rows `dt`, `exp(s)`, `exp(s_end - s)` and
    `dt exp(s_end - s)`."""
    st, dt = s_ref[...], dt_ref[...]
    Q = st.shape[1]
    to_end = jnp.exp(st[:, Q - 1:Q] - st)
    lower = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
             >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    return dict(st=st, s=st.T, dt=dt, es=jnp.exp(st), to_end=to_end,
                dte=dt * to_end, lower=lower,
                cb=_dot(c_ref[...], b_ref[...], _NT))


def _decay(k, h):
    """Head `h`'s `exp(s_i - s_j)` where `j <= i`, else 0: [Q, Q]."""
    d = k["s"][:, h:h + 1] - k["st"][h:h + 1, :]
    return jnp.exp(jnp.where(k["lower"], d, -jnp.inf))


def _fwd_kernel(x_ref, dt_ref, s_ref, end_ref, b_ref, c_ref, out_ref, h_scr,
                *, states: bool):
    """One chunk of one group, forward: `y`, or the state coming in; the
    state going out is carried in `h_scr`. A head is `P` rows of `x`
    transposed, `[P, Q]`; `y` is made transposed and turned once."""
    r = dt_ref.shape[0]
    P, N = x_ref.shape[1] // r, b_ref.shape[1]
    cd = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    k = _chunk_terms(dt_ref, s_ref, b_ref, c_ref)
    hin = h_scr[...]
    if states:
        out_ref[...] = hin
    else:
        across_t = _dot(hin.astype(cd), c_ref[...], _NT)   # [r P, Q]
        y_t = []
    xt = x_ref[...].astype(jnp.float32).T                  # [r P, Q]
    b = b_ref[...]
    for h in range(r):
        rows = slice(h * P, (h + 1) * P)
        row = lambda name: k[name][h:h + 1, :]              # [1, Q]
        xth = xt[rows]
        if not states:
            w = (k["cb"] * _decay(k, h)).astype(cd)
            u_t = (xth * row("dt")).astype(cd)
            y_t.append(_dot(u_t, w, _NT) + across_t[rows] * row("es"))
        v_t = (xth * row("dte")).astype(cd)
        end = jnp.broadcast_to(end_ref[h:h + 1, :], (P, N))
        h_scr[rows, :] = hin[rows, :] * end + _dot(v_t, b)
    if not states:
        out_ref[...] = jnp.concatenate(y_t, axis=0).T


def _bwd_kernel(x_ref, dt_ref, s_ref, end_ref, b_ref, c_ref, hin_ref, dy_ref,
                dx_ref, ddt_ref, ds_ref, db_ref, dc_ref, dh_scr):
    """One chunk of one group, backward, chunks in reverse: the state's
    cotangent going out of the chunk is carried in `dh_scr`. A head is `P`
    rows of `x` and `dy` transposed, `[P, Q]`, so a sum over its channels
    (of `ddt` and `ds`) is a sum over sublanes; `dx` is made transposed
    and turned once, and `dB` and `dC` take one product over all heads."""
    r, Q = dt_ref.shape
    P, N = x_ref.shape[1] // r, b_ref.shape[1]
    cd = x_ref.dtype
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        dh_scr[...] = jnp.zeros_like(dh_scr)

    k = _chunk_terms(dt_ref, s_ref, b_ref, c_ref)
    b, c = b_ref[...], c_ref[...]
    xt = x_ref[...].astype(f32).T                          # [r P, Q]
    dyt = dy_ref[...].astype(f32).T
    hin, dh = hin_ref[...], dh_scr[...]
    hin_c, dh_c = hin.astype(cd), dh.astype(cd)
    across_t = _dot(hin_c, c, _NT)                         # [r P, Q]
    dv_t = _dot(dh_c, b, _NT)                              # [r P, Q]
    last = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1) == Q - 1
    head = jax.lax.broadcasted_iota(jnp.int32, (r, Q), 0)
    colsum = lambda a: jnp.sum(a, axis=0, keepdims=True)
    dcb = jnp.zeros((Q, Q), f32)
    ddt = jnp.zeros((r, Q), f32)
    ds = jnp.zeros((r, Q), f32)
    dx_t, v_t, dys_t = [], [], []
    for h in range(r):
        rows = slice(h * P, (h + 1) * P)
        row = lambda name: k[name][h:h + 1, :]              # [1, Q]
        xth, dyth = xt[rows], dyt[rows]
        dy_c = dyth.astype(cd)
        decay = _decay(k, h)
        weights = k["cb"] * decay
        w = weights.astype(cd)
        u_t = (xth * row("dt")).astype(cd)
        v_t.append((xth * row("dte")).astype(cd))
        # inside the chunk: y = w u
        dw_decay = _dot(dy_c, u_t, _TN) * decay
        dcb = dcb + dw_decay
        du_t = _dot(dy_c, w)                                # [P, Q]
        # (weights u)^T with the weights in float32, as two parts of x's
        # dtype: it is summed into `ds`, where the decays' terms cancel
        y_in_t = _dot(u_t, w, _NT) + _dot(
            u_t, (weights - w.astype(f32)).astype(cd), _NT)
        # the state going out: exp(s_end) hin + v^T B
        dvx = colsum(dv_t[rows] * xth)                      # [1, Q]
        dx_t.append(du_t * row("dt") + dv_t[rows] * row("dte"))
        ddt = jnp.where(head == h, colsum(du_t * xth) + dvx * row("to_end"),
                        ddt)
        to_end_s = dvx * row("dte")                         # d(s_end - s_j)
        end = jnp.broadcast_to(end_ref[h:h + 1, :], (P, N))
        end_s = jnp.sum(to_end_s, axis=1, keepdims=True) \
            + jnp.sum(dh[rows, :] * hin[rows, :] * end, keepdims=True)
        # d(s_i - s_j) of the decays: summed over j, sum_p dy (w u) with
        # `dy` rounded as for `dw` (the two sums cancel over a chunk); over
        # i, a sum over sublanes. Across: y += exp(s) C hin^T
        ds_h = (colsum(dy_c.astype(f32) * y_in_t
                       + dyth * across_t[rows] * row("es"))
                - colsum(dw_decay * k["cb"]) - to_end_s
                + jnp.where(last, end_s, 0.0))
        ds = jnp.where(head == h, ds_h, ds)
        dys_t.append((dyth * row("es")).astype(cd))
        dh_scr[rows, :] = dh[rows, :] * end + _dot(dys_t[-1], c)
    dx_ref[...] = jnp.concatenate(dx_t, axis=0).T.astype(dx_ref.dtype)
    ddt_ref[...] = ddt
    ds_ref[...] = ds
    dcb = dcb.astype(cd)
    dc = _dot(jnp.concatenate(dys_t, axis=0), hin_c, _TN) + _dot(dcb, b)
    db = _dot(jnp.concatenate(v_t, axis=0), dh_c, _TN) + _dot(dcb, c, _TN)
    dc_ref[...] = dc.astype(dc_ref.dtype)
    db_ref[...] = db.astype(db_ref.dtype)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _inputs(x, dt, s, B, C, reverse: bool):
    """The operands both kernels read, their block specs, and makers of
    block specs for the outputs. Besides `x`, `dt`, `s`, `B` and `C`:
    `exp(s_end)` of each head across the state's `N` lanes, `[r, N]` a
    chunk (the chip's compiler broadcasts no `[1, 1]` across both sublanes
    and lanes)."""
    b, G, c, r, Q = dt.shape
    rP, N = x.shape[-1] // G, B.shape[-1] // G
    chunk = (lambda i: c - 1 - i) if reverse else (lambda i: i)
    chan = lambda width: pl.BlockSpec(
        (None, Q, width), lambda bi, g, i: (bi, chunk(i), g))
    small = lambda shape: pl.BlockSpec(
        (None, None, None) + shape, lambda bi, g, i: (bi, g, chunk(i), 0, 0))
    end = jnp.broadcast_to(jnp.exp(s[..., Q - 1:]), (b, G, c, r, N))
    specs = [chan(rP), small((r, Q)), small((r, Q)), small((r, N)),
             chan(N), chan(N)]
    return (x, dt, s, end, B, C), specs, chan, small


# The kernels' calls are jitted: a pallas_call traces and lowers its kernel
# anew at every call, and a step program asks for them sixteen times and
# more (four mixers; the forward, the forward again under recomputation,
# the states and the backward; autodiff's traces); under a jit a process
# traces each once and a program lowers each once, as for the expert
# kernels' dispatch and combine. Unjitted, they cost the Nemotron cell
# about 30 s of warm set-up on a TPU v5e host.

@functools.partial(jax.jit, static_argnames=("states", "interpret"))
def _forward(x, dt, s, B, C, *, states=False, interpret=False):
    """x [b, T, H P], dt and s [b, G, c, r, Q] float32, B and C [b, T, G N]
    -> y [b, T, H P] float32, or with `states` the states coming into the
    chunks [b, G, c, r P, N] float32."""
    b, G, c, r, Q = dt.shape
    rP, N = x.shape[-1] // G, B.shape[-1] // G
    operands, specs, chan, small = _inputs(x, dt, s, B, C, reverse=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, states=states),
        grid=(b, G, c), in_specs=specs,
        out_specs=small((rP, N)) if states else chan(rP),
        out_shape=jax.ShapeDtypeStruct(
            (b, G, c, rP, N) if states else x.shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((rP, N), jnp.float32)],
        compiler_params=_params(), name="ssd_fwd", interpret=interpret,
    )(*operands)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _backward(x, dt, s, B, C, states, dy, *, interpret=False):
    """`_forward`'s operands, the states it makes and the cotangent of `y`
    -> (dx, the direct part of ddt, ds, dB, dC) in the operands' layouts."""
    b, G, c, r, Q = dt.shape
    rP, N = x.shape[-1] // G, B.shape[-1] // G
    operands, specs, chan, small = _inputs(x, dt, s, B, C, reverse=True)
    return pl.pallas_call(
        _bwd_kernel,
        grid=(b, G, c),
        in_specs=specs + [small((rP, N)), chan(rP)],
        out_specs=[chan(rP), small((r, Q)), small((r, Q)), chan(N), chan(N)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(dt.shape, jnp.float32),
                   jax.ShapeDtypeStruct(dt.shape, jnp.float32),
                   jax.ShapeDtypeStruct(B.shape, B.dtype),
                   jax.ShapeDtypeStruct(C.shape, C.dtype)],
        scratch_shapes=[pltpu.VMEM((rP, N), jnp.float32)],
        compiler_params=_params(), name="ssd_bwd", interpret=interpret,
    )(*operands, states, dy)


@jax.custom_vjp
def _scan(x, dt, s, B, C):
    return _forward(x, dt, s, B, C, interpret=_interpret())


def _scan_fwd(x, dt, s, B, C):
    return _scan(x, dt, s, B, C), (x, dt, s, B, C)


def _scan_bwd(res, dy):
    states = _forward(*res, states=True, interpret=_interpret())
    return tuple(_backward(*res, states, dy, interpret=_interpret()))


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(x, dt, A, B, C, chunk: int):
    """`ssm.ssd_chunked`'s contract: x [b, t, H, P], dt [b, t, H], A [H], B
    and C [b, t, G, N] -> y [b, t, H, P] float32. A length that is no
    multiple of `chunk` is padded with `dt = 0`, as there. The running sums
    of `dt A` inside the chunks and the `[b, G, c, r, Q]` views of `dt` and
    of them are XLA's; `x`, `y`, `B` and `C` keep the channel layout."""
    b, t, H, P = x.shape
    G, N = B.shape[2:]
    r, Q = H // G, int(chunk)
    pad = (-t) % Q
    chan = lambda a: a.reshape(b, t, -1)
    x, dt, B, C = chan(x), dt.astype(jnp.float32), chan(B), chan(C)
    if pad:
        grow = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        x, dt, B, C = grow(x), grow(dt), grow(B), grow(C)
    c = (t + pad) // Q
    s = jnp.cumsum((dt * A.astype(jnp.float32)).reshape(b, c, Q, H), axis=2)
    rows = lambda a: jnp.transpose(a.reshape(b, c, Q, G, r), (0, 3, 1, 4, 2))
    y = _scan(x, rows(dt), rows(s), B, C)
    return y[:, :t].reshape(b, t, H, P)


def _vmem_bytes(Q, r, P, N, itemsize):
    """A generous estimate of a backward grid step's VMEM: its blocks
    double-buffered (`x`, `dy`, `dx`, the state, `B`, `C` and their
    cotangents, the `[r, Q]` rows, `exp(s_end)`), the carried cotangent,
    and the float32 values of a chunk: `x` and `dy`, the state and its
    cotangent, eight `[Q, Q]`, seven `[Q, r]` columns (a vreg a row), `dB`
    and `dC`."""
    rP, rows = r * P, -(-r // 8) * 8
    blocks = 2 * (Q * rP * (2 * itemsize + 4) + rP * N * 4
                  + 4 * Q * N * itemsize + 4 * rows * Q * 4 + rows * N * 4)
    values = 4 * (2 * Q * rP + 2 * rP * N + 8 * Q * Q + 7 * Q * LANES
                  + 2 * Q * N)
    return blocks + rP * N * 4 + values


def supported(*, x_shape, dtype, n_groups, state_size, chunk, **_):
    """A pure function of backend, shapes and dtype: a TPU (or the
    interpreter in a CPU test), bf16 or float32 operands, a chunk of whole
    8-row blocks, a state of whole lanes, a group's heads `r P` of whole
    lanes and a head of whole 16-row blocks (a head is a slice of rows at
    a multiple of `P`, in bf16 too), and a grid step inside VMEM."""
    _, _, H, P = x_shape
    G, N, Q = int(n_groups), int(state_size), int(chunk)
    if not (_interpret() or jax.default_backend() == "tpu"):
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float32)):
        return False
    if H % G or Q % 8 or N % LANES or (H // G * P) % LANES or P % 16:
        return False
    return _vmem_bytes(Q, H // G, P, N, jnp.dtype(dtype).itemsize) \
        <= STEP_LIMIT


def register():
    from deeplearning4j_tpu.ops.helpers import register_helper

    register_helper("ssd_scan", ssd_scan, supported, name="pallas_ssd_scan")


register()

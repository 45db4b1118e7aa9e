"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh axis.

NEW capability beyond the reference (SURVEY §2.4: DL4J ships data
parallelism only — no tensor/pipeline/expert parallelism anywhere). When a
model's layer stack does not fit one chip's HBM, its repeated blocks are
sharded over the "stage" mesh axis: device s permanently holds stage s's
parameters, activations flow stage-to-stage over ICI neighbor links with
`lax.ppermute`, and the batch is split into microbatches so all stages work
concurrently (the GPipe schedule; Huang et al.). The whole schedule is a
`lax.scan` inside one `shard_map` — XLA sees a static loop and overlaps
each tick's permute with the next tick's compute, and autodiff through
scan+ppermute yields the reverse (backward) pipeline for free, so the same
jitted train step the rest of the framework uses works unchanged.

Layout:
  stage params  — every leaf stacked on a leading [S] dim, sharded over
                  the "stage" axis (`shard_stage_params`)
  activations   — microbatch-resident, [mb, ...]; only the ppermute edge
                  crosses devices
  inputs/outputs— replicated [B, ...]; stage 0 feeds microbatch t at tick
                  t, the last stage's outputs are psum-broadcast once at
                  the end

The schedule runs S + M - 1 ticks for M microbatches over S stages
(pipeline bubble = (S-1)/(S+M-1) of the ticks; raise M to amortize).

Equivalence proof vs the sequential stack (values AND gradients) on the
8-device CPU mesh: tests/test_pipeline_parallel.py.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

STAGE_AXIS = "stage"


def pipeline_parallel_mesh(devices=None, axis_name: str = STAGE_AXIS) -> Mesh:
    """1-D mesh over the given (or all) devices with a single "stage" axis."""
    import numpy as np

    devices = list(devices) if devices is not None else jax.devices()
    return Mesh(np.array(devices), (axis_name,))


def shard_stage_params(stacked_params, mesh: Mesh,
                       axis_name: str = STAGE_AXIS):
    """Place stage-stacked parameters (every leaf [S, ...]) with their
    leading dim sharded over the stage axis — device s holds only stage
    s's slice, the pipeline analog of tensor.py's `shard_params_tp`."""
    sh = NamedSharding(mesh, PartitionSpec(axis_name))
    return jax.tree_util.tree_map(lambda a: jax.device_put(a, sh),
                                  stacked_params)


def _pipeline_body(stage_fn, stacked_params, x_mb, *, axis_name: str,
                   n_stages: int):
    """The shard_map body. `stacked_params` leaves arrive as [1, ...] local
    slices (this device's stage); `x_mb` is the full [M, mb, ...]
    microbatch stack, replicated. Returns the pipeline output [M, mb, ...]
    (replicated via one final psum)."""
    S = n_stages
    M = x_mb.shape[0]
    idx = lax.axis_index(axis_name)
    local_params = jax.tree_util.tree_map(lambda a: a[0], stacked_params)

    perm = [(i, i + 1) for i in range(S - 1)]  # stage i -> i+1, no wrap
    zero_state = jnp.zeros(x_mb.shape[1:], x_mb.dtype)

    def tick(state, t):
        # stage 0 ingests microbatch t. Drain ticks (t >= M) re-feed
        # microbatch M-1: its re-processed results can never reach the
        # last stage within the S+M-1-tick window, so they are
        # output-invisible (forward and backward) — deliberate trade-off
        # keeping every tick's ops identical for XLA instead of gating
        # stage-0 compute on t < M
        feed = lax.dynamic_index_in_dim(x_mb, jnp.clip(t, 0, M - 1), 0,
                                        keepdims=False)
        state_in = jnp.where(idx == 0, feed, state)
        out = stage_fn(local_params, state_in)
        # the last stage's result at tick t is final output microbatch
        # t - (S - 1); zero elsewhere so the end-of-scan psum broadcasts it
        y_t = jnp.where(idx == S - 1, out, jnp.zeros_like(out))
        if S > 1:
            nxt = lax.ppermute(out, axis_name, perm)
        else:
            nxt = out
        return nxt, y_t

    if hasattr(lax, "pcast"):
        zero_state = lax.pcast(zero_state, (axis_name,), to="varying")
    elif hasattr(lax, "pvary"):  # pre-0.9 jax
        zero_state = lax.pvary(zero_state, (axis_name,))
    _, ys = lax.scan(tick, zero_state, jnp.arange(S + M - 1))
    ys = ys[S - 1:]                      # drop fill ticks: [M, mb, ...]
    return lax.psum(ys, axis_name)       # only the last stage is nonzero


def pipeline_apply(stage_fn: Callable, stacked_params, x, *, mesh: Mesh,
                   n_microbatches: int, axis_name: str = STAGE_AXIS):
    """Run `x` through S pipelined stages of `stage_fn`.

    Args:
        stage_fn: (params_one_stage, x[mb, ...]) -> y[mb, ...] — must be
            shape-preserving (same in/out shape, as for repeated blocks);
            put embed/head layers outside the pipelined region.
        stacked_params: pytree, every leaf [S, ...] (stage-major), placed
            with `shard_stage_params` (or any layout GSPMD can reshard).
        x: global batch [B, ...], B divisible by n_microbatches.
        mesh: mesh with the stage axis; its size is S.
        n_microbatches: M — higher amortizes the (S-1)-tick bubble.

    Returns [B, ...], replicated. Differentiable: `jax.grad` through this
    yields the reverse pipeline schedule.
    """
    S = int(mesh.shape[axis_name])
    B = x.shape[0]
    M = int(n_microbatches)
    if B % M:
        raise ValueError(f"batch {B} not divisible by n_microbatches {M}")
    x_mb = x.reshape((M, B // M) + x.shape[1:])

    body = partial(_pipeline_body, stage_fn, axis_name=axis_name,
                   n_stages=S)
    p_spec = jax.tree_util.tree_map(
        lambda _: PartitionSpec(axis_name), stacked_params)
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(p_spec, PartitionSpec()),
        out_specs=PartitionSpec(),
    )(stacked_params, x_mb)
    return out.reshape((B,) + out.shape[2:])


def sequential_apply(stage_fn: Callable, stacked_params, x):
    """Single-device reference semantics: the same stages applied in
    order (what the pipeline must exactly reproduce)."""
    S = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    for s in range(S):
        p_s = jax.tree_util.tree_map(lambda a: a[s], stacked_params)
        x = stage_fn(p_s, x)
    return x

"""MeshPlan — the mainline multi-chip train-step sharding authority.

This is the SPMD data-parallel recipe (Megatron-style in-graph
collectives) promoted from `parallel/wrapper.py`'s opt-in batch-transform
hook into the thing `fit()` does by default on a multi-device platform:

* parameters + updater state are committed to the mesh **replicated**
  (or left in whatever NamedSharding a tp/pp helper already placed them
  with — `shard_params_tp` placements are honored, never clobbered);
* every global batch is **sharded on the "data" axis** (dim 0), padded
  and loss-masked to a stable shard-divisible shape so the tail batch
  neither recompiles nor drops to replicated execution;
* the optimizer step is ONE jitted program built with explicit
  `NamedSharding` in-shardings and the single-sourced donation rule
  (`TrainStep._step_donate_argnums`, audited by JX006), with the gradient
  all-reduce pinned **inside the program** by a sharding constraint at
  the grad site — there is no host-side averaging anywhere in the step
  path (the DL4J ParallelWrapper semantics this replaces: per-step
  gradient psum/mean == parameter averaging with frequency 1, see
  tests/test_parallel.py::test_allreduce_equals_parameter_averaging);
* the reduction itself is **bucketed** (`CollectivePlan`): the flattened
  gradient leaves are grouped reverse-topologically (the last layers'
  grads finish first in the backward pass) into ~`bucket_bytes` flat
  payloads, each reduced by its own in-graph collective — the PyTorch
  DDP / Horovod bucketing design at the GSPMD level. Each bucket depends
  only on its own leaves, so XLA's latency-hiding scheduler can launch
  early buckets' collectives while the remaining backward still
  computes, instead of one tail-end reduction gated on the LAST grad.
  The f32 bucketed path is bit-identical to the monolithic constraint
  (concat/split is exact; the per-element cross-device sum order is
  unchanged — pinned by tests/test_collectives.py). `bucket_bytes=0`
  restores the monolithic tail-end constraint;
* opt-in `set_mesh(..., grad_dtype="bf16")` casts bucket payloads to
  bf16 before the reduce and back to f32 after — halving the wire bytes
  (`allreduce_bytes_total` and the ring estimate account the bf16
  payload) at a bounded trajectory cost. Never the default.

Attach with `net.set_mesh(mesh)` (None = 1-D "data" mesh over all
devices). `fit()` attaches one automatically when more than one device
is visible — disable with `DL4J_AUTO_MESH=0` (tests/conftest.py does,
so the 8-virtual-device tier-1 suite doesn't shard every tiny fit; the
dedicated sharding tests and the t1.sh 2-device smoke opt back in).

tp/pp/sp compose via config: build the mesh with `mesh_2d` and apply
`shard_params_tp` BEFORE `set_mesh` — `place_net` keeps any leaf
already committed to this mesh, and `jit_step` derives per-leaf
in-shardings from the live placement, so Megatron column/row splits ride
the same jitted step. The pipeline/sequence helpers (`pipeline_apply`,
`ring_self_attention`) stay shard_map-level building blocks for models
that need them.
"""

from __future__ import annotations

import functools
import os
import time
from typing import List, Optional, Tuple

import numpy as np

# DDP-style default bucket size. Small enough that a ResNet-50-class
# gradient tree splits into ~25 buckets (overlap granularity), large
# enough that per-collective launch latency stays amortized.
DEFAULT_BUCKET_BYTES = 4 << 20


def auto_mesh_enabled() -> bool:
    """Should `fit()` auto-attach a data-parallel mesh on a multi-device
    platform? Default yes — the mainline multi-chip path. `DL4J_AUTO_MESH=0`
    disables (read per fit, so tests can flip it per-case)."""
    return os.environ.get("DL4J_AUTO_MESH", "1") not in ("0", "false", "no")


def default_bucket_bytes() -> int:
    """The gradient-bucket size knob: `DL4J_GRAD_BUCKET_BYTES` (0 =
    monolithic tail-end reduction), else the DDP-style 4 MiB default."""
    env = os.environ.get("DL4J_GRAD_BUCKET_BYTES")
    if env is not None:
        return int(env)
    return DEFAULT_BUCKET_BYTES


def _jax():
    import jax

    return jax


class CollectivePlan:
    """Bucketed gradient-reduction schedule over one net's flattened
    gradient leaves.

    Buckets are assigned in REVERSE leaf order — the params list is in
    layer topo order, so reversed leaves approximate backward-pass
    completion order (the output head's grads are ready first). Each
    bucket holds consecutive same-dtype leaves up to ~`bucket_bytes` of
    wire payload and is reduced as ONE flat concatenated collective; a
    leaf whose target sharding is not fully replicated (tp/pp splits)
    stays outside the buckets and keeps its per-leaf constraint (its
    gradient is deliberately sharded — there is nothing to all-reduce).

    `grad_dtype="bf16"` prices (and casts) the wire payload at 2
    bytes/element; accumulation back into the f32 gradient happens after
    the reduce (`MeshPlan.reduce_grads`)."""

    def __init__(self, buckets: List[dict], unbucketed: List[int],
                 n_leaves: int, bucket_bytes: int,
                 grad_dtype: Optional[str]):
        self.buckets = buckets          # [{"leaves": [flat idx], "bytes", "dtype"}]
        self.unbucketed = unbucketed    # flat leaf indices constrained per-leaf
        self.n_leaves = n_leaves
        self.bucket_bytes = bucket_bytes
        self.grad_dtype = grad_dtype or "f32"

    @classmethod
    def build(cls, leaves, sharding_leaves, replicated, bucket_bytes: int,
              grad_dtype: Optional[str]) -> "CollectivePlan":
        bf16 = grad_dtype == "bf16"
        buckets: List[dict] = []
        unbucketed: List[int] = []
        cur: List[int] = []
        cur_bytes = 0
        cur_dtype = None

        def flush():
            nonlocal cur, cur_bytes, cur_dtype
            if cur:
                buckets.append({"leaves": cur, "bytes": cur_bytes,
                                "dtype": cur_dtype})
            cur, cur_bytes, cur_dtype = [], 0, None

        for i in reversed(range(len(leaves))):
            leaf = leaves[i]
            if sharding_leaves[i] != replicated:
                unbucketed.append(i)
                continue
            dt = str(leaf.dtype)
            nb = int(leaf.size) * (2 if bf16 else leaf.dtype.itemsize)
            if cur and (dt != cur_dtype
                        or cur_bytes + nb > max(1, bucket_bytes)):
                flush()
            cur.append(i)
            cur_bytes += nb
            cur_dtype = dt
        flush()
        return cls(buckets, unbucketed, len(leaves), bucket_bytes,
                   grad_dtype)

    def wire_bytes(self) -> int:
        """Total wire payload of one step's bucketed collectives."""
        return sum(b["bytes"] for b in self.buckets)

    def describe(self) -> dict:
        sizes = [b["bytes"] for b in self.buckets]
        return {
            "bucket_bytes": self.bucket_bytes,
            "grad_dtype": self.grad_dtype,
            "n_buckets": len(self.buckets),
            "bucketed_leaves": sum(len(b["leaves"]) for b in self.buckets),
            "unbucketed_leaves": len(self.unbucketed),
            "wire_bytes_per_step": self.wire_bytes(),
            "bucket_sizes_bytes": sizes,
        }


class MeshPlan:
    """Sharding plan of one net over one `jax.sharding.Mesh`.

    Single source of truth for: parameter/updater placement, batch
    sharding (the `_batch_transform` the input pipeline runs off the
    dispatch critical path), the step jit's in-shardings + donation, the
    in-graph gradient-reduction constraint, and the per-step collective
    accounting (`allreduce_bytes_total` / `train_step_collective_seconds`).
    """

    def __init__(self, mesh, *, bucket_bytes: Optional[int] = None,
                 grad_dtype: Optional[str] = None):
        from jax.sharding import NamedSharding, PartitionSpec

        from deeplearning4j_tpu.parallel.mesh import DATA_AXIS, data_shards

        if DATA_AXIS not in mesh.axis_names:
            raise ValueError(
                f"mesh axes {mesh.axis_names} have no '{DATA_AXIS}' axis — "
                "the sharded train step needs one to split the batch over")
        if grad_dtype not in (None, "f32", "bf16"):
            raise ValueError(
                f"grad_dtype must be 'f32' or 'bf16', got {grad_dtype!r}")
        self.mesh = mesh
        self.n_data_shards = data_shards(mesh)
        self.replicated = NamedSharding(mesh, PartitionSpec())
        # batch dim 0 over "data"; stacked variants (fused multi-batch
        # programs, [K, B, ...]) shard dim 1
        self.batch = NamedSharding(mesh, PartitionSpec(DATA_AXIS))
        self.batch_stacked = NamedSharding(
            mesh, PartitionSpec(None, DATA_AXIS))
        # collective knobs: bucket size (0 = monolithic tail-end
        # constraint) and the opt-in bf16 wire payload
        self.bucket_bytes = (default_bucket_bytes() if bucket_bytes is None
                             else int(bucket_bytes))
        self.grad_dtype = "f32" if grad_dtype is None else grad_dtype
        # pad-up-to target: largest shard-divisible batch seen this fit,
        # so a short tail reuses the full batches' executable (reset by
        # the fit loop at each run start)
        self._pad_target = 0
        # per-net cached gradient payload bytes (the allreduce books)
        self._payload_bytes: Optional[int] = None
        # per-net cached bucket schedule + measured-collective probe
        self._cplan: Optional[CollectivePlan] = None
        self._probe = None               # (jitted fn, staged args)
        self._probe_steps = 0            # sharded steps since last sample

    # -- placement -----------------------------------------------------------

    def _on_this_mesh(self, a) -> bool:
        jax = _jax()
        if not isinstance(a, jax.Array):
            return False
        sh = getattr(a, "sharding", None)
        return getattr(sh, "mesh", None) == self.mesh

    def place_net(self, net) -> "MeshPlan":
        """Commit the net's params, layer state and updater state to the
        mesh, replicated — the once-per-attach analog of the reference
        copying the source model into every worker replica. Leaves a
        tp/pp helper already committed to THIS mesh keep their sharding
        (re-putting them replicated would silently all-gather a
        deliberately distributed weight)."""
        jax = _jax()

        def put(a):
            if a is None or self._on_this_mesh(a):
                return a
            return jax.device_put(a, self.replicated)

        tm = lambda t: jax.tree_util.tree_map(put, t)
        net.params_list = tm(net.params_list)
        net.state_list = tm(net.state_list)
        net.upd_state = tm(net.upd_state)
        self._payload_bytes = None
        self._cplan = None
        self._probe = None
        return self

    def tree_shardings(self, tree):
        """Per-leaf NamedShardings of a live pytree — the in-shardings of
        the params/updater arguments. Leaves not committed to this mesh
        (e.g. freshly-restored checkpoint numpy) fall back to replicated,
        which is what the step's first dispatch will commit them to."""
        jax = _jax()
        return jax.tree_util.tree_map(
            lambda a: a.sharding if self._on_this_mesh(a) else self.replicated,
            tree)

    # -- batch sharding ------------------------------------------------------

    def reset_pad_target(self) -> None:
        """Per-fit state: a later fit with a smaller batch size must not
        keep padding to the old larger shape."""
        self._pad_target = 0

    def _stage_array(self, a, sh, pad: int, target: int):
        """One batch array onto the mesh. Fast paths, in order: already
        committed with the target sharding -> zero-copy passthrough
        (the `_pipeline_staged` contract extended to sharded placement —
        a pre-staged batch is never transferred twice); already a device
        array and no pad needed -> device-side reshard, no host hop.
        Only a padded tail takes the host round-trip (np.resize wrap)."""
        jax = _jax()
        if a is None:
            return None
        if pad == 0 and isinstance(a, jax.Array):
            cur = getattr(a, "sharding", None)
            if cur == sh:
                return a
            try:
                if cur is not None and cur.is_equivalent_to(sh, a.ndim):
                    return a
            except Exception:
                pass
            return jax.device_put(a, sh)
        from deeplearning4j_tpu.parallel.mesh import pad_wrap

        return jax.device_put(pad_wrap(np.asarray(a), target), sh)

    def shard_batch(self, ds):
        """Shard a global batch's dim 0 across the data axis (DataSet or
        MultiDataSet — ComputationGraph fit yields the latter). Installed
        as the net's `_batch_transform`, so under async_prefetch it runs
        inside the device-prefetch worker thread, off the dispatch
        critical path.

        Pad-and-mask tail handling (moved verbatim from the old
        ParallelWrapper): a batch not divisible by the shard count is
        padded to the next multiple by WRAPPING examples and the pad rows
        are excluded from the loss via an all-zero labels-mask row
        (losses use masked_example_mean, so the padded step computes
        exactly the unpadded score/gradients). A labels mask of ones is
        supplied for full batches too, keeping ONE trace signature — the
        tail batch neither recompiles nor drops to replicated serial
        execution. Wrapped pad rows do still enter batch-norm batch
        statistics — a stochastic duplicate-sample effect on the tail
        step only."""
        jax = _jax()
        from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet

        n = ds.num_examples()
        target = max(n + ((-n) % self.n_data_shards), self._pad_target)
        self._pad_target = target
        pad = target - n
        sh = self.batch

        def stage(a):
            return self._stage_array(a, sh, pad, target)

        def pad_lmask(lm):
            """Existing labels mask: pad rows of zeros. Absent: 0/1
            vector."""
            if lm is not None:
                if pad == 0:
                    return stage(lm)
                lm = np.asarray(lm)
                z = np.zeros((pad,) + lm.shape[1:], lm.dtype)
                return jax.device_put(np.concatenate([lm, z]), sh)
            m = np.ones((n + pad,), np.float32)
            if pad:
                m[n:] = 0.0
            return jax.device_put(m, sh)

        if isinstance(ds, MultiDataSet):
            lmasks = ds.labels_masks
            if lmasks is None:
                lmasks = [None] * len(ds.labels)
            out = MultiDataSet(
                [stage(f) for f in ds.features],
                [stage(l) for l in ds.labels],
                None if ds.features_masks is None
                else [stage(m) for m in ds.features_masks],
                [pad_lmask(m) for m in lmasks],
            )
        else:
            out = DataSet(
                stage(ds.features),
                stage(ds.labels),
                stage(ds.features_mask),
                pad_lmask(ds.labels_mask),
            )
        # listeners/counters must see the REAL example count, not the pad
        out.reported_examples = getattr(ds, "reported_examples", None) or n
        return out

    # -- the sharded step jit ------------------------------------------------

    def jit_step(self, net, step, *, donate_argnums: Tuple[int, ...],
                 stacked_data: bool = False):
        """jit an optimizer-step program `step(params, states, upd_state,
        data, lr, t, rng)` (nn/trainstep: every program has these seven
        arguments) with explicit NamedSharding in-shardings: per-leaf
        placements for params (argnum 0) and updater state (argnum 2) —
        which is what lets tp-sharded weights ride the same program — the
        batch sharding for the batch (argnum 3), replicated for
        everything else (layer state, lr, t, rng). The donation rule
        arrives from the ONE definition every step builder uses
        (`TrainStep._step_donate_argnums`, recorded on the net for the
        JX006 audit); donated in/out layouts match because the step body
        constrains its gradient (and hence its outputs) back to the
        parameter shardings. The body is traced as a partitioned program
        (ops/helpers.partitioned_program): a Pallas helper is an opaque
        custom call the partitioner cannot split, so on a mesh of more
        than one device the layers keep their XLA lowering."""
        jax = _jax()
        rep = self.replicated
        in_shardings = (
            self.tree_shardings(net.params_list), rep,
            self.tree_shardings(net.upd_state),
            self.batch_stacked if stacked_data else self.batch,
            rep, rep, rep)
        from deeplearning4j_tpu.ops.helpers import partitioned_program

        n_devices = int(self.mesh.devices.size)

        @functools.wraps(step)
        def partitioned(*args):
            # the body runs while jit traces: kernel helpers see that
            # this program is split over the mesh and decline
            with partitioned_program(n_devices):
                return step(*args)

        return jax.jit(partitioned, in_shardings=in_shardings,
                       donate_argnums=donate_argnums)

    def grad_shardings(self, net):
        """Per-leaf shardings the step body constrains its gradients to
        (`with_sharding_constraint` right after value_and_grad): the
        parameter shardings. For replicated dp params this pins the
        cross-device psum/mean INSIDE the program at the grad site —
        the in-graph all-reduce; tp-sharded params keep their sharded
        gradients (no gather)."""
        return self.tree_shardings(net.params_list)

    # -- the bucketed in-graph reduction -------------------------------------

    def collective_plan(self, net) -> Optional[CollectivePlan]:
        """The bucket schedule for this net's gradient tree (cached —
        shapes are static for a fit). None when bucketing is off
        (`bucket_bytes=0` and f32 wire): the step body then falls back
        to the monolithic whole-tree sharding constraint."""
        if self.bucket_bytes <= 0 and self.grad_dtype != "bf16":
            return None
        if self._cplan is None:
            jax = _jax()
            leaves = jax.tree_util.tree_leaves(net.params_list)
            sh_leaves = jax.tree_util.tree_leaves(
                self.grad_shardings(net))
            # bucket_bytes=0 with bf16 wire: one bucket per leaf (the
            # cast/reduce/uncast still applies, just unbatched)
            bb = self.bucket_bytes if self.bucket_bytes > 0 else 1
            self._cplan = CollectivePlan.build(
                leaves, sh_leaves, self.replicated, bb, self.grad_dtype)
        return self._cplan

    def reduce_grads(self, net, grads):
        """Emit the in-graph gradient reduction inside a step body
        (called under trace by nn/trainstep's `_make_step_body`).
        Monolithic mode is the historical whole-tree `with_sharding_constraint`; bucketed
        mode concatenates each bucket's flattened leaves into ONE flat
        payload, constrains it replicated (ONE collective per bucket),
        and splits it back — bit-identical for f32 (the per-element
        cross-device sum order is unchanged; concat/reshape are exact).
        bf16 wire casts the payload before the constraint and
        accumulates back into the leaf dtype after."""
        jax = _jax()
        import jax.numpy as jnp

        gshard = self.grad_shardings(net)
        cplan = self.collective_plan(net)
        if cplan is None:
            return jax.lax.with_sharding_constraint(grads, gshard)
        bf16 = cplan.grad_dtype == "bf16"
        flat, treedef = jax.tree_util.tree_flatten(grads)
        sflat = jax.tree_util.tree_leaves(gshard)
        for b in cplan.buckets:
            idxs = b["leaves"]
            if len(idxs) == 1 and not bf16:
                # a lone leaf needs no concat round-trip
                i = idxs[0]
                flat[i] = jax.lax.with_sharding_constraint(
                    flat[i], sflat[i])
                continue
            parts = [flat[i] for i in idxs]
            payload = (parts[0].reshape(-1) if len(parts) == 1
                       else jnp.concatenate([p.reshape(-1) for p in parts]))
            acc_dtype = payload.dtype
            if bf16 and acc_dtype != jnp.bfloat16:
                payload = payload.astype(jnp.bfloat16)
            payload = jax.lax.with_sharding_constraint(
                payload, self.replicated)
            if payload.dtype != acc_dtype:
                payload = payload.astype(acc_dtype)
            off = 0
            for i in idxs:
                sz = int(flat[i].size)
                piece = jax.lax.slice_in_dim(payload, off, off + sz)
                off += sz
                flat[i] = jax.lax.with_sharding_constraint(
                    piece.reshape(flat[i].shape), sflat[i])
        for i in cplan.unbucketed:
            flat[i] = jax.lax.with_sharding_constraint(flat[i], sflat[i])
        return jax.tree_util.tree_unflatten(treedef, flat)

    # -- collective accounting ----------------------------------------------

    def grad_payload_bytes(self, net) -> int:
        """Logical all-reduce WIRE payload of ONE optimizer step: the
        summed gradient leaf bytes at the wire dtype (== parameter bytes
        for f32; half that under `grad_dtype="bf16"`). Cached — shapes
        are static for a fit."""
        if self._payload_bytes is None:
            jax = _jax()
            bf16 = self.grad_dtype == "bf16"
            total = 0
            for leaf in jax.tree_util.tree_leaves(net.params_list):
                size = getattr(leaf, "size", None)
                if not size:
                    continue
                itemsize = 2 if bf16 else leaf.dtype.itemsize
                total += int(size) * itemsize
            self._payload_bytes = total
        return self._payload_bytes

    def collective_seconds_estimate(self, net) -> float:
        """Cost-model ESTIMATE of one step's gradient all-reduce time:
        ring all-reduce moves 2(n-1)/n of the wire payload over each
        chip's ICI links (`flops.ici_bandwidth_per_chip`); a bf16 wire
        halves the payload. An estimate, not a measurement — labeled as
        such on the metric; the roofline's honesty discipline (every
        published number names its source). The `source="measured"`
        sibling (`maybe_measure_collective`) is what falsifies it."""
        n = self.n_data_shards
        if n <= 1:
            return 0.0
        from deeplearning4j_tpu.utils.flops import ici_bandwidth_per_chip

        wire = 2.0 * (n - 1) / n * self.grad_payload_bytes(net)
        return wire / ici_bandwidth_per_chip()

    def _collective_probe(self, net):
        """A jitted reduction-only program with the live bucket schedule:
        one data-sharded input per bucket, summed over the sharded dim
        into a replicated result — GSPMD lowers that to exactly the
        cross-device all-reduce the train step's bucket runs, on the
        same backend/interconnect. Built (and warmed) once; the staged
        zero inputs stay resident so a sample is one dispatch."""
        if self._probe is None:
            jax = _jax()
            import jax.numpy as jnp

            cplan = self.collective_plan(net)
            if cplan is not None and cplan.buckets:
                shapes = [(b["bytes"] // max(1, _np_dtype(b["dtype"],
                                                          cplan.grad_dtype).itemsize),
                           _np_dtype(b["dtype"], cplan.grad_dtype))
                          for b in cplan.buckets]
            else:
                bf16 = self.grad_dtype == "bf16"
                dt = np.dtype("float32") if not bf16 else _np_dtype(
                    "float32", "bf16")
                shapes = [(self.grad_payload_bytes(net) // dt.itemsize, dt)]
            n = self.n_data_shards
            rep = self.replicated

            def probe(*bufs):
                return tuple(
                    jax.lax.with_sharding_constraint(b.sum(axis=0), rep)
                    for b in bufs)

            fn = jax.jit(probe, in_shardings=(self.batch,) * len(shapes))
            args = tuple(
                jax.device_put(jnp.zeros((n, max(1, int(elems))), dtype=dt),
                               self.batch)
                for elems, dt in shapes)
            jax.block_until_ready(fn(*args))  # warm: exclude compile time
            self._probe = (fn, args)
        return self._probe

    def maybe_measure_collective(self, net, n_steps: int,
                                 sample_every: int) -> Optional[float]:
        """Sampled MEASUREMENT of the collective cost, devprof-style:
        every `sample_every`-th sharded step, time one blocking dispatch
        of the reduction-only probe and attribute it to every step since
        the last sample. Returns the attributed seconds (probe wall time
        x steps covered) or None off-sample. `sample_every=0` disables —
        the same knob that keeps devprof's blocking reads out of tier-1."""
        if self.n_data_shards <= 1 or not sample_every:
            return None
        self._probe_steps += int(n_steps)
        if self._probe_steps < sample_every:
            return None
        covered, self._probe_steps = self._probe_steps, 0
        jax = _jax()
        fn, args = self._collective_probe(net)
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        return (time.perf_counter() - t0) * covered

    def describe(self) -> dict:
        return {
            "devices": int(self.mesh.devices.size),
            "axes": {name: int(self.mesh.shape[name])
                     for name in self.mesh.axis_names},
            "data_shards": self.n_data_shards,
        }

    def collective_describe(self, net) -> dict:
        """The chosen collective schedule, for `cli doctor` and the
        bench artifact: bucket count/sizes, wire dtype and bytes, and
        the ring estimate they imply."""
        cplan = self.collective_plan(net)
        out = {
            "mode": "monolithic" if cplan is None else "bucketed",
            "grad_dtype": self.grad_dtype,
            "wire_bytes_per_step": self.grad_payload_bytes(net),
            "ring_estimate_seconds": round(
                self.collective_seconds_estimate(net), 6),
        }
        if cplan is not None:
            out.update(cplan.describe())
        return out


def _np_dtype(name: str, grad_dtype: str) -> np.dtype:
    """Wire dtype of a bucket for the measured-collective probe: bf16
    wire (or bf16 param leaves) uses ml_dtypes' bfloat16 when importable
    (jax ships it), else f16 — SAME byte width, so the probe payload
    stays honest even without the exact dtype."""
    if grad_dtype == "bf16" or name == "bfloat16":
        try:
            import ml_dtypes

            return np.dtype(ml_dtypes.bfloat16)
        except Exception:
            return np.dtype("float16")
    try:
        return np.dtype(name)
    except TypeError:
        return np.dtype("float32")

"""Shared network machinery for MultiLayerNetwork and ComputationGraph.

The reference factors this via the Model interface + BaseLayer inheritance
(nn/api/Model.java); here it is a small base class holding the pieces that
are identical for sequential and DAG networks: listener management, the
epoch/iteration fit loop (with async prefetch and ETL timing), the
batch-transform hook used by parallel.ParallelWrapper, and the flattened
parameter view API (params()/setParams(), reference:
MultiLayerNetwork.java:102-104 flattenedParams).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import List, Optional

import numpy as np

from deeplearning4j_tpu.data.iterators import AsyncDataSetIterator
from deeplearning4j_tpu.nn.layers.registry import (
    init_layer_params,
    init_layer_state,
    publish_slots,
)
from deeplearning4j_tpu.nn.params import (
    flat_to_params,
    num_params,
    param_table,
    params_to_flat,
)
from deeplearning4j_tpu.nn.trainstep import TrainStep
from deeplearning4j_tpu.utils import blackbox as _blackbox
from deeplearning4j_tpu.utils import devprof as _devprof
from deeplearning4j_tpu.utils import faultpoints as _faults
from deeplearning4j_tpu.utils import health as _health
from deeplearning4j_tpu.utils import locktrace as _locktrace
from deeplearning4j_tpu.utils import metrics as _metrics
from deeplearning4j_tpu.utils import resourcemeter as _resourcemeter
from deeplearning4j_tpu.utils import runledger as _runledger
from deeplearning4j_tpu.utils import tracing as _tracing
from deeplearning4j_tpu.train import sentinel as _sentinel

logger = logging.getLogger("deeplearning4j_tpu")


class NetworkBase(TrainStep):
    """Common trainable-network state + loops. Subclasses implement
    `_fit_dataset(ds)` (one optimizer step or TBPTT segment loop),
    `_ordered_layer_confs()` (layer configs aligned with params_list) and
    what nn/trainstep.TrainStep — the optimizer-step programs and the
    host code that runs them — asks of an engine."""

    def __init__(self):
        self.listeners = []
        self.iteration = 0
        self.epoch = 0
        self.params_list = None
        self.state_list = None
        self.upd_state = None
        self._score = None  # last minibatch score (device array, lazy read)
        self._last_etl_ms = 0.0
        # opt-in per-iteration grad/update/param mean-magnitude collection
        # for the stats/UI pipeline (reference: BaseStatsListener payloads)
        self._collect_stats = False
        self._last_stats = None
        # hook applied to each DataSet before the step — installed by
        # set_mesh (the MeshPlan's shard_batch) to shard the batch across
        # the mesh. Under async_prefetch it runs inside the device-prefetch
        # worker thread (off the dispatch critical path); staged batches
        # carry `_pipeline_staged` so the loop never applies it twice
        self._batch_transform = None
        # the attached parallel.sharded.MeshPlan (set_mesh): params and
        # updater state live on its mesh, batches shard on its "data"
        # axis, and every step jit gets its NamedSharding in-shardings.
        # None = single-device semantics. fit() auto-attaches one on
        # multi-device platforms (DL4J_AUTO_MESH=0 disables).
        self._mesh_plan = None
        # on-device batch transform (data/transforms.DeviceBatchTransform)
        # applied after placement — set_input_transform
        self._input_transform = None
        # device-prefetch queue depth (staged batches held ahead of the
        # step; device memory bound = depth + 1 batches)
        self._prefetch_depth = 2
        # fuse K consecutive same-shape minibatches into ONE jitted
        # dispatch (set_fused_steps) — the dispatch-latency amortizer
        self._fused_k = 1
        # the jitted step programs (nn/trainstep): the plain train step
        # under its own name, every other one by (kind, shape key)
        self._train_step_fn = None
        self._step_programs = {}
        # forward (`output`) traces compiled so far — bumped by the
        # subclasses' shape-keyed output caches; serving layers surface it
        # so a compile storm is a metric, not a latency mystery. The lock
        # makes concurrent cache misses on one key produce ONE entry
        # (ParallelInference calls output() from several threads)
        self._output_compiles = 0
        self._output_cache_lock = threading.Lock()
        # shared-registry fit instruments, resolved ONCE on first use so
        # the per-step hot path touches cached children only (the ISSUE's
        # overhead guard: zero registry lookups per step)
        self._fit_instruments = None
        # `t_end` of the fit thread's last dispatch on tracing.now_ns()
        # (the epoch's start before the first): the next record's
        # `t_wait0`, so the timeline's phases tile with no hole
        self._fit_t_mark = 0
        # ordinal of the current `fit()` call on this net (`fit/run`'s
        # `fit_call`)
        self._fit_calls = 0
        # donate_argnums the step builders actually used (recorded by
        # _step_donate_argnums) — the doctor's JX006 check audits THIS,
        # not a reconstruction of the policy
        self._donate_argnums = None
        # the watchdog heartbeat of the CURRENT fit (utils/health) — set
        # for the duration of _run_fit; the step path beats it
        self._fit_heartbeat = None
        # mid-epoch resume bookkeeping (train_state()): epoch, batches
        # consumed within it, and the data iterator's epoch-start state —
        # captured by the fit loop, embedded in checkpoints, replayed by
        # fit(resume_from=...)
        self._train_state = None
        # where the hang action dumped the flight recorder before raising
        # StepHangError into the fit thread (read when enriching the
        # async-raised bare exception)
        self._hang_dump_path = None
        # the attached train/sentinel.DivergenceSentinel (set_sentinel).
        # None = the fit loop pays one attribute read per dispatch
        self._sentinel = None
        # in-graph step diagnostic: a [loss, grad_norm] 2-vector every
        # step body returns next to the score — ONE device transfer
        # resolves both for the sentinel's per-step judgment
        self._step_diag = None

    # -- to be provided by subclasses ----------------------------------------

    def init(self):
        """Parameters and layer state from the configuration's seed, layer
        by layer in flattening order, and the updater's state: one
        always-on `net/init` span (and `net_init_seconds`), under which the
        initialisers' own programs show as `compile/*` spans."""
        import jax

        _tracing.watch_compiles()
        seconds = _metrics.get_registry().histogram(
            "net_init_seconds",
            "wall time of a net's init(): parameters, layer state and "
            "updater state, with the compiles or cache loads of the "
            "initialisers' programs").labels()
        with _tracing.phase("net/init", observe=seconds,
                            engine=type(self).__name__) as span:
            key = jax.random.PRNGKey(self.net_conf.seed)
            dtype = self.policy.param_dtype
            self.params_list = []
            self.state_list = []
            for i, conf in enumerate(self._ordered_layer_confs()):
                self.params_list.append(
                    init_layer_params(jax.random.fold_in(key, i), conf, dtype))
                self.state_list.append(init_layer_state(conf, dtype))
            self.upd_state = self.updater_def.init_tree(self.params_list)
            leaves = jax.tree_util.tree_leaves(self.params_list)
            span.args.update(
                n_leaves=len(leaves),
                param_bytes=int(sum(leaf.nbytes for leaf in leaves)))
        return self

    def _fit_dataset(self, ds):
        raise NotImplementedError

    def _ordered_layer_confs(self) -> List:
        """Layer configs in flattening order, aligned with params_list."""
        raise NotImplementedError

    def _require_init(self):
        if self.params_list is None:
            self.init()

    @property
    def output_compile_count(self) -> int:
        """Forward traces compiled by `output()` so far — one per distinct
        (training, input shape/dtype) key. Steady state for a serving
        workload is a constant (one per batch bucket); growth under
        traffic means shape churn is forcing recompiles."""
        return self._output_compiles

    def _cached_output_fn(self, key, make_fn):
        """Shape-keyed get-or-insert into the `output()` jit cache, bumping
        `output_compile_count` on insert. Under the lock so concurrent
        cache misses on one key (ParallelInference calls output() from
        several threads) produce ONE entry; the actual trace happens at
        call time outside the lock and jax serializes it internally."""
        with self._output_cache_lock:
            if not isinstance(self._output_fn, dict):
                self._output_fn = {}
            fn = self._output_fn.get(key)
            if fn is None:
                fn = self._output_fn[key] = make_fn()
                self._output_compiles += 1
                self._note_compile("output", key)
            return fn

    def _note_compile(self, kind: str, key=None):
        """Record an insertion into this net's own program dicts (a step
        program built, an `output()` shape first seen) as
        `compile_total{kind}` and a flight-recorder event with the shape
        signature. It counts what the net asked for, not what jax did: a
        batch of another shape retraces inside the one `jax.jit` and leaves
        it alone. Real traces, compiles and cache loads, with their seconds,
        are `jit_compile_seconds{phase}` and the always-on `compile/*` spans
        (utils/tracing.watch_compiles)."""
        _metrics.get_registry().counter(
            "compile_total",
            "insertions into a net's own program dicts (a step program "
            "built, an output() shape first seen); what jax really traced, "
            "compiled or loaded is jit_compile_seconds{phase}",
            ("kind",)).labels(kind).inc()
        _blackbox.get_recorder().record_event(
            "compile", compile_kind=kind,
            key=None if key is None else str(key))

    # -- multi-device mesh ----------------------------------------------------

    def set_mesh(self, mesh=None, *, plan=None, bucket_bytes=None,
                 grad_dtype=None):
        """Attach a device mesh: the mainline multi-chip training path.
        Params/layer state/updater state are committed to the mesh
        replicated (tp/pp placements already on the mesh are honored),
        each fit batch is sharded on the "data" axis by the input
        pipeline, and the optimizer step compiles to ONE donated SPMD
        program with the gradient all-reduce in-graph — bucketed per the
        plan's CollectivePlan (see parallel/sharded.py). `mesh=None`
        builds a 1-D "data" mesh over all visible devices; `plan`
        overrides the MeshPlan (the multi-host DCN plan does).
        `bucket_bytes` sets the gradient-bucket size (0 = monolithic
        tail-end reduction; default DL4J_GRAD_BUCKET_BYTES or 4 MiB);
        `grad_dtype="bf16"` opts the all-reduce wire payload into bf16
        (f32 accumulation after the reduce — never the default). `fit()`
        calls this automatically when more than one device is visible
        (DL4J_AUTO_MESH=0 disables)."""
        from deeplearning4j_tpu.parallel.sharded import MeshPlan

        self._require_init()
        if mesh is None:
            from deeplearning4j_tpu.parallel.mesh import data_parallel_mesh

            mesh = data_parallel_mesh()
        if plan is None:
            plan = MeshPlan(mesh, bucket_bytes=bucket_bytes,
                            grad_dtype=grad_dtype)
        elif bucket_bytes is not None or grad_dtype is not None:
            raise ValueError(
                "bucket_bytes/grad_dtype are MeshPlan knobs — pass them "
                "to the plan's constructor, not alongside plan=")
        plan.place_net(self)
        self._mesh_plan = plan
        self._batch_transform = plan.shard_batch
        self._reset_step_programs()
        return self

    def unset_mesh(self):
        """Detach the mesh plan (single-device semantics again). Params/
        state/updater are re-committed to the default device: leaving
        them committed to the multi-device mesh would hand the rebuilt
        un-sharded jit arguments on incompatible device sets (mesh-
        committed params vs default-device batches) — and the leftover
        NamedSharding would also block auto-mesh from re-attaching."""
        if self._mesh_plan is not None:
            import jax

            dev = jax.devices()[0]
            put = lambda t: jax.tree_util.tree_map(
                lambda a: jax.device_put(a, dev), t)
            self.params_list = put(self.params_list)
            self.state_list = put(self.state_list)
            self.upd_state = put(self.upd_state)
            self._mesh_plan = None
            self._batch_transform = None
            self._reset_step_programs()
        return self

    def _maybe_auto_mesh(self):
        """The fit-loop default: on a multi-device platform with no mesh
        attached and no caller-installed batch transform, engage the
        sharded data-parallel step over all devices — multi-chip training
        is the mainline, not an opt-in wrapper. DL4J_AUTO_MESH=0 opts a
        process out (tests/conftest.py does, so tier-1's 8-virtual-device
        suite doesn't shard every tiny fit)."""
        if self._mesh_plan is not None or self._batch_transform is not None:
            return
        from deeplearning4j_tpu.parallel.sharded import auto_mesh_enabled

        if not auto_mesh_enabled():
            return
        import jax

        if len(jax.devices()) < 2:
            return
        if self.params_list is not None:
            from jax.sharding import NamedSharding

            for leaf in jax.tree_util.tree_leaves(self.params_list):
                if isinstance(getattr(leaf, "sharding", None), NamedSharding):
                    # params already carry a mesh placement (shard_params_tp
                    # or an explicit set_mesh/unset_mesh sequence): that is
                    # a deliberate parallelism decision — don't clobber it
                    # with an auto data mesh
                    return
        logger.info(
            "multi-device platform (%d devices): engaging the sharded "
            "data-parallel train step (net.set_mesh; DL4J_AUTO_MESH=0 "
            "disables)", len(jax.devices()))
        self.set_mesh()

    # -- model FLOPs (the MFU numerator) -------------------------------------

    def model_flops_per_example(self):
        """(per-example optimizer-step FLOPs, source) for live MFU
        accounting (utils/devprof, PerformanceListener). Lazily the
        analytic per-layer estimate; upgraded to the jaxpr cost model
        when one is attached (`attach_cost_model` — bench.py and
        `cli perf` do). (None, source) when the conf carries no
        InputType to estimate from."""
        v = getattr(self, "_flops_per_example", None)
        if v is None:
            from deeplearning4j_tpu.utils import flops as _flops

            v = self._flops_per_example = \
                _flops.analytic_step_flops_per_example(self.conf)
        return v

    def set_model_flops_per_example(self, flops, source: str = "costmodel"):
        self._flops_per_example = (float(flops), str(source))
        return self

    def attach_cost_model(self, cm, batch: Optional[int] = None):
        """Adopt an analysis/costmodel.CostModel as this net's FLOP and
        static-memory accounting: live MFU gauges switch to its model
        FLOPs (source "costmodel") and the `device_memory_bytes{kind=
        activations_est}` watermark and OOM forensics use its
        liveness-based activation peak."""
        b = batch or cm.batch or 1
        self.set_model_flops_per_example(cm.model_flops / max(1, b))
        self._cost_model_meta = {
            "activation_peak_bytes": cm.activation_peak_bytes,
            "resident_bytes": cm.resident_bytes,
            "largest_activation": cm.largest_activation,
            "model_flops": cm.model_flops,
            "batch": b,
            "source": "costmodel",
        }
        return self

    def set_tenant(self, tenant):
        """Register this net under a tenant identity — the SAME identity
        the serving tier books under (utils/tenancy). When process-wide
        metering is enabled (utils/resourcemeter), the net's devprof
        device-time windows, HBM residency, and all-reduce wire bytes
        are attributed to that tenant; unmetered, this is just an
        interned attribute."""
        _resourcemeter.register_net(self, tenant)
        return self

    # -- static analysis -----------------------------------------------------

    def doctor(self, *, batch_size: int = 2, timesteps: int = 8,
               jaxpr: bool = True):
        """Pre-flight static analysis of this network: shape/dtype flow
        over the configuration (analysis/shapeflow — nIn/nOut wiring,
        missing preprocessors, merge conflicts, dead vertices) and, when
        the config is sound and `jaxpr` is True, one abstract trace of
        the train-step loss audited for TPU hazards (analysis/jaxpr_audit
        — f64, widening casts, folded constants, host callbacks, dead
        weights, donation). No compile, no device step, no mutation.

        Returns a list of analysis.Finding; `cli doctor` is this method
        with a command line. Opt-in by design — construction stays
        cheap; call it before committing real device time to a model."""
        from deeplearning4j_tpu.analysis import doctor_network

        return doctor_network(self, batch_size=batch_size,
                              timesteps=timesteps, jaxpr=jaxpr)

    # -- listeners -----------------------------------------------------------

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def add_listener(self, listener):
        self.listeners.append(listener)
        return self

    def set_collect_stats(self, flag: bool = True):
        """Toggle fused per-iteration grad/update/param mean-magnitude
        collection (used by ui.StatsListener). Rebuilds the train step."""
        flag = bool(flag)
        if flag != self._collect_stats:
            self._collect_stats = flag
            self._train_step_fn = None
            self._step_programs.clear()
        return self

    def set_sentinel(self, sentinel):
        """Attach a train/sentinel.DivergenceSentinel: every optimizer
        step is judged against the in-graph (loss, grad-norm) diagnostic;
        anomalous steps are discarded (quarantine), persistent anomalies
        restore the last-good checkpoint (rollback) and bounded failures
        raise TrainingDivergedError. Pass None to detach. A judged step
        blocks on its own diagnostic, so the sentinel trades the async
        dispatch pipeline's lookahead for per-step safety — attach it to
        runs that must survive numerical failure, not to microbenchmarks.
        Disables step fusion (each step must be judged individually)."""
        self._sentinel = sentinel
        return self

    def set_fused_steps(self, k: int):
        """Run up to `k` consecutive same-shape minibatches as ONE jitted
        dispatch (a `lax.scan` over the stacked batches — same math, same
        per-step lr/rng/iteration bookkeeping, k-1 fewer host->device
        round-trips). The host-side analog of the reference's
        AsyncDataSetIterator throughput role (MultiLayerNetwork.java:
        1023-1025) taken to its XLA conclusion: when dispatch latency is
        the bottleneck (small models, remote links), amortize it.

        Fusion engages only when it is observationally equivalent to the
        per-step loop: no listeners (per-iteration callbacks must see
        their iteration's params), no stats collection, no batch
        transform, and the subclass supports it (`_fused_fit_supported`);
        partial/ragged chunks fall back to per-step fits."""
        self._fused_k = max(1, int(k))
        return self

    def set_input_transform(self, transform):
        """Install an on-device batch transform (e.g.
        data.transforms.DeviceBatchTransform): under async_prefetch it
        runs jitted on the staged device batch inside the prefetch
        worker; with prefetch off it runs inline before the step — same
        math, same per-batch rng step, either way. Pass None to remove."""
        self._input_transform = transform
        return self

    def set_prefetch_depth(self, depth: int):
        """How many device-staged batches the input pipeline holds ahead
        of the train step (see data.prefetch.DevicePrefetchIterator)."""
        self._prefetch_depth = max(1, int(depth))
        return self

    def _notify(self, batch_size, ds=None):
        if not self.listeners:
            return
        info = {
            "score": lambda: self._score,
            "batch_size": batch_size,
            "etl_ms": self._last_etl_ms,
            "stats": lambda: self._last_stats,
            # the batch that produced this iteration (activation-visualizing
            # listeners forward it through the net; lambda keeps it lazy)
            "batch": lambda: ds,
        }
        for lst in self.listeners:
            lst.iteration_done(self, self.iteration - 1, info)

    # -- fit-loop observability ----------------------------------------------

    def _fit_obs(self):
        """Fit-loop instruments from the shared registry, resolved ONCE
        per network and cached — the per-step hot path touches these
        children only, never the registry (the ISSUE's overhead guard)."""
        ins = self._fit_instruments
        if ins is None:
            reg = _metrics.get_registry()
            ins = self._fit_instruments = {
                "steps": reg.counter(
                    "fit_step_total", "optimizer steps run").labels(),
                "examples": reg.counter(
                    "fit_examples_total",
                    "training examples consumed").labels(),
                "data_wait": reg.histogram(
                    "fit_data_wait_seconds",
                    "time blocked on the data iterator (ETL) before a "
                    "dispatch").labels(),
                "dispatch": reg.histogram(
                    "fit_dispatch_seconds",
                    "host time in the train-step call: the dispatch and "
                    "the wait for a free slot in the device's queue (on a "
                    "busy chip it tracks the device step); a program's "
                    "first dispatch also holds its trace and its compile "
                    "or cache load, which jit_compile_seconds{phase} and "
                    "the compile/* spans' `iteration` set apart").labels(),
                "examples_unknown": reg.counter(
                    "fit_examples_unknown_total",
                    "fit batches whose example count could not be "
                    "determined (excluded from fit_examples_total — "
                    "an under-report made explicit, not silent)").labels(),
                "allreduce_bytes": reg.counter(
                    "allreduce_bytes_total",
                    "gradient bytes all-reduced in-graph by the sharded "
                    "train step (logical payload: summed gradient leaf "
                    "bytes per optimizer step)").labels(),
                "collective_seconds": reg.counter(
                    "train_step_collective_seconds",
                    "time attributed to the train step's gradient "
                    "all-reduce, by accounting source (estimate = ring "
                    "wire bytes / ICI bandwidth — a cost model, not a "
                    "measurement; measured = sampled blocking dispatch "
                    "of a reduction-only probe with the live bucket "
                    "schedule)", ("source",)).labels("estimate"),
                "collective_seconds_measured": reg.counter(
                    "train_step_collective_seconds",
                    "time attributed to the train step's gradient "
                    "all-reduce, by accounting source",
                    ("source",)).labels("measured"),
                "recorder": _blackbox.get_recorder(),
                "timeline": _tracing.get_step_timeline().append,
                "devprof": _devprof.get_profiler(),
            }
            phases = reg.histogram(
                "fit_phase_seconds",
                "wall time of a fit() call's own entry (setup: auto mesh, "
                "resume, staging the input pipeline), exit (teardown: the "
                "layers' books, the pipeline's close) and each blocking "
                "read of the layers' books (publish_books: it drains the "
                "steps in flight)", ("phase",))
            for phase in ("setup", "teardown", "publish_books"):
                ins["phase_" + phase] = phases.labels(phase)
        return ins

    def _timed_fit(self, fit_fn, data_wait: float, n_examples: int,
                   n_batches: int = 1, batches=None):
        """Run one dispatch (a single `_fit_dataset` or a fused flush)
        under the step-phase timers. The clock (`tracing.now_ns`) is read
        once at each boundary — dispatch start, dispatch end, end of the
        observers — and the readings feed the histograms, the always-on
        step timeline (one tuple a dispatch; the flight recorder and the
        benchmark read it) and, when the tracer is on, the spans
        `fit/step` > `fit/dispatch`, `fit/observe`. Nothing here blocks
        on the device, tracer on or off: observability must not change
        the async dispatch pipeline it observes (devprof's sampled read
        is the one exception, and the timeline records its interval).
        `batches` names the DataSet(s) behind this dispatch for the
        divergence sentinel's quarantine records and the `nan` fault
        kind's batch taint."""
        ins = self._fit_obs()
        it0 = self.iteration
        # resume bookkeeping BEFORE the dispatch: a checkpoint listener
        # firing inside it (post-step _notify) must record this batch as
        # consumed — the snapshot's params already include its update
        ts = self._train_state
        if ts is not None:
            ts["batch_in_epoch"] += n_batches
        # beat on entry AND exit: each phase (data wait, dispatch) must
        # individually exceed hang_timeout to read as a stall, instead of
        # their sum tripping the watchdog on an input-bound step
        hb0 = self._fit_heartbeat
        if hb0 is not None:
            hb0.beat()
        # sentinel pre-capture: one attribute read with no sentinel
        # attached (the <10us off-path contract); with one, the pre-step
        # references that make an anomalous step's update discardable
        pre = _sentinel.pre_step(self)
        now_ns, cpu_ns = _tracing.now_ns, time.thread_time_ns
        with _tracing.span("fit/step", data_wait_ms=round(data_wait * 1e3, 3)):
            with _tracing.span("fit/dispatch"):
                t_d0, c0 = now_ns(), cpu_ns()
                # chaos hook: an `oom` fault here is a device allocator
                # failure mid-fit — it unwinds through _run_fit's OOM
                # forensics exactly as a real RESOURCE_EXHAUSTED would;
                # a `nan` fault taints this batch's features so the
                # divergence makes it into the REAL dispatch (NaN loss,
                # NaN grads — exactly what the sentinel exists to catch)
                injected = _faults.fault_point("train_step")
                if injected == "nan" and batches:
                    _faults.taint_nan(batches[0])
                # CN003 probe: entering the jitted step with a traced
                # lock held stalls every contender for a whole device
                # program (off = one module-global read)
                _locktrace.note_dispatch("fit/dispatch")
                fit_fn()
                t_d1, c1 = now_ns(), cpu_ns()
            with _tracing.span("fit/observe"):
                sampled = None
                try:
                    sampled = self._observe_step(
                        ins, it0, data_wait, (t_d1 - t_d0) * 1e-9,
                        n_examples, pre, batches)
                finally:
                    # the record lands even when the sentinel raises: an
                    # anomalous step stays visible in the flight recorder
                    # though its update is about to be discarded
                    s0, s1 = sampled or (0, 0)
                    t_end = now_ns()
                    ins["timeline"]((
                        self.iteration - 1, max(1, self.iteration - it0),
                        self._fit_t_mark or t_d0, t_d0, t_d1, t_end, s0, s1,
                        c1 - c0, cpu_ns() - c1, self._score))
                    self._fit_t_mark = t_end

    def _observe_step(self, ins, it0: int, data_wait: float, dispatch: float,
                      n_examples: int, pre, batches):
        """Everything the fit thread does for its observers after a
        dispatch returned: counters and histograms, collective books, the
        flight recorder's count, devprof, the run ledger, the sentinel's
        judgment, the heartbeat. Returns devprof's `(t0, t1)` where this
        dispatch was sampled."""
        n_steps = max(1, self.iteration - it0)
        ins["steps"].inc(n_steps)
        ins["examples"].inc(n_examples)
        ins["data_wait"].observe(data_wait)
        ins["dispatch"].observe(dispatch)
        # collective books: each sharded optimizer step all-reduced one
        # gradient payload in-graph — scrape-able evidence the reduction
        # runs on the interconnect, not through host averaging
        plan = self._mesh_plan
        if plan is not None and plan.n_data_shards > 1:
            payload = plan.grad_payload_bytes(self) * n_steps
            ins["allreduce_bytes"].inc(payload)
            # tenant wire-bytes attribution for the same payload (a net
            # registered via set_tenant; one global read unmetered)
            _resourcemeter.note_wire(getattr(self, "_tenant", None),
                                     _resourcemeter.TIER_TRAINING, payload)
            ins["collective_seconds"].inc(
                plan.collective_seconds_estimate(self) * n_steps)
            # the estimate's falsifier: every sample_every-th sharded
            # step, ONE blocking dispatch of the reduction-only probe
            # (same wire payload + bucket schedule), attributed to the
            # steps since the last sample — devprof's sampling contract,
            # so tier-1 (sample_every=0) never blocks here
            measured = plan.maybe_measure_collective(
                self, n_steps, ins["devprof"].sample_every)
            if measured is not None:
                ins["collective_seconds_measured"].inc(measured)
        # black box + liveness: the recorder counts the dispatch (its
        # record is the timeline's, appended by the caller; the score
        # stays a device reference — never synced here)
        ins["recorder"].note_step()
        # device-side accounting: two integer ops on unsampled steps,
        # one blocking score read every sample_every-th (utils/devprof)
        sampled = ins["devprof"].on_step(self, n_examples, self._score)
        # run-ledger hook: ONE module-global read with no ledger
        # attached (the off-by-default overhead contract); sampling
        # itself lives on the ledger's own daemon, never here
        _runledger.note_fit_step(self)
        # sentinel judgment AFTER the step's own forensics recorded it.
        # May raise RollbackSignal (answered by _run_fit) or
        # TrainingDivergedError.
        if pre is not None:
            _sentinel.post_step(self, pre, batches)
        hb = self._fit_heartbeat
        if hb is not None:
            hb.beat()
        return sampled

    def _ds_examples(self, ds) -> int:
        """Example count for `fit_examples_total`. Only structural
        can't-know failures (no such method/attribute, malformed shape)
        degrade to 0 — and those are counted under
        `fit_examples_unknown_total` so the under-report is visible. A
        real iterator bug raising anything else propagates; the old bare
        `except Exception` swallowed those."""
        try:
            return int(getattr(ds, "reported_examples", None)
                       or ds.num_examples())
        except (AttributeError, TypeError, IndexError):
            self._fit_obs()["examples_unknown"].inc()
            return 0

    # -- the fit loop --------------------------------------------------------

    def _run_fit(self, iterator, epochs: int, async_prefetch: bool,
                 prefetch_buffer: int = 4,
                 hang_timeout: Optional[float] = None,
                 resume_from: Optional[str] = None,
                 run_ledger=None):
        """One `fit()` call: the always-on span `fit/run`, whose trace id
        every span of the call shares, around `fit/setup` (from the entry
        to just before the first epoch), the epochs and `fit/teardown`
        (from the epochs' return, or their raise, to the end of the
        clean-up). The listeners' `on_fit_end` hooks run last, once
        `fit/run` is in the ring: a `TracingListener` writes it out."""
        _tracing.watch_compiles()
        self._fit_calls += 1
        try:
            with _tracing.phase("fit/run", fit_call=self._fit_calls,
                                epochs=int(epochs)) as run:
                self._run_fit_phases(run, iterator, epochs, async_prefetch,
                                     prefetch_buffer, hang_timeout,
                                     resume_from, run_ledger)
        finally:
            # fires even when an epoch raises: listeners that flipped
            # process-global state for the run (TracingListener) restore
            # it here instead of leaking it past a failed fit
            for lst in self.listeners:
                hook = getattr(lst, "on_fit_end", None)
                if hook is not None:
                    hook(self)
        return self

    def _run_fit_phases(self, run, iterator, epochs: int,
                        async_prefetch: bool, prefetch_buffer: int,
                        hang_timeout: Optional[float],
                        resume_from: Optional[str], run_ledger):
        ins = self._fit_obs()
        with _tracing.phase("fit/setup", observe=ins["phase_setup"],
                            async_prefetch=bool(async_prefetch)) as setup:
            # run-ledger opt-in (ONE knob): a path builds a RunLedger there
            # (closed when the fit ends — the per-run artifact), an instance
            # is attached for the fit's duration and left open for its
            # owner. Hooks stay a single flag check when this is None.
            owned_ledger = attached_ledger = None
            if run_ledger is not None:
                if isinstance(run_ledger, str):
                    owned_ledger = _runledger.RunLedger(run_ledger)
                    attached_ledger = _runledger.attach(owned_ledger)
                else:
                    attached_ledger = _runledger.attach(run_ledger)
            # multi-device default: engage the sharded data-parallel step
            # BEFORE restore/staging so the restored state lands on the mesh
            # and the pipeline stages batches with the mesh sharding
            self._maybe_auto_mesh()
            if self._mesh_plan is not None:
                self._mesh_plan.reset_pad_target()
            skip_batches = 0
            if resume_from is not None:
                # restore BEFORE staging: the iterator state lands on the
                # caller's iterator, not the pipeline wrappers about to be
                # composed around it
                skip_batches, epochs, _ = self._restore_for_resume(
                    resume_from, iterator, epochs)
                if self._mesh_plan is not None:
                    # checkpoint arrays arrive as host numpy: re-commit them
                    # to the mesh so the sharded step's in-shardings match
                    self._mesh_plan.place_net(self)
            owned = None
            if async_prefetch:
                staged = self._stage_input_pipeline(iterator, prefetch_buffer)
                if staged is not iterator:
                    iterator = owned = staged
            # a caller-installed batch transform disables fusion (per-batch
            # hooks must see their own batch) — EXCEPT the mesh plan's own
            # shard_batch: sharded batches stack fine, and the stacked fused
            # programs shard batch dim 1 (stacked_data in _jit_step), so
            # mesh-attached nets keep their dispatch-fusion opt-in. The
            # divergence sentinel also disables fusion: quarantine must be
            # able to discard ONE step's update, not a fused group's.
            plan_shard = (None if self._mesh_plan is None
                          else self._mesh_plan.shard_batch)
            fuse_k = self._fused_k if (
                self._fused_k > 1
                and not self.listeners
                and not self._collect_stats
                and self._sentinel is None
                and (self._batch_transform is None
                     or self._batch_transform == plan_shard)
                and self._fused_fit_supported()
            ) else 1
            # sentinel wiring: resolve the rollback directory (explicit >
            # resume_from > an attached CheckpointListener) and reset the
            # per-fit escalation counters
            if self._sentinel is not None:
                self._sentinel.bind(self, resume_dir=resume_from)
            # the epoch target the rollback loop restores toward: `epochs`
            # is already "remaining" here (the initial resume consumed the
            # completed ones), so the absolute target is epoch + remaining
            total_epoch_target = int(self.epoch) + int(epochs)
            # liveness: the fit thread holds a busy slot on the "fit"
            # heartbeat for the whole run and beats once per dispatch
            # (_timed_fit). With hang_timeout the watchdog's stall action
            # dumps the flight recorder and raises StepHangError here —
            # a wedged step becomes a diagnosable exception, not a hang.
            hb = _health.get_health().register(
                "fit",
                stall_after=hang_timeout if hang_timeout else 600.0,
                on_stall=self._hang_action() if hang_timeout else None)
            self._fit_heartbeat = hb
            setup.args["mesh"] = (None if self._mesh_plan is None
                                  else str(dict(self._mesh_plan.mesh.shape)))
        # a resume moved the count: the first step this call runs
        run.args["first_iteration"] = int(self.iteration)
        teardown = _tracing.phase("fit/teardown",
                                  observe=ins["phase_teardown"])
        try:
            with hb.busy():
                with _tracing.steps_of(self):
                    while True:
                        try:
                            self._fit_epochs(iterator, epochs, fuse_k,
                                             skip_batches)
                            break
                        except _sentinel.RollbackSignal:
                            # the sentinel's escalation: restore the last-
                            # good checkpoint and replay — bounded attempts
                            # (note_rollback raises TrainingDivergedError
                            # past the budget)
                            skip_batches, epochs = self._rollback_restore(
                                iterator, total_epoch_target)
                teardown.__enter__()
                # counters that layers keep on the device reach the
                # registry here and on devprof's sampled steps, never on a
                # plain step
                self._publish_layer_books()
        except _health.StepHangError as e:
            if e.dump_path is not None:
                raise  # already carries its forensics
            raise _health.StepHangError(
                f"fit step exceeded hang_timeout={hang_timeout}s without "
                f"progress (see flight-recorder dump)",
                dump_path=self._hang_dump_path) from None
        except Exception as e:
            # device allocator failure: capture the largest live buffers
            # + the static activation estimate BEFORE unwinding (the
            # buffers are gone once the frames release their references),
            # then let the original exception carry on
            if _devprof.is_oom(e):
                path = _devprof.oom_forensics("fit", e, net=self)
                logger.error("RESOURCE_EXHAUSTED in fit; OOM forensics "
                             "dump at %s", path)
            raise
        finally:
            if not teardown.t0:
                # the epochs raised: the clean-up is the teardown
                teardown.__enter__()
            run.args["last_iteration"] = int(self.iteration) - 1
            # the ledger scope ends with the fit: an owned (path-built)
            # ledger takes its final sample and closes; a caller-owned
            # one is only detached (its recording thread lives on)
            if owned_ledger is not None:
                owned_ledger.close()
            elif attached_ledger is not None:
                _runledger.detach(attached_ledger)
            self._fit_heartbeat = None
            # resume coordinates die with the fit: a preemption save
            # AFTER a completed fit must record a clean epoch boundary,
            # not a stale mid-epoch position
            self._train_state = None
            # the devprof sampling window dies with the fit too: a
            # stale last-sample timestamp would make the NEXT fit's
            # first window span the inter-fit idle gap and publish
            # garbage step-time/MFU gauges
            self._devprof_state = None
            _health.get_health().unregister(hb)
            # pipeline workers this fit created must die with it, raise
            # or return (the generators' own finally handles the common
            # case; this covers anything still live after an exception)
            if owned is not None:
                owned.close()
            teardown.__exit__(None, None, None)

    def _publish_layer_books(self):
        """Publish counters that layers carry as state on the device,
        through the hooks their kinds registered (`register_layer(...,
        publish_fn=)`), and zero them. One attribute read for a net without
        such layers; else one blocking read of a few hundred bytes, made
        only where the fit loop blocks anyway."""
        slots = getattr(self, "_book_slots", None)
        if slots is None:
            slots = self._book_slots = publish_slots(
                self._ordered_layer_confs())
        if not slots or self.state_list is None:
            return None
        import jax

        confs = self._ordered_layer_confs()
        flat = [i for idx in slots.values() for i in idx]
        # the blocking read: it waits for every step in flight, so the
        # always-on span is the drain at the end of a fit()
        with _tracing.phase("fit/publish_books",
                            observe=self._fit_obs()["phase_publish_books"],
                            n_slots=len(flat)):
            books = dict(zip(flat, jax.device_get(
                [self.state_list[i] for i in flat])))
            out = {}
            for hook, idx in slots.items():
                out.update(hook([confs[i] for i in idx],
                                [books[i] for i in idx]) or {})
            for i in flat:
                self.state_list[i] = jax.tree_util.tree_map(
                    jax.numpy.zeros_like, self.state_list[i])
        return out

    def _hang_action(self):
        """The watchdog-side stall action for fit(hang_timeout=...):
        runs on the dl4j-watchdog thread — dump the black box first (the
        forensics must exist before the exception unwinds the fit), then
        async-raise StepHangError into the fitting thread."""
        fit_tid = threading.get_ident()

        def on_stall(hb, stalled_for):
            self._hang_dump_path = _blackbox.get_recorder().dump(
                reason=f"fit step hang: no progress for "
                       f"{stalled_for:.3f}s (hang_timeout={hb.stall_after}s)")
            # the dump takes real time: re-check the fit is still OURS
            # and still stalled before the irrevocable async raise — a
            # step that unblocked (or a fit that finished) meanwhile must
            # not receive a StepHangError in its cleanup or afterwards
            if self._fit_heartbeat is not hb:
                return
            state, _, _ = hb.check()
            if state == _health.OK:
                return
            if not _health._async_raise(fit_tid, _health.StepHangError):
                logger.error(
                    "fit hang detected but StepHangError could not be "
                    "delivered; dump at %s", self._hang_dump_path)

        return on_stall

    def _stage_input_pipeline(self, iterator, prefetch_buffer: int):
        """Compose the staged input pipeline around a fit's iterator:

            [caller's host ETL] -> AsyncDataSetIterator -> device prefetch

        * If the caller already built a DevicePrefetchIterator, it IS the
          pipeline — used as-is (bench/resnet pass pre-staged batches).
        * A caller-provided host stage (AsyncDataSetIterator or
          ParallelDataSetIterator multi-worker ETL) is kept; otherwise a
          single async host-prefetch thread is added (the pre-pipeline
          behavior).
        * The device stage runs `_batch_transform` (the mesh plan's
          per-shard batch split under set_mesh) — or a committed
          default-device `device_put` — plus the on-device input
          transform, all in its worker thread, `_prefetch_depth` batches
          ahead: host->device transfer leaves the dispatch critical path.
        """
        from deeplearning4j_tpu.data.prefetch import (
            DevicePrefetchIterator,
            ParallelDataSetIterator,
        )

        if isinstance(iterator, DevicePrefetchIterator):
            # caller-built pipeline: it must carry the net's configured
            # staging, or the loop would silently train unsharded /
            # untransformed (staged batches skip the inline application)
            for mine, theirs, what in (
                (self._batch_transform, iterator.placement,
                 "batch transform (mesh batch sharding)"),
                (self._input_transform, iterator.transform,
                 "input transform"),
            ):
                # `!=`, not `is not`: bound methods (the MeshPlan's
                # shard_batch) are fresh objects per attribute access
                # but compare equal on (__self__, __func__)
                if mine is not None and theirs != mine:
                    raise ValueError(
                        f"a DevicePrefetchIterator was passed to fit() but "
                        f"the network has a {what} configured that the "
                        f"iterator does not apply — build the iterator "
                        f"with it (placement=/transform=), or pass the "
                        f"un-staged base iterator and let fit compose "
                        f"the pipeline")
            return iterator
        host = iterator
        wrapped = False
        if not isinstance(host, (AsyncDataSetIterator,
                                 ParallelDataSetIterator)):
            host = AsyncDataSetIterator(host, prefetch_buffer)
            wrapped = True
        return DevicePrefetchIterator(
            host, depth=self._prefetch_depth,
            placement=self._batch_transform,
            transform=self._input_transform,
            close_base=wrapped)

    def _capture_iterator_state(self, iterator) -> Optional[dict]:
        """The iterator's epoch-start state (the data/iterators
        `state()` protocol), JSON-safe, for checkpoints. None when the
        iterator is stateless or its capture fails — resume then replays
        positionally only."""
        state_fn = getattr(iterator, "state", None)
        if not callable(state_fn):
            return None
        try:
            return state_fn()
        except Exception:
            logger.warning("iterator state capture failed; checkpoints "
                           "will resume positionally only", exc_info=True)
            return None

    def train_state(self) -> Optional[dict]:
        """Point-in-time resume coordinates of the CURRENT fit: epoch,
        batches consumed within it, and the iterator's epoch-start state.
        Embedded into checkpoints (utils/model_serializer trainState.json)
        and replayed by fit(resume_from=...). None outside a fit."""
        ts = self._train_state
        return None if ts is None else dict(ts)

    def _restore_for_resume(self, directory: str, iterator,
                            epochs: int, require_finite: bool = False,
                            lr_drift_ok: bool = False,
                            reject_iterations=()):
        """Load the newest GOOD checkpoint in `directory` into this net
        and prime the mid-epoch replay: restores the iterator's
        epoch-start state and returns (batches to skip in the first
        epoch, epochs remaining out of the requested total, the restored
        path or None). An empty/missing directory is a fresh start — the
        same command line works on first boot and after a preemption.

        "Good" is enforced, not assumed: each candidate's per-entry
        SHA-256 manifest is verified before the load (a bit-flipped or
        torn zip is skipped — loudly, counted — and the previous
        checkpoint is used instead), a candidate that fails to
        deserialize is skipped the same way, and the sentinel's rollback
        path additionally rejects checkpoints whose restored params
        carry NaN/Inf (`require_finite`) or whose iteration falls inside
        a quarantined step (`reject_iterations` — a listener can save
        DURING the anomalous dispatch, before the sentinel judged it) —
        "last-good" must actually be good."""
        from deeplearning4j_tpu.train.checkpoint import (
            NoUsableCheckpointError,
            checkpoint_candidates,
            note_bad_checkpoint,
            verified_checkpoints,
        )
        from deeplearning4j_tpu.utils.model_serializer import (
            ConfigMismatchError,
            restore_fit_state,
        )

        meta = path = None
        for cand_path, cand_meta in verified_checkpoints(directory):
            if reject_iterations and int(
                    cand_meta.get("iteration", -1)) in reject_iterations:
                # a save captured DURING a quarantined step holds the
                # very update the sentinel discarded — finite, digest-
                # clean, and still not "good"
                note_bad_checkpoint(
                    cand_path, "captured from a quarantined step")
                continue
            try:
                meta = restore_fit_state(self, cand_path,
                                         ignore_lr=lr_drift_ok)
            except ConfigMismatchError:
                # a changed architecture is a USER error every candidate
                # repeats — raise it, don't silently discard the whole
                # checkpoint history and "start fresh"
                raise
            except Exception as e:
                note_bad_checkpoint(
                    cand_path, f"restore failed: {type(e).__name__}: {e}")
                meta = None
                continue
            if require_finite and not self._params_finite():
                note_bad_checkpoint(
                    cand_path, "restored parameters are non-finite")
                meta = None
                continue
            path = cand_path
            break
        if meta is None:
            if any(True for _ in checkpoint_candidates(directory)):
                # checkpoints EXIST but every one was rejected: raising
                # beats silently restarting from iteration 0 (which
                # would then GC the corrupt zips — progress AND evidence
                # gone); the rollback path converts this to
                # TrainingDivergedError
                raise NoUsableCheckpointError(
                    f"resume_from={directory!r}: checkpoints exist but "
                    f"every candidate was rejected (see "
                    f"checkpoint_integrity_failures_total and the "
                    f"checkpoint_corrupt events) — not starting fresh "
                    f"over a corrupted history")
            logger.info("resume_from=%r: no checkpoint found — starting "
                        "fresh", directory)
            return 0, epochs, None
        ts = meta.get("train_state") or {}
        skip = int(ts.get("batch_in_epoch", 0))
        it_state = ts.get("iterator_state")
        if it_state is not None:
            restore = getattr(iterator, "restore_state", None)
            if callable(restore):
                restore(it_state)
            else:
                logger.warning(
                    "checkpoint carries iterator state but the iterator "
                    "has no restore_state(); mid-epoch replay may not be "
                    "deterministic")
        remaining = max(0, int(epochs) - int(self.epoch))
        if remaining == 0 and skip > 0:
            remaining = 1  # died inside the final epoch: finish it
        logger.info(
            "resumed from %s: iteration=%d epoch=%d, replaying %d "
            "batch(es), %d epoch(s) remaining", path, self.iteration,
            self.epoch, skip, remaining)
        _blackbox.get_recorder().record_event(
            "resume", checkpoint=path, iteration=int(self.iteration),
            epoch=int(self.epoch), skip_batches=skip)
        return skip, remaining, path

    def _params_finite(self) -> bool:
        """Host check that every parameter leaf is finite — the
        rollback path's guard against restoring a checkpoint that was
        saved after the divergence already poisoned the params."""
        import jax

        for leaf in jax.tree_util.tree_leaves(self.params_list):
            if not np.all(np.isfinite(np.asarray(leaf))):
                return False
        return True

    def _rollback_restore(self, iterator, total_epoch_target: int):
        """Answer a sentinel RollbackSignal: account the attempt
        (bounded; optional LR backoff), tear down the abandoned
        mid-epoch pipeline run, restore the newest checkpoint that
        verifies AND loads AND is finite, and re-commit it to the mesh.
        Returns the (skip_batches, epochs_remaining) the replay needs."""
        sent = self._sentinel
        directory = sent.note_rollback(self)
        hb = self._fit_heartbeat
        if hb is not None:
            hb.beat()
        # the RollbackSignal left `for ds in iterator` mid-iteration:
        # close the run (its worker would keep consuming the base
        # concurrently with the replay's fresh run) and rewind to the
        # epoch start — restore_state below overrides the position when
        # the iterator supports the resume protocol
        close = getattr(iterator, "close", None)
        if callable(close):
            close()
        iterator.reset()
        # lr_drift_ok: a previous rollback's lr backoff (or this one's)
        # must not disqualify checkpoints saved at the original rate
        from deeplearning4j_tpu.train.checkpoint import (
            NoUsableCheckpointError,
        )

        try:
            skip, remaining, path = self._restore_for_resume(
                directory, iterator, total_epoch_target,
                require_finite=True, lr_drift_ok=True,
                reject_iterations=sent.tainted_iterations)
        except NoUsableCheckpointError as e:
            sent.diverged(str(e))
        if path is None:
            sent.diverged(
                f"rollback found no usable checkpoint in {directory!r}")
        if self._mesh_plan is not None:
            # checkpoint arrays arrive as host numpy: re-commit to the
            # mesh so the sharded step's in-shardings stay valid
            self._mesh_plan.place_net(self)
        self._step_diag = None
        if hb is not None:
            hb.beat()
        return skip, remaining

    def _fit_epochs(self, iterator, epochs: int, fuse_k: int,
                    skip_batches: int = 0):
        skip = int(skip_batches)
        for _ in range(epochs):
            for lst in self.listeners:
                lst.on_epoch_start(self, self.epoch)
            # resume coordinates for this epoch: captured BEFORE the
            # first batch is pulled, so a checkpoint taken anywhere in
            # the epoch can restore the iterator to the same epoch start
            # (e.g. the shuffle permutation) and skip forward
            self._train_state = {
                "epoch": int(self.epoch),
                "batch_in_epoch": 0,
                "iterator_state": self._capture_iterator_state(iterator),
            }
            t_etl = time.perf_counter()
            self._fit_t_mark = _tracing.now_ns()
            buf, sig = [], None
            # data-wait accumulates across buffered (fused) batches so a
            # fused dispatch's histogram entry covers ALL the iterator
            # blocking it amortizes, not just the last batch's
            wait_accum = 0.0
            n_buf = 0
            for ds in iterator:
                wait = time.perf_counter() - t_etl
                self._last_etl_ms = wait * 1e3
                if not getattr(ds, "_pipeline_staged", False):
                    # prefetch-off path: staging work runs inline (same
                    # ops, same order — byte-identical to the pipeline)
                    if self._batch_transform is not None:
                        ds = self._batch_transform(ds)
                    if self._input_transform is not None:
                        ds = self._input_transform(ds)
                if skip > 0:
                    # mid-epoch replay: this batch was trained before the
                    # checkpoint. It is CONSUMED — pulled through the
                    # pipeline and transformed, so every stage's rng/step
                    # counter advances exactly as in the original run —
                    # but not dispatched (its update is already in the
                    # restored params).
                    skip -= 1
                    self._train_state["batch_in_epoch"] += 1
                    t_etl = time.perf_counter()
                    continue
                if (self._sentinel is not None
                        and self._sentinel.should_skip_batch(self, ds)):
                    # quarantined batch re-encountered (post-rollback
                    # replay, or the next epoch's pass over bad data):
                    # consume it without dispatching — re-running it
                    # would deterministically diverge again
                    self._train_state["batch_in_epoch"] += 1
                    t_etl = time.perf_counter()
                    continue
                if fuse_k > 1:
                    s = self._ds_signature(ds)
                    if buf and s != sig:
                        # flush BEFORE charging this batch's wait: it
                        # belongs to the group this batch starts, not the
                        # one it closes
                        flushed, n = list(buf), n_buf
                        self._timed_fit(
                            lambda: self._flush_fused(flushed, fuse_k),
                            wait_accum, n, n_batches=len(flushed),
                            batches=flushed)
                        wait_accum, n_buf = 0.0, 0
                        buf = []
                    wait_accum += wait
                    sig = s
                    buf.append(ds)
                    n_buf += self._ds_examples(ds)
                    if len(buf) == fuse_k:
                        flushed, n = list(buf), n_buf
                        self._timed_fit(
                            lambda: self._flush_fused(flushed, fuse_k),
                            wait_accum, n, n_batches=len(flushed),
                            batches=flushed)
                        wait_accum, n_buf = 0.0, 0
                        buf = []
                else:
                    wait_accum += wait
                    self._timed_fit(lambda: self._fit_dataset(ds),
                                    wait_accum, self._ds_examples(ds),
                                    batches=[ds])
                    wait_accum = 0.0
                t_etl = time.perf_counter()
            if buf:
                flushed, n = list(buf), n_buf
                self._timed_fit(lambda: self._flush_fused(flushed, fuse_k),
                                wait_accum, n, n_batches=len(flushed),
                                batches=flushed)
            if skip > 0:
                # the resumed epoch ended with replay batches still owed:
                # the iterator yields fewer batches than the checkpoint's
                # batch_in_epoch said (dataset shrank, batch size grew,
                # or the iterator state failed to restore). Dropping the
                # leftover into the NEXT epoch would silently swallow its
                # first `skip` real batches — reset instead, loudly.
                logger.warning(
                    "resume fast-forward ran out of batches with %d still "
                    "to skip (iterator shorter than at checkpoint time); "
                    "continuing from the next epoch start", skip)
                skip = 0
            for lst in self.listeners:
                lst.on_epoch_end(self, self.epoch)
            self.epoch += 1
            iterator.reset()

    def _flush_fused(self, buf, fuse_k):
        """Full chunks run fused; ragged tails fall back to per-step fits
        (one jitted program per chunk size would defeat the cache)."""
        if len(buf) == fuse_k:
            self._fit_datasets_fused(buf)
        else:
            for ds in buf:
                self._fit_dataset(ds)

    # -- flattened params API ------------------------------------------------

    def params(self):
        """Flattened parameter vector (reference: Model.params())."""
        self._require_init()
        return params_to_flat(self._ordered_layer_confs(), self.params_list)

    def set_params(self, flat):
        self._require_init()
        self.params_list = flat_to_params(
            self._ordered_layer_confs(), self.params_list, flat
        )

    def num_params(self) -> int:
        self._require_init()
        return num_params(self._ordered_layer_confs(), self.params_list)

    def param_table(self):
        self._require_init()
        return param_table(self._ordered_layer_confs(), self.params_list)

    def summary(self) -> str:
        self._require_init()
        lines = ["=" * 70]
        total = 0
        for i, (conf, p) in enumerate(
            zip(self._ordered_layer_confs(), self.params_list)
        ):
            n = sum(int(np.prod(v.shape)) for v in p.values())
            total += n
            lines.append(f"{i:>3}  {type(conf).__name__:<28} params: {n}")
        lines.append(f"total params: {total}")
        lines.append("=" * 70)
        return "\n".join(lines)

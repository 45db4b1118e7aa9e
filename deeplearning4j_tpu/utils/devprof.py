"""Always-on device performance & memory accounting — the runtime half
of the device observability layer (analysis/costmodel.py is the static
half; each checks the other).

The fit loop's host-side phase timers (PR 3) can say the host is not
the bottleneck, but every *device*-side number — step time, MFU,
FLOP/s — previously existed only in bench runs. This module makes them
first-class, always-on series at fixed cost:

* **Sampled device time**: every `sample_every`-th dispatch the
  profiler runs ONE `block_until_ready` on that step's score; wall time
  between consecutive samples divided by the steps in between is the
  per-step device-visible time. Unsampled steps cost two integer ops —
  the async dispatch pipeline never bubbles between samples. Under
  tier-1 sampling is OFF (`sample_every=0`, set by tests/conftest.py)
  so the suite's timing stays stable.
* **Live MFU**: `step_mfu` and `step_flops_per_second` gauges computed
  from the measured window × the net's model FLOPs — sourced from the
  jaxpr cost model when one was attached (`net.attach_cost_model`,
  which bench.py and `cli perf` do), else from the analytic per-layer
  estimator (`utils/flops`); the `source` label says which, so an MFU
  number can always be traced to its FLOP accounting.
* **HBM watermarks**: `device_memory_bytes{kind=params|updater|
  activations_est|live}` gauges polled at each sample — params/updater
  from the net's buffers, `activations_est` from the attached static
  model, `live` from JAX device memory stats where the backend exposes
  them (TPU/GPU; on CPU the sum of live jax arrays stands in). The
  flight recorder folds these into its periodic registry deltas, so a
  post-crash dump shows the memory trajectory leading into an OOM.
* **OOM forensics**: `is_oom()` recognizes RESOURCE_EXHAUSTED escaping
  the fit loop or the serving dispatcher; `oom_forensics()` records the
  largest live device buffers alongside the static activation estimate
  and dumps the flight recorder — rendered by `cli blackbox` as an "OOM
  forensics" section. Deterministically injectable: the `oom` fault
  kind (utils/faultpoints) raises an error that takes exactly this
  path.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import List, Optional

from deeplearning4j_tpu.utils import blackbox as _blackbox
from deeplearning4j_tpu.utils import metrics as _metrics
from deeplearning4j_tpu.utils import resourcemeter as _resourcemeter
from deeplearning4j_tpu.utils.tracing import now_ns as _now_ns

logger = logging.getLogger("deeplearning4j_tpu")

# every Nth fit dispatch pays one blocking score read; 0 disables the
# sampled sync entirely (tier-1 sets this — timing-stable tests)
DEFAULT_SAMPLE_EVERY = int(os.environ.get("DL4J_DEVPROF_SAMPLE_EVERY", "16"))

_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory",
                "Resource exhausted")


def is_oom(exc: BaseException) -> bool:
    """Does this exception look like a device allocator failure? XLA
    surfaces OOM as XlaRuntimeError('RESOURCE_EXHAUSTED: ...'); the
    injected `oom` fault kind carries the same marker by construction."""
    msg = str(exc)
    return any(m in msg for m in _OOM_MARKERS)


def largest_live_buffers(top: int = 12) -> List[dict]:
    """The biggest live device arrays right now — the "what is actually
    holding HBM" half of an OOM dump. Never raises (forensics must not
    shadow the failure being diagnosed)."""
    try:
        arrays = _jax().live_arrays()
    except Exception:
        return []
    seen = []
    for a in arrays:
        try:
            seen.append({
                "shape": tuple(int(s) for s in a.shape),
                "dtype": str(a.dtype),
                "nbytes": int(a.nbytes),
            })
        except Exception:
            continue
    seen.sort(key=lambda d: -d["nbytes"])
    return seen[:top]


def _jax():
    import jax

    return jax


class DeviceProfiler:
    """Process-global step accounting. One instance (`get_profiler()`);
    per-net sampling state lives on the net (`net._devprof_state`) so
    concurrent fits never share a window."""

    def __init__(self, sample_every: Optional[int] = None):
        self.sample_every = (DEFAULT_SAMPLE_EVERY if sample_every is None
                             else int(sample_every))
        self._ins = None
        self._lock = threading.Lock()

    def configure(self, sample_every: int) -> "DeviceProfiler":
        """0 disables the sampled device sync (repo 0-disables
        convention); the memory/MFU gauges then only move when a sample
        is forced (`sample_now`) or a cost model is attached."""
        self.sample_every = int(sample_every)
        return self

    def _instruments(self):
        ins = self._ins
        if ins is None:
            reg = _metrics.get_registry()
            with self._lock:
                ins = self._ins
                if ins is None:
                    ins = self._ins = {
                        "mfu": reg.gauge(
                            "step_mfu",
                            "measured model-FLOPs utilization over the "
                            "last devprof sample window", ("source",)),
                        "fps": reg.gauge(
                            "step_flops_per_second",
                            "model FLOP/s over the last devprof sample "
                            "window", ("source",)),
                        "step_seconds": reg.gauge(
                            "step_device_seconds",
                            "per-step device-visible time over the last "
                            "devprof sample window"),
                        "samples": reg.counter(
                            "devprof_samples_total",
                            "sampled block_until_ready device-time "
                            "measurements").labels(),
                        "memory": reg.gauge(
                            "device_memory_bytes",
                            "device memory watermarks polled at devprof "
                            "samples", ("kind",)),
                        "oom": reg.counter(
                            "oom_total",
                            "RESOURCE_EXHAUSTED failures that reached "
                            "the OOM forensics path", ("where",)),
                    }
        return ins

    # -- the fit-loop hook ---------------------------------------------------

    def on_step(self, net, n_examples: int, score):
        """Called by netbase._timed_fit after every dispatch. Unsampled
        steps: two integer adds and a modulo — the fixed cost the
        overhead A/B test pins <1% of the fit loop. On a sampled step,
        returns the `(t0, t1)` of the blocking read on `tracing.now_ns()`
        for the step timeline; else None."""
        se = self.sample_every
        if se <= 0:
            return None
        st = self._state(net)
        st["dispatches"] += 1
        st["examples"] += n_examples
        if st["dispatches"] % se:
            return None
        return self._sample(net, st, score)

    def sample_now(self, net, score=None) -> None:
        """Force one sample outside the cadence (tests; end-of-fit)."""
        self._sample(net, self._state(net), score)

    @staticmethod
    def _state(net) -> dict:
        st = getattr(net, "_devprof_state", None)
        if st is None:
            st = net._devprof_state = {
                "dispatches": 0, "examples": 0, "last_t": None,
                "iter_at_last": None,
                "params_bytes": None, "updater_bytes": None,
            }
        return st

    def _sample(self, net, st: dict, score):
        ins = self._instruments()
        blocked = None
        if score is not None:
            t0 = _now_ns()
            try:
                _jax().block_until_ready(score)
            except Exception:
                pass  # a failed sync is the step's problem, not ours
            # the step has ended: what layers count on the device is read
            # inside this same blocked interval, so no other step pays a
            # host read for it
            publish = getattr(net, "_publish_layer_books", None)
            if publish is not None:
                publish()
            blocked = (t0, _now_ns())
        now = time.perf_counter()
        last = st["last_t"]
        iteration = int(getattr(net, "iteration", 0))
        dt = 0.0
        window_examples = st["examples"]
        if last is not None and now > last and st["examples"] > 0:
            dt = now - last
            per_example, source = net.model_flops_per_example()
            # optimizer steps, NOT dispatches: one fused/TBPTT dispatch
            # advances the iteration counter by its whole segment count,
            # and per-step device time must divide by that
            prev_iter = st.get("iter_at_last")
            steps = max(1, iteration - prev_iter) if prev_iter is not None \
                else max(1, st["dispatches"])
            ins["step_seconds"].labels().set(dt / steps)
            if per_example:
                # PER-CHIP accounting: a mesh-attached net consumes the
                # global batch across n data shards, so the model FLOP/s
                # divide by n before meeting the per-chip peak —
                # otherwise multi-chip MFU over-reports n×
                n_chips = _data_shards_of(net)
                fps = per_example * st["examples"] / dt / n_chips
                from deeplearning4j_tpu.utils.flops import (
                    peak_flops_per_chip,
                )

                ins["fps"].labels(source).set(fps)
                ins["mfu"].labels(source).set(fps / peak_flops_per_chip())
            ins["samples"].inc()
        st["last_t"] = now
        st["iter_at_last"] = iteration
        st["examples"] = 0
        self.poll_memory(net, st)
        if dt > 0:
            # tenant chip-budget attribution rides the SAME measured
            # window (no extra sync): after poll_memory so the cached
            # params/updater byte sums exist for the HBM gauge. One
            # module-global read when the process is unmetered.
            _resourcemeter.note_device_window(net, dt,
                                              examples=window_examples)
        return blocked

    # -- memory watermarks ---------------------------------------------------

    def poll_memory(self, net=None, st: Optional[dict] = None) -> dict:
        """Refresh the `device_memory_bytes{kind}` gauges. Cheap:
        params/updater byte sums are cached per net (their shapes are
        static for a fit); `live` reads the backend allocator where
        available, else sums live jax arrays (CPU stand-in)."""
        ins = self._instruments()
        out = {}
        if net is not None:
            if st is None:
                st = getattr(net, "_devprof_state", None) or {}
            pb = st.get("params_bytes")
            if pb is None:
                pb = st["params_bytes"] = _tree_bytes(net.params_list)
                st["updater_bytes"] = _tree_bytes(net.upd_state)
            out["params"] = pb
            out["updater"] = st.get("updater_bytes", 0)
            attached = getattr(net, "_cost_model_meta", None)
            if attached and attached.get("activation_peak_bytes"):
                # activations are batch-sharded on a mesh-attached net:
                # the per-chip estimate divides by the data-axis size
                out["activations_est"] = (
                    attached["activation_peak_bytes"]
                    // _data_shards_of(net))
        live = device_bytes_in_use()
        if live is not None:
            out["live"] = live
        for kind, v in out.items():
            ins["memory"].labels(kind).set(float(v))
        return out

    # -- OOM forensics -------------------------------------------------------

    def oom_forensics(self, where: str, exc: BaseException,
                      net=None) -> Optional[str]:
        """RESOURCE_EXHAUSTED escaped a hot path: record the largest
        live buffers and the static memory picture, then dump the
        flight recorder. Returns the dump path (None when the dump
        itself failed — never raises; the OOM is the story)."""
        try:
            ins = self._instruments()
            ins["oom"].labels(where).inc()
            top = largest_live_buffers()
            static = {}
            if net is not None:
                try:
                    static["params_bytes"] = _tree_bytes(net.params_list)
                    static["updater_bytes"] = _tree_bytes(net.upd_state)
                except Exception:
                    pass
                meta = getattr(net, "_cost_model_meta", None)
                if meta is None:
                    # no model attached: one abstract trace now, CACHED
                    # on the net — a fit-path OOM pays it while dying,
                    # and a serving-path OOM (the process survives,
                    # clients retry) must not re-trace per failing
                    # request. Failures cache too, for the same reason.
                    try:
                        from deeplearning4j_tpu.analysis.costmodel import (
                            train_step_cost,
                        )

                        cm = train_step_cost(net, batch_size=2)
                        meta = {
                            "activation_peak_bytes":
                                cm.activation_peak_bytes,
                            "resident_bytes": cm.resident_bytes,
                            "largest_activation": cm.largest_activation,
                            "source": "costmodel(post-hoc, batch=2)",
                        }
                    except Exception:
                        meta = {"source": "unavailable"}
                    try:
                        net._cost_model_meta = meta
                    except Exception:
                        pass
                if meta and meta.get("source") != "unavailable":
                    static["activation_peak_bytes"] = meta.get(
                        "activation_peak_bytes")
                    static["largest_activation"] = meta.get(
                        "largest_activation")
                    static["flops_source"] = meta.get("source")
            live = device_bytes_in_use()
            if live is not None:
                static["live_bytes"] = live
            rec = _blackbox.get_recorder()
            rec.record_event("oom", where=where,
                             error=str(exc)[:400],
                             top_buffers=top, static=static)
            return rec.dump(reason=f"RESOURCE_EXHAUSTED in {where}: "
                                   f"{str(exc)[:200]}")
        except Exception:
            logger.exception("OOM forensics failed")
            return None


def _data_shards_of(net) -> int:
    """Data-axis shard count of a mesh-attached net (1 otherwise) —
    the divisor that keeps every per-chip number per-chip."""
    plan = getattr(net, "_mesh_plan", None)
    n = getattr(plan, "n_data_shards", 1) if plan is not None else 1
    return max(1, int(n))


def _tree_bytes(tree) -> int:
    """PER-CHIP byte sum of a pytree: sharded leaves (a tp split, a
    data-sharded batch) count their per-device shard, replicated leaves
    their full size — `device_memory_bytes{kind}` is a single chip's
    watermark, not the global footprint."""
    total = 0
    try:
        for leaf in _jax().tree_util.tree_leaves(tree):
            nb = getattr(leaf, "nbytes", None)
            if nb is None:
                continue
            nb = int(nb)
            sh = getattr(leaf, "sharding", None)
            if sh is not None:
                try:
                    shard = sh.shard_shape(leaf.shape)
                    size = 1
                    for s in shard:
                        size *= int(s)
                    nb = size * int(leaf.dtype.itemsize)
                except Exception:
                    pass
            total += nb
    except Exception:
        return 0
    return total


def device_bytes_in_use() -> Optional[int]:
    """Allocator bytes-in-use of device 0 where the backend reports it
    (TPU/GPU memory_stats); on CPU the sum of live jax array bytes —
    a weaker but still trajectory-shaped signal. None when neither
    works."""
    try:
        jax = _jax()
        dev = jax.devices()[0]
        stats = getattr(dev, "memory_stats", lambda: None)()
        if stats and stats.get("bytes_in_use") is not None:
            return int(stats["bytes_in_use"])
        return sum(int(a.nbytes) for a in jax.live_arrays())
    except Exception:
        return None


# -- the process-global profiler ----------------------------------------------

_PROFILER = DeviceProfiler()


def get_profiler() -> DeviceProfiler:
    return _PROFILER


def configure(sample_every: int) -> DeviceProfiler:
    return _PROFILER.configure(sample_every)


def oom_forensics(where: str, exc: BaseException, net=None) -> Optional[str]:
    return _PROFILER.oom_forensics(where, exc, net=net)

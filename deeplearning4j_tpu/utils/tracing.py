"""Host-side distributed tracing — the Dapper-style request/step half of
the observability layer (counters live in utils/metrics.py).

A span is a named, timed section of host code. Every span belongs to a
**trace**: the root span of a causal chain mints a 128-bit `trace_id`
(W3C trace-context format), and children inherit it — through the
thread-local parent stack on one thread, through an explicit
`SpanContext` handed across a queue to another thread (`attach()` /
`detach()` / `attached_ctx`), or through a W3C `traceparent` header
across a process boundary (`format_traceparent` / `parse_traceparent`;
utils/jsonhttp joins incoming headers on the server side and
`traced_headers()` injects them on the client side). A shed 429 or a
p99 outlier is therefore attributable: grep one `trace_id` across span
exports, JSON logs (`configure_logging(json_lines=True)`), flight-
recorder events, and histogram exemplars (utils/metrics.py), then feed
the export to `cli trace` (analysis/tracecrit.py) for the span tree and
its critical path.

Completed spans land in a bounded ring buffer (old traffic ages out; a
serving process never grows without bound) and export two ways:

* JSONL — one span per line, newest last (`InferenceServer GET /trace`,
  `TracingListener(jsonl_path=...)`); greppable, tail-able.
* Chrome trace event JSON — load the dict from `to_chrome_trace()` into
  chrome://tracing / Perfetto and the host timeline sits next to the
  device xplane timeline captured by utils/profiler.py.

The clock: every timestamp here is `now_ns()` — integer nanoseconds
since the UNIX epoch, advanced by `time.perf_counter_ns()` from one anchor
taken at import. It is monotonic, and it is the clock the profiler's
xplane lines and events carry, so a span and a device event can be laid
side by side without a conversion. The chrome and JSONL exports derive
their microseconds (`ts`, `dur`) from the stored `start_ns` / `dur_ns`.

Device correlation: when enabled, each span also enters
`jax.profiler.TraceAnnotation(name)`, so the SAME names show up inside a
`jax.profiler.trace()` capture — `cli profile` op tables and host spans
line up by name. Turning the tracer on adds host work only: no span
blocks on the device.

The step timeline (`StepTimeline`, `step_timeline()`) is the one per-step
ring of the program and is ALWAYS on: the fit loop appends one fixed tuple
a dispatch — the four phase boundaries on `now_ns()`, the interval of
devprof's sampled blocking read, and the fit thread's CPU time in the
dispatch and in the observers — with no lock, no dict and no rounding.
The flight recorder (utils/blackbox) builds its "final steps" from it, and
the benchmark attributes device idle gaps to the fit thread's phases
with it.

Always-on spans: boundaries that are crossed once a `fit()` or `init()`
call, and never once a step, are recorded whether the tracer is enabled or
not (`phase()`: two clock reads and one append into the same ring, on the
same parent stack). They are what a slow start is made of, and a slow
start is over before anybody thinks of `enable(True)`:

* `net/init` (both engines' `init()`), `fit/run` > `fit/setup`,
  `fit/teardown` and `fit/publish_books` (nn/netbase.py);
* `compile/trace`, `compile/lower`, `compile/backend` >
  `compile/cache_load`: every stage of every program jax compiles or loads
  from its persistent cache, opened and closed by jax's own monitoring
  events (`watch_compiles()`, installed once on the first `init()` or
  `fit()`). Each names its program (`fun_name`), the optimizer step it fell
  in (`iteration`, inside `steps_of(net)`; None elsewhere), its self time
  (`self_s`: the duration less what nested compile spans cover) and, on
  `compile/backend`, `cache` = "hit" / "miss" / "off" and `saved_s`. The
  registry holds the sums: `jit_compile_seconds{phase}` (self times, so the
  phases of one program add up to the wall it held the thread) and
  `jit_cache_total{result}`.

Everything else needs `enable(True)`: `fit/step` > `fit/dispatch`,
`fit/observe` (they then nest under `fit/run` by the stack; with the tracer
off, `fit/run`'s `first_iteration` / `last_iteration` tie it to the step
timeline's records), the serving and parameter-server spans, instants.
After a slow start: `get_tracer().write_jsonl(path)` (or a
`TracingListener(jsonl_path=...)` on the net, which writes when the fit
ends), then `cli trace <path>` for the tree and the critical path of
`fit/run`.

Overhead contract: span recording is OFF by default and every propagation entry
point — `span()`, `instant()`, `attach()`/`detach()`,
`current_context()`, `current_traceparent()`, `record_complete()` —
degrades to one flag check on the disabled path: no allocation, no lock,
no clock read, no id minting. The fit loop's phase timers and the
serving/jsonhttp hot paths depend on this (the <10µs-per-call guard in
tests covers span creation AND the context hooks). `phase()` is never
called once a step, and the compile listener runs only when jax compiles.
"""

from __future__ import annotations

import json
import itertools
import logging
import os
import threading
import time
from collections import deque
from typing import List, Optional

from deeplearning4j_tpu.utils import tenancy as _tenancy

logger = logging.getLogger("deeplearning4j_tpu")

# span ids are ints, unique within a process and unlikely to collide
# across processes: the counter starts at a random 60-bit offset so two
# processes exporting into one trace don't both hand out 1, 2, 3...
# (traceparent masks to the W3C 64-bit field; parse restores the int)
_counter = itertools.count(
    (int.from_bytes(os.urandom(5), "big") << 20) + 1)
_tls = threading.local()

_SPAN_ID_MASK = (1 << 64) - 1

# the one clock of the program's spans: UNIX-epoch nanoseconds at import,
# advanced by the monotonic counter. The anchor is the midpoint of two
# counter reads around the wall-clock read, so it is off by at most half
# of that read's duration.
_p0 = time.perf_counter_ns()
_UNIX_ANCHOR_NS = time.time_ns()
_PERF_ANCHOR_NS = (_p0 + time.perf_counter_ns()) // 2
del _p0


def now_ns() -> int:
    """Nanoseconds since the UNIX epoch, monotonic within the process:
    the clock of every span, timeline record and export of this module,
    and of the profiler's xplane."""
    return _UNIX_ANCHOR_NS + (time.perf_counter_ns() - _PERF_ANCHOR_NS)


def perf_to_ns(t: float) -> int:
    """A `time.perf_counter()` reading (seconds) on the `now_ns()` clock."""
    return _UNIX_ANCHOR_NS + (int(t * 1e9) - _PERF_ANCHOR_NS)


# attach() on the disabled path returns this token; detach() recognizes
# it and does nothing — the pair stays one flag check when tracing is off
_DISABLED_TOKEN = object()


def _mint_trace_id() -> str:
    """128-bit random trace id, 32 lowercase hex chars (W3C format)."""
    return os.urandom(16).hex()


class SpanContext:
    """The thread/process-portable identity of a span: which trace it
    belongs to and which span is the parent of anything recorded under
    it. Hand one across a queue (`attach()`) or a process boundary
    (`traceparent()`) and parentage survives the hop."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: int):
        self.trace_id = trace_id
        self.span_id = int(span_id)

    def traceparent(self) -> str:
        """W3C trace-context header value: 00-<trace>-<span>-01."""
        return (f"00-{self.trace_id}"
                f"-{self.span_id & _SPAN_ID_MASK:016x}-01")

    def __repr__(self):  # debugging / assertion messages
        return f"SpanContext({self.trace_id!r}, {self.span_id})"


def format_traceparent(ctx: SpanContext) -> str:
    return ctx.traceparent()


_HEX_DIGITS = frozenset("0123456789abcdefABCDEF")


def _is_hex(s: str) -> bool:
    # NOT int(s, 16): that tolerates '+'/'-' signs and '_' separators, so
    # a malformed header would join the trace and be re-emitted outbound
    # as a W3C-invalid traceparent strict downstream tracers drop
    return not set(s) - _HEX_DIGITS


def parse_traceparent(value: Optional[str]) -> Optional[SpanContext]:
    """Parse a W3C traceparent header into a SpanContext, or None when
    the header is absent or malformed — a bad header must yield a fresh
    root downstream, never a half-empty context."""
    if not value:
        return None
    parts = value.strip().split("-")
    if len(parts) < 4:
        return None
    ver, tid, sid = parts[0], parts[1], parts[2]
    if len(ver) != 2 or len(tid) != 32 or len(sid) != 16:
        return None
    if not (_is_hex(ver) and _is_hex(tid) and _is_hex(sid)):
        return None
    if ver.lower() == "ff":
        return None
    if ver == "00" and len(parts) != 4:
        # version 00 is exactly 4 fields; FUTURE versions may append more
        return None
    span_id = int(sid, 16)
    if span_id == 0 or set(tid) == {"0"}:
        return None
    return SpanContext(tid.lower(), span_id)


class _NullSpan:
    """Shared disabled-path context manager: truthy checks, enter/exit
    no-ops, one instance for the whole process."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    @property
    def context(self):
        return None


NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("tracer", "name", "args", "observe", "id", "parent",
                 "trace", "t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: Optional[dict],
                 observe=None):
        self.tracer = tracer
        self.name = name
        self.args = args
        # a histogram child that takes the duration in seconds on exit
        # (`phase(..., observe=)`), or None
        self.observe = observe
        self.id = next(_counter)
        self.parent = None
        self.trace = None
        self.t0 = 0  # 0 until entered
        self._ann = None

    @property
    def context(self) -> SpanContext:
        """This span's identity — valid during AND after the span (the
        exemplar/latency record after a `with` block still needs it)."""
        return SpanContext(self.trace, self.id)

    def __enter__(self):
        self.parent, self.trace = _ambient()
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self)
        # an always-on phase enters the device annotation only when the
        # tracer is on (`span()` makes no _Span when it is off)
        if self.tracer.enabled and self.tracer.annotate_device:
            ann = _trace_annotation(self.name)
            if ann is not None:
                self._ann = ann
                ann.__enter__()
        self.t0 = now_ns()
        return self

    def __exit__(self, *exc):
        t1 = now_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        stack = getattr(_tls, "stack", None)
        if stack and stack[-1] is self:
            stack.pop()
        self._book(t1 - self.t0, stack)
        self.tracer._record(self.name, self.t0, t1 - self.t0, self.id,
                            self.parent, self.args or None, trace=self.trace)
        return False

    def _book(self, dur_ns: int, stack) -> None:
        if self.observe is not None:
            self.observe.observe(dur_ns * 1e-9)


def _ambient():
    """(parent span id, trace id) of a record made now on this thread: the
    innermost open span's; for a thread-root record the attach()ed context
    (the explicit cross-thread / cross-process handoff); with nothing
    attached it is a trace root and mints the id."""
    stack = getattr(_tls, "stack", None)
    if stack:
        return stack[-1].id, stack[-1].trace
    att = getattr(_tls, "attached", None)
    if att is not None:
        return att.span_id, att.trace_id
    return None, _mint_trace_id()


def _trace_annotation(name: str):
    """jax.profiler.TraceAnnotation(name) or None when jax (or the
    profiler module) is unavailable — tracing must work in a stub
    environment."""
    try:
        from jax.profiler import TraceAnnotation
    except Exception:
        return None
    try:
        return TraceAnnotation(name)
    except Exception:
        return None


class Tracer:
    """Bounded ring buffer of completed spans + the enable switch."""

    def __init__(self, capacity: int = 8192, annotate_device: bool = True):
        self.enabled = False
        self.annotate_device = annotate_device
        self._lock = threading.Lock()
        self._events: deque = deque(maxlen=int(capacity))

    # -- recording -----------------------------------------------------------

    def span(self, name: str, **args):
        """Context manager timing a section. Disabled -> shared no-op.
        With a thread-ambient tenant attached (utils/tenancy — REST
        handlers attach it from X-Tenant), spans carry it as a `tenant`
        attribute; an explicit tenant= arg wins."""
        if not self.enabled:
            return NULL_SPAN
        if "tenant" not in args:
            t = _tenancy.current_tenant()
            if t is not None:
                args["tenant"] = t
        return _Span(self, name, args or None)

    def instant(self, name: str, **args):
        """Zero-duration marker event (helper auto-disables, injected
        faults, ...). Parents to the innermost
        active span — or the attach()ed context on a worker thread — so
        markers land inside the trace that caused them."""
        if not self.enabled:
            return
        parent, trace = _ambient()
        self._record(name, now_ns(), 0, next(_counter),
                     parent, args or None, phase="i", trace=trace)

    def record_complete(self, name: str, t0: float, t1: float,
                        parent: Optional[SpanContext] = None,
                        **args) -> Optional[SpanContext]:
        """Record an already-finished span from explicit timestamps
        (time.perf_counter() domain) under an explicit parent context —
        the retroactive form the serving pipeline uses for per-request
        lifecycle spans measured across thread handoffs (a queued-time
        span is only known when the collector picks the request up).
        Returns the recorded span's context (chain children off it), or
        None when tracing is disabled."""
        if not self.enabled:
            return None
        sid = next(_counter)
        trace = parent.trace_id if parent is not None else _mint_trace_id()
        start_ns = perf_to_ns(t0)
        self._record(name, start_ns, perf_to_ns(t1) - start_ns, sid,
                     parent.span_id if parent is not None else None,
                     args or None, trace=trace)
        return SpanContext(trace, sid)

    def _record(self, name, start_ns, dur_ns, span_id, parent, args,
                phase="X", trace=None):
        ev = {
            "name": name,
            "ph": phase,
            "start_ns": start_ns,
            "dur_ns": dur_ns,
            "id": span_id,
            "parent": parent,
            "trace": trace,
            "tid": threading.get_ident(),
        }
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    # -- readout -------------------------------------------------------------

    def recent(self, n: Optional[int] = None) -> List[dict]:
        """The n newest events (all when n is None, none when n <= 0 —
        a negative slice must never invert into 'everything BUT the
        newest n')."""
        with self._lock:
            evs = list(self._events)
        if n is not None:
            n = int(n)
            evs = evs[-n:] if n > 0 else []
        # the exports' microseconds (chrome trace, JSONL, cli trace),
        # derived here and never stored
        return [dict(ev, ts=ev["start_ns"] / 1e3, dur=ev["dur_ns"] / 1e3)
                for ev in evs]

    def clear(self):
        with self._lock:
            self._events.clear()

    def to_jsonl(self, n: Optional[int] = None) -> str:
        return "\n".join(json.dumps(ev) for ev in self.recent(n)) + "\n"

    def to_chrome_trace(self) -> dict:
        """chrome://tracing / Perfetto "trace event format" document."""
        events = []
        for ev in self.recent():
            ce = {
                "name": ev["name"],
                "ph": ev["ph"],
                "ts": ev["ts"],
                "pid": 1,
                "tid": ev["tid"],
            }
            if ev["ph"] == "X":
                ce["dur"] = ev["dur"]
            else:
                ce["s"] = "t"  # instant scope: thread
            args = dict(ev.get("args") or {})
            args["span_id"] = ev["id"]
            if ev.get("parent") is not None:
                args["parent_span_id"] = ev["parent"]
            if ev.get("trace"):
                args["trace_id"] = ev["trace"]
            ce["args"] = args
            events.append(ce)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def write_chrome_trace(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path

    def write_jsonl(self, path: str) -> str:
        with open(path, "w") as f:
            f.write(self.to_jsonl())
        return path


# -- the step timeline ---------------------------------------------------------

class StepTimeline:
    """The always-on ring of fit dispatches: fixed tuples

        (iteration, n_steps, t_wait0, t_dispatch0, t_dispatch1, t_end,
         sync0, sync1, cpu_dispatch_ns, cpu_observe_ns, score_ref)

    `iteration` is the last optimizer step of the dispatch and `n_steps`
    how many it ran (a fused dispatch runs several). The four boundaries
    are on `now_ns()`: `t_wait0` is the previous dispatch's `t_end` (or
    the epoch's start), so data wait, dispatch and observe tile the fit
    thread's time with no hole. `sync0, sync1` is the interval of devprof's
    blocking read when this dispatch was sampled, else 0, 0. The two CPU
    times are `time.thread_time_ns()` of the fit thread inside the
    dispatch and inside the observers. `score_ref` is the step's score as
    the device array it is: never read here.

    `append` is the deque's own: one call a dispatch, no lock (one writer,
    and copying a deque is atomic under the interpreter lock). 4096 records
    hold a traced second and the 1.5 s after it down to a 0.6 ms step."""

    def __init__(self, capacity: int = 4096):
        self._ring: deque = deque(maxlen=int(capacity))
        self.append = self._ring.append

    def records(self, since_ns: Optional[int] = None) -> List[tuple]:
        """The tuples, oldest first; with `since_ns`, those that ended
        at or after it."""
        recs = list(self._ring)
        if since_ns is None:
            return recs
        return [r for r in recs if r[5] >= since_ns]

    def spans(self, since_ns: Optional[int] = None) -> List[dict]:
        """The records as spans: a `fit/step` for each dispatch, whose
        identifier `step` is the iteration, with the children
        `fit/data_wait`, `fit/dispatch`, `fit/observe` and, where the
        dispatch was sampled, `devprof/sample` under `fit/observe`.
        `parent` names the parent span of the same `step`; `cpu_ns` is
        the fit thread's CPU time where it was taken."""
        out = []

        def span(name, step, start, stop, parent, cpu_ns=None):
            out.append({"name": name, "start_ns": start, "end_ns": stop,
                        "parent": parent, "step": step, "cpu_ns": cpu_ns})

        for (it, n, w0, d0, d1, end, s0, s1, cpu_d, cpu_o, _) in \
                self.records(since_ns):
            span("fit/step", it, w0, end, None, cpu_d + cpu_o)
            out[-1]["n_steps"] = n
            span("fit/data_wait", it, w0, d0, "fit/step")
            span("fit/dispatch", it, d0, d1, "fit/step", cpu_d)
            span("fit/observe", it, d1, end, "fit/step", cpu_o)
            if s1 > s0:
                span("devprof/sample", it, s0, s1, "fit/observe")
        return out


# -- the process-global tracer and timeline -----------------------------------

_TRACER = Tracer()
_STEPS = StepTimeline()


def get_step_timeline() -> StepTimeline:
    return _STEPS


def step_timeline(since_ns: Optional[int] = None) -> List[dict]:
    """The process's fit-phase timeline as spans (`StepTimeline.spans`)."""
    return _STEPS.spans(since_ns)


def get_tracer() -> Tracer:
    return _TRACER


def enable(flag: bool = True):
    """Turn span recording on/off process-wide."""
    _TRACER.enabled = bool(flag)


def is_enabled() -> bool:
    return _TRACER.enabled


def span(name: str, **args):
    """Module-level shortcut: `with tracing.span("fit/step"): ...`."""
    if not _TRACER.enabled:
        return NULL_SPAN
    return _TRACER.span(name, **args)


def instant(name: str, **args):
    _TRACER.instant(name, **args)


def record_complete(name: str, t0: float, t1: float,
                    parent: Optional[SpanContext] = None,
                    **args) -> Optional[SpanContext]:
    return _TRACER.record_complete(name, t0, t1, parent, **args)


# -- always-on lifecycle phases ------------------------------------------------

def phase(name: str, observe=None, **args) -> _Span:
    """An always-on span for a boundary crossed once a `fit()` or `init()`
    call, never once a step: `with tracing.phase("fit/setup"): ...` records
    into the ring whether the tracer is enabled or not (two clock reads and
    one append), nests on the thread's parent stack like any span, and
    enters the device annotation only when the tracer is on. `observe` is a
    histogram child that takes the duration in seconds on exit. The span's
    `args` is a dict the caller may fill until the exit."""
    return _Span(_TRACER, name, args, observe)


class steps_of:
    """`with tracing.steps_of(net): ...` — while it is open on a thread, a
    compile span recorded there carries `iteration=net.iteration`: the
    optimizer step whose data wait or dispatch the compile fell in (the
    net's count moves on when the step program returns, so a compile among
    the observers reads the next step's). One thread-local store a `fit()`
    call, none a step."""

    __slots__ = ("net", "_prev")

    def __init__(self, net):
        self.net = net

    def __enter__(self):
        self._prev = getattr(_tls, "stepper", None)
        _tls.stepper = self.net
        return self

    def __exit__(self, *exc):
        _tls.stepper = self._prev
        return False


# -- jax's compiles as spans ----------------------------------------------------

# jax's monitoring events (jax/_src/dispatch.py, jax 0.9.0): each stage is
# announced by a scalar event when it starts and a duration event when it
# ends, both with `fun_name`, on the thread that compiles
_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
# ... and inside the backend stage (jax/_src/compiler.py): a request that
# goes through the persistent cache, a hit, and on a hit what the load took
# and what it saved
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_SAVED = "/jax/compilation_cache/compile_time_saved_sec"


def _compile_books():
    """(`jit_compile_seconds`, `jit_cache_total`) of the shared registry,
    both children of the second made: no miss reads 0, not an absent
    series. Looked up at each compile and never kept, so a registry that a
    test reset gets them back."""
    from deeplearning4j_tpu.utils import metrics  # imports this module

    reg = metrics.get_registry()
    seconds = reg.histogram(
        "jit_compile_seconds",
        "self time of each stage of each program jax traced, lowered, "
        "compiled or loaded from its persistent cache (backend: the "
        "compile, or the look in the cache around a load; cache_load: the "
        "load on a hit); the stages of one program add up to the wall it "
        "held its thread", ("phase",))
    cache = reg.counter(
        "jit_cache_total",
        "programs asked of jax's persistent compilation cache, by result",
        ("result",))
    cache.labels("hit"), cache.labels("miss")
    return seconds, cache


class _CompileSpan(_Span):
    """One stage of one program's compilation: an always-on span that jax's
    start event opens and its duration event closes (`watch_compiles`)."""

    __slots__ = ("stage", "covered", "skipped", "cache")

    def __init__(self, stage: str, fun_name):
        super().__init__(_TRACER, "compile/" + stage, {"fun_name": fun_name})
        self.stage = stage
        self.covered = 0   # ns of this span that nested compile spans cover
        self.skipped = 0   # nested traces open now that were given no span
        self.cache = "off"  # backend: "hit" / "miss" once the cache was asked

    def _book(self, dur_ns: int, stack) -> None:
        # self time: an inner jit traced (or a constant compiled) while
        # this stage ran has its own spans, and its seconds are booked there
        self_ns = max(0, dur_ns - self.covered)
        if stack and isinstance(stack[-1], _CompileSpan):
            stack[-1].covered += dur_ns
        self.args.update(iteration=_iteration(), self_s=self_ns * 1e-9)
        seconds, cache = _compile_books()
        seconds.labels(self.stage).observe(self_ns * 1e-9)
        if self.stage == "backend":
            self.args["cache"] = self.cache
            if self.cache != "off":
                cache.labels(self.cache).inc()


def _iteration() -> Optional[int]:
    stepper = getattr(_tls, "stepper", None)
    return None if stepper is None else int(stepper.iteration)


def _record_compile(stage: str, dur_ns: int, fun_name) -> None:
    """A compile span known only when it ended: it ends now, under
    whatever is open on the thread. Nothing nested in it was subtracted."""
    end = now_ns()
    args = {"fun_name": fun_name, "iteration": _iteration(),
            "self_s": dur_ns * 1e-9}
    parent, trace = _ambient()
    _TRACER._record("compile/" + stage, end - dur_ns, dur_ns,
                    next(_counter), parent, args, trace=trace)
    _compile_books()[0].labels(stage).observe(dur_ns * 1e-9)


def _open_compile_span():
    stack = getattr(_tls, "stack", None)
    top = stack[-1] if stack else None
    return top if isinstance(top, _CompileSpan) else None


def _on_jax_scalar(event: str, value, **kw) -> None:
    stage = _COMPILE_STAGES.get(event)
    if stage is None:
        return
    top = _open_compile_span()
    if stage == "trace" and top is not None and not _TRACER.enabled:
        # a jit called while an outer one is traced or lowered (jax.numpy's
        # own are: two thousand a step program of four layers). With the
        # tracer off its seconds stay the outer span's: the same union
        top.skipped += 1
        return
    _CompileSpan(stage, kw.get("fun_name")).__enter__()


def _on_jax_duration(event: str, duration: float, **kw) -> None:
    stage = _COMPILE_STAGES.get(event)
    top = _open_compile_span()
    dur_ns = int(duration * 1e9)
    if stage is not None:
        if stage == "trace" and top is not None and top.skipped:
            top.skipped -= 1
        elif top is not None and top.stage == stage:
            top.__exit__(None, None, None)
        else:
            # no start event was seen (another jax, or the listener came
            # between the two)
            _record_compile(stage, dur_ns, kw.get("fun_name"))
    elif top is not None and top.stage == "backend":
        if event == _CACHE_LOAD:
            top.covered += dur_ns
            _record_compile("cache_load", dur_ns, top.args["fun_name"])
        elif event == _CACHE_SAVED:
            top.args["saved_s"] = duration


def _on_jax_event(event: str, **kw) -> None:
    if event != _CACHE_REQUEST and event != _CACHE_HIT:
        return
    top = _open_compile_span()
    if top is None or top.stage != "backend":
        return
    if event == _CACHE_HIT:
        top.cache = "hit"
    else:
        # jax announces the request whether or not a directory is set
        import jax

        if jax.config.jax_compilation_cache_dir is not None:
            top.cache = "miss"


def _never_raising(listener):
    """jax calls its listeners from inside a compile: a fault in the
    tracing must not become a fault of the program."""
    def call(event, *a, **kw):
        try:
            listener(event, *a, **kw)
        except Exception:
            logger.exception("tracing: the listener of jax's event %s "
                             "failed", event)
    return call


_watch_lock = threading.Lock()
_watching = False


def watch_compiles() -> None:
    """Record every jit trace, lowering, compile and cache load of the
    process as always-on spans `compile/trace`, `compile/lower`,
    `compile/backend` > `compile/cache_load`, and book their self times
    under `jit_compile_seconds{phase}` and the cache's answers under
    `jit_cache_total{result}`. Idempotent: the first `init()` or `fit()`
    installs the three listeners with `jax.monitoring`, which keeps them
    for the life of the process; later calls are one flag check."""
    global _watching
    if _watching:
        return
    with _watch_lock:
        if _watching:
            return
        from jax import monitoring

        monitoring.register_scalar_listener(_never_raising(_on_jax_scalar))
        monitoring.register_event_duration_secs_listener(
            _never_raising(_on_jax_duration))
        monitoring.register_event_listener(_never_raising(_on_jax_event))
        _compile_books()
        _watching = True


# -- context propagation ------------------------------------------------------

def current_context() -> Optional[SpanContext]:
    """The active span context on this thread: the innermost open span,
    else the attach()ed handoff context, else None. Disabled -> None
    after one flag check."""
    if not _TRACER.enabled:
        return None
    stack = getattr(_tls, "stack", None)
    if stack:
        top = stack[-1]
        return SpanContext(top.trace, top.id)
    return getattr(_tls, "attached", None)


def current_trace_id() -> Optional[str]:
    """Just the active trace id (log records, flight-recorder events)."""
    if not _TRACER.enabled:
        return None
    stack = getattr(_tls, "stack", None)
    if stack:
        return stack[-1].trace
    att = getattr(_tls, "attached", None)
    return att.trace_id if att is not None else None


def current_traceparent() -> Optional[str]:
    """The active context as a W3C traceparent header value, or None —
    what an outbound HTTP client attaches so the remote server joins
    this trace."""
    ctx = current_context()
    return ctx.traceparent() if ctx is not None else None


def attach(ctx: Optional[SpanContext]):
    """Make `ctx` the ambient parent for root spans (and instants) on
    THIS thread — the explicit handoff that keeps parentage across a
    queue hop (collector -> dispatcher, prefetch workers, push drains)
    instead of silently starting new roots. Returns a token for
    detach(); always pair them (or use `attached_ctx`). attach(None)
    deliberately clears the ambient context (a worker starting an item
    that carried no context must not inherit the previous item's)."""
    if not _TRACER.enabled:
        return _DISABLED_TOKEN
    prev = getattr(_tls, "attached", None)
    _tls.attached = ctx
    return prev


def detach(token):
    """Restore the ambient context saved by the paired attach()."""
    if token is _DISABLED_TOKEN:
        return
    _tls.attached = token


class attached_ctx:
    """`with tracing.attached_ctx(ctx): ...` — scope-bound attach/detach."""

    __slots__ = ("ctx", "_tok")

    def __init__(self, ctx: Optional[SpanContext]):
        self.ctx = ctx

    def __enter__(self):
        self._tok = attach(self.ctx)
        return self.ctx

    def __exit__(self, *exc):
        detach(self._tok)
        return False

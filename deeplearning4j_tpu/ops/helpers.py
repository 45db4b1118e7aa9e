"""Helper SPI — the vendor-kernel plugin point.

Reference: the cuDNN Helper interfaces (ConvolutionHelper.java:35,
BatchNormalizationHelper.java:29, ...) loaded reflectively by layer impls
(ConvolutionLayer.java:68-72) with checkSupported() fallback to the
built-in path. TPU-native shape: layers ask get_helper("op") before their
default XLA lowering; a registered helper answers `supported(**ctx)` and,
when true, its `fn` replaces the default. Pallas kernels register here
(ops/pallas_lstm.py); anything unsupported falls back silently, exactly
like the reference's cuDNN fallback.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import logging
from typing import Callable, Dict, Iterator, List, Optional, Sequence

from deeplearning4j_tpu.utils import faultpoints as _faults
from deeplearning4j_tpu.utils import metrics as _metrics
from deeplearning4j_tpu.utils import tracing as _tracing

logger = logging.getLogger("deeplearning4j_tpu")


def _count(metric: str, op: str, helper: str, family: str,
           reason: Optional[str] = None):
    """Helper SPI events in the shared registry: selection hits,
    builtin-path fallbacks (with why), and auto-disables, each carrying
    the kernel FAMILY (e.g. conv3x3s2, bn_bwd) so per-family hit rates
    are scrape-able — one op slot can route many shapes to many kernels.
    Family values come from the registration's `family(**ctx)` callable,
    which must return a bounded slug set (the metrics tests assert the
    cardinality stays bounded). These happen at trace time, not per
    device step, so a registry lookup per event is fine — and it makes
    PR 2's "helper silently auto-disabled mid-run" kill switch a
    scrape-able series instead of a bench-only check."""
    reg = _metrics.get_registry()
    if reason is None:
        reg.counter(metric, "Helper SPI events",
                    ("op", "helper", "family")).labels(op, helper,
                                                       family).inc()
    else:
        reg.counter(metric, "Helper SPI events",
                    ("op", "helper", "family",
                     "reason")).labels(op, helper, family, reason).inc()
    if metric != "helper_hit_total":
        # fallbacks and auto-disables are rare, diagnosis-relevant events
        # — they ride in the flight recorder so a crash dump shows the
        # kernel story leading up to the failure (hits would be noise)
        from deeplearning4j_tpu.utils import blackbox as _blackbox

        _blackbox.get_recorder().record_event(
            metric.replace("_total", ""), op=op, helper=helper,
            **({"reason": reason} if reason else {}))


class HelperError(RuntimeError):
    """A registered helper fn raised at trace/run time. The helper has
    already been disabled and the failure logged; callers catch this and
    retry their built-in lowering (the reference behaves the same way: a
    cuDNN helper that throws is dropped and the layer falls back)."""


@dataclasses.dataclass
class Helper:
    name: str
    fn: Callable
    supported: Callable[..., bool] = lambda **ctx: True
    enabled: bool = True
    family: Optional[Callable[..., str]] = None


_HELPERS: Dict[str, Helper] = {}

# Device count of the program being traced. Set by the one place that
# builds a multi-device step program (parallel/sharded.MeshPlan.jit_step);
# anything traced outside it is a one-device program.
_PROGRAM_DEVICES: contextvars.ContextVar = contextvars.ContextVar(
    "dl4j_program_devices", default=1)


@contextlib.contextmanager
def partitioned_program(n_devices: int) -> Iterator[None]:
    """Mark the trace inside as a program GSPMD partitions over
    `n_devices`. A helper's kernel is an opaque custom call that the
    partitioner cannot split: left inside, every device would gather the
    whole batch and run the kernel on all of it. So under n_devices > 1
    `get_helper` declines (reason "partitioned_program") and the layer's
    XLA lowering, which the partitioner does split, runs instead."""
    token = _PROGRAM_DEVICES.set(int(n_devices))
    try:
        yield
    finally:
        _PROGRAM_DEVICES.reset(token)


def register_helper(op: str, fn: Callable,
                    supported: Optional[Callable[..., bool]] = None,
                    name: Optional[str] = None,
                    family: Optional[Callable[..., str]] = None) -> None:
    """Install a helper for an op slot ("lstm_sequence", "conv2d", ...).
    Last registration wins (the reference loads exactly one helper class
    per layer type). `family(**ctx)` maps a call context to the bounded
    kernel-family slug the helper metrics are labeled with (default: the
    op name itself, which is trivially bounded)."""
    _HELPERS[op] = Helper(
        name=name or getattr(fn, "__name__", op),
        fn=fn,
        supported=supported or (lambda **ctx: True),
        family=family,
    )


def _family_of(op: str, h: Helper, ctx: dict) -> str:
    if h.family is None:
        return op
    try:
        return str(h.family(**ctx))
    except Exception:  # a broken family fn must never kill the metric
        return op


def get_helper(op: str, **ctx) -> Optional[Callable]:
    """The helper's fn if one is registered, enabled, and supports this
    call context; else None (caller uses its built-in path).

    The returned callable is guarded: a helper fn that raises (e.g. a
    Pallas lowering failure at trace time) is logged and DISABLED, and the
    call raises HelperError so the caller retries its built-in path —
    without the guard a broken kernel would kill the layer with no
    fallback even though the probe passed."""
    h = _HELPERS.get(op)
    if h is None:
        return None
    fam = _family_of(op, h, ctx)
    if not h.enabled:
        _count("helper_fallback_total", op, h.name, fam, "disabled")
        return None
    if _PROGRAM_DEVICES.get() > 1:
        _count("helper_fallback_total", op, h.name, fam,
               "partitioned_program")
        return None
    try:
        if not h.supported(**ctx):
            _count("helper_fallback_total", op, h.name, fam, "unsupported")
            return None
    except Exception as e:  # a broken probe must never kill the fallback
        logger.warning("helper %s probe failed: %s", h.name, e)
        _count("helper_fallback_total", op, h.name, fam, "probe_error")
        return None
    _count("helper_hit_total", op, h.name, fam)

    def guarded(*args, **kwargs):
        try:
            # chaos hook: an `error` fault here IS a raising helper fn —
            # it rides the real auto-disable + HelperError + builtin-
            # retry path below, so injected kernel failures exercise
            # exactly the degradation the PR 2 kill switch promises
            _faults.fault_point("helper_fn", op=op, helper=h.name)
            return h.fn(*args, **kwargs)
        except Exception as e:
            h.enabled = False
            logger.warning(
                "helper %s (op %s) raised %s: %s — helper disabled, "
                "falling back to the built-in path", h.name, op,
                type(e).__name__, e)
            _count("helper_auto_disable_total", op, h.name, fam)
            _count("helper_fallback_total", op, h.name, fam, "raised")
            _tracing.instant("helper/auto_disable", op=op, helper=h.name,
                             error=f"{type(e).__name__}: {e}")
            raise HelperError(f"helper {h.name} failed: {e}") from e

    return guarded


def set_helper_enabled(op: str, enabled: bool) -> None:
    if op in _HELPERS:
        _HELPERS[op].enabled = bool(enabled)


def helper_enabled(op: str) -> Optional[bool]:
    """Current enabled state (None when no helper is registered) — lets
    callers snapshot/restore the kill switch and detect a mid-run
    auto-disable (a helper fn that raised)."""
    h = _HELPERS.get(op)
    return None if h is None else h.enabled


def interpret_mode(flag: bool) -> bool:
    """A kernel module's interpret flag, as its call sites and probes may
    use it. Interpret mode runs a kernel through the Pallas interpreter
    for CPU tests; on a TPU it would silently measure the interpreter, so
    there it is an error and not a mode."""
    if flag:
        import jax

        if jax.default_backend() == "tpu":
            raise RuntimeError(
                "Pallas interpret mode is on with a TPU backend: it is "
                "for CPU tests only (unset DL4J_PALLAS_INTERPRET, leave "
                "the kernel modules' _INTERPRET flags False)")
    return bool(flag)


def helper_names() -> Dict[str, str]:
    return {op: h.name for op, h in _HELPERS.items()}


def count_fallback_raised(op: str, family: str) -> None:
    """Book a fallback taken OUTSIDE `get_helper`'s guard (a kernel
    shortcut that caught its own exception) under the same
    `helper_fallback_total{reason="raised"}` series, so that
    `hidden_fallbacks` sees it."""
    h = _HELPERS.get(op)
    _count("helper_fallback_total", op, h.name if h else op, family,
           "raised")


def helper_books(since: Optional[dict] = None) -> dict:
    """The helper counters of this process, read back from the shared
    registry and summed by kernel family: {"hits": {family: n},
    "auto_disable": {family: n}, "fallbacks": {reason: {family: n}}}.
    With `since` (an earlier `helper_books()`), what moved since then;
    families and reasons that did not move are left out."""
    reg = _metrics.get_registry()
    books: dict = {"hits": {}, "auto_disable": {}, "fallbacks": {}}

    def add(into: dict, family: str, value: float) -> None:
        into[family] = into.get(family, 0) + int(value)

    for name, key in (("helper_hit_total", "hits"),
                      ("helper_auto_disable_total", "auto_disable")):
        fam = reg.get(name)
        for labels, child in (fam.children() if fam else ()):
            add(books[key], labels[2], child.value)
    fam = reg.get("helper_fallback_total")
    for labels, child in (fam.children() if fam else ()):
        add(books["fallbacks"].setdefault(labels[3], {}), labels[2],
            child.value)
    if since is None:
        return books

    def minus(new: dict, old: dict) -> dict:
        return {k: v - old.get(k, 0) for k, v in sorted(new.items())
                if v > old.get(k, 0)}

    moved = {reason: minus(fams, since["fallbacks"].get(reason, {}))
             for reason, fams in sorted(books["fallbacks"].items())}
    return {"hits": minus(books["hits"], since["hits"]),
            "auto_disable": minus(books["auto_disable"],
                                  since["auto_disable"]),
            "fallbacks": {r: d for r, d in moved.items() if d}}


def hidden_fallbacks(since: dict, expect_enabled: Sequence[str] = ()
                     ) -> List[str]:
    """What a measured path may not hide: the auto-disables, raised
    helper fns and raised probes booked since the `helper_books()`
    snapshot `since`, and every op of `expect_enabled` whose helper is
    not enabled now. The fallback mechanism stays (a broken kernel must
    not kill a user's fit); a benchmark or smoke phase that measured
    through it has measured the built-in path and must fail. Returns
    the problems, empty when there are none."""
    moved = helper_books(since)
    problems = [f"helper auto-disabled {family}: {n}"
                for family, n in moved["auto_disable"].items()]
    for reason in ("raised", "probe_error"):
        problems += [f"helper fallback ({reason}) {family}: {n}"
                     for family, n in
                     moved["fallbacks"].get(reason, {}).items()]
    problems += [f"helper for {op} not enabled ({helper_enabled(op)})"
                 for op in expect_enabled if helper_enabled(op) is not True]
    return problems

"""From a profiler trace and the program's step timeline to device time by
phase and by layer, and idle gaps by what the fit thread was doing.

`trace_reduce` reads a trace through `jax.profiler.ProfileData`, which shows
an event's name and times and hides the stats of its metadata, and the HLO
`op_name` (the `jax.named_scope` path: `jit(step)/transpose(jvp(
L2_convolution))/conv_general_dilated`) is one of those (looked at on the
chip, PERF.md PR 25). So `read_xspace` reads the `.xplane.pb` itself: a small
reader of the protobuf wire format (varints and length-delimited fields) for
the handful of messages of `xplane.proto`, with no TensorFlow import.

The clocks. An xplane's events count from the profiling session's start,
which the `Task Environment` plane gives on the UNIX epoch
(`profile_start_time`); the program's step timeline
(`deeplearning4j_tpu.utils.tracing.step_timeline()`) is on
`tracing.now_ns()`, the same epoch. `benchmark/tools/clock_check.py` measures
how far they disagree on the chip.

`reduce` is plain Python over rows and spans, so that the test can hand it
the recorded ones under `benchmark/fixtures/`. It returns None, and every
reader then reports nothing, when there is no device plane, when under 95%
of the busy time carries one of the program's scopes, or when the clocks
cannot be shown to agree.
"""

from __future__ import annotations

import os
import re
import struct
from collections import defaultdict
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from benchmark import trace_reduce

TASK_PLANE = "Task Environment"
SCOPE_STATS = ("tf_op", "op_name", "long_name")
MIN_SCOPED_SHARE = 0.95
PHASES = ("fit/data_wait", "fit/dispatch", "fit/observe")

# (plane, line, name, start_ns, duration_ns, scope): a row of trace_reduce
# with the event's scope path ("" where it has none), in whole nanoseconds
# on the epoch
ScopedRow = Tuple[str, str, str, int, int, str]


# -- the wire format ----------------------------------------------------------

def _varint(buf, pos: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf, pos: int, end: int):
    """(field number, wire type, value) of one message: an int for a
    varint, the 8 or 4 raw bytes of a fixed field, `(start, end)` into
    `buf` for a length-delimited one."""
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value = (pos, pos + size)
            pos += size
        elif wire == 1:
            value = bytes(buf[pos:pos + 8])
            pos += 8
        elif wire == 5:
            value = bytes(buf[pos:pos + 4])
            pos += 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")
        yield number, wire, value


def _int64(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _stat(buf, span):
    """XStat: (metadata id, value); a `ref_value` comes back as
    ("ref", id of the stat metadata that holds the string)."""
    key, value = 0, None
    for number, wire, v in _fields(buf, *span):
        if number == 1:
            key = _int64(v)
        elif number == 2:
            value = struct.unpack("<d", v)[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _int64(v)
        elif number in (5, 6):
            value = _text(buf, v)
        elif number == 7:
            value = ("ref", v)
    return key, value


def _map_entry(buf, span):
    key, value = 0, None
    for number, _, v in _fields(buf, *span):
        if number == 1:
            key = _int64(v)
        elif number == 2:
            value = v
    return key, value


def _plane(buf, span, want: Callable[[str], bool]) -> Optional[dict]:
    name = ""
    for number, _, v in _fields(buf, *span):
        if number == 2:
            name = _text(buf, v)
            break
    if not want(name):
        return None
    stat_names: Dict[int, str] = {}
    raw_meta, raw_stats, raw_lines = [], [], []
    for number, _, v in _fields(buf, *span):
        if number == 3:
            raw_lines.append(v)
        elif number == 4:
            raw_meta.append(_map_entry(buf, v))
        elif number == 5:
            key, entry = _map_entry(buf, v)
            for n, _, w in _fields(buf, *entry):
                if n == 2:
                    stat_names[key] = _text(buf, w)
        elif number == 6:
            raw_stats.append(v)

    def named(stats) -> Dict[str, object]:
        out = {}
        for key, value in stats:
            if isinstance(value, tuple):
                value = stat_names.get(value[1], "")
            out[stat_names.get(key, str(key))] = value
        return out

    metadata = {}
    for key, entry in raw_meta:
        if entry is None:
            continue
        ev_name, stats = "", []
        for n, _, w in _fields(buf, *entry):
            if n == 2:
                ev_name = _text(buf, w)
            elif n == 5:
                stats.append(_stat(buf, w))
        metadata[key] = (ev_name, named(stats))
    lines = []
    for line_span in raw_lines:
        line_name, timestamp_ns, events = "", 0, []
        for n, _, w in _fields(buf, *line_span):
            if n == 2:
                line_name = _text(buf, w)
            elif n == 3:
                timestamp_ns = _int64(w)
            elif n == 4:
                meta_id = offset_ps = duration_ps = 0
                for m, _, x in _fields(buf, *w):
                    if m == 1:
                        meta_id = _int64(x)
                    elif m == 2:
                        offset_ps = _int64(x)
                    elif m == 3:
                        duration_ps = _int64(x)
                events.append((meta_id, offset_ps, duration_ps))
        lines.append({"name": line_name, "timestamp_ns": timestamp_ns,
                      "events": events})
    return {"name": name, "lines": lines, "event_metadata": metadata,
            "stats": named(_stat(buf, s) for s in raw_stats)}


def _is_device_or_task(name: str) -> bool:
    return name == TASK_PLANE or bool(trace_reduce.DEVICE_PLANE.match(name))


def read_xspace(path: str, want: Callable[[str], bool] = _is_device_or_task
                ) -> List[dict]:
    """The planes of an `.xplane.pb` whose name `want` takes (the device
    planes and the session's by default: a host plane can hold millions
    of events): `{"name", "stats", "lines": [{"name", "timestamp_ns",
    "events": [(metadata id, offset_ps, duration_ps)]}],
    "event_metadata": {id: (name, {stat name: value})}}`."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    planes = []
    for number, _, v in _fields(buf, 0, len(buf)):
        if number == 1:
            plane = _plane(buf, v, want)
            if plane is not None:
                planes.append(plane)
    return planes


def session_start_ns(planes: Iterable[dict]) -> Optional[int]:
    """The session's start on the UNIX epoch, which every event's time
    counts from; None where the trace does not say, and its clock then
    cannot be matched with the program's."""
    for plane in planes:
        if plane["name"] == TASK_PLANE:
            start = plane["stats"].get("profile_start_time")
            return int(start) if start else None
    return None


def events_of(plane: dict, line_name: str, base_ns: int = 0):
    """(name, start_ns, end_ns, stats of the event's metadata) of each
    event of the plane's line of that name."""
    for line in plane["lines"]:
        if line["name"] != line_name:
            continue
        t0 = base_ns + line["timestamp_ns"]
        for meta_id, offset_ps, duration_ps in line["events"]:
            name, stats = plane["event_metadata"].get(meta_id, ("", {}))
            # whole nanoseconds: a float cannot hold the epoch's 1.8e18
            # to better than 256 of them
            start = t0 + (offset_ps + 500) // 1000
            yield name, start, start + (duration_ps + 500) // 1000, stats


def scoped_rows(planes: List[dict]) -> Optional[List[ScopedRow]]:
    """The device planes' `XLA Ops` and `XLA Modules` lines as rows with
    each event's scope path, times on the UNIX epoch. None where the trace
    does not give the session's start."""
    base = session_start_ns(planes)
    if base is None:
        return None
    rows: List[ScopedRow] = []
    for plane in planes:
        if not trace_reduce.DEVICE_PLANE.match(plane["name"]):
            continue
        for line in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE):
            for name, start, end, stats in events_of(plane, line, base):
                scope = next((str(stats[k]) for k in SCOPE_STATS
                              if stats.get(k)), "")
                rows.append((plane["name"], line, name, start, end - start,
                             scope))
    return rows


# -- the reduction -------------------------------------------------------------

_LAYER_SCOPE = re.compile(r"(?<![A-Za-z0-9_])L(\w+?)_([a-z0-9]+)(?=[)/:]|$)")
_OWN_SCOPE = re.compile(r"(?:^|[/(])(?:loss|update|reduce_grads)(?=[)/:]|$)")
_UPDATE_SCOPE = re.compile(r"(?:^|[/(])update(?=[)/:]|$)")
_MATMUL = re.compile(r"(?:conv_general_dilated|dot_general):?$")


def layer_of(scope: str) -> Optional[str]:
    """The layer scope in a path (`L2_convolution` in `jit(step)/
    transpose(jvp(L2_convolution))/conv_general_dilated:`), the outermost
    where scopes nest."""
    m = _LAYER_SCOPE.search(scope)
    return m.group(0) if m else None


def has_program_scope(scope: str) -> bool:
    """Does the path hold one of the scopes the program sets itself: a
    layer's, `loss`, `update` or `reduce_grads`? JAX's own names (`jit(`,
    `jvp(`) are in every path, scoped program or not."""
    return bool(_LAYER_SCOPE.search(scope) or _OWN_SCOPE.search(scope))


def phase_of(scope: str) -> str:
    """`update` if the path holds `update`, else `bwd` if it holds
    `transpose(` or `reduce_grads`, else `fwd` (so `loss` falls to forward
    or backward by the same rule): every scoped event is in one phase."""
    if _UPDATE_SCOPE.search(scope):
        return "update"
    if "transpose(" in scope or "reduce_grads" in scope:
        return "bwd"
    return "fwd"


def _merged(intervals) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a0, a1, b0, b1):
    return max(0, min(a1, b1) - max(a0, b0))


def reduce(rows: Optional[Iterable[ScopedRow]], spans: Optional[List[dict]]
           ) -> Optional[Dict]:
    """Scoped device rows and the timeline's spans, both on the epoch, to
    numbers over the whole runs of the main module in the trace (the
    window `trace_reduce.reduce_rows` cuts: from the second run's start to
    the last but one's end, where there are four or more):

    - `steps`, `window_ns`, `busy_ns`, `scoped_share`;
    - `phase_ns`: the union of the events of each phase (`fwd`, `bwd`,
      `update`), and `matmul_ns`: of those whose scope ends in
      `conv_general_dilated` or `dot_general`;
    - `layers`: {layer scope: {"fwd": ns, "bwd": ns}}, plain sums;
    - `idle_ns`: every idle gap of the window split over the fit thread's
      phases it overlaps (`fit/data_wait`, `fit/dispatch`, `fit/observe`;
      `devprof/sample` is the part of `fit/observe` inside devprof's
      blocking read; `unattributed` is what no `fit/step` covers), and
      `idle_gaps`: the longest, each with its split;
    - `fit_steps`, `cpu_ns`: the optimizer steps and the fit thread's CPU
      time of the `fit/step` spans that lie inside the window.

    All of one device plane (the first by name): the fit thread's phases
    are the same for every chip. None as the module's docstring says."""
    if not rows or spans is None:
        return None
    by_plane = defaultdict(lambda: {"ops": [], "modules": []})
    for plane, line, name, start, dur, scope in rows:
        kind = "ops" if line == trace_reduce.OPS_LINE else "modules"
        by_plane[plane][kind].append((name, start, start + dur, scope))
    planes = {p: v for p, v in by_plane.items() if v["ops"] and v["modules"]}
    if not planes:
        return None
    v = planes[min(planes)]
    seconds: Dict[str, float] = defaultdict(float)
    for n, s, e, _ in v["modules"]:
        seconds[trace_reduce.op_family(n)] += e - s
    main_module = max(seconds, key=seconds.get)
    main = sorted((s, e) for n, s, e, _ in v["modules"]
                  if trace_reduce.op_family(n) == main_module)
    if len(main) >= 4:
        main = main[1:-1]
    lo, hi = main[0][0], main[-1][1]
    ops = [(n, max(s, lo), min(e, hi), scope) for n, s, e, scope in v["ops"]
           if e > lo and s < hi]
    busy = _merged((s, e) for _, s, e, _ in ops)
    busy_ns = sum(e - s for s, e in busy)
    scoped_ns = trace_reduce.union_ns(
        (s, e) for _, s, e, scope in ops if has_program_scope(scope))
    if not busy_ns or scoped_ns < MIN_SCOPED_SHARE * busy_ns:
        return None

    steps = [s for s in spans if s["name"] == "fit/step"]
    dispatches = [s for s in spans if s["name"] == "fit/dispatch"]
    # the clocks: no run of the step program can start on the device before
    # the host started its first dispatch, and the fit thread must have
    # been seen on both sides of the window
    if not dispatches or not steps \
            or min(s["start_ns"] for s in dispatches) > lo \
            or max(s["end_ns"] for s in steps) < hi:
        return None

    phase_iv = defaultdict(list)
    matmul_iv = []
    layers: Dict[str, Dict[str, int]] = defaultdict(
        lambda: {"fwd": 0, "bwd": 0})
    for _, s, e, scope in ops:
        if not scope:
            continue
        phase = phase_of(scope)
        phase_iv[phase].append((s, e))
        if _MATMUL.search(scope):
            matmul_iv.append((s, e))
        layer = layer_of(scope)
        if layer is not None and phase != "update":
            layers[layer][phase] += e - s

    children = [s for s in spans if s["name"] in PHASES
                or s["name"] == "devprof/sample"]
    idle = defaultdict(int)
    gaps = []
    # the window's own edges count, so that the gaps add up to window
    # less busy, which is what `device_idle_pct.train` reads
    edges = [[lo, lo]] + busy + [[hi, hi]]
    for a, b in zip(edges, edges[1:]):
        g0, g1 = a[1], b[0]
        if g1 <= g0:
            continue
        split = defaultdict(int)
        for s in children:
            part = _overlap(g0, g1, s["start_ns"], s["end_ns"])
            if part:
                split[s["name"]] += part
        covered = sum(split[p] for p in PHASES)
        if g1 - g0 - covered > 0:
            split["unattributed"] = g1 - g0 - covered
        for name, part in split.items():
            idle[name] += part
        gaps.append({"start_ns": g0, "ns": g1 - g0, "by": dict(split)})
    inside = [s for s in steps if s["start_ns"] >= lo and s["end_ns"] <= hi]
    return {
        "main_module": main_module, "steps": len(main),
        "window_ns": hi - lo, "busy_ns": busy_ns,
        "scoped_share": scoped_ns / busy_ns,
        "phase_ns": {p: trace_reduce.union_ns(phase_iv[p])
                     for p in ("fwd", "bwd", "update")},
        "matmul_ns": trace_reduce.union_ns(matmul_iv),
        "layers": {k: dict(v) for k, v in layers.items()},
        "idle_ns": dict(idle),
        "idle_gaps": sorted(gaps, key=lambda g: -g["ns"])[:10],
        "fit_steps": sum(s.get("n_steps", 1) for s in inside),
        "cpu_ns": sum(s["cpu_ns"] or 0 for s in inside),
    }


# -- one run's reduction, for the readers ----------------------------------------

_BY_TRACE: Dict[Tuple[str, float], Optional[Dict]] = {}


def program_spans() -> Optional[List[dict]]:
    """The program's step timeline; None from a program that has none."""
    try:
        from deeplearning4j_tpu.utils import tracing
    except ImportError:
        return None
    timeline = getattr(tracing, "step_timeline", None)
    return timeline() if timeline is not None else None


def of_run(facts: dict) -> Optional[Dict]:
    """`reduce` of the run's trace and the program's timeline, worked out
    once for each trace directory (eight readers ask)."""
    trace_dir = facts.get("trace_dir")
    path = trace_reduce.newest_xplane(trace_dir) if trace_dir else None
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _BY_TRACE:
        _BY_TRACE[key] = reduce(scoped_rows(read_xspace(path)),
                                program_spans())
    return _BY_TRACE[key]


def per_step_ms(facts: dict, trace, *keys: str, over: str = "steps"
                ) -> Optional[float]:
    """What each reader under `benchmark/metrics/` returns: the number
    under `keys` in the run's reduction (nought where a phase never came
    up) over its `over` (the device's step runs, or the fit thread's
    steps), in milliseconds; None without a device trace."""
    if trace is None:
        return None
    reduced = of_run(facts)
    if reduced is None or not reduced[over]:
        return None
    value = reduced
    for key in keys:
        value = value.get(key, 0)
    return value / reduced[over] * 1e-6

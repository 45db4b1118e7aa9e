"""From a profiler trace to a few numbers.

`load_rows` reads the `.xplane.pb` that `jax.profiler` wrote, with nothing
but JAX (`jax.profiler.ProfileData`), and keeps the device planes' lines as
rows `(plane, line, name, start_ns, duration_ns)`. `reduce_rows` turns rows
into numbers; it is plain Python, so that the test can hand it the recorded
rows under `benchmark/fixtures/`.

What a TPU trace holds (looked at by hand, PERF.md "Findings"): one plane
for each chip, `/device:TPU:<n>`, with a line `XLA Ops` that has an event
for every executed HLO instruction (fusions, convolutions, custom calls,
copies) and a line `XLA Modules` with an event for every executed program.
Busy time is the union of the `XLA Ops` intervals, so nested or overlapping
events are not counted twice; the traced window is the span from the first
to the last device event.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

Row = Tuple[str, str, str, float, float]

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_INSTANCE = re.compile(r"(\.\d+)+$")


def newest_xplane(trace_dir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def load_rows(xplane_path: str) -> List[Row]:
    from jax.profiler import ProfileData

    rows: List[Row] = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                rows.append((plane.name, line.name, ev.name,
                             float(ev.start_ns), float(ev.duration_ns)))
    return rows


def op_family(name: str) -> str:
    """`fusion.123` and `%fusion.5 = ...` fall into `fusion`: the HLO
    instruction's name without its parameter list and instance counters
    (the grouping of the program's `utils/profiler.op_family`)."""
    base = name.split("(")[0].split(" = ")[0].strip().lstrip("%")
    return _INSTANCE.sub("", base) or name


_CUSTOM_CALL_OPCODE = re.compile(r"(?<![%\w.\-])custom-call\(")


def is_custom_call(name: str) -> bool:
    """A Pallas (Mosaic) kernel runs as an HLO custom call: the event's
    name is the instruction's text, `%jvp__.71 = bf16[...] custom-call(
    ...), custom_call_target="tpu_custom_call"`. The opcode counts, not an
    operand that is called `%custom-call.5`; a custom call to another
    target than the kernels' is not one of them."""
    if not _CUSTOM_CALL_OPCODE.search(name):
        return name.split(".")[0].lstrip("%") == "custom-call" \
            and "(" not in name
    return "custom_call_target" not in name or "tpu_custom_call" in name


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of `(start, end)` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(intervals, limit: int):
    """The longest idle gaps between merged busy intervals:
    [(start_ns, length_ns)] by length, longest first."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    gaps = [(b[0] - a[1], a[1]) for a, b in zip(merged, merged[1:])]
    return [(start, length) for length, start in sorted(gaps, reverse=True)
            [:limit]]


def _clip(spans, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in spans if e > lo and s < hi]


def reduce_rows(rows: Iterable[Row], top: int = 10) -> Optional[Dict]:
    """Busy and window seconds averaged over the device planes, custom-call
    seconds, the runs of the main program (the module that took most time:
    the train step), the op families that took most time and the longest
    idle gaps. Where the trace has module events, the window is cut to
    whole runs of the main program: from the second one's start to the
    last but one's end. None when no device ran an operation."""
    by_plane = defaultdict(lambda: {"ops": [], "modules": []})
    for plane, line, name, start, dur in rows:
        if line == OPS_LINE:
            by_plane[plane]["ops"].append((name, start, start + dur))
        elif line == MODULES_LINE:
            by_plane[plane]["modules"].append((name, start, start + dur))
    planes = {p: v for p, v in by_plane.items() if v["ops"]}
    if not planes:
        return None
    busy, window, custom, runs = [], [], [], []
    families: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[str, float]] = []
    main_module = None
    for plane, v in sorted(planes.items()):
        lo = min(s for _, s, _ in v["ops"])
        hi = max(e for _, _, e in v["ops"])
        if v["modules"]:
            seconds: Dict[str, float] = defaultdict(float)
            for n, s, e in v["modules"]:
                seconds[op_family(n)] += e - s
            main_module = max(seconds, key=seconds.get)
            main = sorted((s, e) for n, s, e in v["modules"]
                          if op_family(n) == main_module)
            if len(main) >= 4:
                # a run that the trace's start or end cut short is recorded
                # as a short event of its own: the first and last go
                main = main[1:-1]
            lo, hi = main[0][0], main[-1][1]
            runs.append(len(main))
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in v["ops"]
               if e > lo and s < hi]
        spans = [(s, e) for _, s, e in ops]
        busy.append(union_ns(spans) * 1e-9)
        window.append((hi - lo) * 1e-9)
        custom.append(union_ns((s, e) for n, s, e in ops
                               if is_custom_call(n)) * 1e-9)
        for n, s, e in ops:
            families[op_family(n)] += (e - s) * 1e-9 / len(planes)
        for start, length in _gaps(spans, top):
            gaps.append((f"{plane} idle at +{(start - lo) * 1e-6:.3f}ms",
                         length * 1e-9))
    n = len(planes)
    return {
        "devices": n,
        "busy_s": sum(busy) / n,
        "window_s": sum(window) / n,
        "custom_call_s": sum(custom) / n,
        "main_module": main_module,
        "main_module_runs": sum(runs) / n if runs else None,
        "device_ops": sorted(families.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(gaps, key=lambda kv: -kv[1])[:top],
    }


def reduce_trace(trace_dir: str) -> Optional[Dict]:
    path = newest_xplane(trace_dir)
    return reduce_rows(load_rows(path)) if path else None

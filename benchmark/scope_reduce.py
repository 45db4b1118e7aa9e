"""Device time of named parts of the step program, for the readers of the
per-kernel metrics: a layer kind's time (`mamba2` of the `Lb0_mixer_mamba2`
scopes) and the time of the events under an inner scope (`ssd_scan`,
`experts`).

Read from the scoped device rows themselves and not through
`span_reduce.reduce`, which reports nothing unless 95% of the busy time
carries one of the program's scopes: in a step of this many layout copies
(which XLA inserts and names nothing) 94.4% did (my chip run, PR 28). A
`while` event is a container (the loop's body events lie inside it and carry
the scopes), so it is never summed.

`part_ns` is plain Python over rows, so that the test can hand it the
recorded ones under `benchmark/fixtures/`. Every function returns None,
never 0, where there is nothing to read: no trace, or a program that has no
such layer or scope (the parent of the PR that added it).
"""

from __future__ import annotations

import os
import re
from typing import Callable, Dict, Iterable, Optional, Tuple

from benchmark import span_reduce, trace_reduce

_ROWS: Dict[Tuple[str, float], Optional[list]] = {}


def of_layer_kinds(*kinds: str) -> Callable[[str], bool]:
    """Scopes whose layer (`Lb0_mixer_mamba2`) is of one of those kinds."""
    def match(scope: str) -> bool:
        layer = span_reduce.layer_of(scope)
        return layer is not None and layer.rsplit("_", 1)[-1] in kinds
    return match


def of_component(part: str) -> Callable[[str], bool]:
    """Scopes whose path holds the whole component `part`."""
    pattern = re.compile(rf"(?:^|[/(]){re.escape(part)}(?=[)/:]|$)")
    return lambda scope: pattern.search(scope) is not None


def part_ns(rows: Optional[Iterable], match: Callable[[str], bool]
            ) -> Optional[Tuple[int, int]]:
    """(device ns of the events whose scope `match` takes, whole runs of
    the main module they were summed over) on the first device plane, over
    the window `span_reduce.reduce` cuts: from the second run's start to
    the last but one's end where there are four or more. None where no
    event matches."""
    if not rows:
        return None
    first = min(r[0] for r in rows)
    modules = [r for r in rows
               if r[0] == first and r[1] == trace_reduce.MODULES_LINE]
    if not modules:
        return None
    seconds: Dict[str, int] = {}
    for _, _, name, _, dur, _ in modules:
        family = trace_reduce.op_family(name)
        seconds[family] = seconds.get(family, 0) + dur
    main_module = max(seconds, key=seconds.get)
    main = sorted((s, s + d) for _, _, n, s, d, _ in modules
                  if trace_reduce.op_family(n) == main_module)
    if len(main) >= 4:
        main = main[1:-1]
    lo, hi = main[0][0], main[-1][1]
    total = 0
    for plane, line, name, start, dur, scope in rows:
        if plane != first or line != trace_reduce.OPS_LINE or not scope \
                or start + dur <= lo or start >= hi \
                or trace_reduce.op_family(name) == "while" \
                or not match(scope):
            continue
        total += min(start + dur, hi) - max(start, lo)
    return (total, len(main)) if total else None


def ms_per_step(facts: dict, trace, match: Callable[[str], bool]
                ) -> Optional[float]:
    """What the readers return: ms a step of the run's traced slice, the
    trace parsed once for all of them."""
    if trace is None:
        return None
    trace_dir = facts.get("trace_dir")
    path = trace_reduce.newest_xplane(trace_dir) if trace_dir else None
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _ROWS:
        _ROWS.clear()
        _ROWS[key] = span_reduce.scoped_rows(span_reduce.read_xspace(path))
    found = part_ns(_ROWS[key], match)
    return None if found is None else found[0] / found[1] * 1e-6

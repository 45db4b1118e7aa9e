"""Look at one trace by hand: `python3 -m benchmark.tools.trace_dump
<trace_dir> <out_dir>` writes what planes and lines the newest trace under
`<trace_dir>` holds, the names that took most time on each line, and a
trimmed copy of the device rows that can serve as a recorded fixture."""

from __future__ import annotations

import json
import os
import sys
from collections import Counter

from benchmark import trace_reduce


def main(argv) -> int:
    trace_dir, out_dir = argv[1], argv[2]
    from jax.profiler import ProfileData

    path = trace_reduce.newest_xplane(trace_dir)
    if path is None:
        print(f"no xplane under {trace_dir}", file=sys.stderr)
        return 1
    os.makedirs(out_dir, exist_ok=True)
    summary = {"xplane": path, "bytes": os.path.getsize(path), "planes": []}
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            time_by_name = Counter()
            for ev in events:
                time_by_name[ev.name] += ev.duration_ns
            lines.append({"line": line.name, "events": len(events),
                          "top": [[n[:160], t * 1e-9] for n, t in
                                  time_by_name.most_common(25)]})
        summary["planes"].append({"plane": plane.name, "lines": lines})
    with open(os.path.join(out_dir, "trace_structure.json"), "w") as f:
        json.dump(summary, f, indent=1)
    rows = trace_reduce.load_rows(path)
    rows.sort(key=lambda r: r[3])
    t0 = rows[0][3] if rows else 0.0
    # the first 0.25 s of device rows, times from the first row
    keep = [[p, l, n[:400], s - t0, d] for p, l, n, s, d in rows
            if s - t0 < 0.25e9]
    with open(os.path.join(out_dir, "trace_rows.json"), "w") as f:
        json.dump(keep, f)
    print(json.dumps({"planes": [p["plane"] for p in summary["planes"]],
                      "rows": len(rows), "kept": len(keep),
                      "reduced": trace_reduce.reduce_rows(rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

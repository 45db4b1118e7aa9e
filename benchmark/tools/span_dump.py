"""Record a scoped fixture: `python3 -m benchmark.tools.span_dump <cell>
<seed> <out_dir>` makes one traced run of the cell through the harness's own
`run_cell` (on the chip: there is no CPU mode), prints its result line, and
writes the trace itself and `<out_dir>/scoped_trace.json`: the device rows of three whole step
runs of the traced slice with each event's scope (names cut to 400
characters, names and scopes as tables, times in whole nanoseconds from the
first row), the spans of the program's step timeline over the same seconds,
and what `span_reduce.reduce` makes of the whole slice."""

from __future__ import annotations

import bisect
import json
import os
import shutil
import sys

from benchmark import run as bench_run
from benchmark import span_reduce, trace_reduce


def cut(rows, spans, runs: int = 3):
    """The rows of `runs + 2` runs of the main module in a row (`reduce`
    drops the first and the last) and the spans that touch those seconds,
    both counted from the first row. The cut holds the end of devprof's
    blocking read, where the slice has one: the host runs steps ahead of
    the device, so the read ends when the device has caught up, and the
    device then waits for the host."""
    modules = sorted((r for r in rows if r[1] == trace_reduce.MODULES_LINE
                      and trace_reduce.op_family(r[2]) == "jit_step"),
                     key=lambda r: r[3])
    starts = [m[3] for m in modules]
    sample = next((s["end_ns"] for s in spans
                   if s["name"] == "devprof/sample"
                   and starts[2] <= s["end_ns"] < starts[-2]), None)
    first = 1 if sample is None else bisect.bisect(starts, sample) - 3
    first = max(0, min(first, len(modules) - runs - 2))
    last = modules[first + runs + 1]
    lo, hi = starts[first], last[3] + last[4]
    kept = [r for r in rows if r[3] + r[4] > lo and r[3] < hi]
    t0 = min(r[3] for r in kept)
    names, scopes = {}, {}
    table = [[r[0], r[1], names.setdefault(r[2][:400], len(names)),
              r[3] - t0, r[4], scopes.setdefault(r[5], len(scopes))]
             for r in sorted(kept, key=lambda r: r[3])]
    near = [dict(s, start_ns=s["start_ns"] - t0, end_ns=s["end_ns"] - t0)
            for s in spans if s["end_ns"] > lo - 200_000_000
            and s["start_ns"] < hi + 200_000_000]
    return {"names": list(names), "scopes": list(scopes), "rows": table,
            "spans": near}


def main(argv) -> int:
    cell, seed, out_dir = argv[1], int(argv[2]), argv[3]
    os.makedirs(out_dir, exist_ok=True)
    loaded = bench_run.load_cell(bench_run.ROOT, cell)
    peaks = bench_run.load_peaks()
    bench_run.place_compile_cache(bench_run.ROOT)
    device = bench_run.check_device(int(loaded["cell"]["chips"]), peaks)
    out = bench_run.run_cell(loaded, seed=seed, seconds=10.0, trace=True,
                             device=device, peaks=peaks, root=bench_run.ROOT)
    print(json.dumps(bench_run.jsonable(out["line"])), flush=True)
    path = trace_reduce.newest_xplane(os.path.join(
        bench_run.ROOT, ".bench_trace", cell))
    rows = span_reduce.scoped_rows(span_reduce.read_xspace(path))
    spans = span_reduce.program_spans()
    shutil.copy(path, os.path.join(out_dir, "scoped_trace.xplane.pb"))
    doc = cut(rows, spans)
    doc["whole_slice"] = span_reduce.reduce(rows, spans)
    doc["recorded"] = {"cell": cell, "seed": seed,
                       "device": device["kind"]}
    with open(os.path.join(out_dir, "scoped_trace.json"), "w") as f:
        json.dump(doc, f)
    print(json.dumps({"rows": len(doc["rows"]), "spans": len(doc["spans"]),
                      "whole_slice": {k: v for k, v in (
                          doc["whole_slice"] or {}).items()
                          if k not in ("layers",)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

"""Record a scoped fixture: `python3 -m benchmark.tools.span_dump <cell>
<seed> <out_dir> [runs]` makes one traced run of the cell through the
harness's own `run_cell` (on the chip: there is no CPU mode), with the cell's
own per-layer metrics as `benchmark.run --trace 1` reads them, prints its
result line, and writes the trace itself and `<out_dir>/scoped_trace.json`:
the device rows of `runs` (1 unless given) whole step runs of the traced
slice between their two neighbours, in `compact` form (planes, lines, names
cut to 400 characters and scopes as tables, times in whole nanoseconds from
the first row), the spans of the program's step timeline over the same
seconds, and under `recorded` what the run read: the line's metrics, what
`span_reduce.reduce` makes of the whole slice, and the program's expert
books. `fixtures/vgg16_scoped_trace.json` (PR 25, `runs` 3) predates
`compact`: its rows carry the plane and the line as strings."""

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


def compact(doc: dict) -> dict:
    """`cut`'s document with the planes and lines as tables too (a step of
    a decoder cell is 26,000 events; their two strings were half the file):
    rows `[plane, line, name, start_ns, duration_ns, scope]`, all but the
    times as positions in `planes`, `lines`, `names`, `scopes`."""
    planes, lines = {}, {}
    rows = [[planes.setdefault(p, len(planes)), lines.setdefault(l, len(lines)),
             n, s, d, sc] for p, l, n, s, d, sc in doc["rows"]]
    return dict(doc, planes=list(planes), lines=list(lines), rows=rows)


def expand(doc: dict) -> list:
    """The scoped rows of a compact document, as `scope_reduce` and
    `span_reduce.reduce` take them."""
    return [(doc["planes"][p], doc["lines"][l], doc["names"][n], s, d,
             doc["scopes"][sc]) for p, l, n, s, d, sc in doc["rows"]]


def program_books() -> dict:
    """The program's expert books as its registry holds them at the end of
    the run (all of the process's fits): `experts_overflow_total` has to
    read 0. Empty from a program that keeps none."""
    try:
        from deeplearning4j_tpu.utils.metrics import get_registry
    except ImportError:
        return {}
    return {k: v for k, v in get_registry().scalar_values().items()
            if k.startswith("experts_")}


def main(argv) -> int:
    cell, seed, out_dir = argv[1], int(argv[2]), argv[3]
    runs = int(argv[4]) if len(argv) > 4 else 1
    os.makedirs(out_dir, exist_ok=True)
    loaded = bench_run.load_cell(bench_run.ROOT, cell)
    peaks = bench_run.load_peaks()
    bench_run.place_compile_cache(bench_run.ROOT)
    device = bench_run.check_device(int(loaded["cell"]["chips"]), peaks)
    out = bench_run.run_cell(loaded, seed=seed, seconds=10.0, trace=True,
                             device=device, peaks=peaks, root=bench_run.ROOT)
    print(json.dumps(bench_run.jsonable({"info": out["info"]})), flush=True)
    print(json.dumps(bench_run.jsonable(out["line"])), flush=True)
    path = trace_reduce.newest_xplane(os.path.join(
        bench_run.ROOT, ".bench_trace", cell))
    rows = span_reduce.scoped_rows(span_reduce.read_xspace(path))
    spans = span_reduce.program_spans()
    shutil.copy(path, os.path.join(out_dir, "scoped_trace.xplane.pb"))
    doc = compact(cut(rows, spans, runs=runs))
    doc["recorded"] = {"cell": cell, "seed": seed, "device": device["kind"],
                       "metrics": out["line"]["metrics"],
                       "whole_slice": span_reduce.reduce(rows, spans),
                       "books": program_books()}
    with open(os.path.join(out_dir, "scoped_trace.json"), "w") as f:
        json.dump(bench_run.jsonable(doc), f, separators=(",", ":"))
    print(json.dumps({"rows": len(doc["rows"]), "spans": len(doc["spans"]),
                      "books": doc["recorded"]["books"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

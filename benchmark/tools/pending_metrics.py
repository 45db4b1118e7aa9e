"""Report the per-layer metrics of `benchmark/pending_per_layer.json` (readers
that are here, entries that `BENCHMARK.json` cannot take yet; the file says
why) from one traced run of a cell on the chip, and record the run's scoped
fixture as `tools/span_dump.py` does:

    python3 -m benchmark.tools.pending_metrics <cell> <seed> <out_dir>

One process, the harness's own `run_cell`, the cell's own per-layer metrics
with the pending ones of that cell appended. Prints the result line and writes
the trace itself and `<out_dir>/scoped_trace.json`: three whole step runs of
the traced slice in `compact` form, the timeline's spans of those seconds and
what the run read (`recorded`)."""

from __future__ import annotations

import json
import os
import shutil
import sys

from benchmark import run as bench_run
from benchmark import span_reduce, trace_reduce
from benchmark.tools import span_dump


def pending_for(cell: str) -> list:
    with open(os.path.join(bench_run.PKG, "pending_per_layer.json")) as f:
        return [m for m in json.load(f)["per_layer"]
                if cell in m.get("workloads", [cell])]


def main(argv) -> int:
    cell, seed, out_dir = argv[1], int(argv[2]), argv[3]
    os.makedirs(out_dir, exist_ok=True)
    loaded = bench_run.load_cell(bench_run.ROOT, cell)
    loaded["per_layer"] = loaded["per_layer"] + pending_for(cell)
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
    doc = compact(span_dump.cut(rows, spans, runs=1))
    doc["recorded"] = {"cell": cell, "seed": seed, "device": device["kind"],
                       "metrics": out["line"]["metrics"],
                       "whole_slice": span_reduce.reduce(rows, spans)}
    with open(os.path.join(out_dir, "scoped_trace.json"), "w") as f:
        json.dump(bench_run.jsonable(doc), f, separators=(",", ":"))
    print(json.dumps({"rows": len(doc["rows"]), "spans": len(doc["spans"]),
                      "books": program_books()}))
    return 0


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


def compact(doc: dict) -> dict:
    """`span_dump.cut`'s document with the planes and lines as tables too
    (a step of this cell is 26,000 events; their two strings were half the
    file): rows `[plane, line, name, start_ns, duration_ns, scope]`, all
    but the times as positions in `planes`, `lines`, `names`, `scopes`."""
    planes, lines = {}, {}
    rows = [[planes.setdefault(p, len(planes)), lines.setdefault(l, len(lines)),
             n, s, d, sc] for p, l, n, s, d, sc in doc["rows"]]
    return dict(doc, planes=list(planes), lines=list(lines), rows=rows)


def expand(doc: dict) -> list:
    """The scoped rows of a compact document, as `scope_reduce` and
    `span_reduce.reduce` take them."""
    return [(doc["planes"][p], doc["lines"][l], doc["names"][n], s, d,
             doc["scopes"][sc]) for p, l, n, s, d, sc in doc["rows"]]


if __name__ == "__main__":
    sys.exit(main(sys.argv))

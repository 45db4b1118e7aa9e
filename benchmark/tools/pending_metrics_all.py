"""`tools/pending_metrics.py` over every `benchmark/pending_per_layer*.json`:
the per-layer metrics whose readers are here and whose entries
`BENCHMARK.json` cannot take yet, of whichever file lists the cell, from one
traced run of the cell on the chip:

    python3 -m benchmark.tools.pending_metrics_all <cell> <seed> <out_dir>

A metric that two files list for one cell is reported once. Everything else
(the run, the result line, the scoped fixture, the books) is
`pending_metrics.main`, which looks `pending_for` up in its own module: this
tool puts its own there for the call."""

from __future__ import annotations

import glob
import json
import os
import sys

from benchmark import run as bench_run
from benchmark.tools import pending_metrics


def pending_for(cell: str) -> list:
    out, seen = [], set()
    for path in sorted(glob.glob(os.path.join(bench_run.PKG,
                                              "pending_per_layer*.json"))):
        with open(path) as f:
            for m in json.load(f)["per_layer"]:
                if cell in m.get("workloads", [cell]) \
                        and m["name"] not in seen:
                    seen.add(m["name"])
                    out.append(m)
    return out


def main(argv) -> int:
    theirs = pending_metrics.pending_for
    pending_metrics.pending_for = pending_for
    try:
        return pending_metrics.main(argv)
    finally:
        pending_metrics.pending_for = theirs


if __name__ == "__main__":
    sys.exit(main(sys.argv))

"""The upper readings of a cell whose model fills the chip: `tools/calibrate.py`
keeps the program's net on the chip while the reference runs, which a net of
two thirds of the chip's memory leaves no room for. Here no net is built. For
each of `--seeds` seeds the float32 reference takes the cell's checked steps,
then, each compared with it by `compare.first_step_gaps`:

* the control: the reference computed in fp8, the nearest precision below the
  bf16 the configurations state, which `correct` has to fail;
* with `--witness`, the reference computed in bf16, which must side with it;
* the planted fault "half of the batch left out, the mean taken over the
  rest";
* the planted fault "one step skipped" (the state after one step fewer).

    python3 -m benchmark.tools.calibrate_controls --workload <cell> \
        --seeds 3 --out chiprun_out/controls_<cell>.json

A state left unchanged reads 1 by `compare`'s measure and needs no run. The
lower readings (the program against the reference) are the `checks` of the
cell's own runs. PERF.md holds the readings and the limits set from them.

Every control and fault is then judged by `compare.judge` against the
`limits` of the cell's configuration, as a run of the cell is: each row
carries `fails`, the limits it passed, and the exit code is 1 when any of
them would have been `correct` (or a witness would not), so limits that let
a control through cannot be committed unseen."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from benchmark import compare, run as bench_run
from benchmark.tools.calibrate import NUMBERS
from benchmark.traffic import fit_loop


WRONG = ("control_fp8", "fault_half_batch", "fault_skipped_step")


def judged(row, limits):
    """The limits this reading is over: what `correct` fails it by."""
    checks = compare.judge({k: row[k] for k in NUMBERS}, limits)["checks"]
    return [name for name, c in checks.items()
            if c["limit"] is not None and not c["value"] <= c["limit"]]


def let_through(out, limits):
    """(kind, seed) of every control or fault that no limit fails, and of
    every witness that one does: none, for limits that may be committed."""
    bad = []
    for key in WRONG + ("witness_bf16",):
        for row in out.get(key, ()):
            row["fails"] = judged(row, limits)
            if bool(row["fails"]) != (key in WRONG):
                bad.append((key, row["seed"]))
    return bad


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=3)
    parser.add_argument("--first-seed", type=int, default=2147500003)
    parser.add_argument("--witness", action="store_true")
    parser.add_argument("--out", required=True)
    args = parser.parse_args(argv)

    loaded = bench_run.load_cell(bench_run.ROOT, args.workload)
    bench_run.place_compile_cache(bench_run.ROOT)
    device = bench_run.check_device(int(loaded["cell"]["chips"]),
                                    bench_run.load_peaks())
    config, traffic = loaded["config"], loaded["traffic"]
    batch = int(traffic["batch_per_chip"]) * int(loaded["cell"]["chips"])
    n_steps = int(traffic["checked_steps"])
    rows_block = traffic.get("rows_block")
    out = {"workload": args.workload, "device": device, "batch": batch,
           "steps": n_steps, "control_fp8": [], "witness_bf16": [],
           "fault_half_batch": [], "fault_skipped_step": []}

    def first_steps(seed, pool, steps=n_steps, **kw):
        t0 = time.perf_counter()
        got = fit_loop.first_steps_of_reference(
            config, seed, pool, steps, rows_block=rows_block, **kw)
        return got, time.perf_counter() - t0

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        pool = fit_loop.make_pool(seed, n_steps, batch, config,
                                  traffic.get("seq_len"))
        reference, seconds = first_steps(seed, pool)
        print(json.dumps({"seed": seed, "reference_seconds": seconds,
                          "losses": reference["losses"]}), flush=True)
        variants = [("control_fp8", {"precision": "fp8"}),
                    ("fault_half_batch", {"rows": slice(0, batch // 2)})]
        if args.witness:
            variants.append(("witness_bf16", {"precision": "bf16"}))
        for key, kw in variants:
            other, took = first_steps(seed, pool, **kw)
            gaps = compare.first_step_gaps(other, reference)
            row = dict({k: gaps[k] for k in NUMBERS}, seed=seed,
                       worst=gaps["worst"], losses=other["losses"],
                       seconds=took)
            out[key].append(row)
            print(json.dumps(bench_run.jsonable({key: row})), flush=True)
        # a step skipped: the losses of the steps taken are the reference's
        # own; the parameters moved one step less
        short, took = first_steps(seed, pool, steps=n_steps - 1)
        short["losses"] = short["losses"] + [reference["losses"][-1]]
        gaps = compare.first_step_gaps(short, reference)
        row = dict({k: gaps[k] for k in NUMBERS}, seed=seed,
                   worst=gaps["worst"], seconds=took)
        out["fault_skipped_step"].append(row)
        print(json.dumps(bench_run.jsonable({"fault_skipped_step": row})),
              flush=True)

    out["summary_min_max"] = {
        key: {name: [min(r[name] for r in out[key]),
                     max(r[name] for r in out[key])] for name in NUMBERS}
        for key in ("control_fp8", "witness_bf16", "fault_half_batch",
                    "fault_skipped_step") if out[key]}
    out["limits"] = dict(config["limits"])
    out["let_through"] = let_through(out, out["limits"])
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(bench_run.jsonable(out), f, indent=1)
    print(json.dumps(bench_run.jsonable(out["summary_min_max"])))
    for key in WRONG + ("witness_bf16",):
        for row in out[key]:
            print(f"{key} seed {row['seed']}: fails {row['fails']}")
    for key, seed in out["let_through"]:
        print(f"NOT SEPARATED: {key} seed {seed} is judged "
              f"{'correct' if key in WRONG else 'not correct'} by the "
              f"cell's limits", file=sys.stderr)
    return 1 if out["let_through"] else 0


if __name__ == "__main__":
    sys.exit(main())

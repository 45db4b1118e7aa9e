"""Read what the limits of a training cell's `correct` are set from, on the
chip, in one process:

    python3 -m benchmark.tools.calibrate --workload <cell> --seeds 12 \
        --control-seeds 3 --out chiprun_out/calibrate_<cell>.json

* the lower readings: the program's first steps (the timed path's own call
  and feed, at the cell's own batch) against the float32 reference, over
  `--seeds` seeds;
* the upper readings, over `--control-seeds` seeds, each against the same
  float32 reference: the control (the reference computed in fp8, the nearest
  precision below the bf16 the configurations state), the planted fault
  "half of the batch left out, the mean taken over the rest" (the reference
  over the first half of each batch's rows), and, as a second witness that
  must side with the reference, the reference computed in bf16.

A state left unchanged reads 1 by `compare`'s measure and needs no run. No
window is measured. PERF.md holds the readings and the limits set from them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from benchmark import compare, run as bench_run
from benchmark.traffic import fit_loop


NUMBERS = ("loss1_gap", "loss_gap", "grad_gap", "grad_median_gap",
           "delta_gap", "delta_median_gap")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--first-seed", type=int, default=1000003)
    parser.add_argument("--out", required=True)
    args = parser.parse_args(argv)

    loaded = bench_run.load_cell(bench_run.ROOT, args.workload)
    bench_run.place_compile_cache(bench_run.ROOT)
    device = bench_run.check_device(int(loaded["cell"]["chips"]),
                                    bench_run.load_peaks())
    import jax
    import jax.numpy as jnp

    config, traffic = loaded["config"], loaded["traffic"]
    batch = int(traffic["batch_per_chip"]) * int(loaded["cell"]["chips"])
    n_steps = int(traffic["checked_steps"])
    ref = fit_loop.load_reference(config)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]

    net = fit_loop.build_net(config, seeds[0])
    state0 = jax.device_get(net.state_list)
    out = {"workload": args.workload, "device": device, "batch": batch,
           "steps": n_steps, "program": [], "control_fp8": [],
           "fault_half_batch": [], "witness_bf16": []}

    def gaps(readings, reference):
        g = compare.first_step_gaps(readings, reference)
        return {k: g[k] for k in NUMBERS + ("worst",)}

    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        pool = fit_loop.make_pool(seed, n_steps, batch, config)
        fit_loop.install_weights(net, ref.init_params(seed, config))
        net.upd_state = net.updater_def.init_tree(net.params_list)
        net.state_list = jax.tree_util.tree_map(jnp.asarray, state0)
        net.iteration = net.epoch = 0
        program = fit_loop.first_steps_of_program(net, pool, config, n_steps)
        reference = fit_loop.first_steps_of_reference(config, seed, pool,
                                                      n_steps)
        row = dict(gaps(program, reference), seed=seed,
                   losses=program["losses"],
                   reference_losses=reference["losses"])
        out["program"].append(row)
        if i < args.control_seeds:
            for key, kw in (("control_fp8", {"precision": "fp8"}),
                            ("witness_bf16", {"precision": "bf16"}),
                            ("fault_half_batch",
                             {"rows": slice(0, batch // 2)})):
                other = fit_loop.first_steps_of_reference(
                    config, seed, pool, n_steps, **kw)
                out[key].append(dict(gaps(other, reference), seed=seed,
                                     losses=other["losses"]))
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(bench_run.jsonable(row)), flush=True)

    summary = {}
    for key in ("program", "control_fp8", "witness_bf16",
                "fault_half_batch"):
        summary[key] = {
            name: [min(r[name] for r in out[key]),
                   max(r[name] for r in out[key])]
            for name in NUMBERS} \
            if out[key] else None
    out["summary_min_max"] = summary
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(bench_run.jsonable(out), f, indent=1)
    print(json.dumps(bench_run.jsonable(summary)))
    return 0


if __name__ == "__main__":
    sys.exit(main())

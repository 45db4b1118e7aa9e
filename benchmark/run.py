"""Run one cell of BENCHMARK.json once.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell asks
for. One process holds them from start to end. The last line of standard
output is the result:

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, ["breakdown": {...},] "checks": {...}}

with the cell's end-to-end metrics under `--trace 0` and its per-layer
metrics under `--trace 1`. `checks` holds every number that `correct`
compared beside its limit; the same numbers are the last lines of standard
error. Earlier lines of standard output are orientation (helper hits, first
losses, compiles in the window) and no part of the contract.

There is no CPU branch, no smaller size and no retry: a device that is not
a TPU of `benchmark/peaks.json`, or another number of chips than the cell
asks for, ends the run with a non-zero code and no result line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Dict  # noqa: E402

PKG = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PKG)


class Refused(Exception):
    """The run cannot be made here: no result line, a non-zero exit."""


def _load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, workload: str) -> dict:
    """The cell of that name with its configuration, its traffic and its
    metrics, all found by the names in BENCHMARK.json."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json "
                      f"(it has {sorted(cells)})")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = _load_json(os.path.join(root, entry["file"]))
    traffic = _load_json(os.path.join(
        root, "benchmark", "traffic", cell["traffic"] + ".json"))

    def reported(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    end_to_end = [m for m in bench["end_to_end"] if reported(m)]
    names = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if reported(m) and m["moves"] in names]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": end_to_end, "per_layer": per_layer}


def load_peaks() -> dict:
    return {k: v for k, v in _load_json(os.path.join(PKG, "peaks.json")
                                        ).items() if not k.startswith("_")}


def check_device(chips: int, peaks: dict) -> dict:
    """What jax runs on, or `Refused`: a TPU of the peaks table, and as
    many chips as the cell asks for."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise Refused(f"no TPU: jax found platform {dev.platform!r}; the "
                      "benchmark has no CPU mode")
    if dev.device_kind not in peaks:
        raise Refused(f"device kind {dev.device_kind!r} is not in "
                      f"benchmark/peaks.json (it has {sorted(peaks)})")
    if len(devices) != chips:
        raise Refused(f"the cell asks for {chips} chip(s), jax found "
                      f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def place_compile_cache(root: str) -> None:
    """JAX_COMPILATION_CACHE_DIR if it is set (jax reads it itself), else
    one fixed directory in the checkout: the path is part of the cache's
    key. Every program is kept, however quickly it compiled."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def load_reader(name: str):
    """`benchmark/metrics/<name>.py`, found by the metric's name."""
    path = os.path.join(PKG, "metrics", name + ".py")
    if not os.path.exists(path):
        raise Refused(f"no reader benchmark/metrics/{name}.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def load_generator(traffic: dict):
    return importlib.import_module(
        f"benchmark.traffic.{traffic['generator']}")


def run_cell(loaded: dict, *, seed: int, seconds: float, trace: bool,
             device: dict, peaks: dict, root: str,
             t_start: float = T_START) -> Dict:
    """Drive one run of a loaded cell on the device jax has (the look for
    a chip is `check_device`, before this) and return the result line as a
    dict, `info` (orientation) beside it."""
    from benchmark import trace_reduce

    cell, config, traffic = loaded["cell"], loaded["config"], \
        loaded["traffic"]
    generator = load_generator(traffic)
    ctx = {"cell": cell, "chips": int(cell["chips"]), "config": config,
           "traffic": traffic, "seed": int(seed), "seconds": float(seconds),
           "trace": bool(trace), "t_start": t_start,
           "trace_dir": os.path.join(root, ".bench_trace", cell["name"])}
    facts = generator.run(ctx)
    facts["peak_flops_per_s"] = peaks[device["kind"]]["bf16_flops_per_s"]
    verdict = generator.verify(ctx, facts)

    reduced = None
    if trace:
        if facts.get("trace_error"):
            raise RuntimeError(f"the profiler failed: {facts['trace_error']}")
        reduced = trace_reduce.reduce_trace(facts["trace_dir"])
    metrics = {}
    for m in loaded["per_layer"] if trace else loaded["end_to_end"]:
        value = load_reader(m["name"])(facts, reduced)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    dev = dict(device, memory_peak_bytes=facts["memory_peak_bytes"])
    line = {"correct": bool(verdict["correct"]),
            "attempted": facts["attempted"], "failed": facts["failed"],
            "metrics": metrics, "device": dev}
    if reduced is not None:
        dev["busy_s"], dev["window_s"] = reduced["busy_s"], \
            reduced["window_s"]
        line["breakdown"] = {
            "device_ops": [[n, s] for n, s in reduced["device_ops"]],
            "idle_gaps": [[n, s] for n, s in reduced["idle_gaps"]]}
    line["checks"] = verdict["checks"]
    info = {k: facts[k] for k in (
        "setup_s", "setup_parts_s", "window_s", "steps", "examples",
        "first_losses",
        "last_loss", "error", "helper_books", "compiles_in_window",
        "pool_bytes", "flops_per_example", "trace_steps", "trace_wall_s",
        "trace_stop_s", "memory_stats", "helper_books_total")}
    info.update(reference_losses=verdict["reference_losses"],
                worst_leaves=verdict["worst_leaves"],
                leaves=verdict["leaves"],
                leaves_left_out=verdict["leaves_left_out"],
                main_module=reduced and reduced["main_module"],
                main_module_runs=reduced and reduced["main_module_runs"])
    return {"line": line, "info": info}


def jsonable(value):
    """Floats that JSON cannot hold (inf, nan) as their names."""
    if isinstance(value, float) and (value != value or value in (
            float("inf"), float("-inf"))):
        return repr(value)
    if isinstance(value, dict):
        return {k: jsonable(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [jsonable(v) for v in value]
    return value


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        loaded = load_cell(ROOT, args.workload)
        peaks = load_peaks()
        place_compile_cache(ROOT)
        device = check_device(int(loaded["cell"]["chips"]), peaks)
        out = run_cell(loaded, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace), device=device, peaks=peaks,
                       root=ROOT)
    except Refused as e:
        print(f"benchmark.run: {e}", file=sys.stderr)
        return 2
    print(json.dumps(jsonable({"info": out["info"]})), flush=True)
    line = out["line"]
    for name, check in line["checks"].items():
        print(f"check {name}: value {check['value']!r} limit "
              f"{check['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(jsonable(line)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Operations and bytes of the kernels whose share of the roofline the
benchmark reports, from the configuration and the traffic file alone: the
same whatever implements the scope. A training step is the forward pass and
a backward pass of twice its size (as `flops.py` counts a step), operands
in the configuration's compute type (2 bytes for bf16) and float32 `dt`.

`share(...)` is the roofline share: the least time the chip could take (the
larger of operations over the peak and bytes over the memory's bandwidth)
over the device time the scope's events took.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Optional

_PASSES = 3            # forward, and a backward pass of twice its size
_BYTES = {"bf16": 2, "f32": 4}


def _tokens(traffic: dict) -> int:
    return int(traffic["batch_per_chip"]) * int(traffic["seq_len"])


def _count(config: dict, letter: str) -> int:
    return config["hybrid_override_pattern"].count(letter)


def ssd_scan(config: dict, traffic: dict) -> Dict[str, float]:
    """The state-space recurrence of every Mamba-2 mixer, a step on one
    chip: `2 heads head_dim state` multiply-accumulates a position (the
    state's update and its read-out), and `x`, `B`, `C`, `dt` read and `y`
    written once a pass."""
    H, P = config["mamba_num_heads"], config["mamba_head_dim"]
    G, N = config["n_groups"], config["ssm_state_size"]
    wide = _BYTES[config["precision"]]
    macs = 2 * H * P * N
    moved = (H * P + 2 * G * N + H * P) * wide + H * 4
    n = _tokens(traffic) * _count(config, "M") * _PASSES
    return {"flops": 2.0 * macs * n, "bytes": float(moved * n)}


def experts(config: dict, traffic: dict) -> Dict[str, float]:
    """The routed experts' two products in every sparse-expert layer, a
    step on one chip, at the uniform share: a token meets
    `num_experts_per_tok * held / router_width` of this chip's experts. The
    held experts' weights are read once a pass; an assignment's input row
    is read, its hidden row written and read, its output row written."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    held = config["n_routed_experts"]
    wide = _BYTES[config["precision"]]
    rows = _tokens(traffic) * config["num_experts_per_tok"] * held \
        / config.get("router_width", held)
    macs = rows * 2 * d * f
    moved = held * 2 * d * f * wide + rows * (2 * d + 2 * f) * wide
    n = _count(config, "E") * _PASSES
    return {"flops": 2.0 * macs * n, "bytes": float(moved * n)}


def cell_of_run(facts: dict) -> Optional[dict]:
    """The configuration, the traffic and the chip's peaks of a traced run,
    found from what `facts` holds: the trace lies under
    `<root>/.bench_trace/<cell>`, and the chip is the one of peaks.json
    with the run's `peak_flops_per_s`. None where they cannot be found."""
    trace_dir = facts.get("trace_dir")
    if not trace_dir:
        return None
    root = os.path.dirname(os.path.dirname(os.path.abspath(trace_dir)))
    try:
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            bench = json.load(f)
        cell = {w["name"]: w for w in bench["workloads"]}[
            os.path.basename(os.path.abspath(trace_dir))]
        entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
        with open(os.path.join(root, entry["file"])) as f:
            config = json.load(f)
        with open(os.path.join(root, "benchmark", "traffic",
                               cell["traffic"] + ".json")) as f:
            traffic = json.load(f)
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "peaks.json")) as f:
            table = json.load(f)
    except (OSError, KeyError, ValueError):
        return None
    peaks = next((p for k, p in table.items() if not k.startswith("_")
                  and p["bf16_flops_per_s"] == facts.get("peak_flops_per_s")),
                 None)
    return {"config": config, "traffic": traffic, "peaks": peaks}


def share(work: Dict[str, float], device_ms: Optional[float],
          peaks: Optional[dict]) -> Optional[float]:
    """Percent of the roofline: None where no time was read."""
    if not device_ms or not peaks:
        return None
    least_s = max(work["flops"] / peaks["bf16_flops_per_s"],
                  work["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (device_ms * 1e-3)

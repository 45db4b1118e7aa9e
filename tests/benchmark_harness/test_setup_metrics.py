"""The five per-layer metrics that move `setup_s`: each reads the program's
registry as `fit_loop.run` snapshots it when the window opens
(`facts["registry_before"]`), under the keys `scalar_values()` renders, and
reads nothing from a program that keeps no such family. CPU only; the traced
run at the end is test_harness.py's tiny cell."""

from __future__ import annotations

import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SETUP = ["net_init_s.setup", "jit_compile_s.setup", "jit_programs.setup",
         "jit_cache_hit_pct.setup", "fit_overhead_s.setup"]


def _registry(**families):
    """A registry's `scalar_values()` from hand-made families: the keys are
    rendered by the program's own registry, so a label's quoting is its."""
    from deeplearning4j_tpu.utils.metrics import MetricsRegistry

    reg = MetricsRegistry()
    for phase, seconds in families.get("compile", {}).items():
        for s in seconds:
            reg.histogram("jit_compile_seconds", "", ("phase",)) \
                .labels(phase).observe(s)
    for result, n in families.get("cache", {}).items():
        reg.counter("jit_cache_total", "", ("result",)).labels(result).inc(n)
    for phase, seconds in families.get("fit", {}).items():
        for s in seconds:
            reg.histogram("fit_phase_seconds", "", ("phase",)) \
                .labels(phase).observe(s)
    for s in families.get("init", []):
        reg.histogram("net_init_seconds", "").observe(s)
    return reg.scalar_values()


WARM = _registry(
    init=[2.5],
    compile={"trace": [0.5, 1.5], "lower": [0.25, 0.75],
             "backend": [0.125, 0.375], "cache_load": [1.0, 3.0]},
    cache={"hit": 2, "miss": 0},
    fit={"setup": [0.01, 0.01, 0.01, 0.01], "teardown": [1.0, 1.0, 1.0, 0.5],
         "publish_books": [0.9, 0.9, 0.9, 0.4]})
COLD = _registry(
    init=[2.5], compile={"trace": [2.0], "lower": [1.0], "backend": [30.0]},
    cache={"hit": 1, "miss": 3}, fit={"setup": [0.5], "teardown": [0.25]})
NO_CACHE = _registry(compile={"backend": [1.0]}, cache={"hit": 0, "miss": 0})


@pytest.mark.parametrize("name,before,expected", [
    ("net_init_s.setup", WARM, 2.5),
    # every phase once: the load is inside the backend stage, whose own
    # seconds are what is left of it
    ("jit_compile_s.setup", WARM, 2.0 + 1.0 + 0.5 + 4.0),
    ("jit_compile_s.setup", COLD, 33.0),
    ("jit_programs.setup", WARM, 2.0),
    ("jit_programs.setup", COLD, 1.0),
    ("jit_cache_hit_pct.setup", WARM, 100.0),
    ("jit_cache_hit_pct.setup", COLD, 25.0),
    # both children are there from the start: nothing asked reads 0
    ("jit_cache_hit_pct.setup", NO_CACHE, 0.0),
    # publish_books is inside teardown and is not added again
    ("fit_overhead_s.setup", WARM, 0.04 + 3.5),
    ("fit_overhead_s.setup", COLD, 0.75),
    # a program older than the families: nothing to read
    *[(name, {"fit_step_total": 7.0}, None) for name in SETUP],
    # one family of two, or one child of two, is not a reading
    ("jit_cache_hit_pct.setup", {'jit_cache_total{result="hit"}': 3.0}, None),
    ("fit_overhead_s.setup",
     {'fit_phase_seconds{phase="setup"}:sum': 1.0}, None),
])
def test_a_setup_reader_on_hand_made_facts(name, before, expected):
    # what the window adds is not read: the readers take the state when it
    # opened
    after = dict(before, **{k: v + 100.0 for k, v in before.items()})
    value = bench_run.load_reader(name)(
        {"registry_before": before, "registry_after": after}, None)
    assert value == (pytest.approx(expected) if expected is not None
                     else None)


@pytest.mark.parametrize("name", SETUP)
def test_the_entry_moves_setup_s_in_every_cell(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["moves"] == "setup_s" and "workloads" not in entry
    assert entry["source"] == "program_counter"
    for cell in BENCH["workloads"]:
        loaded = bench_run.load_cell(ROOT, cell["name"])
        assert name in {m["name"] for m in loaded["per_layer"]}
    layers = {m["layer"] for m in BENCH["per_layer"] if m["name"] != name}
    assert entry["layer"] in layers | {"net construction"}


def test_the_tiny_cells_traced_run_reports_all_five(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "benchmark_harness_test_harness_for_setup",
        os.path.join(HERE, "test_harness.py"))
    h = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(h)
    out = h._run(h.TINY_RESNET, tmp_path, seconds=1.0, trace=True)
    line = out["line"]
    assert line["correct"], line["checks"]
    metrics = line["metrics"]
    assert set(SETUP) <= set(metrics)
    units = {m["name"]: m["unit"] for m in BENCH["per_layer"]}
    for name in SETUP:
        assert metrics[name]["unit"] == units[name]
        assert metrics[name]["value"] >= 0.0
    assert metrics["jit_programs.setup"]["value"] >= 1
    assert 0.0 <= metrics["jit_cache_hit_pct.setup"]["value"] <= 100.0
    # the checked steps' fit() calls and the warm one, each with its
    # entry and exit (the registry is the process's: earlier tests' fits
    # are in it too, so the sums are not held against this run's set-up)
    from deeplearning4j_tpu.utils.metrics import get_registry

    calls = get_registry().scalar_values()[
        'fit_phase_seconds{phase="setup"}:count']
    assert calls >= h.TINY_TRAFFIC["checked_steps"] + 2

"""CPU tests of the benchmark's harness, at tiny configurations of the
tests' own (the command has no flag for a smaller size). Nothing here loads
the TPU library; the chip's numbers come from the chip alone."""

from __future__ import annotations

import copy
import json
import math
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, flops, run as bench_run, trace_reduce  # noqa: E402
from benchmark.traffic import fit_loop  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

TINY_RESNET = {
    "name": "tiny_resnet",
    "factory": "deeplearning4j_tpu.models.resnet:resnet_conf",
    "factory_args": {"blocks": [1, 1], "widths": [4, 8], "num_classes": 5,
                     "image_size": 16, "channels": 3, "stem_width": 4,
                     "precision": "f32"},
    "engine": "deeplearning4j_tpu.nn.compgraph:ComputationGraph",
    "reference": "resnet50",
    "image_size": 16, "channels": 3, "num_classes": 5, "stem_width": 4,
    "blocks": [1, 1], "widths": [4, 8], "bn_eps": 1e-5,
    "updater": {"name": "nesterovs", "learning_rate": 0.1, "momentum": 0.9},
    # float32 on both sides here: the gaps are round-off
    "limits": {"loss_gap": 1e-4, "grad_gap": 2e-3, "delta_gap": 2e-3},
}
TINY_VGG = {
    "name": "tiny_vgg",
    "factory": "deeplearning4j_tpu.models.vgg16:vgg16_conf",
    "factory_args": {"num_classes": 10, "image_size": 32,
                     "precision": "f32"},
    "engine": "deeplearning4j_tpu.nn.multilayer:MultiLayerNetwork",
    "reference": "vgg16",
    "image_size": 32, "channels": 3, "num_classes": 10,
    "conv_blocks": [[64, 2], [128, 2], [256, 3], [512, 3], [512, 3]],
    "dense_widths": [4096, 4096],
    "updater": {"name": "sgd", "learning_rate": 0.1},
    "limits": {"loss_gap": 1e-4, "grad_gap": 2e-3, "delta_gap": 2e-3},
}
TINY_TRAFFIC = {"generator": "fit_loop", "batch_per_chip": 8,
                "pool_batches": 4, "checked_steps": 3, "warm_batches": 2,
                "trace_before_end_s": 0.3, "trace_seconds": 0.2}


def _loaded(config, chips=1, traffic=None):
    return {"cell": {"name": "tiny", "chips": chips,
                     "config": config["name"], "traffic": "tiny"},
            "config": copy.deepcopy(config),
            "traffic": dict(traffic or TINY_TRAFFIC),
            "end_to_end": BENCH["end_to_end"],
            "per_layer": BENCH["per_layer"]}


def _run(config, tmp_path, *, chips=1, seed=11, seconds=0.4, trace=False,
         traffic=None):
    """The rest of a run, the look for a chip skipped."""
    import time

    return bench_run.run_cell(
        _loaded(config, chips, traffic), seed=seed, seconds=seconds,
        trace=trace, device=dict(V5E, count=chips),
        peaks=bench_run.load_peaks(), root=str(tmp_path),
        t_start=time.perf_counter())


# -- the feed and the window's arithmetic --------------------------------------

def test_pool_iterator_stops_at_the_deadline():
    ticks = iter([0.0, 1.0, 2.0, 3.0, 4.0])
    pool = [(np.zeros((2, 1)), np.zeros((2, 1)))] * 3
    it = fit_loop.pool_iterator(pool, deadline=2.5, clock=lambda: next(ticks))
    assert len(list(it)) == 3   # clock read 0, 1, 2; 3 is past the deadline
    assert it.yielded == 3 and it.batch_size() == 2


def test_pool_iterator_cycles_the_pool_and_counts():
    pool = [(np.full((1, 1), i), np.zeros((1, 1))) for i in range(3)]
    it = fit_loop.pool_iterator(pool, max_batches=7)
    assert [int(ds.features[0, 0]) for ds in it] == [0, 1, 2, 0, 1, 2, 0]


def test_the_rate_is_all_examples_over_the_whole_window():
    # 100 steps of 128, one of which stalled the window to 10 s: the
    # stall is in the rate, where a median of chunks would hide it
    read = bench_run.load_reader("train_examples_per_s_per_chip")
    facts = {"examples": 12800, "window_s": 10.0, "chips": 1}
    assert read(facts, None) == 1280.0
    assert read(dict(facts, chips=4), None) == 320.0


def test_pool_is_a_function_of_the_seed_alone():
    a = fit_loop.make_pool(2 ** 31 + 17, 2, 3, TINY_RESNET)
    b = fit_loop.make_pool(2 ** 31 + 17, 2, 3, TINY_RESNET)
    c = fit_loop.make_pool(5, 2, 3, TINY_RESNET)
    assert all(np.array_equal(x, u) and np.array_equal(y, v)
               for (x, y), (u, v) in zip(a, b))
    assert a[0][0].shape == c[0][0].shape == (3, 16, 16, 3)
    assert not np.array_equal(a[0][0], c[0][0])
    assert not np.array_equal(a[0][0], a[1][0])
    assert a[0][1].sum() == 3.0


# -- FLOP arithmetic -----------------------------------------------------------

@pytest.mark.parametrize("name,expected", [("resnet50", 24.535e9),
                                           ("vgg16", 92.82e9)])
def test_train_flops_per_example(name, expected):
    config = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                         name + ".json")))
    layers = fit_loop.load_reference(config).layers(config)
    got = flops.train_flops_per_example(layers)
    assert abs(got - expected) / expected < 1e-3
    assert got == 6 * sum(flops.layer_macs(l) for l in layers)


def test_flops_against_the_programs_own_accounting():
    """The program's analytic walk counts the same convs and dense layers
    (it may add elementwise work): within 5% at the tiny size."""
    from deeplearning4j_tpu.utils import flops as program_flops

    conf = fit_loop._resolve(TINY_RESNET["factory"])(
        **TINY_RESNET["factory_args"])
    theirs, _ = program_flops.analytic_step_flops_per_example(conf)
    mine = flops.train_flops_per_example(
        fit_loop.load_reference(TINY_RESNET).layers(TINY_RESNET))
    assert abs(theirs - mine) / mine < 0.05


def test_flops_refuse_an_unknown_layer_kind():
    with pytest.raises(ValueError):
        flops.layer_macs({"kind": "attention"})


# -- the trace reduction -------------------------------------------------------

def test_union_counts_overlap_once():
    assert trace_reduce.union_ns([(0, 10), (5, 15), (20, 30), (22, 25)]) == 25
    assert trace_reduce.union_ns([]) == 0.0


def test_op_family_and_custom_call_names():
    assert trace_reduce.op_family("%fusion.123 = bf16[...] fusion(...)") \
        == "fusion"
    assert trace_reduce.op_family("convolution.5.1") == "convolution"
    assert trace_reduce.op_family("jit_step(1234)") == "jit_step"
    assert trace_reduce.is_custom_call("custom-call.7")
    assert not trace_reduce.is_custom_call("fusion.7")
    # names as the chip's trace has them: the instruction's whole text
    kernel = ('%jvp__.71 = bf16[401408,256]{1,0:T(8,128)(2,1)} custom-call('
              'bf16[401408,256]{1,0} %pallas_call.388), '
              'custom_call_target="tpu_custom_call"')
    assert trace_reduce.is_custom_call(kernel)
    assert trace_reduce.op_family(kernel) == "jvp__"
    # an operand that is called %custom-call.10 does not make a fusion one
    assert not trace_reduce.is_custom_call(
        "%fusion.69 = (f32[], f32[3,3,512,512]{3,2,1,0}) fusion(f32[3,3,512,"
        "512]{3,2,1,0} %custom-call.10, f32[] %neg.36)")
    # nor is a custom call to another target one of the kernels
    assert not trace_reduce.is_custom_call(
        '%custom-call.5 = u32[128,28,512]{2,0,1} custom-call(bf16[1] %p), '
        'custom_call_target="SomethingElse"')


def test_reduce_rows_cuts_the_window_to_whole_program_runs():
    dev, ops, mods = "/device:TPU:0", trace_reduce.OPS_LINE, \
        trace_reduce.MODULES_LINE
    rows = [
        (dev, ops, "fusion.1", -50.0, 20.0),        # before the first run
        (dev, mods, "jit_step(1)", 0.0, 100.0),
        (dev, ops, "fusion.1", 0.0, 40.0),
        (dev, ops, "custom-call.2", 40.0, 30.0),
        (dev, mods, "jit_step(1)", 150.0, 100.0),
        (dev, ops, "fusion.1", 150.0, 40.0),
        (dev, ops, "custom-call.2", 190.0, 30.0),
        (dev, mods, "jit_small(2)", 120.0, 5.0),
        ("/host:CPU", ops, "ignored", 0.0, 1e9),
    ]
    rows = [r for r in rows if r[0] == dev]  # load_rows keeps device planes
    out = trace_reduce.reduce_rows(rows)
    assert out["main_module"] == "jit_step" and out["main_module_runs"] == 2
    assert out["window_s"] == pytest.approx(250e-9)
    assert out["busy_s"] == pytest.approx(140e-9)
    assert out["custom_call_s"] == pytest.approx(60e-9)
    assert out["device_ops"][0][0] == "fusion"
    assert out["idle_gaps"][0][1] == pytest.approx(80e-9)


def test_reduce_rows_drops_the_runs_that_the_trace_cut_short():
    dev, ops, mods = "/device:TPU:0", trace_reduce.OPS_LINE, \
        trace_reduce.MODULES_LINE
    rows = [(dev, mods, "jit_step(1)", 0.0, 6.0),      # the tail of a run
            (dev, ops, "fusion.1", 0.0, 6.0)]
    for i in range(3):                                  # three whole runs
        rows += [(dev, mods, "jit_step(1)", 10.0 + 100 * i, 90.0),
                 (dev, ops, "fusion.1", 10.0 + 100 * i, 80.0)]
    rows += [(dev, mods, "jit_step(1)", 310.0, 20.0),  # the head of one
             (dev, ops, "fusion.1", 310.0, 20.0)]
    out = trace_reduce.reduce_rows(rows)
    assert out["main_module_runs"] == 3
    assert out["window_s"] == pytest.approx(290e-9)
    assert out["busy_s"] == pytest.approx(240e-9)


@pytest.mark.parametrize("name,expected", [
    ("vgg16", {"busy_s": 0.173815769, "window_s": 0.173875954,
               "runs": 2, "kernels_s": 0.0, "top": "fusion"}),
    ("resnet50", {"busy_s": 0.108516695, "window_s": 0.108585588,
                  "runs": 1, "kernels_s": 0.035469959,
                  "top": "transpose_jvp___"}),
])
def test_reduce_rows_on_the_recorded_chip_trace(name, expected):
    """Rows recorded on the v5e (my chip run, PR 24): VGG16's first quarter
    second (two whole steps of 86.9 ms between two cut ones) and one whole
    ResNet-50 step of 108.6 ms with its 271 Pallas custom calls."""
    rows = [tuple(r) for r in json.load(open(os.path.join(
        ROOT, "benchmark", "fixtures", name + "_trace_rows.json")))]
    out = trace_reduce.reduce_rows(rows)
    assert out["devices"] == 1 and out["main_module"] == "jit_step"
    assert out["main_module_runs"] == expected["runs"]
    assert out["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-6)
    assert out["window_s"] == pytest.approx(expected["window_s"], rel=1e-6)
    assert out["custom_call_s"] == pytest.approx(expected["kernels_s"],
                                                 abs=1e-6)
    assert out["device_ops"][0][0] == expected["top"]
    assert 0.0 < out["busy_s"] <= out["window_s"]
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    # a kernel does not overlap another: the union is the plain sum
    plain_sum = sum(d for _, line, n, _, d in rows
                    if line == trace_reduce.OPS_LINE
                    and trace_reduce.is_custom_call(n)) * 1e-9
    assert out["custom_call_s"] == pytest.approx(plain_sum, rel=1e-6,
                                                 abs=1e-6)


def test_reduce_rows_with_nothing_on_a_device_reads_nothing():
    assert trace_reduce.reduce_rows([]) is None
    assert trace_reduce.reduce_trace(os.path.join(ROOT, "benchmark")) is None


def test_load_rows_reads_a_profile_with_jax_alone(tmp_path):
    """The reading path, on a trace taken here: the CPU has no device
    plane, so no row comes back, and nothing raises."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jax.block_until_ready(jax.jit(lambda a: a @ a)(jnp.ones((64, 64))))
    jax.profiler.stop_trace()
    path = trace_reduce.newest_xplane(str(tmp_path))
    assert path is not None and path.endswith(".xplane.pb")
    assert trace_reduce.load_rows(path) == []


# -- the look for a chip -------------------------------------------------------

class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("devices,chips,why", [
    ([_Dev("cpu", "cpu")], 1, "no TPU"),
    ([_Dev("tpu", "TPU v9 mega")], 1, "not in"),
    ([_Dev("tpu", "TPU v5 lite")] * 4, 1, "asks for 1"),
    ([_Dev("tpu", "TPU v5 lite")], 4, "asks for 4"),
])
def test_check_device_refuses(monkeypatch, devices, chips, why):
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: devices)
    with pytest.raises(bench_run.Refused, match=why):
        bench_run.check_device(chips, bench_run.load_peaks())


def test_check_device_takes_a_v5e(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_Dev("tpu", "TPU v5 lite")] * 4)
    assert bench_run.check_device(4, bench_run.load_peaks()) == dict(
        V5E, count=4)


def test_the_command_exits_non_zero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_an_unknown_workload_is_refused():
    with pytest.raises(bench_run.Refused):
        bench_run.load_cell(ROOT, "no_such_cell")


# -- BENCHMARK.json and the files found by its names ----------------------------

def test_benchmark_json_has_exactly_the_contracts_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = len(BENCH["workloads"])
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200, "run_seconds does not fit a full check of 24 cells"
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) \
        <= max(1, cells // 4)
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1
               for m in BENCH["end_to_end"])


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_by_name(cell):
    loaded = bench_run.load_cell(ROOT, cell)
    config = loaded["config"]
    assert loaded["traffic"]["generator"] == "fit_loop"
    assert config["reduced"] == [] and config["assumed"]
    limits = config["limits"]
    assert set(limits) == {"loss1_gap", "loss_gap", "grad_gap",
                           "grad_median_gap", "delta_gap",
                           "delta_median_gap"}
    # the control has to fail loss_gap, the planted faults the others
    assert all(limits[k] is not None and limits[k] > 0 for k in (
        "loss_gap", "grad_median_gap", "delta_gap", "delta_median_gap"))
    assert limits["delta_gap"] < 1.0   # a state left unchanged reads 1
    assert {m["name"] for m in loaded["end_to_end"]} >= {
        "setup_s", "train_examples_per_s_per_chip"}
    assert loaded["per_layer"]
    for m in loaded["end_to_end"] + loaded["per_layer"]:
        assert callable(bench_run.load_reader(m["name"]))


@pytest.mark.parametrize("entry", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entries_keep_to_the_contract(entry):
    assert NAME.match(entry["name"]) and UNIT.match(entry["unit"])
    assert entry["better"] in ("lower", "higher")
    assert entry["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    extra = set(entry) - {"name", "unit", "better", "source", "workloads",
                          "bound", "layer", "moves"}
    assert not extra
    if "bound" in entry:
        assert 0.01 <= entry["bound"] <= 0.1
        assert entry["source"] in ("host_clock", "device_trace")
    else:
        assert entry["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        assert "\n" not in entry["layer"] and len(entry["layer"]) <= 200
    for cell in entry.get("workloads", []):
        assert cell in {w["name"] for w in BENCH["workloads"]}


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"],
                         ids=lambda e: e["name"])
def test_config_and_cell_entries_keep_to_the_contract(entry):
    assert NAME.match(entry["name"])
    assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    if "file" in entry:
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        assert any(entry["file"].startswith(p + "/")
                   for p in BENCH["paths"])
        assert json.load(open(os.path.join(ROOT, entry["file"])))[
            "reduced"] == entry["reduced"]
    else:
        assert set(entry) == {"name", "config", "traffic", "chips", "why"}
        assert entry["chips"] in (1, 4)
        assert entry["config"] in {c["name"] for c in BENCH["configs"]}


def test_peaks_table_holds_the_v5e_with_its_source():
    raw = json.load(open(os.path.join(ROOT, "benchmark", "peaks.json")))
    assert "TPU v5e" in raw["_source"]
    assert bench_run.load_peaks() == {"TPU v5 lite": {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9}}


# -- the readers ---------------------------------------------------------------

FACTS = {"examples": 2560, "steps": 20, "window_s": 2.0, "chips": 1,
         "setup_s": 30.0, "flops_per_example": 24.535e9,
         "peak_flops_per_s": 197e12, "memory_peak_bytes": 2 ** 33,
         "trace_steps": 9,
         "registry_before": {"fit_data_wait_seconds:sum": 1.0,
                             "fit_dispatch_seconds:sum": 2.0},
         "registry_after": {"fit_data_wait_seconds:sum": 1.1,
                            "fit_dispatch_seconds:sum": 2.4}}
TRACE = {"busy_s": 0.9, "window_s": 1.0, "custom_call_s": 0.2,
         "main_module_runs": 10}


@pytest.mark.parametrize("name,expected", [
    ("train_examples_per_s_per_chip", 1280.0),
    ("setup_s", 30.0),
    ("data_wait_ms.train", 5.0),
    ("dispatch_ms.train", 20.0),
    ("step_mfu_pct.train", 100.0 * 24.535e9 * 1280.0 / 197e12),
    ("device_step_ms.train", 90.0),
    ("pallas_ms.train", 20.0),
    ("device_idle_pct.train", 10.0),
    ("peak_hbm_gib.train", 8.0),
])
def test_reader_arithmetic(name, expected):
    assert bench_run.load_reader(name)(FACTS, TRACE) == pytest.approx(expected)


@pytest.mark.parametrize("name", ["device_step_ms.train", "pallas_ms.train",
                                  "device_idle_pct.train"])
def test_a_trace_reader_with_nothing_to_read_returns_nothing(name):
    read = bench_run.load_reader(name)
    assert read(FACTS, None) is None
    assert bench_run.load_reader("pallas_ms.train")(
        FACTS, dict(TRACE, custom_call_s=0.0)) is None


def test_counter_readers_with_nothing_to_read_return_nothing():
    empty = dict(FACTS, steps=0, examples=0, memory_peak_bytes=0)
    for name in ("data_wait_ms.train", "dispatch_ms.train",
                 "step_mfu_pct.train", "peak_hbm_gib.train"):
        assert bench_run.load_reader(name)(empty, None) is None


# -- the comparison that decides `correct` --------------------------------------

def _readings(scale=1.0, loss=2.0):
    return {"losses": [loss, loss, loss],
            "grad_norms": {"a/W": 1.0 * scale, "b/W": 2.0 * scale,
                           "c/b": 1e-9},
            "delta_norms": {"a/W": 0.1 * scale, "b/W": 0.2 * scale,
                            "c/b": 5.0}}


def test_gaps_of_equal_readings_are_nought():
    g = compare.first_step_gaps(_readings(), _readings())
    assert (g["loss_gap"], g["grad_gap"], g["delta_gap"]) == (0.0, 0.0, 0.0)
    # c/b's gradient is nought to rounding: left out of the change
    assert g["leaves_left_out"] == 1


def test_a_state_left_unchanged_reads_one():
    still = dict(_readings(), delta_norms={"a/W": 0.0, "b/W": 0.0,
                                           "c/b": 0.0})
    assert compare.first_step_gaps(still, _readings())["delta_gap"] == 1.0


def test_a_small_leaf_is_measured_against_the_median_leaf():
    got = _readings()
    got["grad_norms"] = dict(got["grad_norms"], **{"c/b": 0.01})
    # |0.01 - 1e-9| against the median leaf's 1.0, not against 1e-9
    assert compare.first_step_gaps(got, _readings())["grad_gap"] == \
        pytest.approx(0.01)


def test_a_loss_that_is_not_finite_is_an_infinite_gap():
    assert compare.first_step_gaps(_readings(loss=math.nan),
                                   _readings())["loss_gap"] == math.inf


def test_judge_holds_each_number_to_its_own_limit():
    v = compare.judge({"a": 0.1, "b": 0.5, "c": 7.0},
                      {"a": 0.2, "b": 0.4, "c": None})
    assert not v["correct"]
    assert v["checks"]["b"] == {"value": 0.5, "limit": 0.4}
    assert compare.judge({"a": 0.1, "c": 7.0}, {"a": 0.2, "c": None})[
        "correct"]
    assert not compare.judge({"a": math.nan}, {"a": 0.2})["correct"]


def test_mismatched_leaves_are_an_error():
    other = _readings()
    other["grad_norms"].pop("c/b")
    with pytest.raises(ValueError):
        compare.first_step_gaps(other, _readings())


# -- whole runs at tiny sizes ---------------------------------------------------

@pytest.mark.parametrize("config", [TINY_RESNET, TINY_VGG],
                         ids=lambda c: c["name"])
def test_a_run_of_each_engine_is_correct_against_its_reference(config,
                                                               tmp_path):
    out = _run(config, tmp_path)
    line, info = out["line"], out["info"]
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert line["failed"] == 0 and line["attempted"] == info["steps"] > 0
    assert set(line["metrics"]) == {"train_examples_per_s_per_chip",
                                    "setup_s"}
    rate = line["metrics"]["train_examples_per_s_per_chip"]["value"]
    assert rate == pytest.approx(info["examples"] / info["window_s"])
    assert info["examples"] == info["steps"] * 8
    assert info["compiles_in_window"] == 0
    assert line["device"]["platform"] == "tpu"  # as handed in; no look here
    json.dumps(bench_run.jsonable(line), allow_nan=False)


def test_a_traced_run_reports_the_per_layer_metrics_it_can_read(tmp_path):
    out = _run(TINY_RESNET, tmp_path, seconds=1.0, trace=True)
    line = out["line"]
    assert line["correct"]
    # the CPU trace has no device plane: the trace's readers read nothing
    # and are left out; the counters' and the clock's are there
    assert set(line["metrics"]) == {"data_wait_ms.train", "dispatch_ms.train",
                                    "step_mfu_pct.train"}
    assert out["info"]["trace_steps"] > 0


def test_the_same_harness_drives_every_device_the_process_holds(
        tmp_path, monkeypatch):
    """`chips` > 1 needs no harness change: with fit()'s auto mesh on, the
    traffic spans the 8 virtual devices, the global batch is 8 x 2, and the
    sharded step still agrees with the one-device reference."""
    import jax

    monkeypatch.setenv("DL4J_AUTO_MESH", "1")
    chips = len(jax.devices())
    assert chips > 1
    out = _run(TINY_RESNET, tmp_path, chips=chips,
               traffic=dict(TINY_TRAFFIC, batch_per_chip=2))
    line, info = out["line"], out["info"]
    assert line["correct"], line["checks"]
    assert info["examples"] == info["steps"] * 2 * chips
    assert line["metrics"]["train_examples_per_s_per_chip"]["value"] == \
        pytest.approx(info["examples"] / info["window_s"] / chips)


# -- faults the cell can have, planted under the timed path ---------------------

def _break_step_unchanged(monkeypatch):
    """A step that returns its state unchanged."""
    from deeplearning4j_tpu.nn.compgraph import ComputationGraph

    real = ComputationGraph._fit_step

    def fit_step(self, *a, **kw):
        params, upd = self.params_list, self.upd_state
        import jax

        keep = jax.tree_util.tree_map(lambda x: x.copy(), (params, upd))
        out = real(self, *a, **kw)
        self.params_list, self.upd_state = keep
        return out

    monkeypatch.setattr(ComputationGraph, "_fit_step", fit_step)


def _break_half_batch(monkeypatch):
    """Half of the batch left out, the mean taken over the rest."""
    from deeplearning4j_tpu.nn.compgraph import ComputationGraph

    real = ComputationGraph._fit_step

    def fit_step(self, xs, ys, f_masks, l_masks, **kw):
        half = xs[0].shape[0] // 2
        return real(self, [x[:half] for x in xs], [y[:half] for y in ys],
                    f_masks, l_masks, **kw)

    monkeypatch.setattr(ComputationGraph, "_fit_step", fit_step)


@pytest.mark.parametrize("plant", [_break_step_unchanged, _break_half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_a_fault_under_the_timed_path_is_not_correct(plant, tmp_path,
                                                     monkeypatch):
    plant(monkeypatch)
    line = _run(TINY_RESNET, tmp_path)["line"]
    assert not line["correct"], line["checks"]
    failed = [k for k, c in line["checks"].items()
              if c["limit"] is not None and not c["value"] <= c["limit"]]
    assert set(failed) & {"loss_gap", "grad_gap", "delta_gap"}


def test_a_hidden_fallback_is_not_correct(tmp_path, monkeypatch):
    real = fit_loop.run

    def run(ctx):
        facts = real(ctx)
        facts["hidden_fallbacks"] = 1
        return facts

    monkeypatch.setattr(fit_loop, "run", run)
    line = _run(TINY_RESNET, tmp_path)["line"]
    assert not line["correct"]
    assert line["checks"]["hidden_fallbacks"] == {"value": 1.0, "limit": 0.0}


# -- the control: the reference in the nearest precision below -------------------

SMALL_VGG = dict(TINY_VGG, conv_blocks=[[16, 2], [32, 2], [64, 2]],
                 dense_widths=[128, 128])


@pytest.fixture(scope="module")
def small_readings():
    pool = fit_loop.make_pool(4, 3, 16, SMALL_VGG)
    out = {p: fit_loop.first_steps_of_reference(SMALL_VGG, 4, pool, 3,
                                                precision=p)
           for p in ("f32", "bf16", "fp8")}
    out["half"] = fit_loop.first_steps_of_reference(SMALL_VGG, 4, pool, 3,
                                                    rows=slice(0, 8))
    return out


def test_the_fp8_control_reads_well_above_the_stated_precision(
        small_readings):
    """The control at a size a test run can hold: the cell's reference in
    fp8, put in the program's place, reads several times what the same
    reference in the stated bf16 reads, on the number the control has to
    fail on the chip (`loss_gap`) and on the median leaf's gradient. The
    chip's readings at the cell's own size are in PERF.md."""
    stated = compare.first_step_gaps(small_readings["bf16"],
                                     small_readings["f32"])
    control = compare.first_step_gaps(small_readings["fp8"],
                                      small_readings["f32"])
    for name in ("loss_gap", "grad_median_gap"):
        assert control[name] > 3 * stated[name], (name, stated, control)


def test_the_half_batch_fault_reads_well_above_the_stated_precision(
        small_readings):
    stated = compare.first_step_gaps(small_readings["bf16"],
                                     small_readings["f32"])
    got = compare.first_step_gaps(small_readings["half"],
                                  small_readings["f32"])
    for name in ("loss_gap", "grad_median_gap", "delta_median_gap"):
        assert got[name] > 10 * stated[name], (name, stated, got)

"""The cell `nemotron3_nano_train_b4_s4096` and what it brought under
`benchmark/`: the configuration and its cut, the reference's layer list, the
readers of the cell's own per-layer metrics with their `BENCHMARK.json`
entries, and the roofline arithmetic. CPU only; nothing here loads the TPU
library."""

from __future__ import annotations

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops, rooflines, run as bench_run  # noqa: E402
from benchmark import scope_reduce  # noqa: E402
from benchmark.reference import nemotron_h as ref  # noqa: E402
from benchmark.traffic import fit_loop  # noqa: E402

CELL = "nemotron3_nano_train_b4_s4096"
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "nemotron3_nano_30b_a3b.json")))
TRAFFIC = json.load(open(os.path.join(
    ROOT, "benchmark", "traffic", "train_fit_seq4096_b4.json")))
# the cell's own per-layer metrics (readers since PR 28, entries since PR 34)
OWN = ["ssm_ms.train", "experts_ms.train", "attention_ms.train",
       "head_loss_ms.train", "ssm_scan_roofline_pct.train",
       "experts_roofline_pct.train", "expert_load_max_over_mean.train"]
FIXTURE = os.path.join(ROOT, "benchmark", "fixtures",
                       "nemotron_h_scoped_trace.json")
READINGS = json.load(open(os.path.join(
    ROOT, "benchmark", "fixtures", "nemotron_h_control_readings.json")))
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
       "hbm_bytes": 16e9}


def _harness():
    """`tests/benchmark_harness/test_harness.py` as a module: `check_cell`
    and the tiny traffic live there."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_harness_tests", os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "test_harness.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# -- the configuration and its cut ---------------------------------------------------

def check_the_cell_by_files_alone(root):
    h = _harness()
    loaded = h.check_cell(root, CELL)
    assert loaded["cell"]["chips"] == 1
    assert loaded["traffic"]["rows_block"] == 1
    # the six per-layer metrics that list no cells are read here too, and
    # beside the cell's own whatever else lists it
    h.check_cell_metrics(root, CELL, own=OWN)


def test_the_cell_keeps_to_the_contract_by_files_alone():
    check_the_cell_by_files_alone(ROOT)


def test_no_width_is_cut_and_the_cut_is_stated():
    widths = {"hidden_size": 2688, "mamba_num_heads": 64,
              "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 8,
              "conv_kernel": 4, "chunk_size": 128,
              "num_attention_heads": 32, "num_key_value_heads": 2,
              "head_dim": 128, "moe_intermediate_size": 1856,
              "moe_shared_expert_intermediate_size": 3712,
              "num_experts_per_tok": 6, "routed_scaling_factor": 2.5,
              "norm_topk_prob": True, "layer_norm_epsilon": 1e-5,
              "n_group": 1, "topk_group": 1, "mlp_hidden_act": "relu2"}
    for key, value in widths.items():
        assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["num_hidden_layers",
                                 "hybrid_override_pattern",
                                 "n_routed_experts", "vocab_size"]
    assert CONFIG["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131072, "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"}
    assert (CONFIG["num_hidden_layers"], CONFIG["hybrid_override_pattern"],
            CONFIG["n_routed_experts"], CONFIG["vocab_size"]) == (
        9, "MEMEM*EME", 8, 16384)
    # the router keeps its published width; the chip holds experts 0..7
    assert CONFIG["router_width"] == 128
    assert CONFIG["experts_held"] == list(range(8))
    assert CONFIG["deployment"]["chips_sharing_a_layer"] == 16
    assert CONFIG["precision"] == "bf16"
    assert CONFIG["updater"] == {"name": "adam", "learning_rate": 1e-4,
                                 "beta1": 0.9, "beta2": 0.95,
                                 "epsilon": 1e-8}
    assert len(CONFIG["assumed"]) >= 6 and len(CONFIG["guarantees"]) >= 3
    # what the factory is given is what the file states
    args = CONFIG["factory_args"]
    for key in ("hybrid_override_pattern", "hidden_size", "vocab_size",
                "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
                "n_groups", "conv_kernel", "chunk_size",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "n_routed_experts", "router_width", "experts_held",
                "num_experts_per_tok", "moe_intermediate_size",
                "moe_shared_expert_intermediate_size",
                "routed_scaling_factor", "layer_norm_epsilon",
                "mlp_hidden_act", "time_step_min", "time_step_max",
                "time_step_floor"):
        assert args[key] == CONFIG[key], key
    assert args["precision"] == CONFIG["precision"]
    assert (args["learning_rate"], args["beta1"], args["beta2"],
            args["epsilon"]) == (1e-4, 0.9, 0.95, 1e-8)
    assert args["seq_len"] == TRAFFIC["seq_len"] == 4096
    assert TRAFFIC["batch_per_chip"] == 4


def test_the_limits_compare_the_first_loss_and_stay_under_a_skipped_step():
    limits = CONFIG["limits"]
    for name in ("loss1_gap", "loss_gap", "grad_median_gap", "delta_gap",
                 "delta_median_gap"):
        assert limits[name] is not None and limits[name] > 0, name
    assert limits["delta_gap"] < 1.0


def _readings(key):
    return [dict(zip(READINGS["numbers"], row[1:]), seed=row[0])
            for row in READINGS[key]]


def test_the_limits_pass_every_recorded_sound_run_and_fail_every_control():
    """The chip readings the limits were set from (my chip run, PR 28),
    judged as `tools/calibrate_controls.py` judges them on the chip: no
    control or planted fault is let through, every sound run passes, and
    each compared limit has room on both sides of it."""
    from benchmark.tools import calibrate_controls as cc

    limits = CONFIG["limits"]
    wrong = {key: _readings(key) for key in cc.WRONG}
    assert cc.let_through(wrong, limits) == []
    assert all("grad_median_gap" in row["fails"]
               for row in wrong["control_fp8"])
    assert all("delta_gap" in row["fails"]
               for key in ("fault_half_batch", "fault_skipped_step")
               for row in wrong[key])
    sound = _readings("sound")
    assert len(sound) >= 17
    for row in sound:
        assert cc.judged(row, limits) == [], row["seed"]
    # the reading above each limit: the control's smallest where the control
    # separates, else the smallest of the planted faults that do
    above = {"loss1_gap": "fault_half_batch", "loss_gap": "control_fp8",
             "grad_median_gap": "control_fp8", "delta_gap": "fault_half_batch",
             "delta_median_gap": "control_fp8"}
    for name, key in above.items():
        largest_sound = max(row[name] for row in sound)
        smallest_wrong = min(row[name] for row in wrong[key])
        assert largest_sound * 1.4 <= limits[name] <= smallest_wrong / 1.4, \
            (name, largest_sound, limits[name], smallest_wrong)


def test_the_factory_builds_the_graph_the_reference_names():
    """At the real widths, without a weight: the vertex and parameter names
    and shapes the reference's `init_params` would hand over."""
    import jax

    from deeplearning4j_tpu.nn.layers.registry import init_layer_params

    conf = fit_loop._resolve(CONFIG["factory"])(**CONFIG["factory_args"])
    net = fit_loop._resolve(CONFIG["engine"])(conf)
    theirs = jax.eval_shape(lambda: ref.init_params(1, CONFIG))
    key = jax.random.PRNGKey(0)
    mine = jax.eval_shape(lambda: {
        name: init_layer_params(key, lc, np.float32)
        for name, lc in zip(net.layer_vertex_names, net._layer_confs)})
    assert set(mine) == set(theirs)
    total = 0
    for name, leaves in mine.items():
        assert set(leaves) == set(theirs[name]), name
        for leaf, a in leaves.items():
            assert a.shape == theirs[name][leaf].shape, (name, leaf)
            total += int(np.prod(a.shape))
    # 10.67 GB at 16 bytes a parameter: two thirds of the chip
    assert total == 666_962_944
    assert conf.recompute is not None and len(conf.recompute) == 9


# -- the reference's layer list -----------------------------------------------------

def test_layers_total_the_hand_count():
    layers = ref.layers(CONFIG)
    macs = sum(flops.layer_macs(l, 1) for l in layers
               if l["kind"] != "attention")
    attention = [l for l in layers if l["kind"] == "attention"]
    assert len(attention) == 1
    # a token of a 4,096-token example meets 4097 / 2 keys on the average
    per_token = macs + flops.layer_macs(attention[0], 4096) // 4096
    assert per_token == 339_505_152
    by_key = {}
    for l in layers:
        by_key.setdefault(l["key"], 0)
        by_key[l["key"]] += flops.layer_macs(l, 4096) // 4096
    mixers = sum(v for k, v in by_key.items() if k in (
        "b0_mixer", "b2_mixer", "b4_mixer", "b7_mixer"))
    experts = sum(v for k, v in by_key.items() if k in (
        "b1_mixer", "b3_mixer", "b6_mixer", "b8_mixer"))
    assert mixers == 4 * 39_780_352 and experts == 4 * 24_041_472
    assert by_key["b5_mixer"] == 40_177_664 and by_key["head"] == 44_040_192
    assert round(100 * mixers / per_token, 1) == 46.9
    assert round(100 * experts / per_token, 1) == 28.3
    total = flops.train_flops_per_example(layers, 4096)
    assert total == 6 * 339_505_152 * 4096 == 8_343_678_615_552
    kinds = {l["kind"] for l in layers}
    assert kinds == {"embedding", "dense", "depthwise_conv1d", "scan",
                     "attention", "experts"}
    routed = [l for l in layers if l["kind"] == "experts"]
    assert len(routed) == 8 and all(
        (l["experts_per_token"], l["held"], l["routed"]) == (6, 8, 128)
        for l in routed)


# -- a tiny cell through the harness's own run ------------------------------------------

TINY = {
    "name": "tiny_nemotron_h",
    "factory": "deeplearning4j_tpu.models.nemotron_h:tiny_nemotron_h_conf",
    "factory_args": {"precision": "f32", "learning_rate": 1e-3},
    "engine": "deeplearning4j_tpu.nn.compgraph:ComputationGraph",
    "reference": "nemotron_h",
    "num_hidden_layers": 4, "hybrid_override_pattern": "ME*M",
    "hidden_size": 64, "vocab_size": 128,
    "mamba_num_heads": 8, "mamba_head_dim": 16, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "chunk_size": 8,
    "time_step_min": 1e-3, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "n_routed_experts": 8, "router_width": 16,
    "experts_held": list(range(8)), "num_experts_per_tok": 3,
    "moe_intermediate_size": 48, "moe_shared_expert_intermediate_size": 96,
    "routed_scaling_factor": 2.5, "layer_norm_epsilon": 1e-5,
    "precision": "f32",
    "feed": {"kind": "tokens", "vocab": 128},
    "updater": {"name": "adam", "learning_rate": 1e-3, "beta1": 0.9,
                "beta2": 0.95, "epsilon": 1e-8},
    # float32 on both sides here: the gaps are the order of the sums
    "limits": {"loss1_gap": 1e-5, "loss_gap": 1e-5, "grad_gap": 1e-3,
               "grad_median_gap": 1e-4, "delta_gap": 0.3,
               "delta_median_gap": 1e-3},
}


def test_a_tiny_cell_runs_and_is_correct(tmp_path):
    """fit() on int32 ids through the harness's own `run_cell`, the first
    three steps against the reference in blocks of two rows, the books read
    after the window; an untraced run reads no per-layer metric."""
    h = _harness()
    traffic = dict(h.TINY_TRAFFIC, batch_per_chip=4, seq_len=32,
                   rows_block=2)
    loaded = h._loaded(TINY, traffic=traffic)
    import time

    out = bench_run.run_cell(
        loaded, seed=2 ** 31 + 5, seconds=0.4, trace=False, device=h.V5E,
        peaks=bench_run.load_peaks(), root=str(tmp_path),
        t_start=time.perf_counter())
    line, info = out["line"], out["info"]
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert info["examples"] == 4 * info["steps"]
    assert info["flops_per_example"] == flops.train_flops_per_example(
        ref.layers(TINY), 32)
    assert set(line["metrics"]) == {"train_examples_per_s_per_chip",
                                    "setup_s"}


def test_a_planted_fault_fails_the_tiny_cell():
    """Half of each batch left out is not the whole batch: `compare` reads
    it on the gradient's norm."""
    from benchmark import compare

    pool = fit_loop.make_pool(9, 3, 4, TINY, 32)
    whole = fit_loop.first_steps_of_reference(TINY, 9, pool, 3)
    half = fit_loop.first_steps_of_reference(TINY, 9, pool, 3,
                                             rows=slice(0, 2))
    blocks = fit_loop.first_steps_of_reference(TINY, 9, pool, 3,
                                               rows_block=1)
    gaps = compare.first_step_gaps(half, whole)
    assert gaps["grad_median_gap"] > 0.05
    same = compare.first_step_gaps(blocks, whole)
    assert same["loss_gap"] < 1e-6 and same["grad_median_gap"] < 1e-5


# -- the cell's own readers and their entries -----------------------------------------

def check_the_cells_entries(bench):
    """The seven entries follow PR 25's eight, in the order their readers
    came in (PR 28), each listing this cell first."""
    h = _harness()
    names = [m["name"] for m in bench["per_layer"]]
    assert names[14:21] == OWN
    for m in bench["per_layer"][14:21]:
        assert h.NAME.match(m["name"]) and h.UNIT.match(m["unit"])
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["workloads"][:1] == [CELL]
        assert m["moves"] == "train_examples_per_s_per_chip"
        assert callable(bench_run.load_reader(m["name"]))
        # a share of a roofline is named for it and is a percentage
        assert ("roofline" in m["name"]) == (m["unit"] == "%")


def test_every_entry_of_the_cell_has_a_reader_and_keeps_to_the_contract():
    check_the_cells_entries(_harness().BENCH)


@pytest.mark.parametrize("name", OWN)
def test_a_scoped_reader_without_a_trace_reads_nothing(name):
    facts = {"registry_after": {}, "trace_dir": None,
             "peak_flops_per_s": 197e12}
    assert bench_run.load_reader(name)(facts, None) is None


def test_the_load_reader_reads_the_registrys_gauge():
    read = bench_run.load_reader("expert_load_max_over_mean.train")
    assert read({"registry_after": {"experts_load_max_over_mean": 1.25}},
                None) == 1.25


def test_parts_of_the_step_on_made_up_rows():
    dev, ops, mods = "/device:TPU:0", "XLA Ops", "XLA Modules"
    scan = "jit(step)/jvp(jvp())/checkpoint/Lb0_mixer_mamba2/ssd_scan/dot"
    back = ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation"
            "/Lb0_mixer_mamba2/ssd_scan/while/body/dot_general:")
    other = "jit(step)/jvp(jvp())/checkpoint/Lb0_mixer_mamba2/dot_general"
    expert = "jit(step)/jvp(jvp())/checkpoint/Lb1_mixer_sparseexperts/router"
    rows = []
    for i in range(4):                      # four runs: two are counted
        t = 1000 * i
        rows += [(dev, mods, "jit_step(1)", t, 900, ""),
                 (dev, ops, "fusion.1", t, 100, scan),
                 # a loop's event holds its body's: never summed itself
                 (dev, ops, "%while.3 = (...) while(...)", t + 100, 200, back),
                 (dev, ops, "fusion.2", t + 100, 200, back),
                 (dev, ops, "fusion.3", t + 300, 400, other),
                 (dev, ops, "copy.9", t + 700, 50, ""),
                 (dev, ops, "fusion.4", t + 750, 100, expert)]
    scan_ns = scope_reduce.part_ns(rows, scope_reduce.of_component("ssd_scan"))
    assert scan_ns == (600, 2)
    assert scope_reduce.part_ns(
        rows, scope_reduce.of_layer_kinds("mamba2")) == (1400, 2)
    assert scope_reduce.part_ns(
        rows, scope_reduce.of_layer_kinds("sparseexperts")) == (200, 2)
    assert scope_reduce.part_ns(
        rows, scope_reduce.of_layer_kinds("mamba2", "sparseexperts")) \
        == (1600, 2)
    assert scope_reduce.part_ns(
        rows, scope_reduce.of_layer_kinds("rnnoutput")) is None
    assert scope_reduce.part_ns(
        rows, scope_reduce.of_component("experts")) is None
    # whole components only
    assert scope_reduce.part_ns(rows, scope_reduce.of_component("scan")) \
        is None
    assert scope_reduce.part_ns([], scope_reduce.of_component("ssd_scan")) \
        is None
    assert scope_reduce.ms_per_step({}, None, lambda scope: True) is None


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="the recorded step is written by the chip run")
def test_the_readers_on_the_recorded_chip_step():
    """Three whole steps of the cell on the v5e (my chip run, PR 28): every
    trace reader of the cell finds its layers and scopes, the parts do not
    pass the whole, and both roofline shares lie under 100%."""
    from benchmark import trace_reduce
    from benchmark.tools import span_dump

    doc = json.load(open(FIXTURE))
    rows = span_dump.expand(doc)
    whole = trace_reduce.reduce_rows([r[:5] for r in rows])
    assert whole["main_module"] == "jit_step"
    assert whole["main_module_runs"] == 3
    step_ns = whole["busy_s"] * 1e9 / 3
    parts = {}
    for kind in ("mamba2", "sparseexperts", "groupedqueryattention",
                 "rnnoutput"):
        ns, steps = scope_reduce.part_ns(rows,
                                         scope_reduce.of_layer_kinds(kind))
        assert steps == 3 and ns > 0, kind
        parts[kind] = ns / steps
    assert sum(parts.values()) <= step_ns
    assert sum(parts.values()) > 0.8 * step_ns
    # the mixers take the largest share, as their FLOPs do
    assert parts["mamba2"] == max(parts.values())
    recorded = doc["recorded"]["metrics"]
    for name, kind in (("ssm_ms.train", "mamba2"),
                       ("experts_ms.train", "sparseexperts"),
                       ("attention_ms.train", "groupedqueryattention"),
                       ("head_loss_ms.train", "rnnoutput")):
        # the cut holds three of the slice's steps: the whole slice's
        # reading to within a hundredth
        assert parts[kind] * 1e-6 == pytest.approx(recorded[name]["value"],
                                                   rel=0.01)
    for part, work, name in (
            ("ssd_scan", rooflines.ssd_scan(CONFIG, TRAFFIC),
             "ssm_scan_roofline_pct.train"),
            ("experts", rooflines.experts(CONFIG, TRAFFIC),
             "experts_roofline_pct.train")):
        ns, steps = scope_reduce.part_ns(rows,
                                         scope_reduce.of_component(part))
        share = rooflines.share(work, ns / steps * 1e-6, V5E)
        assert 0.0 < share <= 100.0, (part, share)
        assert share == pytest.approx(recorded[name]["value"], rel=0.01)
    scan_ns, _ = scope_reduce.part_ns(rows,
                                      scope_reduce.of_component("ssd_scan"))
    assert scan_ns / 3 < parts["mamba2"]
    assert recorded["expert_load_max_over_mean.train"]["value"] >= 1.0


# -- the roofline arithmetic --------------------------------------------------------------

def test_the_scans_operations_and_bytes_against_a_hand_count():
    work = rooflines.ssd_scan(CONFIG, TRAFFIC)
    tokens, mixers, passes = 4 * 4096, 4, 3
    # the state's update and its read-out: 2 x 64 x 64 x 128 a position
    assert work["flops"] == 2 * (2 * 64 * 64 * 128) * tokens * mixers * passes
    # x and y are 4096 wide, B and C 1024 each, in bf16; dt 64 float32
    per_token = (4096 + 1024 + 1024 + 4096) * 2 + 64 * 4
    assert work["bytes"] == per_token * tokens * mixers * passes
    # 101 FLOP a byte: under the v5e's ridge of 240, so the bytes bound it
    assert work["bytes"] / 819e9 > work["flops"] / 197e12
    assert rooflines.share(work, 10.0, V5E) == pytest.approx(
        100.0 * (work["bytes"] / 819e9) / 10e-3)


def test_the_experts_operations_and_bytes_against_a_hand_count():
    work = rooflines.experts(CONFIG, TRAFFIC)
    rows = 4 * 4096 * 6 * 8 // 128            # 6,144 assignments a layer
    assert rows == 6144
    layers, passes = 4, 3
    assert work["flops"] == 2 * rows * (2 * 2688 * 1856) * layers * passes
    weights = 8 * 2 * 2688 * 1856 * 2
    acts = rows * (2 * 2688 + 2 * 1856) * 2
    assert work["bytes"] == (weights + acts) * layers * passes
    assert work["flops"] / 197e12 > work["bytes"] / 819e9   # the MXU bounds
    assert rooflines.share(work, None, V5E) is None
    assert rooflines.share(work, 5.0, None) is None


def test_cell_of_run_finds_the_files_from_the_trace_directory(tmp_path):
    facts = {"trace_dir": os.path.join(ROOT, ".bench_trace", CELL),
             "peak_flops_per_s": 197e12}
    cell = rooflines.cell_of_run(facts)
    assert cell["config"]["hidden_size"] == 2688
    assert cell["traffic"]["seq_len"] == 4096
    assert cell["peaks"]["hbm_bytes_per_s"] == 819e9
    assert rooflines.cell_of_run({"trace_dir": None}) is None
    assert rooflines.cell_of_run(
        {"trace_dir": str(tmp_path / ".bench_trace" / "x")}) is None
    # a cell without such layers: the reader reads nothing
    vgg = {"trace_dir": os.path.join(ROOT, ".bench_trace",
                                     "vgg16_train_b128"),
           "peak_flops_per_s": 197e12}
    for name in ("ssm_scan_roofline_pct.train",
                 "experts_roofline_pct.train"):
        assert bench_run.load_reader(name)(vgg, {"busy_s": 1.0}) is None

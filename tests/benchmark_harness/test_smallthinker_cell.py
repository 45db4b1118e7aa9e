"""The cell `smallthinker_21b_train_b2_s8192` and what it brought under
`benchmark/`: the configuration and its cut, the reference's layer list with
the band's FLOP entry, the three new readers, the roofline arithmetic of
`rooflines_decoder.py` and the cell's per-layer entries in `BENCHMARK.json`.
CPU only; nothing here loads the TPU library."""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops, rooflines_decoder, run as bench_run  # noqa: E402
from benchmark import scope_reduce  # noqa: E402
from benchmark.reference import smallthinker as ref  # noqa: E402
from benchmark.traffic import fit_loop  # noqa: E402

CELL = "smallthinker_21b_train_b2_s8192"
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "smallthinker_21b_a3b.json")))
TRAFFIC = json.load(open(os.path.join(
    ROOT, "benchmark", "traffic", "train_fit_seq8192_b2.json")))
READINGS = json.load(open(os.path.join(
    ROOT, "benchmark", "fixtures", "smallthinker_control_readings.json")))
NEW = ["window_attention_ms.train", "window_attention_roofline_pct.train",
       "gated_experts_roofline_pct.train"]
# readers that came with the Nemotron cell and are generic over layer kinds
REUSED = ["attention_ms.train", "experts_ms.train", "head_loss_ms.train",
          "expert_load_max_over_mean.train"]
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
       "hbm_bytes": 16e9}
# the catalog's entry (model-configs guide, architectures.jsonl), every number
SOURCE = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936}


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
    # the six per-layer metrics that list no cells, the cell's seven, and
    # whatever else lists it
    h.check_cell_metrics(root, CELL, own=NEW + REUSED)


def test_the_cell_keeps_to_the_contract_by_files_alone():
    check_the_cell_by_files_alone(ROOT)


def check_the_first_three_configurations_and_cells(bench):
    """The three configurations and cells that stood after PR 32, where
    they stood; a later PR's lie behind them."""
    assert [c["name"] for c in bench["configs"][:3]] == [
        "vgg16", "nemotron3_nano_30b_a3b", "smallthinker_21b_a3b"]
    assert [w["name"] for w in bench["workloads"][:3]] == [
        "vgg16_train_b128", "nemotron3_nano_train_b4_s4096", CELL]
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert cell == dict(cell, config="smallthinker_21b_a3b",
                        traffic="train_fit_seq8192_b2", chips=1)
    assert bench["run_seconds"] == 10


def test_benchmark_json_gained_one_configuration_and_one_cell():
    check_the_first_three_configurations_and_cells(
        json.load(open(os.path.join(ROOT, "BENCHMARK.json"))))


def test_no_width_is_cut_and_the_cut_is_stated():
    reduced = ["num_hidden_layers", "sliding_window_layout", "rope_layout",
               "moe_num_primary_experts", "vocab_size"]
    assert CONFIG["reduced"] == reduced
    for key, value in SOURCE.items():
        if key in reduced:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key
    assert sorted(CONFIG["published"]) == sorted(reduced)
    assert (CONFIG["num_hidden_layers"], CONFIG["sliding_window_layout"],
            CONFIG["rope_layout"], CONFIG["moe_num_primary_experts"],
            CONFIG["vocab_size"]) == (4, [0, 1, 1, 1], [0, 1, 1, 1], 8, 18992)
    # one whole period, as published: the first four of the 52 layers
    assert SOURCE["sliding_window_layout"][:4] == [0, 1, 1, 1]
    assert 8 * CONFIG["vocab_size"] == SOURCE["vocab_size"]
    # the router keeps its published width; the chip holds experts 0..7
    assert CONFIG["router_width"] == 64
    assert CONFIG["experts_held"] == list(range(8))
    assert CONFIG["deployment"]["chips_sharing_a_layer"] == 8
    assert CONFIG["precision"] == "bf16"
    assert CONFIG["updater"] == {"name": "adam", "learning_rate": 1e-4,
                                 "beta1": 0.9, "beta2": 0.95,
                                 "epsilon": 1e-8}
    assert len(CONFIG["assumed"]) >= 8 and len(CONFIG["guarantees"]) >= 4
    # what the factory is given is what the file states
    args = CONFIG["factory_args"]
    for key in ("num_hidden_layers", "sliding_window_layout", "rope_layout",
                "hidden_size", "vocab_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "sliding_window_size",
                "rope_theta", "moe_num_primary_experts", "router_width",
                "experts_held", "moe_num_active_primary_experts",
                "moe_ffn_hidden_size", "moe_primary_router_apply_softmax",
                "norm_topk_prob", "rms_norm_eps"):
        assert args[key] == CONFIG[key], key
    assert args["precision"] == CONFIG["precision"]
    assert (args["learning_rate"], args["beta1"], args["beta2"],
            args["epsilon"]) == (1e-4, 0.9, 0.95, 1e-8)
    # a row for every token: no step can pass a held expert's buffer
    assert args["capacity_factor"] >= 64 / 6
    assert args["seq_len"] == TRAFFIC["seq_len"] == 8192
    assert TRAFFIC["batch_per_chip"] == 2
    assert CONFIG["feed"] == {"kind": "tokens", "vocab": 18992}


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
    by_layer = {}
    for name, leaves in mine.items():
        assert set(leaves) == set(theirs[name]), name
        for leaf, a in leaves.items():
            assert a.shape == theirs[name][leaf].shape, (name, leaf)
        by_layer[name] = sum(int(np.prod(a.shape)) for a in leaves.values())
    assert by_layer["b0_attn"] == 20_971_520
    assert by_layer["b0_router"] == 163_840
    assert by_layer["b0_experts"] == 8 * 5_898_240
    assert sum(v for k, v in by_layer.items() if k.startswith("b2_")) \
        == 68_326_400
    assert by_layer["embed"] == by_layer["head"] == 48_619_520
    # 5.93 GB at 16 bytes a parameter: 37% of the chip before an activation
    assert sum(by_layer.values()) == 370_547_200
    assert conf.recompute is not None and len(conf.recompute) == 8
    kinds = [(lc.window, lc.rope_theta) for lc in net._layer_confs
             if hasattr(lc, "rope_theta")]
    assert kinds == [(None, None)] + [(4096, 1.5e6)] * 3


# -- the reference's layer list -----------------------------------------------------

def test_layers_total_the_hand_count():
    layers = ref.layers(CONFIG)
    t = 8192
    by_key = {}
    for l in layers:
        by_key.setdefault(l["key"], 0)
        by_key[l["key"]] += flops.layer_macs(l, t)
    per_token = {k: v / t for k, v in by_key.items()}
    # projections 20,971,520 a layer; the full layer's products 3584 x 8193,
    # a window layer's 3584 x 4096 x 12289 / 8192
    assert per_token["b0_attn"] == 20_971_520 + 3584 * 8193
    for i in (1, 2, 3):
        assert per_token[f"b{i}_attn"] == 20_971_520 + 22_021_888
        assert per_token[f"b{i}_router"] == 163_840
        # three matrices of 2560 x 768, 6 a token, an eighth held
        assert per_token[f"b{i}_experts"] == 3 * 2560 * 768 * 6 // 8
    assert per_token["head"] == 48_619_520
    assert sum(by_key.values()) == 246_285_056 * t
    total = flops.train_flops_per_example(layers, t)
    assert total == 6 * 246_285_056 * t == 12_105_403_072_512
    products = 3584 * 8193 + 3 * 22_021_888
    assert round(100 * products / 246_285_056) == 39
    attention = sum(per_token[f"b{i}_attn"] for i in range(4))
    assert round(100 * attention / 246_285_056) == 73
    kinds = {l["kind"] for l in layers}
    assert kinds == {"embedding", "dense", "attention", "experts"}
    assert sum(l["kind"] == "attention" for l in layers) == 1
    routed = [l for l in layers if l["kind"] == "experts"]
    assert len(routed) == 12 and all(
        (l["experts_per_token"], l["held"], l["routed"]) == (6, 8, 64)
        for l in routed)


def test_the_bands_dense_entry_counts_the_masks_ones():
    """A window layer's two products under `flops.py`'s `dense` rule: at
    the cell's size the issue's numbers, and at a small size the ones of the
    literal band mask."""
    band = [l for l in ref.layers(CONFIG) if l.get("band")]
    assert len(band) == 3
    assert all((l["kind"], l["n_in"], l["n_out"]) == ("dense", 1792, 12289)
               for l in band)
    pairs = rooflines_decoder.band_pairs(8192, 4096)
    assert pairs == 25_167_872
    assert flops.layer_macs(band[0], 8192) == 2 * 28 * 128 * pairs
    # a third less than the full triangle's rule would have said
    full = flops.layer_macs({"kind": "attention", "n_heads": 28,
                             "head_dim": 128}, 8192)
    assert 0.74 < flops.layer_macs(band[0], 8192) / full < 0.76
    small = dict(CONFIG, num_hidden_layers=2, sliding_window_layout=[0, 1],
                 rope_layout=[0, 1], num_attention_heads=4, head_dim=16,
                 sliding_window_size=8,
                 factory_args={"seq_len": 32})
    mask = np.asarray(ref.visible(32, 8))
    assert int(mask.sum()) == rooflines_decoder.band_pairs(32, 8) \
        == 8 * 9 // 2 + 24 * 8
    entry = [l for l in ref.layers(small) if l.get("band")]
    assert len(entry) == 1
    assert flops.layer_macs(entry[0], 32) == 2 * 4 * 16 * int(mask.sum())
    assert int(np.asarray(ref.visible(32, None)).sum()) == 32 * 33 // 2
    # at the window's length a window layer is a full one
    at_window = dict(small, sliding_window_size=32)
    assert not [l for l in ref.layers(at_window) if l.get("band")]
    assert sum(l["kind"] == "attention" for l in ref.layers(at_window)) == 2
    # a length that leaves no exact entry is refused, not rounded
    with pytest.raises(ValueError, match="no exact"):
        ref.layers(dict(small, factory_args={"seq_len": 24}))
    with pytest.raises(ValueError, match="seq_len"):
        ref.layers(dict(small, factory_args={}))


# -- the limits and the chip readings they were set from ------------------------------

def _readings(key):
    return [dict(zip(READINGS["numbers"], row[1:]), seed=row[0])
            for row in READINGS[key]]


def test_the_limits_pass_every_recorded_sound_run_and_fail_every_control():
    """The chip readings the limits were set from (my chip run, PR 32),
    judged as `tools/calibrate_controls.py` judges them on the chip: no
    control or planted fault is let through, every sound run passes, and
    each compared limit has room on both sides of it."""
    from benchmark.tools import calibrate_controls as cc

    limits = CONFIG["limits"]
    assert limits["grad_gap"] is None and limits["delta_gap"] < 1.0
    wrong = {key: _readings(key) for key in cc.WRONG}
    assert all(len(rows) >= 6 for rows in wrong.values())
    assert cc.let_through(wrong, limits) == []
    sound = _readings("sound")
    assert len(sound) >= 6
    for row in sound:
        assert cc.judged(row, limits) == [], row["seed"]
    for name, key in READINGS["above"].items():
        largest_sound = max(row[name] for row in sound)
        smallest_wrong = min(row[name] for row in wrong[key])
        assert largest_sound * 1.4 <= limits[name] <= smallest_wrong / 1.4, \
            (name, largest_sound, limits[name], smallest_wrong)
    assert set(READINGS["above"]) == {k for k, v in limits.items()
                                      if v is not None}


# -- a tiny cell through the harness's own run ------------------------------------------

TINY = {
    "name": "tiny_smallthinker",
    "factory": "deeplearning4j_tpu.models.smallthinker:tiny_smallthinker_conf",
    "factory_args": {"precision": "f32", "learning_rate": 1e-3,
                     "seq_len": 32},
    "engine": "deeplearning4j_tpu.nn.compgraph:ComputationGraph",
    "reference": "smallthinker",
    "num_hidden_layers": 4, "sliding_window_layout": [0, 1, 1, 1],
    "rope_layout": [0, 1, 1, 1], "hidden_size": 64, "vocab_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window_size": 8, "rope_theta": 1.5e6,
    "moe_num_primary_experts": 8, "router_width": 16,
    "experts_held": list(range(8)), "moe_num_active_primary_experts": 3,
    "moe_ffn_hidden_size": 48, "rms_norm_eps": 1e-6, "precision": "f32",
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
    three steps against the reference in blocks of two rows; an untraced
    run reads no per-layer metric."""
    h = _harness()
    traffic = dict(h.TINY_TRAFFIC, batch_per_chip=4, seq_len=32,
                   rows_block=2)
    loaded = h._loaded(TINY, traffic=traffic)
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


def test_a_planted_fault_and_a_lower_precision_fail_the_tiny_cell():
    from benchmark import compare

    pool = fit_loop.make_pool(9, 3, 4, TINY, 32)
    whole = fit_loop.first_steps_of_reference(TINY, 9, pool, 3)
    half = fit_loop.first_steps_of_reference(TINY, 9, pool, 3,
                                             rows=slice(0, 2))
    blocks = fit_loop.first_steps_of_reference(TINY, 9, pool, 3,
                                               rows_block=1)
    fp8 = fit_loop.first_steps_of_reference(TINY, 9, pool, 3,
                                            precision="fp8")
    assert compare.first_step_gaps(half, whole)["grad_median_gap"] > 0.05
    same = compare.first_step_gaps(blocks, whole)
    assert same["loss_gap"] < 1e-6 and same["grad_median_gap"] < 1e-5
    rounded = compare.first_step_gaps(fp8, whole)
    assert rounded["grad_median_gap"] > 100 * same["grad_median_gap"]
    assert rounded["grad_median_gap"] > TINY["limits"]["grad_median_gap"]


# -- the cell's entries and their readers -----------------------------------------------

def check_the_cells_entries(bench):
    """The three entries that came with this cell follow the Nemotron
    cell's seven, and the four generic ones of those seven list this cell
    too."""
    h = _harness()
    entries = bench["per_layer"]
    assert [m["name"] for m in entries[21:24]] == NEW
    first = {m["name"]: m for m in entries[14:21]}
    assert set(REUSED) <= set(first)
    for m in entries[21:24] + [first[name] for name in REUSED]:
        assert h.NAME.match(m["name"]) and h.UNIT.match(m["unit"])
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert CELL in m["workloads"]
        assert m["moves"] == "train_examples_per_s_per_chip"
        assert callable(bench_run.load_reader(m["name"]))
    for m in entries[21:24]:
        assert m["workloads"][:1] == [CELL]
        # a layer the Nemotron cell's entries already name, letter for letter
        assert m["layer"] in {e["layer"] for e in first.values()}
    assert {m["name"] for m in entries[21:24] if m["name"].endswith(
        "_roofline_pct.train")} == {NEW[1], NEW[2]}
    assert all(m["unit"] == "%" for m in entries[21:24]
               if "roofline" in m["name"])


def test_the_cells_entries_keep_to_the_contract():
    check_the_cells_entries(_harness().BENCH)


@pytest.mark.parametrize("name", NEW + REUSED)
def test_a_scoped_reader_without_a_trace_reads_nothing(name):
    facts = {"registry_after": {}, "trace_dir": None,
             "peak_flops_per_s": 197e12}
    assert bench_run.load_reader(name)(facts, None) is None


def test_the_new_readers_on_made_up_rows():
    dev, ops, mods = "/device:TPU:0", "XLA Ops", "XLA Modules"
    layer = "jit(step)/jvp(jvp())/checkpoint/Lb1_attn_groupedqueryattention"
    window = layer + "/window_attention/while/body/checkpoint/dot_general"
    back = ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation"
            "/Lb1_attn_groupedqueryattention/window_attention/while/body/"
            "dot_general:")
    full = ("jit(step)/jvp(jvp())/checkpoint/Lb0_attn_groupedqueryattention"
            "/full_attention/checkpoint/dot_general")
    rotary = layer + "/rope/mul"
    experts = ("jit(step)/jvp(jvp())/checkpoint/Lb1_experts_sparseexperts/"
               "experts/cond/branch_1_fun/dot_general")
    router = "jit(step)/jvp(jvp())/Lb1_router_expertrouter/dot_general"
    rows = []
    for i in range(4):                      # four runs: two are counted
        t = 1000 * i
        rows += [(dev, mods, "jit_step(1)", t, 900, ""),
                 (dev, ops, "fusion.1", t, 100, window),
                 # a loop's event holds its body's: never summed itself
                 (dev, ops, "%while.3 = (...) while(...)", t + 100, 200, back),
                 (dev, ops, "fusion.2", t + 100, 200, back),
                 (dev, ops, "fusion.3", t + 300, 150, full),
                 (dev, ops, "fusion.4", t + 450, 50, rotary),
                 (dev, ops, "fusion.5", t + 500, 250, experts),
                 (dev, ops, "fusion.6", t + 750, 20, router),
                 (dev, ops, "copy.9", t + 800, 50, "")]
    part = lambda name: scope_reduce.part_ns(
        rows, scope_reduce.of_component(name))
    assert part("window_attention") == (600, 2)
    assert part("full_attention") == (300, 2)
    assert part("rope") == (100, 2) and part("experts") == (500, 2)
    # whole components only: `attention` alone names neither scope
    assert part("attention") is None
    assert scope_reduce.part_ns(rows, scope_reduce.of_layer_kinds(
        "groupedqueryattention")) == (1000, 2)
    assert scope_reduce.part_ns(rows, scope_reduce.of_layer_kinds(
        "expertrouter")) == (40, 2)
    assert scope_reduce.part_ns(rows, scope_reduce.of_layer_kinds(
        "sparseexperts")) == (500, 2)
    # the shares: the least time over the time read
    work = rooflines_decoder.window_attention(CONFIG, TRAFFIC)
    assert rooflines_decoder.share(work, 300e-6, V5E) == pytest.approx(
        100.0 * (work["flops"] / 197e12) / 300e-9)


def test_a_reader_finds_the_cells_files_and_is_silent_elsewhere(tmp_path):
    facts = {"trace_dir": os.path.join(ROOT, ".bench_trace", CELL),
             "peak_flops_per_s": 197e12}
    cell = rooflines_decoder.cell_of_run(facts)
    assert cell["config"]["hidden_size"] == 2560
    assert cell["traffic"]["seq_len"] == 8192
    # a cell without such layers: the new readers read nothing, and the
    # two-matrix experts' share reads nothing here
    for other in ("vgg16_train_b128", "nemotron3_nano_train_b4_s4096"):
        elsewhere = {"trace_dir": os.path.join(ROOT, ".bench_trace", other),
                     "peak_flops_per_s": 197e12}
        for name in NEW[1:]:
            assert bench_run.load_reader(name)(
                elsewhere, {"busy_s": 1.0}) is None
    for name in ("experts_roofline_pct.train", "ssm_scan_roofline_pct.train"):
        assert bench_run.load_reader(name)(facts, {"busy_s": 1.0}) is None


# -- the roofline arithmetic --------------------------------------------------------------

def test_the_bands_operations_and_bytes_against_a_hand_count():
    work = rooflines_decoder.window_attention(CONFIG, TRAFFIC)
    rows, layers, passes = 2, 3, 3
    pairs = 4096 * 4097 // 2 + 4096 * 4096
    # two products of 28 x 128 multiply-accumulates a pair
    assert work["flops"] == 2 * (2 * 28 * 128 * pairs) * rows * layers * passes
    # q and the output 3,584 wide, k and v 512, in bf16, 8,192 positions
    per_row = 8192 * (3584 + 512 + 512 + 3584) * 2
    assert work["bytes"] == per_row * rows * layers * passes
    # 2,700 FLOP a byte: far over the v5e's ridge of 240, the MXU bounds it
    assert work["flops"] / 197e12 > 10 * work["bytes"] / 819e9
    assert rooflines_decoder.share(work, None, V5E) is None


def test_the_gated_experts_operations_and_bytes_against_a_hand_count():
    work = rooflines_decoder.gated_experts(CONFIG, TRAFFIC)
    rows = 2 * 8192 * 6 * 8 // 64            # 12,288 assignments a layer
    assert rows == 12288
    layers, passes = 4, 3
    assert work["flops"] == 2 * rows * (3 * 2560 * 768) * layers * passes
    weights = 8 * 3 * 2560 * 768 * 2
    acts = rows * (2 * 2560 + 4 * 768) * 2
    assert work["bytes"] == (weights + acts) * layers * passes
    assert work["flops"] / 197e12 > work["bytes"] / 819e9   # the MXU bounds
    # a configuration of two-matrix experts has no such keys
    with pytest.raises(KeyError):
        rooflines_decoder.gated_experts(
            {"hidden_size": 1, "precision": "bf16"}, TRAFFIC)

"""The cell `kanana2_30b_train_b2_s8192` and what it brought under
`benchmark/`: the configuration and its cut, the reference's layer list with
the two half-rule attention entries, the three new readers, the roofline
arithmetic of `rooflines_latent.py` and the cell's per-layer entries in
`BENCHMARK.json`. Every assertion about the file's lists is a function of
`bench` or of a root that finds its entries by name, so that a later PR's
entries behind these change nothing here. CPU only; nothing here loads the
TPU library."""

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

from benchmark import flops, rooflines_latent, run as bench_run  # noqa: E402
from benchmark import scope_reduce  # noqa: E402
from benchmark.reference import deepseek_v3 as ref  # noqa: E402
from benchmark.traffic import fit_loop  # noqa: E402

CELL = "kanana2_30b_train_b2_s8192"
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "kanana2_30b_a3b.json")))
TRAFFIC = json.load(open(os.path.join(
    ROOT, "benchmark", "traffic", "train_fit_seq8192_b2.json")))
READINGS = json.load(open(os.path.join(
    ROOT, "benchmark", "fixtures", "kanana2_control_readings.json")))
NEW = ["latent_attention_ms.train", "latent_attention_roofline_pct.train",
       "gated_mlp_ms.train"]
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
       "hbm_bytes": 16e9}
# the catalog's entry (model-configs guide, architectures.jsonl), every key
SOURCE = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 128256}


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
    # the six per-layer metrics that list no cells, the cell's three, and
    # whatever else lists it
    h.check_cell_metrics(root, CELL, own=NEW)


def test_the_cell_keeps_to_the_contract_by_files_alone():
    check_the_cell_by_files_alone(ROOT)


def check_the_configuration_and_the_cell_are_entered(bench):
    """The configuration and the cell, found by name wherever they stand,
    behind the three configurations and cells that stood before them."""
    configs = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    assert configs.index("kanana2_30b_a3b") >= 3 and cells.index(CELL) >= 3
    entry = {c["name"]: c for c in bench["configs"]}["kanana2_30b_a3b"]
    assert entry["file"] == "benchmark/configs/kanana2_30b_a3b.json"
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == CONFIG["reduced"]
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert cell == dict(cell, config="kanana2_30b_a3b",
                        traffic="train_fit_seq8192_b2", chips=1)
    assert 1 <= len(cell["why"]) <= 200 and 1 <= len(entry["why"]) <= 200
    # no other cell runs this configuration, so no second cell came with it
    assert sum(w["config"] == "kanana2_30b_a3b"
               for w in bench["workloads"]) == 1


def test_benchmark_json_gained_the_configuration_and_the_cell():
    check_the_configuration_and_the_cell_are_entered(_harness().BENCH)


def test_no_width_is_cut_and_the_cut_is_stated():
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert CONFIG["reduced"] == reduced
    for key, value in SOURCE.items():
        if key in reduced:
            assert CONFIG["published"][key] == value, key
        else:
            assert key in CONFIG and CONFIG[key] == value, key
    assert sorted(CONFIG["published"]) == sorted(reduced)
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (5, 16, 16032)
    # the leading dense layer and four of the layers that follow it
    assert CONFIG["num_hidden_layers"] - CONFIG["first_k_dense_replace"] == 4
    assert 8 * CONFIG["vocab_size"] == SOURCE["vocab_size"]
    # the router keeps its published width; the chip holds experts 0..15
    assert CONFIG["router_width"] == 128
    assert CONFIG["experts_held"] == list(range(16))
    assert CONFIG["deployment"]["chips_sharing_a_layer"] == 8
    assert 8 * CONFIG["n_routed_experts"] == SOURCE["n_routed_experts"]
    assert CONFIG["precision"] == "bf16"
    assert CONFIG["updater"] == {"name": "adam", "learning_rate": 1e-4,
                                 "beta1": 0.9, "beta2": 0.95,
                                 "epsilon": 1e-8}
    assert len(CONFIG["assumed"]) >= 10 and len(CONFIG["guarantees"]) >= 4
    assert any("b_select" in line for line in CONFIG["assumed"])
    # what the factory is given is what the file states
    args = CONFIG["factory_args"]
    for key in ("num_hidden_layers", "first_k_dense_replace",
                "moe_layer_freq", "hidden_size", "vocab_size", "hidden_act",
                "intermediate_size", "num_attention_heads", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "rope_theta", "rope_interleave", "rope_scaling",
                "n_routed_experts", "router_width", "experts_held",
                "num_experts_per_tok", "n_shared_experts",
                "moe_intermediate_size", "routed_scaling_factor",
                "norm_topk_prob", "scoring_func", "topk_method", "n_group",
                "topk_group", "rms_norm_eps"):
        assert args[key] == CONFIG[key], key
    assert CONFIG["qk_head_dim"] == CONFIG["qk_nope_head_dim"] \
        + CONFIG["qk_rope_head_dim"]
    assert args["precision"] == CONFIG["precision"]
    assert (args["learning_rate"], args["beta1"], args["beta2"],
            args["epsilon"]) == (1e-4, 0.9, 0.95, 1e-8)
    assert args["seq_len"] == TRAFFIC["seq_len"] == 8192
    assert TRAFFIC["batch_per_chip"] == 2
    assert CONFIG["feed"] == {"kind": "tokens", "vocab": 16032}


def test_the_factory_builds_the_graph_the_reference_names():
    """At the real widths, without a weight: the vertex and parameter names
    and shapes the reference's `init_params` would hand over, and the
    issue's parameter count."""
    import jax

    from deeplearning4j_tpu.nn.conf import layers as L
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
    assert by_layer["b0_attn"] == by_layer["b3_attn"] == 26_345_984
    assert by_layer["b0_mlp"] == 37_748_736
    assert by_layer["b1_experts"] == 262_144 + 128 + 75_497_472
    assert by_layer["b1_shared"] == 9_437_184
    assert sum(v for k, v in by_layer.items() if k.startswith("b0_")) \
        == 64_098_816
    assert sum(v for k, v in by_layer.items() if k.startswith("b2_")) \
        == 111_547_008
    assert by_layer["embed"] == by_layer["head"] == 32_833_536
    # 9.22 GB at 16 bytes a parameter: 57.6% of the chip before an activation
    assert sum(by_layer.values()) == 575_955_968
    assert conf.recompute is not None and len(conf.recompute) == 10
    latent = [lc for lc in net._layer_confs
              if isinstance(lc, L.LatentAttentionLayer)]
    assert len(latent) == 5 and all(
        (lc.n_heads, lc.qk_nope_head_dim, lc.qk_rope_head_dim, lc.v_head_dim,
         lc.kv_lora_rank, lc.rope_theta)
        == (32, 128, 64, 128, 512, 1e6) for lc in latent)
    routed = [lc for lc in net._layer_confs
              if isinstance(lc, L.SparseExpertsLayer)]
    assert len(routed) == 4 and all(
        (lc.router_width, lc.experts_per_token, lc.width, lc.scaling,
         lc.select_bias, lc.gated, lc.score, lc.shared_width)
        == (128, 6, 768, 2.448, True, True, "sigmoid", 0) for lc in routed)


# -- the reference's layer list -----------------------------------------------------

def test_layers_total_the_hand_count():
    layers = ref.layers(CONFIG)
    t = 8192
    by_key = {}
    for l in layers:
        by_key.setdefault(l["key"], 0)
        by_key[l["key"]] += flops.layer_macs(l, t)
    per_token = {k: v / t for k, v in by_key.items()}
    projections = 2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    assert projections == 26_345_472
    # the scores' 192 and the mix's 128 a pair, 32 heads, the causal triangle
    products = 32 * (192 + 128) * 8193 // 2
    assert products == 41_948_160
    for i in range(5):
        assert per_token[f"b{i}_attn"] == projections + products
    assert per_token["b0_mlp"] == 3 * 2048 * 6144 == 37_748_736
    for i in (1, 2, 3, 4):
        # the router, and three matrices of 2048 x 768, 6 a token, 16 of 128
        assert per_token[f"b{i}_experts"] == 262_144 \
            + 3 * 2048 * 768 * 6 * 16 // 128
        assert per_token[f"b{i}_shared"] == 3 * 2048 * 1536 == 9_437_184
    assert per_token["head"] == 2048 * 16032 == 32_833_536
    assert sum(by_key.values()) == 465_003_520 * t == 3_809_308_835_840
    total = flops.train_flops_per_example(layers, t)
    assert total == 6 * 465_003_520 * t
    # 45.71 TFLOP a step of two examples
    assert round(2 * total / 1e12, 2) == 45.71
    assert round(100 * 5 * products / 465_003_520) == 45
    assert round(100 * 5 * (projections + products) / 465_003_520) == 73
    kinds = {l["kind"] for l in layers}
    assert kinds == {"embedding", "dense", "attention", "experts"}
    halves = [l for l in layers if l["kind"] == "attention"]
    assert [(l["n_heads"], l["head_dim"]) for l in halves] \
        == [(32, 96), (32, 64)] * 5
    routed = [l for l in layers if l["kind"] == "experts"]
    assert len(routed) == 12 and all(
        (l["experts_per_token"], l["held"], l["routed"]) == (6, 16, 128)
        for l in routed)


def test_two_half_rules_count_the_two_products_of_unequal_widths():
    """`flops.py`'s attention rule is two products of `head_dim` a pair; a
    score of 192 and a mix of 128 are half a rule each, exactly."""
    rule = lambda hd: flops.layer_macs(
        {"kind": "attention", "n_heads": 4, "head_dim": hd}, 32)
    pairs = 32 * 33 // 2
    assert rule(12) == 4 * 24 * pairs            # the scores' product alone
    assert rule(12) + rule(8) == 4 * (24 + 16) * pairs
    with pytest.raises(ValueError, match="no exact"):
        ref.layers(dict(CONFIG, qk_rope_head_dim=63))


# -- the limits and the chip readings they were set from ------------------------------

def _readings(key):
    return [dict(zip(READINGS["numbers"], row[1:]), seed=row[0])
            for row in READINGS[key]]


def test_the_limits_pass_every_recorded_sound_run_and_fail_every_control():
    """The chip readings the limits were set from (my chip run, PR 35),
    judged as `tools/calibrate_controls.py` judges them on the chip: no
    control or planted fault is let through, every sound run passes, and
    each compared limit has room on both sides of it."""
    from benchmark.tools import calibrate_controls as cc

    limits = CONFIG["limits"]
    assert limits["grad_gap"] is None and limits["delta_gap"] < 1.0
    # the first loss alone is printed, not compared (`limits_note` says why)
    assert limits["loss1_gap"] is None
    wrong = {key: _readings(key) for key in cc.WRONG}
    assert all(len(rows) >= 6 for rows in wrong.values())
    assert cc.let_through(wrong, limits) == []
    sound = _readings("sound")
    assert len(sound) >= 12
    for row in sound:
        assert cc.judged(row, limits) == [], row["seed"]
    for name, key in READINGS["above"].items():
        largest_sound = max(row[name] for row in sound)
        smallest_wrong = min(row[name] for row in wrong[key])
        assert largest_sound * 1.4 <= limits[name] <= smallest_wrong / 1.4, \
            (name, largest_sound, limits[name], smallest_wrong)
    assert set(READINGS["above"]) == {k for k, v in limits.items()
                                      if v is not None}
    # the control of the nearest lower precision fails on every seed
    assert all(row["fails"] for row in wrong["control_fp8"])


# -- a tiny cell through the harness's own run ------------------------------------------

TINY = {
    "name": "tiny_deepseek_v3",
    "factory": "deeplearning4j_tpu.models.deepseek_v3:tiny_deepseek_v3_conf",
    "factory_args": {"precision": "f32", "learning_rate": 1e-3,
                     "seq_len": 32},
    "engine": "deeplearning4j_tpu.nn.compgraph:ComputationGraph",
    "reference": "deepseek_v3",
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 64,
    "vocab_size": 128, "intermediate_size": 96, "num_attention_heads": 4,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "rope_theta": 1e6, "n_routed_experts": 8,
    "router_width": 16, "experts_held": list(range(8)),
    "num_experts_per_tok": 3, "n_shared_experts": 2,
    "moe_intermediate_size": 48, "routed_scaling_factor": 2.448,
    "rms_norm_eps": 1e-6, "precision": "f32",
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
    three steps against the reference in blocks of two rows (the leaf no
    gradient reaches among them: `compare` divides by the median leaf's
    norm); an untraced run reads no per-layer metric."""
    h = _harness()
    traffic = dict(h.TINY_TRAFFIC, batch_per_chip=4, seq_len=32,
                   rows_block=2)
    loaded = h._loaded(TINY, traffic=traffic)
    out = bench_run.run_cell(
        loaded, seed=2 ** 31 + 7, seconds=0.4, trace=False, device=h.V5E,
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
    # the two selection biases are the leaves that move by nothing
    assert info["leaves_left_out"] >= 2


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
    # the bias's leaves read 0 on both sides and are left out of delta_gap
    assert whole["grad_norms"]["b1_experts/b_select"] == 0.0
    assert whole["delta_norms"]["b1_experts/b_select"] == 0.0


# -- the cell's entries and their readers -----------------------------------------------

def check_the_cells_entries(bench):
    """The three entries that came with this cell, found by name: each
    lists this cell first, moves the rate, reads the device's trace and has
    a reader; the shares among them are percentages. The entries that stood
    before them were not given this cell's name."""
    h = _harness()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    assert [n for n in names if n in NEW] == NEW      # in this order
    assert min(names.index(n) for n in NEW) >= 24     # behind what stood
    layers_before = {m["layer"] for m in bench["per_layer"][:24]}
    for name in NEW:
        m = by_name[name]
        assert h.NAME.match(m["name"]) and h.UNIT.match(m["unit"])
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["workloads"][:1] == [CELL]
        assert m["moves"] == "train_examples_per_s_per_chip"
        assert m["source"] == "device_trace"
        assert callable(bench_run.load_reader(m["name"]))
    assert by_name[NEW[0]]["layer"] == by_name[NEW[1]]["layer"] \
        == "attention layers" and "attention layers" in layers_before
    assert (by_name[NEW[1]]["unit"], by_name[NEW[1]]["better"]) \
        == ("%", "higher")
    assert all(by_name[n]["unit"] == "ms/step" and
               by_name[n]["better"] == "lower" for n in (NEW[0], NEW[2]))
    # an accepted entry is not edited by a PR of this kind
    for m in bench["per_layer"][:24]:
        assert CELL not in m.get("workloads", [])


def test_the_cells_entries_keep_to_the_contract():
    check_the_cells_entries(_harness().BENCH)


@pytest.mark.parametrize("name", NEW)
def test_a_scoped_reader_without_a_trace_reads_nothing(name):
    facts = {"registry_after": {}, "trace_dir": None,
             "peak_flops_per_s": 197e12}
    assert bench_run.load_reader(name)(facts, None) is None


def test_the_new_readers_on_made_up_rows():
    dev, ops, mods = "/device:TPU:0", "XLA Ops", "XLA Modules"
    layer = "jit(step)/jvp(jvp())/checkpoint/Lb1_attn_latentattention"
    kernel = layer + "/latent_attention/gqa_fwd/pallas_call:"
    back = ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation"
            "/Lb1_attn_latentattention/latent_attention/gqa_bwd/pallas_call:")
    rotary = layer + "/rope/mul"
    latent = layer + "/latent_kv/dot_general"
    dense = "jit(step)/jvp(jvp())/checkpoint/Lb0_mlp_gatedmlp/dot_general"
    shared = ("jit(step)/transpose(jvp(jvp()))/checkpoint/"
              "rematted_computation/Lb1_shared_gatedmlp/dot_general")
    experts = ("jit(step)/jvp(jvp())/checkpoint/Lb1_experts_sparseexperts/"
               "experts/cond/branch_1_fun/dot_general")
    other = ("jit(step)/jvp(jvp())/checkpoint/Lb1_attn_groupedqueryattention"
             "/full_attention/gqa_fwd/pallas_call:")
    rows = []
    for i in range(4):                      # four runs: two are counted
        t = 1000 * i
        rows += [(dev, mods, "jit_step(1)", t, 900, ""),
                 (dev, ops, "gqa_fwd.1", t, 100, kernel),
                 (dev, ops, "gqa_bwd.2", t + 100, 200, back),
                 (dev, ops, "fusion.3", t + 300, 50, rotary),
                 (dev, ops, "fusion.4", t + 350, 70, latent),
                 (dev, ops, "fusion.5", t + 420, 130, dense),
                 (dev, ops, "fusion.6", t + 550, 60, shared),
                 (dev, ops, "fusion.7", t + 610, 150, experts),
                 (dev, ops, "gqa_fwd.8", t + 760, 40, other),
                 (dev, ops, "copy.9", t + 800, 50, "")]
    part = lambda name: scope_reduce.part_ns(
        rows, scope_reduce.of_component(name))
    assert part("latent_attention") == (600, 2)
    assert part("latent_kv") == (140, 2) and part("rope") == (100, 2)
    # whole components only: neither `attention` nor `latent` names a scope
    assert part("attention") is None and part("latent") is None
    kinds = lambda *k: scope_reduce.part_ns(
        rows, scope_reduce.of_layer_kinds(*k))
    assert kinds("latentattention") == (840, 2)
    assert kinds("gatedmlp") == (380, 2)
    assert kinds("groupedqueryattention") == (80, 2)
    # the share: the least time over the time read
    work = rooflines_latent.latent_attention(CONFIG, TRAFFIC)
    assert rooflines_latent.share(work, 300e-6, V5E) == pytest.approx(
        100.0 * (work["flops"] / 197e12) / 300e-9)
    assert rooflines_latent.share(work, None, V5E) is None


def test_a_reader_finds_the_cells_files_and_is_silent_elsewhere():
    facts = {"trace_dir": os.path.join(ROOT, ".bench_trace", CELL),
             "peak_flops_per_s": 197e12}
    cell = rooflines_latent.cell_of_run(facts)
    assert cell["config"]["kv_lora_rank"] == 512
    assert cell["traffic"]["seq_len"] == 8192
    share = bench_run.load_reader("latent_attention_roofline_pct.train")
    # a cell without such layers: the share reads nothing, never 0
    for other in ("vgg16_train_b128", "nemotron3_nano_train_b4_s4096",
                  "smallthinker_21b_train_b2_s8192"):
        elsewhere = {"trace_dir": os.path.join(ROOT, ".bench_trace", other),
                     "peak_flops_per_s": 197e12}
        assert share(elsewhere, {"busy_s": 1.0}) is None
    # and the other cells' shares read nothing here
    for name in ("experts_roofline_pct.train", "ssm_scan_roofline_pct.train",
                 "window_attention_roofline_pct.train",
                 "gated_experts_roofline_pct.train"):
        assert bench_run.load_reader(name)(facts, {"busy_s": 1.0}) is None


# -- the roofline arithmetic --------------------------------------------------------------

def test_the_latent_products_operations_and_bytes_against_a_hand_count():
    work = rooflines_latent.latent_attention(CONFIG, TRAFFIC)
    rows, layers, passes = 2, 5, 3
    pairs = 8192 * 8193 // 2
    # a pair multiplies 192 for its score and 128 for its mix, in 32 heads
    assert work["flops"] == 2 * (32 * 320 * pairs) * rows * layers * passes
    # q 32 x 192, k_nope, v and the output 32 x 128 each, k_rope 64 once,
    # in bf16, at 8,192 positions
    per_row = 8192 * (6144 + 4096 + 4096 + 64 + 4096) * 2
    assert work["bytes"] == per_row * rows * layers * passes
    # 2,268 FLOP a byte, nine times the v5e's ridge of 240: the MXU bounds it
    assert work["flops"] / 197e12 > 9 * work["bytes"] / 819e9
    # 104.7 ms a step at the peak: what 100% would be
    assert round(work["flops"] / 197e12 * 1e3, 1) == 104.7
    # at a small size, by hand: 2 heads, 12 + 4 and 8 wide, 6 positions
    small = {"num_attention_heads": 2, "qk_nope_head_dim": 12,
             "qk_rope_head_dim": 4, "v_head_dim": 8, "num_hidden_layers": 3,
             "precision": "f32"}
    got = rooflines_latent.latent_attention(
        small, {"seq_len": 6, "batch_per_chip": 5})
    assert got["flops"] == 2 * (2 * (16 + 8) * 21) * 5 * 3 * 3
    assert got["bytes"] == 6 * (2 * 16 + 2 * 12 + 2 * 8 + 4 + 2 * 8) * 4 \
        * 5 * 3 * 3
    # a configuration of another family has no such keys
    with pytest.raises(KeyError):
        rooflines_latent.latent_attention(
            {"num_attention_heads": 1, "precision": "bf16"}, TRAFFIC)

"""The cell `trinity_mini_train_b2_s8192` and what it brought under
`benchmark/`: the configuration and its cut, the reference's layer list with
the gate's and the band's FLOP entries, the reader of
`attention_gating_ms.train`, the limits and the chip readings they were set
from, and the cell's entries in `BENCHMARK.json`. Every assertion about the
file's lists is a function of `bench` or finds its entries by name, so that
a later PR's entries behind these change nothing here. CPU only; nothing
here loads the TPU library."""

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

from benchmark import flops, run as bench_run  # noqa: E402
from benchmark import scope_reduce  # noqa: E402
from benchmark.reference import afmoe as ref  # noqa: E402
from benchmark.traffic import fit_loop  # noqa: E402

CELL = "trinity_mini_train_b2_s8192"
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "trinity_mini_26b_a3b.json")))
TRAFFIC = json.load(open(os.path.join(
    ROOT, "benchmark", "traffic", "train_fit_seq8192_b2.json")))
READINGS = os.path.join(ROOT, "benchmark", "fixtures",
                        "trinity_mini_control_readings.json")
FIXTURE = os.path.join(ROOT, "benchmark", "fixtures",
                       "trinity_mini_scoped_trace.json")
NEW = ["attention_gating_ms.train"]
S, F = "sliding_attention", "full_attention"
# the catalog's entry (model-configs guide, architectures.jsonl), every key
SOURCE = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": [S, S, S, F] * 8, "load_balance_coeff": 0.001,
    "max_position_embeddings": 131072, "model_type": "afmoe",
    "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 32, "num_dense_layers": 2,
    "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_key_value_heads": 4,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
REDUCED = ["num_hidden_layers", "layer_types", "num_dense_layers",
           "num_experts", "vocab_size"]


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
    # the six per-layer metrics that list no cells, the cell's own, and
    # whatever else lists it
    h.check_cell_metrics(root, CELL, own=NEW)


def test_the_cell_keeps_to_the_contract_by_files_alone():
    check_the_cell_by_files_alone(ROOT)


def check_the_configuration_and_the_cell_are_entered(bench):
    """The configuration and the cell, found by name wherever they stand,
    behind the four configurations and cells that stood before them."""
    configs = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    assert configs.index("trinity_mini_26b_a3b") >= 4
    assert cells.index(CELL) >= 4
    entry = {c["name"]: c for c in bench["configs"]}["trinity_mini_26b_a3b"]
    assert entry["file"] == "benchmark/configs/trinity_mini_26b_a3b.json"
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == CONFIG["reduced"] == REDUCED
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert cell == dict(cell, config="trinity_mini_26b_a3b",
                        traffic="train_fit_seq8192_b2", chips=1)
    assert 1 <= len(cell["why"]) <= 200 and 1 <= len(entry["why"]) <= 200
    assert sum(w["config"] == "trinity_mini_26b_a3b"
               for w in bench["workloads"]) == 1


def test_benchmark_json_gained_the_configuration_and_the_cell():
    check_the_configuration_and_the_cell_are_entered(_harness().BENCH)


def test_no_width_is_cut_and_the_cut_is_stated():
    assert CONFIG["reduced"] == REDUCED
    for key, value in SOURCE.items():
        if key in REDUCED:
            assert CONFIG["published"][key] == value, key
        else:
            assert key in CONFIG and CONFIG[key] == value, key
    assert sorted(CONFIG["published"]) == sorted(REDUCED)
    assert (CONFIG["num_hidden_layers"], CONFIG["num_dense_layers"],
            CONFIG["num_experts"], CONFIG["vocab_size"]) == (5, 1, 16, 25024)
    # the published pattern's first five: the dense layer, then one whole
    # period of the expert layers (three window : one full)
    types = CONFIG["layer_types"]
    assert types == SOURCE["layer_types"][:5] == [S, S, S, F, S]
    expert_types = types[CONFIG["num_dense_layers"]:]
    assert sorted(expert_types) == sorted([S, S, S, F])
    assert 8 * CONFIG["vocab_size"] == SOURCE["vocab_size"]
    # the router keeps its published width and top 8; experts 0..15 held
    assert CONFIG["router_width"] == 128
    assert CONFIG["experts_held"] == list(range(16))
    assert CONFIG["deployment"]["chips_sharing_a_layer"] == 8
    assert 8 * CONFIG["num_experts"] == SOURCE["num_experts"]
    assert CONFIG["precision"] == "bf16"
    assert CONFIG["updater"] == {"name": "adam", "learning_rate": 1e-4,
                                 "beta1": 0.9, "beta2": 0.95,
                                 "epsilon": 1e-8}
    assert len(CONFIG["assumed"]) >= 10 and len(CONFIG["guarantees"]) >= 4
    for word in ("q_norm", "sigmoid(h Wg)", "mup_enabled", "b_select",
                 "load_balance_coeff", "capacity_factor"):
        assert any(word in line for line in CONFIG["assumed"]), word
    # what the factory is given is what the file states
    args = CONFIG["factory_args"]
    for key in ("num_hidden_layers", "layer_types", "num_dense_layers",
                "hidden_size", "vocab_size", "hidden_act",
                "intermediate_size", "mup_enabled", "tie_word_embeddings",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "sliding_window", "rope_theta", "rope_scaling", "num_experts",
                "router_width", "experts_held", "num_experts_per_tok",
                "num_shared_experts", "moe_intermediate_size", "score_func",
                "route_norm", "route_scale", "n_group", "topk_group",
                "num_expert_groups", "num_limited_groups", "rms_norm_eps"):
        assert args[key] == CONFIG[key], key
    assert args["precision"] == CONFIG["precision"]
    assert (args["learning_rate"], args["beta1"], args["beta2"],
            args["epsilon"]) == (1e-4, 0.9, 0.95, 1e-8)
    assert args["seq_len"] == TRAFFIC["seq_len"] == 8192
    assert TRAFFIC["batch_per_chip"] == 2
    assert CONFIG["feed"] == {"kind": "tokens", "vocab": 25024}


def test_the_factory_builds_the_graph_the_reference_names():
    """At the real widths, without a weight: the vertex and parameter names
    and shapes the reference's `init_params` would hand over, and the
    parameter count of the issue's table."""
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
    layer = lambda i: sum(v for k, v in by_layer.items()
                          if k.startswith(f"b{i}_"))
    # Wq, Wg and Wo 8,388,608 each, Wk and Wv 1,048,576, q/k norms 256
    assert by_layer["b0_attn"] == by_layer["b3_attn"] == 27_263_232
    assert by_layer["b0_mlp"] == 37_748_736
    assert by_layer["b1_experts"] == 262_144 + 128 + 16 * 6_291_456
    assert by_layer["b1_shared"] == 6_291_456
    assert layer(0) == 65_020_160
    assert layer(1) == layer(4) == 134_488_448
    assert by_layer["embed"] == by_layer["head"] == 51_249_152
    # 11.29 GB at 16 bytes a parameter: 70.5% of the chip before an
    # activation
    assert sum(by_layer.values()) == 705_474_304
    assert conf.recompute is not None and len(conf.recompute) == 10
    attn = [lc for lc in net._layer_confs
            if isinstance(lc, L.GroupedQueryAttentionLayer)]
    assert [(lc.window, lc.rope_theta) for lc in attn] == [
        (2048, 1e4)] * 3 + [(None, None), (2048, 1e4)]
    assert all((lc.n_heads, lc.n_kv_heads, lc.head_dim, lc.qk_norm,
                lc.output_gate, lc.eps) == (32, 4, 128, True, True, 1e-5)
               for lc in attn)
    routed = [lc for lc in net._layer_confs
              if isinstance(lc, L.SparseExpertsLayer)]
    assert len(routed) == 4 and all(
        (lc.router_width, lc.experts_per_token, lc.width, lc.scaling,
         lc.select_bias, lc.gated, lc.score, lc.shared_width,
         lc.capacity_factor)
        == (128, 8, 1024, 2.826, True, True, "sigmoid", 0, 8.0)
        for lc in routed)


# -- the reference's layer list -----------------------------------------------------

def test_layers_total_the_hand_count():
    layers = ref.layers(CONFIG)
    t = 8192
    by_key = {}
    for l in layers:
        by_key[l["key"]] = by_key.get(l["key"], 0) + flops.layer_macs(l, t)
    per_token = {k: v / t for k, v in by_key.items()}
    # Wq, Wk, Wv, the gate's Wg and Wo
    projections = 2048 * (4096 + 512 + 512 + 4096) + 4096 * 2048
    assert projections == 27_262_976
    # the full layer's triangle, two products of 32 x 128 a pair
    full = 32 * 128 * 8193
    # a window layer's band: 2,048 x 2,049 / 2 + 6,144 x 2,048 pairs a head
    band = 2 * 32 * 128 * (2048 * 2049 // 2 + 6144 * 2048) / 8192
    assert full == 33_558_528 and band == 14_681_088
    for i in (0, 1, 2, 4):
        assert per_token[f"b{i}_attn"] == projections + band
    assert per_token["b3_attn"] == projections + full
    assert per_token["b0_mlp"] == 3 * 2048 * 6144 == 37_748_736
    for i in (1, 2, 3, 4):
        # the router, and three matrices of 2048 x 1024, 8 a token, 16 of 128
        assert per_token[f"b{i}_experts"] == 262_144 \
            + 3 * 2048 * 1024 * 8 * 16 // 128
        assert per_token[f"b{i}_shared"] == 3 * 2048 * 1024 == 6_291_456
    assert per_token["head"] == 2048 * 25024 == 51_249_152
    assert sum(by_key.values()) == 368_975_872 * t
    total = flops.train_flops_per_example(layers, t)
    assert total == 6 * 368_975_872 * t
    # 36.3 TFLOP a step of two examples: 184 ms at the v5e's peak
    assert round(2 * total / 1e12, 1) == 36.3
    assert round(2 * total / 197e12 * 1e3) == 184
    # the attention layers: 62% of the step's operations; the gate 11%
    attention = sum(per_token[f"b{i}_attn"] for i in range(5))
    assert round(100 * attention / 368_975_872) == 62
    assert round(100 * 5 * 2048 * 4096 / 368_975_872) == 11
    gates = [l for l in layers if l["kind"] == "dense"
             and l["key"].endswith("_attn") and l["n_out"] == 4096
             and l["n_in"] == 2048]
    assert len(gates) == 10                   # Wq and Wg of five layers
    bands = [l for l in layers if l.get("band")]
    assert [(l["n_in"], l["n_out"]) for l in bands] == [(1024, 14337)] * 4
    with pytest.raises(ValueError, match="no exact"):
        ref.layers(dict(CONFIG, sliding_window=2047))


# -- the limits and the chip readings they were set from ------------------------------

def _readings(doc, key):
    return [dict(zip(doc["numbers"], row[1:]), seed=row[0])
            for row in doc[key]]


def test_the_limits_pass_every_recorded_sound_run_and_fail_every_control():
    """The readings the limits were set from (measured on one v5e chip),
    judged as `tools/calibrate_controls.py` judges them on the chip: no
    control or planted fault is let through, every sound run passes, and
    each compared limit has room on both sides of it."""
    from benchmark.tools import calibrate_controls as cc

    doc = json.load(open(READINGS))
    limits = CONFIG["limits"]
    assert limits["grad_gap"] is None and limits["delta_gap"] < 1.0
    wrong = {key: _readings(doc, key) for key in cc.WRONG}
    assert all(len(rows) >= 6 for rows in wrong.values())
    assert cc.let_through(wrong, limits) == []
    sound = _readings(doc, "sound")
    assert len(sound) >= 6
    for row in sound:
        assert cc.judged(row, limits) == [], row["seed"]
    for name, key in doc["above"].items():
        largest_sound = max(row[name] for row in sound)
        smallest_wrong = min(row[name] for row in wrong[key])
        assert largest_sound * 1.4 <= limits[name] <= smallest_wrong / 1.4, \
            (name, largest_sound, limits[name], smallest_wrong)
    assert set(doc["above"]) == {k for k, v in limits.items()
                                 if v is not None}
    # the control of the nearest lower precision fails on every seed
    assert all(row["fails"] for row in wrong["control_fp8"])


# -- a tiny cell through the harness's own run ------------------------------------------

TINY = {
    "name": "tiny_afmoe",
    "factory": "deeplearning4j_tpu.models.afmoe:tiny_afmoe_conf",
    "factory_args": {"precision": "f32", "learning_rate": 1e-3,
                     "seq_len": 32},
    "engine": "deeplearning4j_tpu.nn.compgraph:ComputationGraph",
    "reference": "afmoe",
    "num_hidden_layers": 5, "layer_types": [S, S, F, S, S],
    "num_dense_layers": 1, "hidden_size": 64, "vocab_size": 128,
    "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 8,
    "rope_theta": 1e4, "num_experts": 8, "router_width": 16,
    "experts_held": list(range(8)), "num_experts_per_tok": 3,
    "num_shared_experts": 1, "moe_intermediate_size": 48,
    "route_scale": 2.826, "rms_norm_eps": 1e-5, "mup_enabled": True,
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
    three steps against the reference in blocks of two rows (the selection
    biases are the leaves no gradient reaches); an untraced run reads no
    per-layer metric."""
    h = _harness()
    traffic = dict(h.TINY_TRAFFIC, batch_per_chip=4, seq_len=32,
                   rows_block=2)
    loaded = h._loaded(TINY, traffic=traffic)
    out = bench_run.run_cell(
        loaded, seed=2 ** 31 + 9, seconds=0.4, trace=False, device=h.V5E,
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
    assert info["leaves_left_out"] >= 4


# -- the cell's entry and its reader ------------------------------------------------------

def check_the_cells_entries(bench):
    """The entry that came with this cell, found by name: it lists this
    cell alone, moves the rate, reads the device's trace, has a reader and
    names the attention layers as the accepted entries do; no accepted
    entry was given this cell's name."""
    h = _harness()
    names = [m["name"] for m in bench["per_layer"]]
    assert min(names.index(n) for n in NEW) >= 32      # behind what stood
    m = {e["name"]: e for e in bench["per_layer"]}[NEW[0]]
    assert h.NAME.match(m["name"]) and h.UNIT.match(m["unit"])
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert m["workloads"][:1] == [CELL]
    assert (m["unit"], m["better"], m["source"], m["moves"]) == (
        "ms/step", "lower", "device_trace", "train_examples_per_s_per_chip")
    assert m["layer"] == "attention layers" \
        and "attention layers" in {e["layer"] for e in bench["per_layer"][:32]}
    assert callable(bench_run.load_reader(m["name"]))
    for e in bench["per_layer"][:32]:
        assert CELL not in e.get("workloads", [])


def test_the_cells_entries_keep_to_the_contract():
    check_the_cells_entries(_harness().BENCH)


def test_the_reader_without_a_trace_reads_nothing():
    facts = {"registry_after": {}, "trace_dir": None,
             "peak_flops_per_s": 197e12}
    assert bench_run.load_reader(NEW[0])(facts, None) is None


def _gating(rows):
    """The reader's own matcher over rows, as `ms_per_step` hands them to
    `part_ns`."""
    match = bench_run.load_reader(NEW[0]).__globals__["match"]
    return scope_reduce.part_ns(rows, match)


def test_the_reader_on_made_up_rows():
    dev, ops, mods = "/device:TPU:0", "XLA Ops", "XLA Modules"
    layer = "jit(step)/jvp(jvp())/checkpoint/Lb1_attn_groupedqueryattention"
    back = ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation"
            "/Lb3_attn_groupedqueryattention")
    rows = []
    for i in range(4):                      # four runs: two are counted
        t = 1000 * i
        rows += [(dev, mods, "jit_step(1)", t, 900, ""),
                 (dev, ops, "fusion.1", t, 40, layer + "/qk_norm/mul"),
                 (dev, ops, "fusion.2", t + 40, 60,
                  layer + "/output_gate/dot_general"),
                 (dev, ops, "fusion.3", t + 100, 30,
                  back + "/output_gate/logistic"),
                 (dev, ops, "fusion.4", t + 130, 20, back + "/qk_norm/rsqrt"),
                 # the kernel, the rotation and the projections are not it
                 (dev, ops, "gqa_fwd.5", t + 150, 300,
                  layer + "/window_attention/gqa_fwd/pallas_call:"),
                 (dev, ops, "fusion.6", t + 450, 50, layer + "/rope/mul"),
                 (dev, ops, "fusion.7", t + 500, 100, layer + "/dot_general"),
                 # a scope of that name outside an attention layer is not it
                 (dev, ops, "fusion.8", t + 600, 70,
                  "jit(step)/jvp(jvp())/Lb1_ffn_post_norm_rmsnorm/qk_norm"),
                 (dev, ops, "copy.9", t + 800, 50, "")]
    assert _gating(rows) == (300, 2)
    assert _gating([r for r in rows if "qk_norm" not in r[5]
                    and "output_gate" not in r[5]]) is None


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="the recorded step is written by the chip run")
def test_the_reader_on_the_recorded_chip_step():
    """One whole step of the cell, recorded on one v5e chip: the
    reader finds the two parts in every attention layer, forward and
    backward, they lie inside the attention layers' time, and its reading
    over the recorded step is the traced run's over its whole slice."""
    from benchmark import trace_reduce
    from benchmark.tools import span_dump

    doc = json.load(open(FIXTURE))
    rows = span_dump.expand(doc)
    whole = trace_reduce.reduce_rows([r[:5] for r in rows])
    assert whole["main_module"] == "jit_step"
    steps = whole["main_module_runs"]
    step_ns = whole["busy_s"] * 1e9 / steps
    ns, counted = _gating(rows)
    attention, _ = scope_reduce.part_ns(
        rows, scope_reduce.of_layer_kinds("groupedqueryattention"))
    assert counted == steps and 0 < ns < attention < step_ns * counted
    for i in range(5):
        for part in ("qk_norm", "output_gate"):
            for phase in ("jvp(", "transpose("):
                assert any(f"Lb{i}_attn_groupedqueryattention/{part}" in r[5]
                           and phase in r[5] for r in rows), (i, part, phase)
    recorded = doc["recorded"]["metrics"]
    assert ns / counted * 1e-6 == pytest.approx(
        recorded[NEW[0]]["value"], rel=0.02)

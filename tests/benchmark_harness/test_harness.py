"""CPU tests of the benchmark's harness, at tiny configurations of the
tests' own (the command has no flag for a smaller size). Nothing here loads
the TPU library; the chip's numbers come from the chip alone."""

from __future__ import annotations

import copy
import hashlib
import importlib.util
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
from benchmark.reference import plain  # noqa: E402
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
ADAM = {"name": "adam", "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.999,
        "epsilon": 1e-8}
# Adam moves an element whose gradient is nought to rounding by about the
# learning rate a step, to whichever side the noise falls: a four-element
# `stem_bn/beta` with one dead channel reads a `delta_gap` of 0.044 between
# two float32 runs (seed 11), so the worst leaf's limit is wide here and the
# median leaf's is round-off.
TINY_RESNET_ADAM = dict(
    TINY_RESNET, name="tiny_resnet_adam", updater=ADAM,
    factory_args=dict(TINY_RESNET["factory_args"], updater="adam",
                      learning_rate=1e-3),
    limits={"loss_gap": 1e-4, "grad_gap": 2e-3, "grad_median_gap": 2e-3,
            "delta_gap": 0.3, "delta_median_gap": 2e-3})
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
TINY_TOKENS = {"feed": {"kind": "tokens", "vocab": 50}}


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


def _pool_digest(pool) -> str:
    digest = hashlib.sha256()
    for x, y in pool:
        digest.update(x.tobytes())
        digest.update(y.tobytes())
    return digest.hexdigest()


def test_the_image_pool_is_the_draw_it_was_before_feeds_had_kinds():
    """Recorded from the parent of PR 27: a configuration without `feed`
    is fed the same bytes from the same seed."""
    images = {"image_size": 16, "channels": 3, "num_classes": 5}
    recorded = ("0c3192bd53dde3429b845cada2ec14a07cd21c4f2046e1e16573c2696c45"
                "8a48")
    assert _pool_digest(fit_loop.make_pool(2 ** 31 + 17, 2, 3, images)) \
        == recorded
    assert _pool_digest(fit_loop.make_pool(
        2 ** 31 + 17, 2, 3, dict(images, feed={"kind": "images"}))) \
        == recorded


def test_the_token_pool_is_ids_and_their_next_token():
    a = fit_loop.make_pool(2 ** 31 + 17, 3, 4, TINY_TOKENS, seq_len=12)
    b = fit_loop.make_pool(2 ** 31 + 17, 3, 4, TINY_TOKENS, seq_len=12)
    c = fit_loop.make_pool(5, 3, 4, TINY_TOKENS, seq_len=12)
    assert len(a) == 3
    for x, y in a:
        assert x.shape == y.shape == (4, 12)
        assert x.dtype == y.dtype == np.int32
        assert x.min() >= 0 and max(x.max(), y.max()) < 50
        assert np.array_equal(y[:, :-1], x[:, 1:])
        assert x.flags["C_CONTIGUOUS"] and y.flags["C_CONTIGUOUS"]
    assert _pool_digest(a) == _pool_digest(b) != _pool_digest(c)
    assert not np.array_equal(a[0][0], a[1][0])
    # every id of the slice is drawn: the vocabulary held here is the feed's
    assert len(np.unique(np.concatenate([x.ravel() for x, _ in a]))) > 40


def test_a_feed_the_harness_does_not_know_is_refused():
    with pytest.raises(ValueError, match="no feed of kind"):
        fit_loop.make_pool(1, 1, 2, {"feed": {"kind": "graphs"}})
    with pytest.raises(ValueError, match="seq_len"):
        fit_loop.make_pool(1, 1, 2, TINY_TOKENS)


@pytest.mark.parametrize("config,seq_len,nbytes", [
    (TINY_RESNET, None, 2 * (6 * 16 * 16 * 3 + 6 * 5) * 4),
    (TINY_TOKENS, 12, 2 * 2 * 6 * 12 * 4),
], ids=["images", "tokens"])
def test_pool_bytes_the_iterator_and_the_half_batch_fault_on_either_kind(
        config, seq_len, nbytes):
    pool = fit_loop.make_pool(3, 2, 6, config, seq_len)
    assert fit_loop.pool_bytes(pool) == nbytes
    it = fit_loop.pool_iterator(pool, max_batches=3)
    fed = list(it)
    assert it.batch_size() == 6 and len(fed) == 3
    assert np.array_equal(fed[2].features, pool[0][0])
    assert np.array_equal(fed[1].labels, pool[1][1])
    # calibrate.py's planted fault: the first half of each batch's rows
    rows = slice(0, 3)
    assert pool[0][0][rows].shape == (3,) + pool[0][0].shape[1:]
    assert pool[0][1][rows].shape == (3,) + pool[0][1].shape[1:]


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
    with pytest.raises(ValueError, match="no FLOP rule"):
        flops.layer_macs({"kind": "fourier_mixer"})
    with pytest.raises(ValueError, match="no FLOP rule"):
        flops.train_flops_per_example([{"kind": "pool"}], positions=4)


@pytest.mark.parametrize("name,expected", [("resnet50", 24535105536),
                                           ("vgg16", 92821585920)])
def test_the_image_nets_totals_are_the_integers_they_were(name, expected):
    """Recorded from the parent of PR 27: the rules for sequences leave the
    conv and dense count of an image, at one position, as it was."""
    config = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                         name + ".json")))
    layers = fit_loop.load_reference(config).layers(config)
    assert flops.train_flops_per_example(layers) == expected
    assert flops.train_flops_per_example(layers, positions=1) == expected


@pytest.mark.parametrize("layer,positions,macs", [
    # a 5 -> 7 matrix at each of 3 positions
    ({"kind": "dense", "n_in": 5, "n_out": 7}, 3, 5 * 7 * 3),
    ({"kind": "dense", "n_in": 5, "n_out": 7}, 1, 35),
    # 2 heads of 4 over 3 positions: position i sees i + 1 keys, so
    # 1 + 2 + 3 = 6 scores of 4 products a head, and as many for the mix
    ({"kind": "attention", "n_heads": 2, "head_dim": 4}, 3,
     2 * (2 * 4 * 6)),
    ({"kind": "attention", "n_heads": 2, "head_dim": 4}, 1, 2 * (2 * 4 * 1)),
    # a 6 -> 10 matrix, 2 experts a token of 16 routed, 4 held here: a
    # token meets 2 * 4 / 16 = half an expert of this chip's, at 8 positions
    ({"kind": "experts", "n_in": 6, "n_out": 10, "experts_per_token": 2,
      "held": 4, "routed": 16}, 8, 6 * 10 * 8 // 2),
    # all experts held: the published top-k
    ({"kind": "experts", "n_in": 6, "n_out": 10, "experts_per_token": 2,
      "held": 16, "routed": 16}, 1, 6 * 10 * 2),
    # 3 heads of 4 with a state of 5: 60 products to update the state and
    # 60 to read it out, at each of 7 positions
    ({"kind": "scan", "heads": 3, "head_dim": 4, "state": 5}, 7,
     (60 + 60) * 7),
    # 6 channels, 4 taps, 5 positions
    ({"kind": "depthwise_conv1d", "channels": 6, "k": 4}, 5, 6 * 4 * 5),
    ({"kind": "embedding", "rows": 1000, "width": 64}, 9, 0),
    # an image's conv has no positions
    ({"kind": "conv", "h_out": 2, "w_out": 2, "k": 3, "c_in": 1,
      "c_out": 2}, 1, 2 * 2 * 9 * 2),
], ids=["dense_seq", "dense_image", "attention", "attention_one_position",
        "experts_share", "experts_all_held", "scan", "depthwise_conv1d",
        "embedding", "conv"])
def test_each_flop_rule_against_a_hand_count(layer, positions, macs):
    assert flops.layer_macs(layer, positions) == macs
    assert flops.train_flops_per_example([layer], positions) == 6 * macs
    assert flops.forward_flops_per_example([layer, layer], positions) \
        == 4 * macs


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

def check_keys_and_counts(bench):
    """What the contract says of the file as a whole: its keys, the run's
    length, and how many entries each list may hold (never how many it
    holds today)."""
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200, "run_seconds does not fit a full check of 24 cells"
    cells = len(bench["workloads"])
    assert 1 <= len(bench["configs"]) <= 24 and 1 <= cells <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16
    assert 1 <= len(bench["per_layer"]) <= 128
    assert sum(w["chips"] == 4 for w in bench["workloads"]) \
        <= max(1, cells // 4)
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1
               for m in bench["end_to_end"])
    for key in ("configs", "workloads"):
        names = [e["name"] for e in bench[key]]
        assert len(set(names)) == len(names), f"a name twice in {key}"
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names), "a metric's name twice"
    assert {c["name"] for c in bench["configs"]} == {
        w["config"] for w in bench["workloads"]}, \
        "every configuration is used by some cell"


def test_benchmark_json_has_exactly_the_contracts_keys():
    check_keys_and_counts(BENCH)


# What the contract asks of a cell whatever its model, as functions of a
# root, so that the same checks judge scratch cells below.

LIMITS = {"loss1_gap", "loss_gap", "grad_gap", "grad_median_gap",
          "delta_gap", "delta_median_gap"}
# keys of a published configuration that count what a chip may hold a share
# of (the model-configs guide, section 4), and what may never be cut
EXPERT_COUNTS = ("n_routed_experts", "num_experts", "num_local_experts",
                 "moe_num_experts")
VOCABULARIES = ("vocab_size",)
LAYER_PATTERNS = ("hybrid_override_pattern", "hybrid_layer_pattern",
                  "layer_types", "layers_block_type", "mlp_layer_types")
WIDTH = re.compile(r"hidden_size|intermediate_size|latent|state_size|_proj"
                   r"|_dim$|_rank$|head_dim|^expand|experts_per_tok|_width$")


def check_generator(traffic):
    """The traffic's generator is a module `benchmark/traffic/<name>.py`
    with `run` and `verify`."""
    name = traffic["generator"]
    assert NAME.match(name), name
    assert importlib.util.find_spec(f"benchmark.traffic.{name}") is not None, \
        f"no generator module benchmark/traffic/{name}.py"
    module = bench_run.load_generator(traffic)
    assert callable(module.run) and callable(module.verify)


def check_reference(config):
    module = fit_loop.load_reference(config)
    for fn in ("layers", "init_params", "loss"):
        assert callable(getattr(module, fn)), fn


def check_reduced(config, entry):
    """A configuration cut from its source says from what, and as what
    share of which deployment, and keeps the guide's floors."""
    reduced = config["reduced"]
    assert reduced == entry["reduced"] and len(reduced) <= 16
    if not reduced:
        return
    for key in reduced:
        assert NAME.match(key) and not WIDTH.search(key), \
            f"`reduced` names a width: {key}"
        assert key in config, f"`reduced` names {key}, the file has none"
    published = config.get("published")
    assert published is not None, "a reduced configuration states `published`"
    assert sorted(published) == sorted(reduced), \
        "`published` holds the source's value of every key in `reduced`"
    for key in reduced:
        assert published[key] != config[key], f"{key} is not cut"
    deployment = config.get("deployment")
    assert deployment is not None, \
        "a reduced configuration states its `deployment`"
    assert int(deployment["chips_sharing_a_layer"]) >= 1
    assert isinstance(deployment["how"], str) and deployment["how"].strip()
    for key in reduced:
        held, whole = config[key], published[key]
        if key in EXPERT_COUNTS:
            assert held >= min(8, whole), \
                f"{key}: at least 8 routed experts are held"
        if key in VOCABULARIES:
            assert 8 * held >= whole, \
                f"{key}: at least an eighth of the vocabulary is held"
        if key in LAYER_PATTERNS:
            assert list(whole[:len(held)]) == list(held), \
                f"{key}: the layers kept are the published pattern's first"
            assert set(held) == set(whole), \
                f"{key}: a whole period, every kind of layer in it"


def check_feed(config, traffic, chips):
    feed = config.get("feed", {"kind": "images"})
    batch = int(traffic["batch_per_chip"]) * chips
    if feed["kind"] == "tokens":
        assert int(traffic["seq_len"]) >= 1
        assert int(feed["vocab"]) >= 2
        for key in VOCABULARIES:
            if key in config:   # a sliced vocabulary is a smaller vocabulary
                assert feed["vocab"] == config[key], \
                    f"the feed draws its ids below {key}"
    else:
        assert feed["kind"] == "images" and "seq_len" not in traffic
        assert {"image_size", "channels", "num_classes"} <= set(config)
    if "rows_block" in traffic:
        assert batch % int(traffic["rows_block"]) == 0, \
            "rows_block divides the batch"
        assert not getattr(fit_loop.load_reference(config),
                           "BATCH_STATISTICS", False), \
            "rows_block: the reference couples the rows of a batch"


def check_cell(root, cell):
    """Everything below, of one cell found by its name under `root`."""
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    loaded = bench_run.load_cell(root, cell)
    config, traffic = loaded["config"], loaded["traffic"]
    entry = {c["name"]: c for c in bench["configs"]}[
        loaded["cell"]["config"]]
    check_generator(traffic)
    check_reference(config)
    check_reduced(config, entry)
    check_feed(config, traffic, int(loaded["cell"]["chips"]))
    assert config["updater"]["name"] in plain.UPDATERS, \
        "the reference has no rule for this updater"
    return loaded


GENERIC = {"data_wait_ms.train", "dispatch_ms.train", "step_mfu_pct.train",
           "device_step_ms.train", "device_idle_pct.train",
           "peak_hbm_gib.train"}


def check_cell_metrics(root, cell, own=()):
    """The per-layer metrics a traced run of `cell` is asked for: the six
    that list no cell, the cell's `own`, and beside them exactly the
    entries that list the cell or list none and move a metric the cell
    reports. However many entries a later PR appends, for whichever cells."""
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    loaded = bench_run.load_cell(root, cell)
    moved = {m["name"] for m in loaded["end_to_end"]}
    assert moved >= {"train_examples_per_s_per_chip", "setup_s"}
    names = [m["name"] for m in loaded["per_layer"]]
    assert len(set(names)) == len(names)
    assert GENERIC | set(own) <= set(names)
    assert set(names) == {
        m["name"] for m in bench["per_layer"]
        if cell in m.get("workloads", [cell]) and m["moves"] in moved}
    for name in own:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert cell in entry["workloads"], name
    return loaded


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_by_name(cell):
    loaded = check_cell_metrics(ROOT, cell)
    config = loaded["config"]
    assert config["assumed"]
    limits = config["limits"]
    assert set(limits) == LIMITS
    # the control has to fail loss_gap, the planted faults the others
    assert all(limits[k] is not None and limits[k] > 0 for k in (
        "loss_gap", "grad_median_gap", "delta_gap", "delta_median_gap"))
    assert limits["delta_gap"] < 1.0   # a state left unchanged reads 1
    assert {m["name"] for m in loaded["end_to_end"]} >= {
        "setup_s", "train_examples_per_s_per_chip"}
    assert loaded["per_layer"]
    for m in loaded["end_to_end"] + loaded["per_layer"]:
        assert callable(bench_run.load_reader(m["name"]))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cells_generator_is_a_module_with_run_and_verify(cell):
    check_generator(bench_run.load_cell(ROOT, cell)["traffic"])


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cells_feed_reference_and_updater_are_ones_the_harness_has(cell):
    loaded = check_cell(ROOT, cell)
    assert loaded["cell"]["name"] == cell


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_every_reduced_configuration_states_what_it_was_cut_from(entry):
    check_reduced(json.load(open(os.path.join(ROOT, entry["file"]))), entry)


# A scratch cell of the kind the harness now takes by files alone: a
# sequence model cut to one chip's share of a deployment, fed tokens, trained
# with Adam, its reference taken in blocks of rows. Entered in a copy of
# BENCHMARK.json under a scratch root; nothing under `paths` is edited.

SEQ_CUT = {
    "name": "seq_cut", "source": "https://example.org/seq/config.json",
    "factory": "deeplearning4j_tpu.models.vgg16:vgg16_conf",
    "factory_args": {}, "engine":
        "deeplearning4j_tpu.nn.multilayer:MultiLayerNetwork",
    "reference": "vgg16",
    "hidden_size": 64, "num_experts_per_tok": 2,
    "num_hidden_layers": 6, "hybrid_override_pattern": "MEM*EM",
    "n_routed_experts": 8, "vocab_size": 512,
    "reduced": ["num_hidden_layers", "hybrid_override_pattern",
                "n_routed_experts", "vocab_size"],
    "published": {"num_hidden_layers": 12,
                  "hybrid_override_pattern": "MEM*EMMEM*EM",
                  "n_routed_experts": 64, "vocab_size": 4096},
    "deployment": {"chips_sharing_a_layer": 8,
                   "how": "experts and vocabulary rows divided evenly; "
                          "the layers left out lie on further chips"},
    "feed": {"kind": "tokens", "vocab": 512},
    "precision": "bf16", "updater": ADAM,
    "assumed": ["weights random from the seed"],
    "limits": {"loss1_gap": None, "loss_gap": 1e-5, "grad_gap": None,
               "grad_median_gap": 0.05, "delta_gap": 0.5,
               "delta_median_gap": 0.05},
}
SEQ_TRAFFIC = dict(TINY_TRAFFIC, seq_len=32, rows_block=2)


def _scratch_root(tmp_path, config=SEQ_CUT, traffic=SEQ_TRAFFIC):
    bench = copy.deepcopy(BENCH)
    bench["configs"].append({
        "name": config["name"], "source": config["source"],
        "file": f"benchmark/configs/{config['name']}.json",
        "reduced": config["reduced"], "why": "a scratch configuration"})
    bench["workloads"].append({
        "name": "seq_cut_train", "config": config["name"],
        "traffic": "train_seq", "chips": 1, "why": "a scratch cell"})
    for sub in ("configs", "traffic"):
        os.makedirs(tmp_path / "benchmark" / sub)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    with open(tmp_path / "benchmark" / "configs"
              / f"{config['name']}.json", "w") as f:
        json.dump(config, f)
    with open(tmp_path / "benchmark" / "traffic" / "train_seq.json",
              "w") as f:
        json.dump(traffic, f)
    return str(tmp_path)


def _without(config, *keys, **changed):
    out = {k: v for k, v in config.items() if k not in keys}
    out.update(changed)
    return out


def test_a_reduced_token_cell_is_taken_by_files_alone(tmp_path):
    loaded = check_cell(_scratch_root(tmp_path), "seq_cut_train")
    assert loaded["traffic"]["rows_block"] == 2
    assert set(loaded["config"]["limits"]) == LIMITS
    # every per-layer metric without a `workloads` list is read here too
    assert "step_mfu_pct.train" in {m["name"] for m in loaded["per_layer"]}
    for m in loaded["end_to_end"] + loaded["per_layer"]:
        assert callable(bench_run.load_reader(m["name"]))


@pytest.mark.parametrize("config,traffic,why", [
    (_without(SEQ_CUT, "published"), SEQ_TRAFFIC, "states `published`"),
    (_without(SEQ_CUT, "deployment"), SEQ_TRAFFIC, "its `deployment`"),
    (_without(SEQ_CUT, published=_without(SEQ_CUT["published"],
                                          "vocab_size")),
     SEQ_TRAFFIC, "every key in `reduced`"),
    (_without(SEQ_CUT, n_routed_experts=4), SEQ_TRAFFIC, "8 routed experts"),
    (_without(SEQ_CUT, vocab_size=256, feed={"kind": "tokens", "vocab": 256}),
     SEQ_TRAFFIC, "an eighth of the vocabulary"),
    (_without(SEQ_CUT, hybrid_override_pattern="MEMEMM"), SEQ_TRAFFIC,
     "pattern's first"),
    (_without(SEQ_CUT, hybrid_override_pattern="MEM"), SEQ_TRAFFIC,
     "a whole period"),
    (_without(SEQ_CUT, feed={"kind": "tokens", "vocab": 4096}), SEQ_TRAFFIC,
     "draws its ids below vocab_size"),
    (_without(SEQ_CUT, reduced=SEQ_CUT["reduced"] + ["hidden_size"]),
     SEQ_TRAFFIC, "names a width"),
    (SEQ_CUT, dict(SEQ_TRAFFIC, generator="no_such_generator"),
     "no generator module"),
    (SEQ_CUT, _without(SEQ_TRAFFIC, "seq_len"), "seq_len"),
    (SEQ_CUT, dict(SEQ_TRAFFIC, rows_block=3), "divides the batch"),
    (_without(SEQ_CUT, reference="resnet50"), SEQ_TRAFFIC,
     "couples the rows"),
    (_without(SEQ_CUT, updater={"name": "adagrad", "learning_rate": 0.1}),
     SEQ_TRAFFIC, "no rule for this updater"),
], ids=["no_published", "no_deployment", "published_lacks_a_key",
        "four_experts", "a_sixteenth_of_the_vocabulary",
        "pattern_not_the_first_layers", "pattern_lacks_a_kind",
        "feed_outside_the_slice", "a_width_in_reduced",
        "generator_missing", "tokens_without_seq_len",
        "rows_block_does_not_divide", "rows_block_with_batch_statistics",
        "updater_without_a_reference_rule"])
def test_a_scratch_cell_that_breaks_the_contract_fails(config, traffic, why,
                                                       tmp_path):
    root = _scratch_root(tmp_path, config, traffic)
    with pytest.raises((AssertionError, KeyError), match=re.escape(why)):
        check_cell(root, "seq_cut_train")


def check_metric_entry(bench, entry):
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
        assert entry["moves"] in {m["name"] for m in bench["end_to_end"]}
        assert "\n" not in entry["layer"] and len(entry["layer"]) <= 200
    for cell in entry.get("workloads", []):
        assert cell in {w["name"] for w in bench["workloads"]}


@pytest.mark.parametrize("entry", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entries_keep_to_the_contract(entry):
    check_metric_entry(BENCH, entry)


def check_config_or_cell_entry(root, bench, entry):
    assert NAME.match(entry["name"])
    assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    if "file" in entry:
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        assert any(entry["file"].startswith(p + "/")
                   for p in bench["paths"])
        assert json.load(open(os.path.join(root, entry["file"])))[
            "reduced"] == entry["reduced"]
    else:
        assert set(entry) == {"name", "config", "traffic", "chips", "why"}
        assert entry["chips"] in (1, 4)
        assert entry["config"] in {c["name"] for c in bench["configs"]}


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"],
                         ids=lambda e: e["name"])
def test_config_and_cell_entries_keep_to_the_contract(entry):
    check_config_or_cell_entry(ROOT, BENCH, entry)


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

@pytest.mark.parametrize("config", [TINY_RESNET, TINY_VGG, TINY_RESNET_ADAM],
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
    # the CPU trace has no device plane: the readers of the trace and of
    # the spans under it read nothing and are left out, whichever cells
    # they list; the counters' and the clock's are there
    assert set(line["metrics"]) >= {"data_wait_ms.train", "dispatch_ms.train",
                                    "step_mfu_pct.train"}
    source = {m["name"]: m["source"] for m in BENCH["per_layer"]}
    assert not [name for name in line["metrics"]
                if source[name] in ("device_trace", "program_span")]
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


@pytest.mark.parametrize("config", [TINY_RESNET, TINY_RESNET_ADAM],
                         ids=lambda c: c["name"])
@pytest.mark.parametrize("plant", [_break_step_unchanged, _break_half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_a_fault_under_the_timed_path_is_not_correct(plant, config, tmp_path,
                                                     monkeypatch):
    plant(monkeypatch)
    line = _run(config, tmp_path)["line"]
    assert not line["correct"], line["checks"]
    failed = {k for k, c in line["checks"].items()
              if c["limit"] is not None and not c["value"] <= c["limit"]}
    assert failed & {"loss_gap", "grad_gap", "delta_gap"}
    if config["updater"]["name"] == "adam":
        # Adam's first steps move every element by about the learning rate
        # whatever the gradient's size: the change no longer sees a halved
        # batch, so the first gradient, read from `m`, has to
        assert "grad_median_gap" in failed
        if plant is _break_step_unchanged:
            assert failed >= {"delta_gap", "delta_median_gap"}
        else:
            assert "loss_gap" in failed


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


@pytest.fixture(scope="module", params=["sgd", "adam"])
def small_readings(request):
    config = SMALL_VGG if request.param == "sgd" else dict(SMALL_VGG,
                                                           updater=ADAM)
    pool = fit_loop.make_pool(4, 3, 16, config)
    out = {p: fit_loop.first_steps_of_reference(config, 4, pool, 3,
                                                precision=p)
           for p in ("f32", "bf16", "fp8")}
    out["half"] = fit_loop.first_steps_of_reference(config, 4, pool, 3,
                                                    rows=slice(0, 8))
    out["updater"] = request.param
    return out


# Under SGD a step's change is the gradient's, so every number sees both
# the control and the halved batch. Adam's first steps move every element by
# about the learning rate whatever the gradient's size: the change no longer
# tells a halved batch from the stated precision (0.07-0.11 against
# 0.02-0.09 here), and the later steps' losses carry the updater's own
# amplification of rounding (the stated bf16 reads 1e-3 where SGD's reads
# 1e-4). What still separates is what the first step alone gives: its loss,
# and the first gradient as read from `m`.
SEPARATES = {"sgd": {"control": ("loss_gap", "grad_median_gap"),
                     "half": ("loss_gap", "grad_median_gap",
                              "delta_median_gap")},
             "adam": {"control": ("loss1_gap", "grad_median_gap"),
                      "half": ("loss1_gap", "loss_gap", "grad_median_gap")}}


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
    for name in SEPARATES[small_readings["updater"]]["control"]:
        assert control[name] > 3 * stated[name], (name, stated, control)


def test_the_half_batch_fault_reads_well_above_the_stated_precision(
        small_readings):
    stated = compare.first_step_gaps(small_readings["bf16"],
                                     small_readings["f32"])
    got = compare.first_step_gaps(small_readings["half"],
                                  small_readings["f32"])
    adam = small_readings["updater"] == "adam"
    for name in SEPARATES[small_readings["updater"]]["half"]:
        times = 5 if adam and name == "loss_gap" else 10
        assert got[name] > times * stated[name], (name, stated, got)
    if adam:
        assert got["delta_gap"] < 3 * stated["delta_gap"]


# -- Adam, and a reference taken in blocks of rows -------------------------------

def test_the_references_adam_is_the_programs_over_three_steps():
    """`plain.adam` against `train/updaters._adam` on a small tree, with an
    element whose gradient is nought and one that is all but."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.train.updaters import make_updater

    rng = np.random.default_rng(0)

    def tree(scale):
        return {"a": {"W": jnp.asarray(scale * rng.normal(size=(3, 4)),
                                       jnp.float32),
                      "b": jnp.asarray(scale * rng.normal(size=(4,)),
                                       jnp.float32)},
                "b": {"W": jnp.asarray(scale * rng.normal(size=(4, 2)),
                                       jnp.float32)}}

    program = make_updater("adam", **{k: v for k, v in ADAM.items()
                                      if k != "name"})
    init, apply = plain.adam(ADAM)
    mine = theirs = start = tree(1.0)
    mine_state = plain.map_leaves(init, mine)
    their_state = program.init_tree(theirs)
    for t in range(3):
        grads = tree(0.1)
        grads["a"]["b"] = grads["a"]["b"].at[0].set(0.0).at[1].set(3e-8)
        updates, their_state = program.apply_tree(
            grads, their_state, ADAM["learning_rate"], float(t))
        theirs = jax.tree_util.tree_map(jnp.add, theirs, updates)
        mine, mine_state = plain.update_tree(apply, mine, mine_state, grads,
                                             jnp.float32(t))
    for got, want in zip(jax.tree_util.tree_leaves((mine, mine_state)),
                         jax.tree_util.tree_leaves((theirs, their_state))):
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-9)
    # about the learning rate a step, whatever the gradient's size; the
    # element whose gradient is nought has not moved
    moved = np.abs(np.asarray(mine["b"]["W"] - start["b"]["W"]))
    assert np.all(moved < 3.3 * ADAM["learning_rate"]) and moved.max() > \
        ADAM["learning_rate"]
    assert float(mine["a"]["b"][0]) == float(start["a"]["b"][0])


def test_no_rule_for_the_first_gradient_of_an_updater_it_does_not_know():
    with pytest.raises(ValueError, match="no rule to recover"):
        fit_loop._first_gradient_norms(None, {"name": "adagrad",
                                              "learning_rate": 0.1}, {}, {})


def _tiny_token_model(seed, vocab=50, width=16):
    """A token model written with `plain.py` blocks alone: embedding, a
    ReLU dense layer, a dense head, next-token cross-entropy."""
    import jax
    import jax.numpy as jnp

    k = jax.random.split(plain.seed_key(seed), 3)
    params = {
        "embed": {"W": jax.random.normal(k[0], (vocab, width), jnp.float32)},
        "mid": {"W": 0.3 * jax.random.normal(k[1], (width, 2 * width)),
                "b": jnp.zeros((2 * width,))},
        "head": {"W": 0.3 * jax.random.normal(k[2], (2 * width, vocab)),
                 "b": jnp.zeros((vocab,))}}

    def loss(p, x, y):
        h = plain.embedding(x, p["embed"]["W"])
        h = jax.nn.relu(plain.dense(h, p["mid"]["W"], p["mid"]["b"], "f32"))
        logits = plain.dense(h, p["head"]["W"], p["head"]["b"], "f32")
        return plain.next_token_cross_entropy(logits, y)

    return params, loss


@pytest.mark.parametrize("updater", [ADAM, {"name": "sgd",
                                            "learning_rate": 0.1},
                                     {"name": "nesterovs", "momentum": 0.9,
                                      "learning_rate": 0.1}],
                         ids=lambda u: u["name"])
@pytest.mark.parametrize("rows_block", [1, 2, 4])
def test_first_steps_in_blocks_of_rows_are_the_whole_batchs(rows_block,
                                                            updater):
    pool = fit_loop.make_pool(9, 3, 4, TINY_TOKENS, seq_len=12)
    params, loss = _tiny_token_model(9)
    whole = plain.first_steps(loss, params, pool, updater)
    params, loss = _tiny_token_model(9)   # the blocks consume their buffers
    blocks = plain.first_steps(loss, params, pool, updater,
                               rows_block=rows_block)
    gaps = compare.first_step_gaps(blocks, whole)
    assert gaps["leaves"] == 5 and gaps["leaves_left_out"] == 0
    for name in ("loss_gap", "grad_gap", "delta_gap"):
        assert gaps[name] < 1e-5, (name, gaps)
    assert whole["losses"][0] == pytest.approx(math.log(50), rel=0.2)


def test_rows_block_that_does_not_divide_the_batch_is_refused():
    pool = fit_loop.make_pool(9, 1, 4, TINY_TOKENS, seq_len=12)
    params, loss = _tiny_token_model(9)
    with pytest.raises(ValueError, match="does not divide"):
        plain.first_steps(loss, params, pool, ADAM, rows_block=3)


def test_rows_block_is_refused_for_a_reference_with_batch_statistics():
    pool = fit_loop.make_pool(4, 1, 4, TINY_RESNET)
    with pytest.raises(ValueError, match="statistics over the batch"):
        fit_loop.first_steps_of_reference(TINY_RESNET, 4, pool, 1,
                                          rows_block=2)
    # and taken for one whose rows do not interact
    pool = fit_loop.make_pool(4, 1, 4, SMALL_VGG)
    blocks = fit_loop.first_steps_of_reference(SMALL_VGG, 4, pool, 1,
                                               rows_block=2)
    whole = fit_loop.first_steps_of_reference(SMALL_VGG, 4, pool, 1)
    assert compare.first_step_gaps(blocks, whole)["grad_gap"] < 1e-5

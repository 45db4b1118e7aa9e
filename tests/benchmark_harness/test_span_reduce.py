"""CPU tests of `benchmark/span_reduce.py`, its eight readers and their
`BENCHMARK.json` entries: the wire-format reader against
`jax.profiler.ProfileData`, the reduction on a recorded scoped chip trace and
on hand-made windows, and the cases in which it has to read nothing."""

from __future__ import annotations

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run, span_reduce, trace_reduce  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NEW = ["fwd_ms.train", "bwd_ms.train", "update_ms.train",
       "conv_dense_ms.train", "host_cpu_ms.train", "idle_data_wait_ms.train",
       "idle_dispatch_ms.train", "idle_observe_ms.train"]
DEV, OPS, MODS = "/device:TPU:0", trace_reduce.OPS_LINE, \
    trace_reduce.MODULES_LINE
MS = 1_000_000


# -- scope paths ----------------------------------------------------------------

@pytest.mark.parametrize("scope,phase,layer,own", [
    ("jit(step)/jvp(L1_convolution)/conv_general_dilated:", "fwd",
     "L1_convolution", True),
    ("jit(step)/transpose(jvp(L1_convolution))/conv_general_dilated:", "bwd",
     "L1_convolution", True),
    ("jit(step)/transpose(jvp(Lstem_bn_batchnorm))/bn_bwd_apply", "bwd",
     "Lstem_bn_batchnorm", True),
    ("jit(step)/jvp(block_s1b3_add)/while/body/Ls1b1_conv_convolution/mul",
     "fwd", "Ls1b1_conv_convolution", True),
    ("jit(step)/jvp(loss)/reduce_sum:", "fwd", None, True),
    ("jit(step)/transpose(jvp(loss))/mul:", "bwd", None, True),
    ("jit(step)/update/reduce_sum:", "update", None, True),
    ("jit(step)/reduce_grads/all-reduce", "bwd", None, True),
    # JAX's own names alone are no scope of the program's: the parent of
    # this PR had these, and reads nothing
    ("jit(step)/jvp()/convert_element_type:", "fwd", None, False),
    ("jit(step)/transpose(jvp(jit(relu)))/select_n", "bwd", None, False),
    ("jit(step)/jit(update_fn)/mul", "fwd", None, False),
    ("", "fwd", None, False),
])
def test_scope_paths(scope, phase, layer, own):
    assert span_reduce.phase_of(scope) == phase
    assert span_reduce.layer_of(scope) == layer
    assert span_reduce.has_program_scope(scope) is own


# -- the wire-format reader ------------------------------------------------------

def test_read_xspace_agrees_with_profile_data(tmp_path):
    """On a trace taken here: every plane, line and event that
    `ProfileData` shows comes out of the wire-format reader with the same
    name and the same times, and the session's start is found."""
    import time

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    before = time.time_ns()
    jax.profiler.start_trace(str(tmp_path))
    with jax.named_scope("Lx_dense"):
        jax.block_until_ready(jax.jit(lambda a: a @ a)(jnp.ones((64, 64))))
    jax.profiler.stop_trace()
    path = trace_reduce.newest_xplane(str(tmp_path))
    mine = span_reduce.read_xspace(path, want=lambda name: True)
    theirs = list(ProfileData.from_file(path).planes)
    assert [p["name"] for p in mine] == [p.name for p in theirs]
    events = 0
    for got, plane in zip(mine, theirs):
        assert [l["name"] for l in got["lines"]] == \
            [l.name for l in plane.lines]
        for line_got, line in zip(got["lines"], plane.lines):
            want = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
            assert len(line_got["events"]) == len(want)
            t0 = line_got["timestamp_ns"]
            for (meta, offset_ps, dur_ps), (name, start, dur) in zip(
                    line_got["events"], want):
                assert got["event_metadata"][meta][0] == name
                assert t0 + offset_ps / 1000.0 == pytest.approx(start,
                                                                abs=1e-3)
                assert dur_ps / 1000.0 == pytest.approx(dur, abs=1e-3)
            events += len(want)
    assert events > 100
    start = span_reduce.session_start_ns(mine)
    assert before <= start <= time.time_ns()
    # by default the host's planes are not even parsed, and a trace with
    # no device plane gives no row
    assert [p["name"] for p in span_reduce.read_xspace(path)] == [
        span_reduce.TASK_PLANE]
    assert span_reduce.scoped_rows(span_reduce.read_xspace(path)) == []
    assert span_reduce.reduce([], []) is None
    assert span_reduce.of_run({"trace_dir": str(tmp_path)}) is None
    assert span_reduce.of_run({"trace_dir": None}) is None


# -- hand-made windows -----------------------------------------------------------

def _step_spans(step, wait0, d0, d1, end, sample=None, cpu=(0, 0)):
    def span(name, a, b, parent, cpu_ns=None):
        return {"name": name, "start_ns": a, "end_ns": b, "parent": parent,
                "step": step, "cpu_ns": cpu_ns}

    out = [dict(span("fit/step", wait0, end, None, sum(cpu)), n_steps=1),
           span("fit/data_wait", wait0, d0, "fit/step"),
           span("fit/dispatch", d0, d1, "fit/step", cpu[0]),
           span("fit/observe", d1, end, "fit/step", cpu[1])]
    if sample:
        out.append(span("devprof/sample", *sample, "fit/observe"))
    return out


def _two_runs(base=0):
    """Two runs of `jit_step`, 10 ms each and busy throughout, 4 ms apart;
    while the device waits, the fit thread ends step 0's observers (inside
    devprof's blocking read for 1.2 ms of it), waits half a millisecond
    for data, and spends the other 2 ms launching step 1."""
    fwd = "jit(step)/jvp(L0_convolution)/conv_general_dilated:"
    bwd = "jit(step)/transpose(jvp(L0_convolution))/conv_general_dilated:"
    rows = []
    for start in (0, 14 * MS):
        rows += [(DEV, MODS, "jit_step(1)", base + start, 10 * MS, ""),
                 (DEV, OPS, "%fusion.1 = f32[] fusion()", base + start,
                  3 * MS, fwd),
                 (DEV, OPS, "%fusion.2 = f32[] fusion()",
                  base + start + 3 * MS, 6 * MS, bwd),
                 (DEV, OPS, "%fusion.3 = f32[] fusion()",
                  base + start + 9 * MS, MS, "jit(step)/update/add:")]
    spans = _step_spans(0, base - 5 * MS, base - 4 * MS, base + 9 * MS,
                        base + 11 * MS + MS // 2,
                        sample=(base + 9 * MS + MS // 5,
                                base + 11 * MS + MS // 5),
                        cpu=(2 * MS, MS))
    spans += _step_spans(1, base + 11 * MS + MS // 2, base + 12 * MS,
                         base + 20 * MS, base + 25 * MS, cpu=(3 * MS, 0))
    return rows, spans


def test_a_gap_is_split_exactly_over_the_phases_it_straddles():
    rows, spans = _two_runs(base=1_790_000_000 * 10 ** 9)
    out = span_reduce.reduce(rows, spans)
    assert out["main_module"] == "jit_step" and out["steps"] == 2
    assert out["window_ns"] == 24 * MS and out["busy_ns"] == 20 * MS
    assert out["scoped_share"] == 1.0
    assert out["phase_ns"] == {"fwd": 6 * MS, "bwd": 12 * MS,
                               "update": 2 * MS}
    assert out["matmul_ns"] == 18 * MS
    assert out["layers"] == {"L0_convolution": {"fwd": 6 * MS,
                                                "bwd": 12 * MS}}
    assert out["idle_ns"] == {"fit/observe": 3 * MS // 2,
                              "fit/data_wait": MS // 2,
                              "fit/dispatch": 2 * MS,
                              "devprof/sample": 6 * MS // 5}
    assert sum(out["idle_ns"][p] for p in span_reduce.PHASES) \
        == out["window_ns"] - out["busy_ns"]
    assert len(out["idle_gaps"]) == 1 and out["idle_gaps"][0]["ns"] == 4 * MS
    # step 1 alone lies inside no window of two runs; step 0 neither
    assert out["fit_steps"] == 0 and out["cpu_ns"] == 0


def test_idle_that_no_fit_step_covers_is_left_unattributed():
    rows, spans = _two_runs()
    # the fit thread's second step is only seen from 13 ms on
    late = [dict(s, start_ns=max(s["start_ns"], 13 * MS))
            for s in spans if s["step"] == 1 and s["name"] != "fit/data_wait"]
    out = span_reduce.reduce(rows, [s for s in spans if s["step"] == 0]
                             + late)
    assert out["idle_ns"]["unattributed"] == 3 * MS // 2
    assert out["idle_ns"]["fit/dispatch"] == MS


@pytest.mark.parametrize("why", ["host_clock_ahead", "host_clock_behind",
                                 "no_timeline", "no_device_plane",
                                 "under_95_percent_scoped",
                                 "parents_names_alone"])
def test_reduce_reads_nothing_rather_than_a_wrong_number(why):
    rows, spans = _two_runs()
    hour = 3600 * 10 ** 9
    shift = lambda by: [dict(s, start_ns=s["start_ns"] + by,
                             end_ns=s["end_ns"] + by) for s in spans]
    if why == "host_clock_ahead":
        # a run starts on the device before any dispatch started
        spans = shift(hour)
    elif why == "host_clock_behind":
        spans = shift(-hour)
    elif why == "no_timeline":
        spans = None
    elif why == "no_device_plane":
        rows = []
    elif why == "under_95_percent_scoped":
        # 6% of the busy time loses its scope
        rows = [r if r[5] != "jit(step)/update/add:" else r[:5] + ("",)
                for r in rows]
        rows = [(p, l, n, s, d + (MS // 5 if "fusion.2" in n else 0), sc)
                for p, l, n, s, d, sc in rows]
    elif why == "parents_names_alone":
        rows = [r[:5] + (re.sub(r"L0_convolution", "", r[5])
                         .replace("update/", ""),) for r in rows]
    assert span_reduce.reduce(rows, spans) is None


def test_the_window_is_the_one_trace_reduce_cuts():
    """Five runs: the first and the last are dropped, as `reduce_rows`
    drops them, and busy and window agree with it."""
    scope = "jit(step)/jvp(L0_dense)/dot_general:"
    rows = []
    for i in range(5):
        rows += [(DEV, MODS, "jit_step(1)", i * 10 * MS, 9 * MS, ""),
                 (DEV, OPS, "%fusion.1 = f32[] fusion()", i * 10 * MS,
                  8 * MS, scope)]
    spans = [s for i in range(6) for s in _step_spans(
        i, (i - 1) * 10 * MS, (i - 1) * 10 * MS + MS, i * 10 * MS - MS,
        i * 10 * MS, cpu=(MS, MS // 2))]
    out = span_reduce.reduce(rows, spans)
    old = trace_reduce.reduce_rows([r[:5] for r in rows])
    assert out["steps"] == old["main_module_runs"] == 3
    assert out["window_ns"] * 1e-9 == pytest.approx(old["window_s"])
    assert out["busy_ns"] * 1e-9 == pytest.approx(old["busy_s"])
    # the fit/step spans that lie inside [10 ms, 39 ms]: steps 2 and 3
    assert out["fit_steps"] == 2 and out["cpu_ns"] == 3 * MS


# -- the recorded chip trace -----------------------------------------------------

def _fixture():
    doc = json.load(open(os.path.join(
        ROOT, "benchmark", "fixtures", "vgg16_scoped_trace.json")))
    rows = [(p, l, doc["names"][n], s, d, doc["scopes"][sc])
            for p, l, n, s, d, sc in doc["rows"]]
    return doc, rows


def test_reduce_on_the_recorded_scoped_chip_trace():
    """Three whole steps of VGG16 at batch 128 on the v5e, around the step
    that devprof sampled, with the fit thread's timeline of the same
    seconds (my chip run, PR 25)."""
    doc, rows = _fixture()
    out = span_reduce.reduce(rows, doc["spans"])
    old = trace_reduce.reduce_rows([r[:5] for r in rows])
    assert out["main_module"] == "jit_step"
    assert out["steps"] == old["main_module_runs"] == 3
    assert out["window_ns"] * 1e-9 == pytest.approx(old["window_s"],
                                                    rel=1e-6)
    assert out["busy_ns"] * 1e-9 == pytest.approx(old["busy_s"], rel=1e-6)
    expected = RECORDED
    assert out["scoped_share"] == pytest.approx(expected["scoped_share"],
                                                abs=1e-4)
    for phase, ns in expected["phase_ns"].items():
        assert out["phase_ns"][phase] == pytest.approx(ns, rel=1e-6), phase
    assert out["matmul_ns"] == pytest.approx(expected["matmul_ns"], rel=1e-6)
    # the three phases cover the scoped events once: within 1% of busy
    assert sum(out["phase_ns"].values()) == pytest.approx(
        out["busy_ns"], rel=0.01)
    for name, ns in expected["idle_ns"].items():
        assert out["idle_ns"].get(name, 0) == pytest.approx(ns, abs=1), name
    assert sum(out["idle_ns"].get(p, 0) for p in span_reduce.PHASES) \
        + out["idle_ns"].get("unattributed", 0) \
        == out["window_ns"] - out["busy_ns"]
    assert out["fit_steps"] == expected["fit_steps"]
    assert out["cpu_ns"] == expected["cpu_ns"]
    assert set(out["layers"]) == {
        f"L{i}_{kind}" for i, kind in enumerate(
            ["convolution"] * 2 + ["subsampling"] + ["convolution"] * 2
            + ["subsampling"] + (["convolution"] * 3 + ["subsampling"]) * 3
            + ["dense"] * 2 + ["output"])}
    heaviest = max(out["layers"], key=lambda k: sum(out["layers"][k].values()))
    assert heaviest == "L1_convolution"
    # the slice's one stall: the device ran dry under devprof's blocking
    # read, and stayed idle while the fit thread launched the next step
    stall = out["idle_gaps"][0]
    assert stall["by"]["devprof/sample"] > 0.9 * stall["ns"] > 2 * MS
    assert out["idle_gaps"][1]["by"]["fit/dispatch"] \
        == out["idle_gaps"][1]["ns"] > 2 * MS


# what `reduce` made of the fixture when it was recorded (my chip run, PR 25:
# seed 424243; the stall is devprof's: 2.80 ms of idle under its blocking
# read, then 3.24 ms until the fit thread had launched the next step)
RECORDED = {
    "scoped_share": 0.99395,
    "phase_ns": {"fwd": 79957591, "bwd": 179721334, "update": 21009},
    "matmul_ns": 224027055,
    "idle_ns": {"fit/observe": 2878713, "devprof/sample": 2739162,
                "fit/data_wait": 167148, "fit/dispatch": 3070035,
                "unattributed": 0},
    "fit_steps": 5, "cpu_ns": 20000000,
}


# -- the readers and their entries ----------------------------------------------

@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_reads_nothing_without_a_device_trace(name):
    read = bench_run.load_reader(name)
    assert read({"trace_dir": None}, None) is None
    assert read({"trace_dir": os.path.join(ROOT, "benchmark")}, None) is None
    # a traced run whose trace holds nothing the reduction can use
    assert read({"trace_dir": os.path.join(ROOT, "benchmark")},
                {"busy_s": 1.0, "window_s": 1.0}) is None


def test_readers_divide_by_the_runs_they_are_over(monkeypatch):
    reduced = {"steps": 4, "fit_steps": 2, "cpu_ns": 6 * MS,
               "matmul_ns": 40 * MS,
               "phase_ns": {"fwd": 8 * MS, "bwd": 20 * MS, "update": 0},
               "idle_ns": {"fit/observe": 2 * MS}}
    monkeypatch.setattr(span_reduce, "of_run", lambda facts: reduced)
    got = {name: bench_run.load_reader(name)({}, {"busy_s": 1.0})
           for name in NEW}
    assert got == {"fwd_ms.train": 2.0, "bwd_ms.train": 5.0,
                   "update_ms.train": 0.0, "conv_dense_ms.train": 10.0,
                   "host_cpu_ms.train": 3.0, "idle_data_wait_ms.train": 0.0,
                   "idle_dispatch_ms.train": 0.0,
                   "idle_observe_ms.train": 0.5}
    monkeypatch.setattr(span_reduce, "of_run",
                        lambda facts: dict(reduced, fit_steps=0))
    assert bench_run.load_reader("host_cpu_ms.train")({}, {}) is None


def test_a_program_without_a_timeline_reads_nothing(monkeypatch):
    """The parent of this PR: the benchmark's new files run against a
    program that has no `step_timeline`, and must not raise."""
    from deeplearning4j_tpu.utils import tracing

    assert span_reduce.program_spans() is not None
    monkeypatch.delattr(tracing, "step_timeline")
    assert span_reduce.program_spans() is None


def check_new_entry(bench, name):
    entries = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(entries) == 1
    entry = entries[0]
    assert re.match(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$", entry["name"])
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # the cells in which the reader finds something to read, this one
    # first: a later cell appends itself once its traced run reports the
    # metric
    assert entry["workloads"][:1] == ["vgg16_train_b128"]
    assert entry["unit"] == "ms/step" and entry["better"] == "lower"
    assert entry["moves"] == "train_examples_per_s_per_chip"
    assert entry["source"] == ("device_trace" if name in NEW[:4]
                               else "program_span")
    # a layer the benchmark already names, letter for letter
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"][:6]}
    assert callable(bench_run.load_reader(name))


@pytest.mark.parametrize("name", NEW)
def test_a_new_entry_keeps_to_the_contract(name):
    check_new_entry(BENCH, name)


def check_the_new_entries_follow_the_first_six(bench):
    """The fourteen entries that stood after PR 25, where they stood:
    whatever a later PR appends lies behind them."""
    names = [m["name"] for m in bench["per_layer"]]
    assert names[:6] == ["data_wait_ms.train", "dispatch_ms.train",
                         "step_mfu_pct.train", "device_step_ms.train",
                         "device_idle_pct.train", "peak_hbm_gib.train"]
    assert names[6:14] == NEW


def test_the_new_entries_are_appended_and_nothing_else_changed():
    check_the_new_entries_follow_the_first_six(BENCH)

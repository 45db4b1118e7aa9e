"""`BENCHMARK.json` grows by entries alone: a copy of the benchmark's files
with a fourth configuration, a fourth cell and a twenty-fifth per-layer
metric appended (files beside them, nothing that is there edited) passes
every assertion that the tests of this directory make about the file's
lists. Those assertions are functions of `bench` or of a root in their own
files, so the code that checks the real file checks the grown one here: a
later PR that pins a list to its own day's entries (a length, a `[-1]`, an
`==` over the whole of `configs`, `workloads` or `per_layer`) fails this test
in its own run. CPU only; nothing here loads the TPU library."""

from __future__ import annotations

import copy
import importlib.util
import json
import os
import shutil
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

SMALLTHINKER = "smallthinker_21b_train_b2_s8192"
READER = '''"""A scratch reader: the steps of the window, from the run's facts."""


def read(facts, trace):
    return float(facts["steps"]) if facts.get("steps") else None
'''


def _tests(name):
    spec = importlib.util.spec_from_file_location(
        "benchmark_harness_" + name, os.path.join(HERE, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def files():
    """The directory's other test files as modules: `h` is test_harness.py."""
    return types.SimpleNamespace(
        h=_tests("test_harness"), span=_tests("test_span_reduce"),
        nemotron=_tests("test_nemotron_h_cell"),
        smallthinker=_tests("test_smallthinker_cell"))


def grown_root(tmp_path, h) -> str:
    """The repository's `BENCHMARK.json` and `benchmark/` (but for the
    recorded fixtures and the tools) under a scratch root, with what a
    later PR brings: a configuration file, a traffic file and their two
    entries (test_harness.py's scratch cell), and a reader with its entry
    in `per_layer`."""
    root = h._scratch_root(tmp_path)
    shutil.copytree(
        os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
        ignore=shutil.ignore_patterns("fixtures", "tools", "__pycache__"),
        dirs_exist_ok=True)
    with open(tmp_path / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench["per_layer"].append({
        "name": "window_steps.train", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "fit-loop dispatch",
        "moves": "train_examples_per_s_per_chip",
        "workloads": ["seq_cut_train", SMALLTHINKER]})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    with open(tmp_path / "benchmark" / "metrics" / "window_steps.train.py",
              "w") as f:
        f.write(READER)
    return root


def test_a_grown_copy_passes_every_list_shaped_assertion(files, tmp_path,
                                                         monkeypatch):
    h, span, nemotron, smallthinker = (
        files.h, files.span, files.nemotron, files.smallthinker)
    root = grown_root(tmp_path, h)
    # the readers are found beside the copy's files, the scratch one too
    monkeypatch.setattr(bench_run, "PKG", os.path.join(root, "benchmark"))
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    real = h.BENCH
    for key in ("configs", "workloads", "per_layer"):
        # the copy is the real file and one entry more in each list
        assert bench[key][:-1] == real[key] and bench[key][-1] not in real[key]
    assert {key: bench[key] for key in bench if key not in (
        "configs", "workloads", "per_layer")} == {
        key: real[key] for key in real if key not in (
            "configs", "workloads", "per_layer")}

    # test_harness.py: the file as a whole, every entry, every cell
    h.check_keys_and_counts(bench)
    for entry in bench["end_to_end"] + bench["per_layer"]:
        h.check_metric_entry(bench, entry)
        assert callable(bench_run.load_reader(entry["name"]))
    for entry in bench["configs"] + bench["workloads"]:
        h.check_config_or_cell_entry(root, bench, entry)
    for cell in bench["workloads"]:
        h.check_cell(root, cell["name"])
        h.check_cell_metrics(root, cell["name"])
    # the fourth cell reads the six that list no cell and the one that
    # lists it; the metric reads something from a run's facts
    h.check_cell_metrics(root, "seq_cut_train", own=["window_steps.train"])
    assert bench_run.load_reader("window_steps.train")({"steps": 7}, None) \
        == 7.0
    assert bench_run.load_reader("window_steps.train")({}, None) is None

    # test_span_reduce.py: PR 25's eight, where they stood
    span.check_the_new_entries_follow_the_first_six(bench)
    for name in span.NEW:
        span.check_new_entry(bench, name)

    # the two decoder cells' files: the first three configurations and
    # cells, each cell's metrics, each cell's entries
    smallthinker.check_the_first_three_configurations_and_cells(bench)
    for cell_tests in (nemotron, smallthinker):
        cell_tests.check_the_cell_by_files_alone(root)
        cell_tests.check_the_cells_entries(bench)
    # and the twenty-fifth metric reached the cell it lists beside its own
    assert "window_steps.train" in {m["name"] for m in bench_run.load_cell(
        root, SMALLTHINKER)["per_layer"]}
    assert "window_steps.train" not in {
        m["name"] for m in bench_run.load_cell(
            root, nemotron.CELL)["per_layer"]}


@pytest.mark.parametrize("broken", [
    pytest.param(lambda b: b["per_layer"].insert(6, b["per_layer"].pop()),
                 id="an_entry_put_among_pr25s_eight"),
    pytest.param(lambda b: b["workloads"].insert(0, b["workloads"].pop()),
                 id="a_cell_put_before_the_first_three"),
    pytest.param(lambda b: b["per_layer"].__delitem__(14),
                 id="an_entry_of_the_nemotron_cell_taken_away"),
    pytest.param(
        lambda b: b["per_layer"][21]["workloads"].remove(SMALLTHINKER),
        id="the_smallthinker_cell_taken_off_an_entry_of_its_own"),
])
def test_a_copy_that_edits_what_stood_fails(broken, files):
    """The other side of the door: what stood may not move. Each of these
    is an edit, not an entry appended, and some check refuses it."""
    bench = copy.deepcopy(files.h.BENCH)
    broken(bench)
    with pytest.raises(AssertionError):
        files.span.check_the_new_entries_follow_the_first_six(bench)
        files.smallthinker.check_the_first_three_configurations_and_cells(
            bench)
        files.nemotron.check_the_cells_entries(bench)
        files.smallthinker.check_the_cells_entries(bench)

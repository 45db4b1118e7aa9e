"""Meta-checks that documentation claims stay true.

Round-4 verdict finding: a docstring cited an equivalence test that did
not exist ("manufactured verification"). This sweep greps every source
docstring/comment for `tests/<file>.py` citations and fails if any cited
file is missing — a claim of test coverage must point at a real test."""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAT = re.compile(r"tests/([A-Za-z0-9_]+\.py)")


def _source_files():
    for root, dirs, files in os.walk(os.path.join(REPO, "deeplearning4j_tpu")):
        dirs[:] = [d for d in dirs if not d.startswith("__pycache__")]
        for f in files:
            if f.endswith(".py"):
                yield os.path.join(root, f)
    for extra in ("bench.py", "__graft_entry__.py"):
        p = os.path.join(REPO, extra)
        if os.path.exists(p):
            yield p


def test_cited_test_files_exist():
    missing = []
    for path in _source_files():
        with open(path, encoding="utf-8") as f:
            text = f.read()
        for m in PAT.finditer(text):
            cited = os.path.join(REPO, "tests", m.group(1))
            if not os.path.exists(cited):
                missing.append(f"{os.path.relpath(path, REPO)} cites "
                               f"{m.group(0)}")
    assert not missing, "dangling test citations:\n" + "\n".join(missing)


def test_bench_vs_baseline_self_reports_trajectory():
    """bench.py's vs_baseline must come from the newest committed
    BENCH_r*.json (per-workload speedup ratios), not a hardcoded null —
    the perf trajectory is self-reporting."""
    import sys

    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.pop(0)

    name, prior = bench._prior_bench()
    if name is None:  # fresh clone without committed bench rounds
        assert bench._vs_baseline({"resnet50": {"value": 1.0}}, "cpu") is None
        return
    assert prior["workloads"]
    wl, entry = next(iter(prior["workloads"].items()))
    doubled = {wl: {"value": entry["value"] * 2}}
    vs = bench._vs_baseline(doubled, prior.get("backend"))
    assert vs["source"] == name
    assert abs(vs["speedup"][wl] - 2.0) < 1e-6
    # cross-backend ratios would be nonsense — omitted, with the reason
    mism = bench._vs_baseline(doubled, "not-" + str(prior.get("backend")))
    assert "speedup" not in mism and "mismatch" in mism["note"]


def test_bench_ab_refuses_mid_run_disabled_kernel():
    """_run_ab must not report a variant under the kernel's name when the
    SPI auto-disabled the helper mid-run (fn raised, layers fell back):
    that number is builtin throughput. Kill-switch state is restored."""
    import sys

    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.pop(0)
    from deeplearning4j_tpu.ops.helpers import (
        _HELPERS,
        helper_enabled,
        register_helper,
        set_helper_enabled,
    )

    register_helper("_ab_test", lambda: None, name="scratch")
    try:
        def run(on):
            if on:  # simulate the SPI guard disabling a raising helper
                set_helper_enabled("_ab_test", False)
            return 1.0

        results, errors = bench._run_ab(
            run, [("kern", True), ("builtin", False)], ("_ab_test",))
        assert "kern" not in results
        assert "disabled mid-run" in errors["kern"]
        assert results["builtin"] == 1.0
        assert helper_enabled("_ab_test") is True  # restored
    finally:
        _HELPERS.pop("_ab_test", None)


def _import_bench():
    import sys

    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.pop(0)
    return bench


def _stub_children(monkeypatch, bench, outcome):
    """Replace the child launcher: outcome(name) -> (json | None, err)."""
    calls = []

    def run_child(args, timeout, extra_env=None):
        calls.append(args)
        return outcome(args[1])

    monkeypatch.setattr(bench, "_run_child", run_child)
    return calls


@pytest.mark.parametrize("failing,err", [
    (None, None),
    ("lenet", "timeout"),
    ("resnet50", "rc=1: RuntimeError: conv/BN A/B arm failed"),
    ("*", "rc=1: no TPU"),
])
def test_bench_exit_code_follows_errors(monkeypatch, capsys, failing, err):
    """`python bench.py` exits non-zero when any workload errors, times
    out or is skipped — and zero only for a round without holes. There
    is no probe child: every launch is a workload."""
    import json

    bench = _import_bench()

    def outcome(name):
        if failing in (name, "*"):
            return None, err
        return {"value": 1.0, "backend": "tpu",
                "device": "TPU v5 lite"}, None

    calls = _stub_children(monkeypatch, bench, outcome)
    monkeypatch.setattr("sys.argv", ["bench.py"])
    rc = bench.main()
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert [c[0] for c in calls] == ["--workload"] * len(bench.WORKLOADS)
    if failing is None:
        assert rc == 0 and "errors" not in result
        assert result["device"] == "TPU v5 lite"
    else:
        assert rc == 1
        assert err in result["errors"]["lenet" if failing == "*"
                                       else failing]
    if failing == "*":  # nothing reached a device: also an infra error
        assert result["infra_error"] and not result["workloads"]


def test_bench_overall_deadline_skip_is_an_error(monkeypatch, capsys):
    bench = _import_bench()
    _stub_children(monkeypatch, bench, lambda name: (
        {"value": 1.0, "backend": "tpu", "device": "TPU v5 lite"}, None))
    monkeypatch.setattr(bench, "OVERALL_DEADLINE", 0.0)
    monkeypatch.setattr("sys.argv", ["bench.py", "--only", "lenet"])
    assert bench.main() == 1
    assert "skipped: overall deadline" in capsys.readouterr().out


def test_bench_child_on_another_backend_is_an_error(monkeypatch, capsys):
    bench = _import_bench()
    _stub_children(monkeypatch, bench, lambda name: (
        {"value": 1.0, "device": "x",
         "backend": "cpu" if name == "lenet" else "tpu"}, None))
    monkeypatch.setattr("sys.argv", ["bench.py", "--only", "resnet50,lenet"])
    assert bench.main() == 1
    assert "backend mismatch" in capsys.readouterr().out


def test_bench_orchestrator_never_initialises_a_backend():
    """A chip belongs to one process: the orchestrator may import jax but
    never bring a backend up, or its children could not have the chip.
    Run in a fresh interpreter (this one has long had a backend), with
    the children stubbed out, through both `main` and `main_multichip`.
    How it knows: `jax._src.xla_bridge.backends_are_initialized()` — the
    flag jax itself consults before `jax.distributed.initialize` — is
    still False afterwards."""
    import json
    import subprocess
    import sys

    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "import bench\n"
        "bench._run_child = lambda args, timeout, extra_env=None: (\n"
        "    {'value': 1.0, 'backend': 'tpu', 'device': 'TPU v5 lite',\n"
        "     'model_flops_per_step': 1e9}, None)\n"
        "sys.argv = ['bench.py']\n"
        "rcs = [bench.main(), bench.main_multichip()]\n"
        "from jax._src import xla_bridge\n"
        "print(json.dumps({'rcs': rcs, 'initialised':\n"
        "                  xla_bridge.backends_are_initialized()}))\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=240, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    assert verdict == {"rcs": [0, 0], "initialised": False}


def test_bench_workload_fails_on_hidden_fallback():
    """A workload in which a helper raised and the layer quietly took the
    built-in path has measured the built-in path: `_run_checked` turns
    that into an error (the child's exit code, hence the orchestrator's).
    The fallback mechanism itself is untouched — the caller still gets
    HelperError and its built-in retry."""
    bench = _import_bench()
    from deeplearning4j_tpu.ops.helpers import (
        _HELPERS,
        HelperError,
        get_helper,
        register_helper,
    )

    def boom():
        raise ValueError("kernel does not lower")

    def workload(name):
        with pytest.raises(HelperError):
            get_helper("_boom_op")()
        return {"value": 1.0}  # the layer's built-in retry "succeeded"

    register_helper("_boom_op", boom, name="boomer")
    try:
        with pytest.raises(RuntimeError, match="hidden fallback") as err:
            bench._run_checked(workload, "w")
        assert "auto-disabled _boom_op" in str(err.value)
        assert "fallback (raised) _boom_op" in str(err.value)
        assert "_boom_op not enabled" in str(err.value)
    finally:
        _HELPERS.pop("_boom_op", None)
    # and a clean workload passes, stamped with where it ran
    out = bench._run_checked(lambda name: {"value": 2.0}, "w")
    assert out["value"] == 2.0 and out["backend"] == "cpu"


def test_ops_import_raises_when_a_kernel_module_cannot_be_imported(
        monkeypatch):
    """One installation: a kernel module that does not import is a bug,
    not a backend without kernels — `deeplearning4j_tpu.ops` lets it
    raise instead of registering nothing and saying nothing."""
    import importlib
    import sys

    import deeplearning4j_tpu.ops as ops

    # None in sys.modules makes the import statement raise ImportError
    monkeypatch.setitem(sys.modules, "deeplearning4j_tpu.ops.pallas_lstm",
                        None)
    monkeypatch.delattr(ops, "pallas_lstm")
    with pytest.raises(ImportError):
        importlib.reload(ops)
    monkeypatch.undo()
    importlib.reload(ops)  # and with the module back it imports again
    assert ops.pallas_lstm is sys.modules["deeplearning4j_tpu.ops.pallas_lstm"]

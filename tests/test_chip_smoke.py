"""chip_smoke.py's phases, in this process, at a tiny size, on the CPU.

The program itself has one configuration (full width) and no CPU mode;
this file reaches its phases through `run_phases(Sizes(...))`, the
test-only entry, with the Pallas kernels in interpret mode. What it can
show here: every phase line parses and carries its keys, a failing phase
makes the exit code non-zero and withholds the final line, no TPU means
non-zero and no final line, and the compile cache lands where the rule
says. What only the chip can show is the program's own job.
"""

import json
import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

from deeplearning4j_tpu.ops import pallas_conv_bn, pallas_lstm  # noqa: E402
from deeplearning4j_tpu.utils import flops  # noqa: E402


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(pallas_conv_bn, "_INTERPRET", True)
    monkeypatch.setattr(pallas_lstm, "_INTERPRET", True)


def _tiny_sizes(**over):
    from deeplearning4j_tpu.models.charlstm import char_lstm_network
    from deeplearning4j_tpu.models.resnet import resnet_conf

    sizes = dict(
        resnet_conf=lambda: resnet_conf(
            blocks=(1, 1), widths=(4, 8), num_classes=5, image_size=16,
            stem_width=4),
        resnet_batch=8, resnet_image=16, resnet_classes=5, resnet_steps=2,
        lstm_net=lambda: char_lstm_network(
            vocab_size=11, hidden=8, layers=2, tbptt_length=4),
        lstm_vocab=11, lstm_batch=4, lstm_seq=8, lstm_batches=2,
        predict_sizes=(1, 3, 4, 2), predict_steps=5, predict_max_batch=4,
        prompts=((1, 2), (3,), (4, 5, 6)), gen_tokens=4, decode_slots=2,
        kernel_marker=None)  # interpret mode leaves no custom call
    sizes.update(over)
    return chip_smoke.Sizes(**sizes)


def _pretend_device(monkeypatch, platform, kind):
    class Dev:
        pass

    Dev.platform, Dev.device_kind = platform, kind
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])


def _phase_lines(capsys):
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    for line in lines:
        assert {"phase", "ok", "seconds", "compile_seconds", "run_seconds",
                "cache_hits", "cache_misses"} <= set(line), line
    return {line["phase"]: line for line in lines}


def test_phases_pass_at_tiny_size(interpret, capsys):
    device = chip_smoke.run_phases(_tiny_sizes(), multichip=False)
    lines = _phase_lines(capsys)
    assert list(lines) == ["device", "resnet50_train", "char_lstm_train",
                           "serve"]
    assert all(line["ok"] for line in lines.values()), lines
    assert device == {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                      "count": len(jax.devices())}
    resnet, lstm, serve = (lines["resnet50_train"], lines["char_lstm_train"],
                           lines["serve"])
    # the kernel path ran: a hit in every covered family, nothing hidden
    assert set(resnet["covered_conv_families"]) <= set(resnet["helper_hits"])
    assert {"bn_apply", "bn_bwd"} <= set(resnet["helper_hits"])
    assert lstm["helper_hits"] == {"lstm_seq": lstm["steps"]}
    assert {"lstm_seq", "lstm_step"} <= set(serve["helper_hits"])
    for line in (resnet, lstm, serve):
        assert line["helper_auto_disable_total"] == 0
        assert not line["helper_fallbacks"].get("raised")
        assert not line["helper_fallbacks"].get("probe_error")
    for line, ref in ((resnet, "xla_scores"), (lstm, "scan_scores")):
        assert line["scores"][:2] == pytest.approx(line[ref], rel=1e-4)
    # behind the server the decode engine keeps its two programs
    assert serve["decode_programs"] == 2
    assert serve["tokens_equal_reference"] == serve["generate_requests"]
    assert serve["forward_compiles"] <= len(serve["buckets"])


def test_multichip_phase_alone_at_tiny_size(interpret, capsys, monkeypatch):
    """With the option: the sharded phase and its one-device comparison,
    and no other phase. fit() attaches the mesh by itself (the product
    default, which conftest switches off for the rest of the suite)."""
    monkeypatch.setenv("DL4J_AUTO_MESH", "1")
    device = chip_smoke.run_phases(_tiny_sizes(), multichip=True)
    lines = _phase_lines(capsys)
    assert list(lines) == ["device", "multichip"]
    multi = lines["multichip"]
    assert multi["ok"], multi
    assert device["count"] == multi["devices"] == len(jax.devices()) > 1
    assert multi["all_reduce_ops_in_step"] > 0
    assert multi["allreduce_bytes_total"] > 0
    # kernels decline a partitioned program, with the reason booked
    assert multi["helpers_declined_partitioned_program"]


def test_failing_phase_fails_the_run_and_later_phases_still_report(
        interpret, capsys):
    """A phase in which a helper raised (and the layer quietly took the
    built-in path) fails; the run returns no device, so `main` prints no
    final line and exits 1."""
    from deeplearning4j_tpu.ops.helpers import (
        _HELPERS,
        helper_enabled,
        register_helper,
    )

    saved = _HELPERS["lstm_sequence"]

    def boom(*a, **k):
        raise ValueError("kernel does not lower")

    register_helper("lstm_sequence", boom, name="boomer",
                    family=lambda **_: "lstm_seq")
    try:
        device = chip_smoke.run_phases(_tiny_sizes(), multichip=False)
        assert helper_enabled("lstm_sequence") is False  # the SPI's doing
    finally:
        _HELPERS["lstm_sequence"] = saved
    assert device is None
    lines = _phase_lines(capsys)
    assert lines["resnet50_train"]["ok"]
    assert not lines["char_lstm_train"]["ok"]
    assert "hidden fallback" in lines["char_lstm_train"]["error"]
    assert "serve" in lines and not lines["serve"]["ok"]


def test_main_without_a_tpu_exits_nonzero_and_prints_no_result(
        capsys, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    for argv in ([], ["--multichip"]):
        assert chip_smoke.main(argv) == 2
        out, err = capsys.readouterr()
        assert out == "" and "no TPU" in err


def test_main_exit_code_and_last_line(monkeypatch, capsys, tmp_path):
    """`main` on a (pretended) one-chip TPU host: exit 0 and the contract's
    last line when the phases pass, exit 1 and no such line when not."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    _pretend_device(monkeypatch, "tpu", "TPU v5 lite")
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "run_phases",
                        lambda sizes, multichip: device)
    assert chip_smoke.main([]) == 0
    assert capsys.readouterr().out.splitlines()[-1] == json.dumps(
        {"ok": True, "device": device})
    assert chip_smoke.main(["--multichip"]) == 2  # four chips, or nothing
    monkeypatch.setattr(chip_smoke, "run_phases",
                        lambda sizes, multichip: None)
    assert chip_smoke.main([]) == 1
    assert capsys.readouterr().out == ""


def test_compile_cache_is_placed_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: the program sets no path. Unset:
    the one fixed directory of the checkout, nothing temporary in it."""
    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: seen.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    chip_smoke._place_compile_cache()
    assert seen == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    chip_smoke._place_compile_cache()
    assert seen == [("jax_compilation_cache_dir",
                     os.path.join(REPO, ".jax_cache"))]


# -- the peak tables answer for the kind the chip reports ---------------------

TABLES = [
    (flops.TPU_PEAK_FLOPS, "BENCH_PEAK_FLOPS", 197e12),
    (flops.TPU_HBM_BYTES, "BENCH_HBM_BYTES", 16e9),
    (flops.TPU_HBM_BANDWIDTH, "BENCH_HBM_BANDWIDTH", 819e9),
    (flops.TPU_ICI_BANDWIDTH, "BENCH_ICI_BANDWIDTH", 200e9),
]


@pytest.mark.parametrize("table,env_var,v5e", TABLES,
                         ids=[t[1] for t in TABLES])
def test_chip_lookup_matches_the_kind_the_chip_reports(
        monkeypatch, table, env_var, v5e):
    monkeypatch.delenv(env_var, raising=False)
    _pretend_device(monkeypatch, "tpu", "TPU v5 lite")
    # by table match: a default that could answer instead is poisoned
    assert flops._chip_lookup(table, env_var, "poison") == v5e == table["v5e"]
    _pretend_device(monkeypatch, "tpu", "TPU v9 imaginary")
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        flops._chip_lookup(table, env_var, "poison")
    # off the TPU: the documented planning constant, not a table guess
    _pretend_device(monkeypatch, "cpu", "cpu")
    assert flops._chip_lookup(table, env_var, "planning") == "planning"
    monkeypatch.setenv(env_var, "123")
    assert flops._chip_lookup(table, env_var, "planning") == 123.0


def test_public_peaks_on_the_v5e(monkeypatch):
    for _, env_var, _ in TABLES:
        monkeypatch.delenv(env_var, raising=False)
    _pretend_device(monkeypatch, "tpu", "TPU v5 lite")
    assert (flops.peak_flops_per_chip(), flops.peak_hbm_bytes_per_chip(),
            flops.hbm_bandwidth_per_chip(), flops.ici_bandwidth_per_chip()) \
        == (197e12, 16e9, 819e9, 200e9)
    _pretend_device(monkeypatch, "cpu", "cpu")
    assert flops.peak_hbm_bytes_per_chip() is None


def test_interpret_mode_is_an_error_on_a_tpu_backend(monkeypatch):
    from deeplearning4j_tpu.ops.helpers import interpret_mode

    assert interpret_mode(False) is False and interpret_mode(True) is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode(False) is False
    with pytest.raises(RuntimeError, match="interpret mode"):
        interpret_mode(True)
    monkeypatch.setattr(pallas_lstm, "_INTERPRET", True)
    with pytest.raises(RuntimeError, match="interpret mode"):
        pallas_lstm.supported(peephole=True, mask=None, gate_act="sigmoid",
                              cell_act="tanh", reverse=False)

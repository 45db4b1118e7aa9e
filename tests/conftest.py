"""Test harness configuration.

Mirrors the reference's test-backend strategy (SURVEY.md §4): tests run on
the CPU backend with a virtual 8-device mesh so data-parallel equivalence
tests (n-device == 1-device) run without TPU hardware — the analog of the
reference's local[N] Spark contexts and thread-based ParallelWrapper tests.

Must set env vars before jax is imported anywhere.
"""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# JAX_PLATFORMS from the environment is honoured, and the driver's command
# sets it to cpu. This pin is for a run that does not (a bare `pytest`):
# rehearsed here with the variable unset, jax then loads the TPU library
# before it settles on the CPU — one xdist worker would take the library's
# lock and the others fail on it, and on a machine with a chip the suite
# would take the chip. With the pin nothing but the CPU platform is tried.
# (tests/test_tpu_compile.py describes the chip on purpose, in a fixture.)
jax.config.update("jax_platforms", "cpu")
# Numeric parity tests assume true-f32 matmuls/convs (the TPU bench path
# deliberately runs bf16 — that is a PrecisionPolicy choice, not a default).
jax.config.update("jax_default_matmul_precision", "highest")

import pytest  # noqa: E402

# Crash forensics for the tier-1 session (scripts/t1.sh sets
# T1_BLACKBOX_ARTIFACT): arm the flight recorder's SIGTERM/faulthandler/
# atexit hooks so a wedged session killed by the suite timeout leaves a
# dump naming the stuck thread (render with `cli blackbox <artifact>`)
# instead of just "pytest died".
_bb_artifact = os.environ.get("T1_BLACKBOX_ARTIFACT")
if _bb_artifact:
    from deeplearning4j_tpu.utils.blackbox import install_crash_hooks

    install_crash_hooks(_bb_artifact)

# Auto-mesh OFF by default under the suite (same discipline as the
# devprof line below): the virtual platform above exposes 8 devices, so
# fit()'s mainline multi-device default would otherwise compile an
# 8-way SPMD program for every tiny fit in the suite — slow on a 2-core
# box and a behavior change under hundreds of single-device numeric
# tests. The dedicated sharding tests opt in (set_mesh / monkeypatch),
# and scripts/t1.sh runs the 2-simulated-device AUTO-mesh smoke in its
# own interpreter with DL4J_AUTO_MESH=1. setdefault, not assignment, so
# that smoke run's explicit =1 wins.
os.environ.setdefault("DL4J_AUTO_MESH", "0")

# Pallas interpret mode OFF for the suite, whatever the invoking shell
# exported: on the CPU test backend the conv/BN kernel probes must refuse
# the real kernel path (tests that want interpret-mode numerics flip
# pcb._INTERPRET themselves via the module fixture, and restore it).
os.environ["DL4J_PALLAS_INTERPRET"] = "0"

# Device-profiler sampling OFF under tier-1 (utils/devprof): the sampled
# block_until_ready would add timing jitter to every fit-heavy test on a
# loaded CI box. Tests that exercise the sampler configure it locally
# (and restore) — the suite's default stays timing-stable.
from deeplearning4j_tpu.utils import devprof as _devprof  # noqa: E402

_devprof.configure(sample_every=0)

# Opt-in session run ledger (scripts/t1.sh T1_LEDGER_DUMP=1): record the
# shared metrics registry's trajectory over the whole pytest session to
# a per-run artifact (utils/runledger), next to the metrics/trace dumps
# — replay with `cli metrics --ledger <artifact>`. The ledger's own
# dl4j-ledger daemon is excluded from the thread-leak guard below (it
# legitimately spans every test); ledgers that TESTS create are not.
_t1_ledger = None
if os.environ.get("T1_LEDGER_DUMP"):
    from deeplearning4j_tpu.utils import runledger as _t1_runledger

    _t1_ledger = _t1_runledger.RunLedger(
        os.environ.get("T1_LEDGER_ARTIFACT", "/tmp/_t1_ledger.jsonl"),
        sample_every=5.0,
        manifest={"run_id": "t1-session"})
    _t1_ledger.start()  # record only — not attach()ed, so the fit/
    # serving hooks stay on their no-ledger path and the overhead
    # guard tests measure what production measures

# Opt-in trace artifact (scripts/t1.sh T1_TRACE_DUMP=1): accumulate every
# span any tracing-enabled test records into one session JSONL, next to
# the metrics dump. Tests deliberately clear the global ring in their
# teardown (never leak spans across tests), so a plain end-of-session
# export would be empty — instead the global tracer's clear() flushes the
# ring to the artifact first, and sessionfinish flushes the remainder.
_t1_trace_path = (os.environ.get("T1_TRACE_ARTIFACT", "/tmp/_t1_trace.jsonl")
                  if os.environ.get("T1_TRACE_DUMP") else None)
if _t1_trace_path:
    import json as _json

    from deeplearning4j_tpu.utils import tracing as _t1_tracing

    try:
        os.unlink(_t1_trace_path)  # fresh artifact per session
    except OSError:
        pass

    def _t1_trace_flush():
        evs = _t1_tracing.get_tracer().recent()
        if evs:
            with open(_t1_trace_path, "a") as f:
                for ev in evs:
                    f.write(_json.dumps(ev) + "\n")

    _t1_orig_clear = _t1_tracing.Tracer.clear

    def _t1_clear_with_flush(self):
        if self is _t1_tracing.get_tracer():
            _t1_trace_flush()
        _t1_orig_clear(self)

    _t1_tracing.Tracer.clear = _t1_clear_with_flush


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests (multi-process, large fits)")


# -- input-pipeline thread-leak guard -----------------------------------------
# Every background thread the data pipeline spawns carries the
# PIPELINE_THREAD_PREFIX name. After each test, none may survive: a live
# one is a producer left blocked on a queue nobody drains (exactly the
# AsyncDataSetIterator break-mid-epoch leak this guard was added to
# catch). The grace window lets a worker that is already past its last
# put finish dying.

import weakref  # noqa: E402

_PIPELINE_LEAKS = []
# thread OBJECTS already charged to a test (idents get recycled, objects
# don't); weak so a reported thread that finally dies can be collected
_REPORTED_LEAKED_THREADS = weakref.WeakSet()


def _live_pipeline_threads():
    import threading

    from deeplearning4j_tpu.data.iterators import PIPELINE_THREAD_PREFIX

    # the ledger recorder daemon (utils/runledger, dl4j-ledger-*) is
    # held to the same contract as pipeline workers: a test that starts
    # a RunLedger must close() it (which unregisters the heartbeat and
    # joins the thread). The session-scoped T1_LEDGER_DUMP ledger is
    # exempt — it deliberately spans the whole run.
    session_ledger_thread = getattr(_t1_ledger, "_thread", None)
    # dl4j-sparse-* (parallel/sparse prefetch workers) are held to the
    # same contract: SparseEmbeddingPipeline.close() joins its worker
    return sorted(((t, t.name) for t in threading.enumerate()
                   if (t.name.startswith(PIPELINE_THREAD_PREFIX)
                       or t.name.startswith("dl4j-ledger")
                       or t.name.startswith("dl4j-sparse"))
                   and t is not session_ledger_thread
                   and t.is_alive()
                   and t not in _REPORTED_LEAKED_THREADS),
                  key=lambda pair: pair[1])


@pytest.fixture(autouse=True)
def _pipeline_thread_leak_guard(request):
    yield
    import time

    deadline = time.monotonic() + 2.0
    leaked = _live_pipeline_threads()
    while leaked and time.monotonic() < deadline:
        time.sleep(0.05)
        leaked = _live_pipeline_threads()
    if leaked:
        # charge each leaked thread to the test that leaked it, once —
        # without this, one leak would cascade failures across the rest
        # of the session
        _REPORTED_LEAKED_THREADS.update(t for t, _ in leaked)
        names = [name for _, name in leaked]
        _PIPELINE_LEAKS.append((request.node.nodeid, names))
        pytest.fail(
            f"leaked input-pipeline worker threads: {names} — a pipeline "
            "stage was not closed (close-on-break contract, "
            "data/iterators.py)", pytrace=False)


def pytest_sessionfinish(session, exitstatus):
    # One greppable line for scripts/t1.sh: the thread-leak guard's
    # verdict for the whole session (each leak also failed its test).
    if _PIPELINE_LEAKS:
        print(f"\nT1 THREAD GUARD: {len(_PIPELINE_LEAKS)} test(s) leaked "
              "pipeline worker threads:")
        for nodeid, names in _PIPELINE_LEAKS:
            print(f"T1 THREAD GUARD:   {nodeid}: {names}")
    else:
        print("\nT1 THREAD GUARD: ok (no leaked pipeline worker threads)")

    # Checkpoint tmp-orphan guard (scripts/t1.sh greps the verdict):
    # every checkpoint/snapshot/latest.json write goes tmp + os.replace,
    # so a `*.tmp` file still in the session's tmp dirs after the run is
    # a writer that died (or was never joined) mid-save — exactly the
    # torn-file class the atomic-rename discipline exists to prevent.
    # (CheckpointListener._gc sweeps stale orphans at runtime; the guard
    # catches tests that leave them behind without ever GC-ing.)
    try:
        basetemp = session.config._tmp_path_factory.getbasetemp()
    except Exception:
        basetemp = None
    orphans = []
    if basetemp is not None:
        try:
            orphans = sorted(
                str(p) for p in basetemp.rglob("*.tmp*")
                if "checkpoint_iter" in p.name
                or p.name.startswith(("latest.json.", "tables.npz.")))
        except OSError:
            pass
    if orphans:
        print(f"T1 CKPT TMP GUARD: {len(orphans)} orphaned checkpoint "
              "tmp file(s) left by the run:")
        for p in orphans:
            print(f"T1 CKPT TMP GUARD:   {p}")
    else:
        print("T1 CKPT TMP GUARD: ok (no orphaned checkpoint tmp files)")

    # Perf snapshot (scripts/t1.sh greps the verdict): the static cost
    # model's totals for the tiny preset, recomputed every session — a
    # FLOP-accounting change (a costmodel.py edit, a new primitive rule)
    # moves these numbers, so accidental model drift is visible in the
    # gate output instead of silently re-basing every MFU claim.
    try:
        from deeplearning4j_tpu.analysis.costmodel import train_step_cost
        from deeplearning4j_tpu.models.resnet import tiny_resnet_conf
        from deeplearning4j_tpu.nn.compgraph import ComputationGraph

        _cm = train_step_cost(ComputationGraph(tiny_resnet_conf()).init(),
                              batch_size=2)
        print(f"T1 PERF SNAPSHOT: tiny_resnet(batch=2) "
              f"model_flops={_cm.model_flops:.0f} "
              f"flops_total={_cm.flops_total:.0f} "
              f"bytes_total={_cm.bytes_total:.0f} "
              f"activation_peak_bytes={_cm.activation_peak_bytes}")
    except Exception as e:  # the snapshot must never fail the suite
        print(f"T1 PERF SNAPSHOT: unavailable ({type(e).__name__}: {e})")

    # Opt-in trace artifact (scripts/t1.sh T1_TRACE_DUMP=1): flush
    # whatever the session's final tests left in the ring; everything
    # earlier was flushed by the clear() hook above. Render with
    # `cli trace <artifact>`.
    if _t1_trace_path:
        try:
            _t1_trace_flush()
        except Exception as e:  # an artifact failure must not fail the
            # suite
            print(f"[conftest] trace dump failed: {e}", file=sys.stderr)

    # Opt-in session run ledger (scripts/t1.sh T1_LEDGER_DUMP=1): final
    # sample + close, so the artifact ends with the session's last
    # registry state (replay: cli metrics --ledger <artifact>).
    if _t1_ledger is not None:
        try:
            _t1_ledger.close()
        except Exception as e:  # an artifact failure must not fail the
            # suite
            print(f"[conftest] ledger dump failed: {e}", file=sys.stderr)

    # Opt-in observability artifact (scripts/t1.sh T1_METRICS_DUMP=1):
    # dump the process-global metrics registry after the run so compile
    # counts / helper events can be diffed across PRs.
    if not os.environ.get("T1_METRICS_DUMP"):
        return
    import json

    from deeplearning4j_tpu.utils.metrics import get_registry

    path = os.environ.get("T1_METRICS_ARTIFACT", "/tmp/_t1_metrics.json")
    try:
        with open(path, "w") as f:
            json.dump(get_registry().snapshot(), f, indent=2, sort_keys=True)
    except Exception as e:  # an artifact failure must not fail the suite
        print(f"[conftest] metrics dump failed: {e}", file=sys.stderr)


@pytest.fixture
def rng_key():
    import jax

    return jax.random.PRNGKey(12345)

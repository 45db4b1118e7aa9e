#!/usr/bin/env bash
# Tier-1 verify — the exact command from ROADMAP.md, wrapped so builders
# and CI invoke ONE entrypoint instead of each re-typing (and drifting
# from) the canonical flags. Prints DOTS_PASSED=<n> after the run.
#
# Gate semantics: the exit status reports REGRESSIONS, not raw failures.
# The growth seed ships 35 pre-existing failures; a raw count (or
# pytest's exit code) cannot distinguish new breakage from inherited
# breakage. So the failing-test NAMES are recorded to an artifact
# ($T1_FAILURES_ARTIFACT, default /tmp/_t1_failures.txt) and diffed
# against the committed baseline tests/tier1_baseline_failures.txt:
#   exit 0  — no failing test that is not already in the baseline
#   exit 1  — new failures (they are listed)
#   exit >1 — pytest itself died (timeout, internal error, interrupt)
# Slow-marked tests (serving load, multi-process) are excluded — that is
# what keeps tier-1 fast.
set -o pipefail
cd "$(dirname "$0")/.."

# -- static-analysis gate ----------------------------------------------------
# Concurrency/robustness lint (analysis/lint.py: bare except, timeout-less
# queue ops, unnamed/non-daemon threads, lock-order cycles, stray print)
# diffed against the committed scripts/lint_baseline.txt. This subsumes
# the old inline print-grep guard (print is finding code CC006).
bash scripts/lint.sh || exit 1

# -- 2-simulated-device sharding smoke ---------------------------------------
# The mainline multi-chip fit() path — auto-attached mesh, in-graph
# gradient all-reduce, sharded == single-device numerics — exercised
# under a forced 2-device CPU platform with the PRODUCTION default
# DL4J_AUTO_MESH=1 (the main suite below runs with auto-mesh off so its
# hundreds of single-device fits don't each compile an 8-way SPMD
# program). A separate interpreter because the device count is fixed at
# backend init. DL4J_GRAD_BUCKET_BYTES=512 forces the smoke nets
# (~1 KB of grads — far under the 4 MiB default, which would collapse
# them to one bucket) to split into >1 gradient bucket, so the BUCKETED
# reduce path is what this smoke exercises, not the degenerate
# one-bucket schedule.
rm -f /tmp/_t1_sharding.log
if timeout -k 10 240 env JAX_PLATFORMS=cpu DL4J_AUTO_MESH=1 \
    DL4J_GRAD_BUCKET_BYTES=512 \
    XLA_FLAGS="--xla_force_host_platform_device_count=2" \
    python -m pytest tests/test_sharded_step.py -q -m 'not slow' -k smoke \
    -p no:cacheprovider > /tmp/_t1_sharding.log 2>&1; then
    echo "T1 SHARDING SMOKE: ok (2 simulated devices, auto-mesh fit)"
else
    echo "T1 SHARDING SMOKE: FAILED — tail of /tmp/_t1_sharding.log:"
    tail -20 /tmp/_t1_sharding.log
    exit 1
fi

# -- decode-engine smoke ------------------------------------------------------
# The continuous-batching autoregressive tier (serving/decode.py): a tiny
# charlstm engine with 4 slots and 2 weighted tenants serves mixed
# prompts through one live weight swap — asserting per-tenant book
# conservation AND a constant program cache after warmup (zero retraces
# across admissions and the swap: the O(1)-compile contract).
rm -f /tmp/_t1_decode.log
if timeout -k 10 180 env JAX_PLATFORMS=cpu \
    python -m deeplearning4j_tpu.serving.decode --smoke \
    > /tmp/_t1_decode.log 2>&1; then
    echo "T1 DECODE SMOKE: ok (4 slots, 2 tenants, 1 weight swap, zero retraces)"
else
    echo "T1 DECODE SMOKE: FAILED — tail of /tmp/_t1_decode.log:"
    tail -20 /tmp/_t1_decode.log
    exit 1
fi

# -- tenant-books smoke --------------------------------------------------------
# The cross-tier chip-budget ledger (utils/resourcemeter + utils/tenancy):
# two tenants through the decode smoke plus one metered fit in its own
# interpreter, asserting per-tenant device-seconds sum to the process
# total per tier (spend conservation), the outcome books balance, and
# `cli tenants` renders the in-process view with exit 0.
rm -f /tmp/_t1_tenants.log
if timeout -k 10 240 env JAX_PLATFORMS=cpu \
    python -m deeplearning4j_tpu.utils.resourcemeter --smoke \
    > /tmp/_t1_tenants.log 2>&1; then
    echo "T1 TENANT BOOKS: ok (decode tenants + metered fit, cross-tier conservation)"
else
    echo "T1 TENANT BOOKS: FAILED — tail of /tmp/_t1_tenants.log:"
    tail -20 /tmp/_t1_tenants.log
    exit 1
fi

# -- recsys sparse-pipeline smoke ---------------------------------------------
# The sparse-embedding tier (parallel/sparse over the sharded
# paramserver): tiny table, 2 in-process endpoints, zipf ids, a few
# pipelined steps — asserting the cache books conserve (pull_rows ==
# cache_hit + cache_miss), the prefetch-on trajectory is byte-identical
# to the synchronous one (cache + prefetch are transparent), and zero
# dl4j-sparse-* threads survive close().
rm -f /tmp/_t1_recsys.log
if timeout -k 10 180 env JAX_PLATFORMS=cpu \
    python -m deeplearning4j_tpu.parallel.sparse --smoke \
    > /tmp/_t1_recsys.log 2>&1; then
    echo "T1 RECSYS SMOKE: ok (2 endpoints, zipf ids, books conserve, prefetch == sync)"
else
    echo "T1 RECSYS SMOKE: FAILED — tail of /tmp/_t1_recsys.log:"
    tail -20 /tmp/_t1_recsys.log
    exit 1
fi

# -- lock-order sanitizer smoke -----------------------------------------------
# The concurrency audit (utils/locktrace + analysis/concurrency_audit):
# serving + decode + sparse/paramserver run with DL4J_LOCKCHECK armed,
# their witnessed lock-acquisition orders merged with the lexical lock
# graph, and ALL CN001/CN002/CN003 finding names diffed against the
# committed scripts/lock_baseline.txt (ideally empty). A new name means
# a lock-order cycle, a blocking call under a lock, or a jitted
# dispatch entered with a lock held crept into a mainline tier.
rm -f /tmp/_t1_lockaudit.log /tmp/_t1_lock_findings.txt
if timeout -k 10 420 env JAX_PLATFORMS=cpu DL4J_LOCKCHECK=1 \
    python -m deeplearning4j_tpu.analysis.concurrency_audit --smoke --quiet \
    --baseline scripts/lock_baseline.txt \
    --names-out /tmp/_t1_lock_findings.txt \
    > /tmp/_t1_lockaudit.log 2>&1; then
    echo "T1 LOCK AUDIT: ok ($(grep -a '^lock audit:' /tmp/_t1_lockaudit.log | tail -1))"
else
    echo "T1 LOCK AUDIT: FAILED — tail of /tmp/_t1_lockaudit.log:"
    tail -20 /tmp/_t1_lockaudit.log
    echo "T1 LOCK AUDIT: finding names artifact: /tmp/_t1_lock_findings.txt"
    exit 1
fi

# -- kernel-coverage smoke ----------------------------------------------------
# The coverage contract (analysis/kernelcoverage.py): every ResNet-50 conv
# instance must resolve to covered, declined-with-roofline-verdict or
# refused-by-the-chip-compiler in planning mode — any other unsupported
# shape is a kernel-family hole nobody decided on, and fails the gate.
# Pure config walking, no trace.
rm -f /tmp/_t1_kcov.log
if timeout -k 10 120 env JAX_PLATFORMS=cpu \
    python -m deeplearning4j_tpu.analysis.kernelcoverage --preset resnet50 \
    > /tmp/_t1_kcov.log 2>&1; then
    echo "T1 KERNEL COVERAGE: ok ($(tail -1 /tmp/_t1_kcov.log))"
else
    echo "T1 KERNEL COVERAGE: FAILED — tail of /tmp/_t1_kcov.log:"
    tail -20 /tmp/_t1_kcov.log
    exit 1
fi

# -- the canonical tier-1 pytest run -----------------------------------------
# T1_METRICS_DUMP=1 makes tests/conftest.py write the shared metrics
# registry's snapshot after the session (T1_METRICS_ARTIFACT, default
# /tmp/_t1_metrics.json) — diff compile counts across PRs.
# T1_BLACKBOX_ARTIFACT arms the flight recorder's crash hooks
# (tests/conftest.py -> utils/blackbox.install_crash_hooks): a session
# the timeout kills leaves a dump naming the wedged thread — render it
# with `python -m deeplearning4j_tpu.cli blackbox <artifact>`.
blackbox="${T1_BLACKBOX_ARTIFACT:-/tmp/_t1_blackbox.json}"
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu T1_BLACKBOX_ARTIFACT="$blackbox" python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)

artifact="${T1_FAILURES_ARTIFACT:-/tmp/_t1_failures.txt}"
baseline="tests/tier1_baseline_failures.txt"
# FAILED lines carry "<id> - <reason>"; ERROR lines (collection errors)
# carry the file — both are regressions when not in the baseline. Strip
# the reason suffix rather than taking field 2: parametrized ids may
# contain spaces and a truncated id could mask a sibling-param regression.
grep -aE '^(FAILED|ERROR) ' /tmp/_t1.log \
    | sed -e 's/^FAILED //' -e 's/^ERROR //' -e 's/ - .*$//' \
    | sort -u > "$artifact"

if [ "$rc" -gt 1 ]; then
    echo "T1: pytest exited rc=$rc (timeout/internal error) — not gating on names"
    if [ -f "$blackbox" ]; then
        echo "T1 BLACKBOX: $blackbox (render: python -m deeplearning4j_tpu.cli blackbox $blackbox)"
        [ -f "$blackbox.stacks.txt" ] && echo "T1 BLACKBOX: native-level thread stacks: $blackbox.stacks.txt"
    else
        echo "T1 BLACKBOX: no artifact at $blackbox (session died before the hooks armed?)"
    fi
    # a wedged session's ledger still holds everything sampled up to the
    # kill — the metric trajectory INTO the failure
    if [ -n "${T1_LEDGER_DUMP:-}" ] && [ -f "${T1_LEDGER_ARTIFACT:-/tmp/_t1_ledger.jsonl}" ]; then
        echo "T1 LEDGER: ${T1_LEDGER_ARTIFACT:-/tmp/_t1_ledger.jsonl} (replay: python -m deeplearning4j_tpu.cli metrics --ledger ${T1_LEDGER_ARTIFACT:-/tmp/_t1_ledger.jsonl})"
    fi
    exit "$rc"
fi
new_failures=$(comm -13 <(sort -u "$baseline") "$artifact")
if [ -n "$new_failures" ]; then
    echo "T1 REGRESSIONS — failing tests not in $baseline:"
    echo "$new_failures"
    exit 1
fi
if [ -n "${T1_METRICS_DUMP:-}" ]; then
    echo "T1 metrics snapshot: ${T1_METRICS_ARTIFACT:-/tmp/_t1_metrics.json}"
fi
# T1_TRACE_DUMP=1 makes tests/conftest.py export the session's span ring
# as JSONL (T1_TRACE_ARTIFACT, default /tmp/_t1_trace.jsonl) — render
# with `python -m deeplearning4j_tpu.cli trace <artifact>`.
if [ -n "${T1_TRACE_DUMP:-}" ]; then
    echo "T1 trace dump: ${T1_TRACE_ARTIFACT:-/tmp/_t1_trace.jsonl}"
fi
# T1_LEDGER_DUMP=1 makes tests/conftest.py record the whole session's
# metrics-registry trajectory as a run-ledger artifact
# (T1_LEDGER_ARTIFACT, default /tmp/_t1_ledger.jsonl) — replay with
# `python -m deeplearning4j_tpu.cli metrics --ledger <artifact>`.
if [ -n "${T1_LEDGER_DUMP:-}" ]; then
    echo "T1 ledger dump: ${T1_LEDGER_ARTIFACT:-/tmp/_t1_ledger.jsonl}"
fi
# surface the conftest thread-leak guard's session verdict (each leak also
# failed its test above — this is the at-a-glance summary)
grep -a '^T1 THREAD GUARD:' /tmp/_t1.log || echo "T1 THREAD GUARD: no verdict line (session died early?)"
# checkpoint tmp-orphan guard: a *.tmp file surviving the session is a
# save that died between write and atomic rename (conftest scans the
# run's tmp dirs — same spirit as the thread-leak guard)
grep -a '^T1 CKPT TMP GUARD:' /tmp/_t1.log || echo "T1 CKPT TMP GUARD: no verdict line (session died early?)"
# perf snapshot: the static cost model's totals for the tiny preset
# (conftest recomputes per session) — accidental FLOP-model drift shows
# up here as a changed number, not as a silently re-based MFU claim
grep -a '^T1 PERF SNAPSHOT:' /tmp/_t1.log || echo "T1 PERF SNAPSHOT: no verdict line (session died early?)"
echo "T1 OK: $(wc -l < "$artifact" | tr -d ' ') failing (all within the $(wc -l < "$baseline" | tr -d ' ')-name baseline); artifact: $artifact"
exit 0

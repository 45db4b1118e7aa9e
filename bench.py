"""Benchmark entry point — prints ONE JSON line.

Headline: ResNet-50 training images/sec/chip (BASELINE.md metric of
record) with an analytic-MFU estimate; the `workloads` field carries the
full table (LeNet-MNIST images/sec, GravesLSTM char-rnn tokens/sec, each
with its own MFU, plus `parallel_inference` serving requests/sec/chip
with p50/p99 latency).

Protocol (BASELINE.md): synthetic data (BenchmarkDataSetIterator
equivalent) to exclude ETL; public fit() API drives every workload;
steady-state steps timed after a warmup fit that includes compilation;
bf16 compute policy on TPU, f32 on CPU. The reference publishes no numbers
(BASELINE.json published={}), so vs_baseline is null — an honest "no
published baseline", not a self-graded 1.0.

Processes: a chip belongs to one process at a time, so the orchestrator
(`main` / `main_multichip`) never initialises a jax backend itself — it
runs each workload in its own child process, strictly one after another,
with a per-workload timeout and an overall deadline. There is no separate
probe: the first workload that finds no device is the probe. The headline
JSON is always printed, with per-workload errors for whatever did not
finish ("timeout", "rc=N ...", or "skipped: ..."), and the exit code is
non-zero whenever there is any such error. A workload also fails when a
kernel arm of its A/B raised or a helper fell back behind its back
(`ops/helpers.hidden_fallbacks`): what was measured then is not what the
row is named for.

Compile cache: children use `JAX_COMPILATION_CACHE_DIR` when it is set and
`<checkout>/.jax_cache` otherwise (`_child_env`); no other path is set.
"""

import json
import os
import subprocess
import sys
import time

import jax  # import only: the orchestrator never initialises a backend
import numpy as np

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import ExistingDataSetIterator
from deeplearning4j_tpu.utils.flops import (
    peak_flops_per_chip,
    train_step_flops_for,
)


def _onehot(rng, n, k):
    y = np.zeros((n, k), np.float32)
    y[np.arange(n), rng.integers(0, k, n)] = 1.0
    return y


def _device_dataset(x, y) -> DataSet:
    """Pre-stage the synthetic batch in HBM — the benchmark protocol
    excludes ETL (BenchmarkDataSetIterator equivalent), and re-uploading
    the same batch every step would measure the host link, not the chip."""
    import jax

    return DataSet(jax.device_put(x), jax.device_put(y))


def _step_flops(net_factory, batch, timesteps: int = 16):
    """Model FLOPs of one optimizer step for a workload's MFU, sourced
    from the jaxpr cost model of the REAL step program (helpers
    disabled during the trace — model FLOPs are implementation-
    independent), falling back to the analytic per-layer estimate.
    Returns (flops_per_step, source); the source is recorded next to
    every MFU so a FLOP-accounting change can never masquerade as a
    speedup (the vs_baseline drift check reads it)."""
    net = net_factory()
    try:
        return train_step_flops_for(net, batch, timesteps=timesteps)
    finally:
        del net  # free the throwaway params before the timed runs


def _doctor_refusal(conf, unit):
    """Honesty mechanism (the PR-2 A/B precedent, applied to model
    validity): a workload whose model config fails the static doctor at
    ERROR severity must not headline a throughput number — a broken
    graph can trace into something fast and wrong. Returns the refusal
    dict to emit instead of benching, or None when the model is sound."""
    from deeplearning4j_tpu.analysis import doctor_errors

    errs = doctor_errors(conf)
    if not errs:
        return None
    return {
        "value": None,
        "unit": unit,
        "doctor_errors": [f"{f.name}: {f.message}" for f in errs],
        "note": "model failed `cli doctor` at ERROR severity; refusing "
                "to headline a broken model's throughput",
    }


def _sync(net):
    """Wait for the device to finish the last dispatched step: its score
    and the params it wrote are outputs of that one program."""
    jax.block_until_ready((net._score, net.params_list))


def _time_fit(net, make_iter, steps, warmup=True, reps=3):
    """Differenced timing: warmup (compile), then time fits of N and 2N
    steps and report t(2N) - t(N) — what one fit() call costs whatever
    its length (pipeline start-up, the final sync) cancels out. The
    warmup runs a full `steps`-length fit so every program the timed runs will use (fused
    multi-batch chunks AND any per-batch tail) is compiled before t1;
    pass warmup=False on repeat measurements of an already-warm net.

    The marginal difference is taken as the MEDIAN of `reps` t-pairs:
    host timing varies run to run by more than some workloads' whole
    measurement window."""

    def timed(k):
        it = make_iter(k)
        before = net.iteration
        t0 = time.perf_counter()
        net.fit(it, epochs=1, async_prefetch=True)
        _sync(net)
        dt = time.perf_counter() - t0
        return dt, net.iteration - before

    if warmup:  # same chunking pattern as the timed run
        timed(steps)
    trials = []
    for _ in range(max(1, reps)):
        t1, n1 = timed(steps)
        t2, n2 = timed(2 * steps)
        assert n2 == 2 * n1, (n1, n2)
        trials.append((max(t2 - t1, 1e-9), n1))
    trials.sort()
    return trials[len(trials) // 2]


def _run_ab(run, variants, ops):
    """Shared A/B harness for helper-vs-builtin workloads: snapshots and
    restores the helper kill-switch state, runs each (name, helpers_on)
    variant, and detects a MID-RUN auto-disable — a helper fn that raised
    was disabled by the SPI and the layers fell back, so that variant
    measured builtin throughput and must not be reported under the
    kernel's name (the availability lie the A/B exists to prevent).
    Returns (results, errors); the workloads raise on any error, so that
    the row is never headlined by the arm that happened to survive."""
    from deeplearning4j_tpu.ops.helpers import (
        helper_enabled,
        set_helper_enabled,
    )

    results, errors = {}, {}
    saved = {op: helper_enabled(op) for op in ops}
    try:
        for name, on in variants:
            try:
                results[name] = run(on)
            except Exception as e:  # e.g. pallas lowering failure
                import traceback

                traceback.print_exc(file=sys.stderr)
                errors[name] = f"{type(e).__name__}: {e}"
                continue
            if on and any(helper_enabled(op) is False for op in ops):
                results.pop(name, None)
                errors[name] = ("helper disabled mid-run (fn raised; see "
                                "log) — measured value was the builtin "
                                "fallback and is not reported as the kernel")
                for op in ops:
                    set_helper_enabled(op, True)
    finally:
        # restore the caller's kill-switch state, don't force-enable
        for op, enabled in saved.items():
            if enabled is not None:
                set_helper_enabled(op, enabled)
    return results, errors


def bench_resnet50(batch=128, steps=8, image_size=224, classes=1000):
    """images/sec/chip for the headline workload. A/B-measures BOTH conv/BN
    paths in the same run — the Pallas conv+BN-stats epilogue fusion
    registered in the conv2d/batch_norm Helper slots
    (ops/pallas_conv_bn.py) and the default XLA lowering — the headline is
    the faster, the loser is reported under `vs_alternate`: the same
    honesty mechanism the char-LSTM workload uses (a kernel that
    compiles-but-loses stays visible instead of silently winning on
    availability)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.resnet import resnet50_conf
    from deeplearning4j_tpu.nn.compgraph import ComputationGraph
    from deeplearning4j_tpu.ops.helpers import get_helper, set_helper_enabled

    on_tpu = jax.default_backend() not in ("cpu",)
    if not on_tpu:  # CPU smoke config — full ResNet-50 on CPU is pointless
        batch, steps, image_size, classes = 8, 4, 64, 10
        # CPU-interpret A/B: run the Pallas kernels through the pallas
        # interpreter so helper-on vs helper-off measures the SAME two
        # code paths the TPU round A/Bs (stash wiring, custom VJPs,
        # fused BN backward) — correctness + not-worse evidence off-TPU,
        # never reported as silicon perf (mfu stays null on cpu)
        from deeplearning4j_tpu.ops import pallas_conv_bn as _pcb

        _pcb.set_interpret(True)
    conf = resnet50_conf(num_classes=classes, image_size=image_size,
                         precision="bf16" if on_tpu else "f32")
    refusal = _doctor_refusal(conf, "images/sec/chip")
    if refusal is not None:
        return refusal
    # NO fused multi-batch dispatch here: profiled 98.2 vs 48.8 ms/step
    # device time (round-9 profile, older than the code, not
    # re-measured) — the scan-carried params defeat
    # XLA's layout/fusion choices on this compute-bound model, while
    # dispatch overhead (the thing fusing removes) is ~5ms/step noise
    rng = np.random.default_rng(0)
    x = rng.random((batch, image_size, image_size, 3), np.float32)
    ds = _device_dataset(x, _onehot(rng, batch, classes))
    step_flops, flops_source = _step_flops(
        lambda: ComputationGraph(conf).init(), batch)

    def run(helpers_on):
        for op in ("conv2d", "batch_norm", "bn_backward"):
            set_helper_enabled(op, helpers_on)
        net = ComputationGraph(conf).init()  # fresh net => fresh trace
        if step_flops:  # devprof's live MFU gauges ride the same model
            net.set_model_flops_per_example(step_flops / batch,
                                            flops_source)
        dt, n_steps = _time_fit(
            net, lambda k: ExistingDataSetIterator([ds] * k), steps,
            reps=3 if on_tpu else 1)
        return batch * n_steps / dt, dt, n_steps

    # a representative stage-2 trunk shape; the probe says whether the
    # Pallas path exists at all on this backend (CPU: never)
    probe = get_helper(
        "conv2d", kernel=(1, 1), stride=(1, 1), dilation=(1, 1), same=True,
        has_bias=False, activation="identity", dtype=jnp.bfloat16,
        n_in=64, n_out=256, x_shape=(batch, 56, 56, 64), training=True)
    variants = [("xla_builtin", False)]
    if probe is not None:
        variants.insert(0, ("pallas_conv_bn_stats", True))
    results, errors = _run_ab(run, variants,
                              ("conv2d", "batch_norm", "bn_backward"))
    if not on_tpu:
        from deeplearning4j_tpu.ops import pallas_conv_bn as _pcb

        _pcb.set_interpret(False)
    if errors:  # an arm that raised or fell back is an error, not a note
        raise RuntimeError(f"conv/BN A/B arm failed: {errors}")
    kernel = max(results, key=lambda k: results[k][0])
    ips, dt, n_steps = results[kernel]
    mfu = ((step_flops * n_steps / dt) / peak_flops_per_chip()
           if on_tpu and step_flops else None)
    alternates = {k: round(v[0], 2) for k, v in results.items() if k != kernel}
    return {
        "value": round(ips, 2),
        "unit": "images/sec/chip",
        "batch": batch,
        "steps": steps,
        "image_size": image_size,
        "classes": classes,
        # fit(async_prefetch=True) routes through the staged input
        # pipeline: batches flow via DevicePrefetchIterator (the protocol
        # still pre-stages them in HBM, so the device_put the prefetch
        # worker issues is a same-device no-op — ETL stays excluded)
        "input_pipeline": "device_prefetch(depth=2, pre-staged batches)",
        "kernel": kernel,
        # pallas_interpret marks a CPU round whose kernel arm ran the
        # interpreter, so the A/B is read as correctness/not-worse
        # evidence and never as silicon perf
        **({"pallas_interpret": True} if not on_tpu else {}),
        "vs_alternate": alternates,
        "seconds": round(dt, 3),
        "model_flops_per_step": step_flops,
        "flops_source": flops_source,
        "mfu": None if mfu is None else round(mfu, 4),
    }


def bench_lenet(batch=512, steps=30):
    from deeplearning4j_tpu.models.lenet import lenet_conf
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    on_tpu = jax.default_backend() not in ("cpu",)
    conf = lenet_conf(precision="bf16" if on_tpu else "f32")
    net = MultiLayerNetwork(conf).init().set_fused_steps(10)
    step_flops, flops_source = train_step_flops_for(net, batch)
    if step_flops:
        net.set_model_flops_per_example(step_flops / batch, flops_source)
    rng = np.random.default_rng(0)
    ds = _device_dataset(rng.random((batch, 784), np.float32),
                         _onehot(rng, batch, 10))
    dt, n_steps = _time_fit(net, lambda k: ExistingDataSetIterator([ds] * k), steps,
                            reps=3 if on_tpu else 1)
    ips = batch * n_steps / dt
    mfu = ((step_flops * n_steps / dt) / peak_flops_per_chip()
           if on_tpu and step_flops else None)
    return {
        "value": round(ips, 1),
        "unit": "images/sec/chip",
        "batch": batch,
        "steps": steps,
        "seconds": round(dt, 3),
        "model_flops_per_step": step_flops,
        "flops_source": flops_source,
        "mfu": None if mfu is None else round(mfu, 4),
    }


def bench_char_lstm(batch=64, seq_len=200, tbptt=50, vocab=77, hidden=200,
                    steps=96, fused=24, reps=3):
    """tokens/sec through the TBPTT fit path (each fit batch = seq_len/tbptt
    optimizer steps, all segments + `steps` consecutive batches in one
    jitted dispatch via set_fused_steps). A/B-measures BOTH kernels —
    the fused Pallas LSTM helper and the default `lax.scan` path — in the
    same run; the headline is the faster, the loser is reported under
    `vs_alternate` so a kernel that compiles-but-loses is visible
    (round-4 lesson: availability-based selection hid a regression).
    Ground truth when wall-clock ties: the xplane
    profile (PROFILE_char_lstm.md) — pallas 31.7ms vs scan 58.4ms device
    time over 20 identical batches."""
    from deeplearning4j_tpu.models.charlstm import char_lstm_conf
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.ops.helpers import get_helper, set_helper_enabled

    on_tpu = jax.default_backend() not in ("cpu",)
    if not on_tpu:
        batch, seq_len, steps, hidden = 16, 100, 3, 64
        # reps=3 even on CPU: the first TIMED fit can pay a compile the
        # warmup does not cover, driving t(2N)-t(N) ≤ 0 (clamped to the
        # 1e-9 floor = an absurd headline); the median over 3 t-pairs is
        # the designed defense and the post-warmup pairs are cheap here
        fused, reps = 3, 3
        # CPU-interpret A/B — same rationale as bench_resnet50: both
        # kernel arms measurable off-TPU, reported as pallas_interpret
        from deeplearning4j_tpu.ops import pallas_lstm as _plstm

        _plstm._INTERPRET = True

    rng = np.random.default_rng(0)
    idx = rng.integers(0, vocab, (batch, seq_len))
    x = np.eye(vocab, dtype=np.float32)[idx]
    yidx = rng.integers(0, vocab, (batch, seq_len))
    y = np.eye(vocab, dtype=np.float32)[yidx]
    ds = _device_dataset(x, y)
    segments = -(-seq_len // tbptt)
    conf0 = char_lstm_conf(vocab_size=vocab, hidden=hidden,
                           tbptt_length=tbptt,
                           precision="bf16" if on_tpu else "f32")
    refusal = _doctor_refusal(conf0, "tokens/sec/chip")
    if refusal is not None:
        return refusal
    # full-sequence step FLOPs (the TBPTT segmentation splits the same
    # matmuls across dispatches; it does not change their count)
    step_flops, flops_source = _step_flops(
        lambda: MultiLayerNetwork(conf0).init(), batch, timesteps=seq_len)

    def run(kernel_on):
        set_helper_enabled("lstm_sequence", kernel_on)
        conf = char_lstm_conf(vocab_size=vocab, hidden=hidden,
                              tbptt_length=tbptt,
                              precision="bf16" if on_tpu else "f32")
        net = MultiLayerNetwork(conf).init().set_fused_steps(fused)
        if step_flops:
            net.set_model_flops_per_example(step_flops / batch,
                                            flops_source)
        dt, n_steps = _time_fit(
            net, lambda k: ExistingDataSetIterator([ds] * k), steps,
            reps=reps)
        fit_batches = n_steps / segments
        return conf, batch * seq_len * fit_batches / dt, dt, fit_batches

    probe = get_helper("lstm_sequence", peephole=True, mask=None,
                       gate_act="sigmoid", cell_act="tanh", reverse=False)
    variants = [("lax_scan", False)]
    if probe is not None:
        variants.insert(0, ("pallas_fused_lstm", True))
    results, errors = _run_ab(run, variants, ("lstm_sequence",))
    if not on_tpu:
        from deeplearning4j_tpu.ops import pallas_lstm as _plstm

        _plstm._INTERPRET = False
    if errors:  # an arm that raised or fell back is an error, not a note
        raise RuntimeError(f"LSTM A/B arm failed: {errors}")
    kernel = max(results, key=lambda k: results[k][1])
    conf, tokens, dt, fit_batches = results[kernel]
    mfu = (step_flops * fit_batches / dt / peak_flops_per_chip()
           if on_tpu and step_flops else None)
    alternates = {k: round(v[1], 1) for k, v in results.items()
                  if k != kernel}
    return {
        "value": round(tokens, 1),
        "unit": "tokens/sec/chip",
        "batch": batch,
        "seq_len": seq_len,
        "tbptt": tbptt,
        "vocab": vocab,
        "hidden": hidden,
        "kernel": kernel,
        **({"pallas_interpret": True} if not on_tpu else {}),
        "vs_alternate": alternates,
        "seconds": round(dt, 3),
        "model_flops_per_step": step_flops,
        "flops_source": flops_source,
        "mfu": None if mfu is None else round(mfu, 4),
        # what "good" is: cuDNN-era fused LSTM training lands ~5-15% MFU
        # at these small-cell shapes; the round-2 scan path measured 0.007
        "mfu_reference": "cudnn-era fused LSTM ~0.05-0.15 at small cells",
    }


def bench_vgg16(batch=32, steps=6, image_size=224, classes=1000):
    """VGG16-via-Keras-import (BASELINE.md workload 5): the conf is built
    THROUGH the Keras 1.x importer (modelimport/keras.py), then trained on
    synthetic data — import path + training measured together."""
    from deeplearning4j_tpu.models.vgg16 import vgg16_conf
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    on_tpu = jax.default_backend() not in ("cpu",)
    if not on_tpu:
        batch, steps, image_size, classes = 4, 3, 32, 10
    conf = vgg16_conf(num_classes=classes, image_size=image_size,
                      precision="bf16" if on_tpu else "f32")
    net = MultiLayerNetwork(conf).init().set_fused_steps(3)
    step_flops, flops_source = train_step_flops_for(net, batch)
    if step_flops:
        net.set_model_flops_per_example(step_flops / batch, flops_source)
    rng = np.random.default_rng(0)
    x = rng.random((batch, image_size, image_size, 3), np.float32)
    ds = _device_dataset(x, _onehot(rng, batch, classes))
    dt, n_steps = _time_fit(net, lambda k: ExistingDataSetIterator([ds] * k), steps,
                            reps=3 if on_tpu else 1)
    ips = batch * n_steps / dt
    mfu = ((step_flops * n_steps / dt) / peak_flops_per_chip()
           if on_tpu and step_flops else None)
    return {
        "value": round(ips, 2),
        "unit": "images/sec/chip",
        "batch": batch,
        "image_size": image_size,
        "seconds": round(dt, 3),
        "model_flops_per_step": step_flops,
        "flops_source": flops_source,
        "mfu": None if mfu is None else round(mfu, 4),
    }


def bench_word2vec(vocab=10_000, n_sents=2_000, sent_len=40, batch=8192,
                   layer_size=128, negative=5):
    """Word2Vec skip-gram words/sec (BASELINE.md Word2Vec workload;
    reference hot loop: SkipGram.java:271 native aggregate ops). Synthetic
    Zipf corpus; measures the device update path + host batching, i.e.
    exactly what SequenceVectors.fit does after vocab construction."""
    from deeplearning4j_tpu.nlp.sequencevectors import (
        SequenceVectors,
        VectorsConfiguration,
    )

    on_tpu = jax.default_backend() not in ("cpu",)
    if not on_tpu:
        vocab, n_sents, batch, layer_size = 1_000, 200, 1024, 32
    rng = np.random.default_rng(0)
    p = 1.0 / np.arange(1, vocab + 1)
    p /= p.sum()
    words = [f"w{i}" for i in range(vocab)]
    sents = [
        [words[j] for j in rng.choice(vocab, p=p, size=sent_len)]
        for i in range(n_sents)
    ]
    conf = VectorsConfiguration(
        layer_size=layer_size, window=5, min_word_frequency=1, epochs=1,
        negative=negative, use_hierarchic_softmax=False, batch_size=batch,
        sampling=1e-3,
    )
    sv = SequenceVectors(conf, sents)
    sv.build_vocab()
    indexed = sv._index_sentences(sents)
    total_words = sum(int(s.size) for s in indexed)
    # warmup on the FULL corpus: the corpus-resident device path compiles
    # per corpus-size bucket, so a small-prefix warmup would leave the
    # full-size compile inside the timed region. Median of 3 timed runs —
    # the corpus upload is host work, whose time varies run to run.
    sv.train_indexed(indexed)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        sv.train_indexed(indexed)
        float(np.asarray(sv.lookup.syn0[0, 0]))  # sync
        times.append(time.perf_counter() - t0)
    times.sort()
    dt = times[1]
    return {
        "value": round(total_words / dt, 1),
        "unit": "words/sec/chip",
        "vocab": vocab,
        "layer_size": layer_size,
        "negative": negative,
        "total_words": total_words,
        "seconds": round(dt, 3),
        # what "good" is: the original word2vec.c does ~0.1-1M words/sec
        # on a multicore host at this config; the reference's native
        # AggregateSkipGram path is the same order of magnitude
        "reference_point": "word2vec.c ~1e5-1e6 words/sec multicore",
    }


def bench_parallel_inference(max_batch=64, n_requests=512, clients=16,
                             n_in=128, hidden=256, classes=16):
    """Serving throughput/latency through the bucketed BATCHED
    ParallelInference path (the InferenceServer's engine): `clients`
    threads submit a mixed-size request stream — sizes 1..max_batch drawn
    zipf-ish (weight 1/size), the small-request-heavy profile of real
    serving traffic — and the workload reports requests/sec/chip plus
    p50/p99 request latency. warmup() precompiles every bucket first, so
    `forward_compiles_after_warmup` staying at 0 IS the bucketing win
    (before this path, every distinct fused group size was a fresh trace).
    Each latency sample ends at the caller's numpy readback (the dispatch
    thread materializes results host-side, which is what a client of the
    server waits for)."""
    import threading

    from deeplearning4j_tpu.nn.conf import (
        DenseLayer,
        NeuralNetConfiguration,
        OutputLayer,
        Updater,
    )
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel import (
        ParallelInference,
        data_parallel_mesh,
    )
    from deeplearning4j_tpu.utils.latency import LatencyTracker

    on_tpu = jax.default_backend() not in ("cpu",)
    if not on_tpu:
        n_requests, clients, hidden = 96, 8, 64
    conf = (
        NeuralNetConfiguration.builder().seed(7).updater(Updater.SGD)
        .learning_rate(0.05).weight_init("xavier")
        .precision("bf16" if on_tpu else "f32").list()
        .layer(DenseLayer(n_in=n_in, n_out=hidden, activation="relu"))
        .layer(DenseLayer(n_in=hidden, n_out=hidden, activation="relu"))
        .layer(OutputLayer(n_in=hidden, n_out=classes,
                           activation="softmax", loss="mcxent"))
        .build()
    )
    net = MultiLayerNetwork(conf).init()
    pi = ParallelInference(net, data_parallel_mesh(),
                           max_batch_size=max_batch, batch_timeout_ms=2.0)
    pi.warmup((n_in,))
    compiles_warm = int(net.output_compile_count)

    rng = np.random.default_rng(0)
    sizes = np.arange(1, max_batch + 1)
    p = 1.0 / sizes
    p /= p.sum()
    req_sizes = rng.choice(sizes, size=n_requests, p=p)
    reqs = [rng.standard_normal((int(s), n_in)).astype(np.float32)
            for s in req_sizes]

    lat = LatencyTracker(window=n_requests)
    next_idx = [0]
    idx_lock = threading.Lock()
    client_errors = []

    def client():
        try:
            while True:
                with idx_lock:
                    i = next_idx[0]
                    if i >= len(reqs):
                        return
                    next_idx[0] = i + 1
                t0 = time.perf_counter()
                out = pi.output(reqs[i])
                assert out.shape[0] == reqs[i].shape[0]
                lat.record(time.perf_counter() - t0)
        except BaseException as e:
            client_errors.append(f"{type(e).__name__}: {e}")

    threads = [threading.Thread(target=client, name=f"dl4j-bench-client-{i}")
               for i in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    if client_errors or lat.count != n_requests:
        # a silently-dead client would otherwise leave requests/sec counting
        # requests that were never served
        raise RuntimeError(
            f"served {lat.count}/{n_requests}; errors: {client_errors[:3]}")
    m = pi.metrics()
    pi.shutdown()
    snap = lat.snapshot()
    return {
        "value": round(n_requests / dt, 1),
        "unit": "requests/sec/chip",
        "examples_per_sec": round(int(req_sizes.sum()) / dt, 1),
        "p50_ms": snap["p50_ms"],
        "p99_ms": snap["p99_ms"],
        "clients": clients,
        "n_requests": n_requests,
        "distinct_request_sizes": int(len(set(req_sizes.tolist()))),
        "max_batch_size": max_batch,
        "buckets": m["buckets"],
        "batches": m["batches"],
        "bucket_hits": {str(k): v for k, v in m["bucket_hits"].items()},
        "forward_compiles_warmup": compiles_warm,
        "forward_compiles_after_warmup":
            m["forward_compiles"] - compiles_warm,
        "seconds": round(dt, 3),
    }


def bench_parallel_inference_overload(duration=3.0, n_in=64, hidden=64,
                                      classes=8, max_batch=4,
                                      queue_capacity=None, slo_ms=100.0,
                                      ledger_path=None):
    """Graceful degradation under sustained ~2x overload — the numbers
    the admission-control/load-shedding tier is graded on, recorded next
    to the throughput benches instead of only living in a slow test.
    Phase 1 saturates the pipeline with few enough closed-loop clients
    that nothing sheds (the measured capacity); phase 2 keeps ~2x the
    pipeline+queue's absorbable outstanding work in flight, so admission
    MUST shed the excess. Reported: shed rate, p99 latency of ADMITTED
    requests vs the SLO (overload must turn into fast 429s, not
    universal lateness), max queue depth vs capacity (boundedness), and
    the conservation law admitted == completed + shed + failed.

    The run additionally records a persistent run ledger
    (utils/runledger) with the default SLO rule pack derived from this
    workload's serving config — the soak gate: the verdict embeds which
    rules fired, `slo_ok` must stay True at the committed operating
    point, and the artifact replays offline via `cli slo --ledger
    <path> --check` / `cli metrics --ledger <path>`."""
    import threading

    from deeplearning4j_tpu.nn.conf import (
        DenseLayer,
        NeuralNetConfiguration,
        OutputLayer,
        Updater,
    )
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel import (
        ParallelInference,
        data_parallel_mesh,
    )
    from deeplearning4j_tpu.parallel.inference import (
        DeadlineExceeded,
        RequestRejected,
    )
    from deeplearning4j_tpu.utils import health as _health
    from deeplearning4j_tpu.utils import resourcemeter
    from deeplearning4j_tpu.utils.latency import LatencyTracker
    from deeplearning4j_tpu.utils.metrics import get_registry

    # two tenants ride the overload so the shed/books verdict is
    # per-customer, not just aggregate; metering attributes the forward
    # device time each tenant actually got
    resourcemeter.enable()
    tenants = ("gold", "free")

    on_tpu = jax.default_backend() not in ("cpu",)
    # queue_capacity=None → per-backend preset: a small CPU box needs a
    # shorter queue (and net) or GIL contention between the closed-loop
    # clients starves the dispatcher into shedding EVERYTHING, measuring
    # contention instead of admission control; an explicit value wins
    if queue_capacity is None:
        queue_capacity = 8 if on_tpu else 4
    if not on_tpu:
        hidden = 48
    # "2x overload" means outstanding work, not offered rate (closed-loop
    # clients self-throttle): the pipeline absorbs ~2 groups in flight
    # plus the queue, so 2x that many 1-row closed-loop clients keeps
    # admission permanently oversubscribed — the client count is DERIVED
    # from that, not a knob
    absorbable = 2 * max_batch + queue_capacity
    clients = 2 * absorbable
    conf = (
        NeuralNetConfiguration.builder().seed(7).updater(Updater.SGD)
        .learning_rate(0.05).weight_init("xavier")
        .precision("bf16" if on_tpu else "f32").list()
        .layer(DenseLayer(n_in=n_in, n_out=hidden, activation="relu"))
        .layer(OutputLayer(n_in=hidden, n_out=classes,
                           activation="softmax", loss="mcxent"))
        .build()
    )
    net = MultiLayerNetwork(conf).init()
    pi = ParallelInference(net, data_parallel_mesh(),
                           max_batch_size=max_batch, batch_timeout_ms=1.0,
                           queue_capacity=queue_capacity,
                           handoff_capacity=1, default_deadline_ms=slo_ms,
                           component_prefix="bench_overload")
    pi.warmup((n_in,))
    # the soak ledger: continuous samples + the default rule pack for
    # THIS serving config, judged live on the recorder thread. Attached
    # AFTER warmup so the objective only grades traffic.
    import tempfile

    from deeplearning4j_tpu.analysis.slo import default_rule_pack
    from deeplearning4j_tpu.utils import runledger as _runledger

    if ledger_path is None:
        ledger_path = os.path.join(
            tempfile.gettempdir(),
            f"BENCH_overload_ledger_{os.getpid()}.jsonl")
    ledger = _runledger.RunLedger(
        ledger_path, sample_every=max(0.25, duration / 8.0),
        rules=default_rule_pack(
            serving={"default_deadline_ms": slo_ms,
                     "queue_capacity": queue_capacity,
                     "handoff_capacity": 1,
                     "component": "bench_overload"},
            sample_every=max(0.25, duration / 8.0),
            # per-tenant chip-budget burn rules ride the same ledger; a
            # whole chip per tenant is a generous bar this single-host
            # soak must stay under
            tenants={t: 1.0 for t in tenants}))
    _runledger.attach(ledger)
    rng = np.random.default_rng(0)
    reqs = [rng.standard_normal((1, n_in)).astype(np.float32)
            for _ in range(64)]
    lat = LatencyTracker(window=100_000)
    stop = threading.Event()
    max_depth = [0]
    client_errors = []

    def client(i, track):
        j = 0
        try:
            while not stop.is_set():
                j += 1
                t0 = time.perf_counter()
                try:
                    pi.output(reqs[(i * 31 + j) % len(reqs)],
                              tenant=tenants[i % len(tenants)])
                    if track:
                        lat.record(time.perf_counter() - t0)
                except (DeadlineExceeded, RequestRejected) as e:
                    # shed totals come from the metrics deltas; honor the
                    # server's Retry-After hint (bounded: a bench client
                    # must keep offering load)
                    stop.wait(min(getattr(e, "retry_after", 0.0), 0.005))
        except BaseException as e:  # noqa: BLE001 - reported, fails run
            client_errors.append(f"{type(e).__name__}: {e}")

    def run_phase(n_clients, seconds, track):
        stop.clear()
        threads = [threading.Thread(target=client, args=(i, track),
                                    daemon=True,
                                    name=f"dl4j-bench-ovl-{i}")
                   for i in range(n_clients)]
        before = pi.metrics()
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        while time.perf_counter() - t0 < seconds:
            max_depth[0] = max(max_depth[0], pi._q.qsize())
            time.sleep(0.005)
        stop.set()
        for t in threads:
            t.join(timeout=30.0)
            if t.is_alive():
                # a wedged client would otherwise surface as a bogus
                # "conservation violated" (its request stays admitted
                # but unresolved when the books are read)
                client_errors.append(f"{t.name}: wedged past join budget")
        dt = time.perf_counter() - t0
        after = pi.metrics()
        return dt, {k: after[k] - before[k]
                    for k in ("admitted", "completed", "shed", "failed",
                              "rejected", "requests")}

    # phase 1: measured capacity — few clients, nothing sheds
    spend0 = resourcemeter.spend_table(get_registry().scalar_values())
    base_dt, base = run_phase(4, duration * 0.5, track=False)
    # phase 2: ~2x the absorbable outstanding work, shedding expected
    max_depth[0] = 0
    over_dt, over = run_phase(clients, duration, track=True)
    m = pi.metrics()
    spend1 = resourcemeter.spend_table(get_registry().scalar_values())
    tenant_cons = resourcemeter.conservation(
        get_registry().scalar_values())
    comps = _health.get_health().status()["components"]
    stalled = [k for k, v in comps.items()
               if k.startswith("bench_overload")
               and v.get("status") != "ok"]
    # close the ledger (final sample) BEFORE reading the verdict: the
    # rule states are part of the committed operating point — an
    # ERROR-severity firing here fails the soak gate
    ledger.close()
    slo_fired = ledger.rules.ever_fired()
    slo_fired_errors = ledger.rules.ever_fired("error")
    pi.shutdown()
    if client_errors:
        raise RuntimeError(f"overload client died: {client_errors[:3]}")
    if m["admitted"] != m["completed"] + m["shed"] + m["failed"]:
        # the books MUST balance — a leak here is a correctness bug, not
        # a perf number
        raise RuntimeError(f"conservation violated: {m}")
    bad_tenants = {t: b for t, b in m["tenants"].items()
                   if not b["conservation_ok"]}
    if bad_tenants or not tenant_cons["ok"]:
        # the PER-TENANT law and the spend sum-to-process-total check:
        # aggregate books can balance while one tenant leaks into
        # another — multi-tenant hosting is graded on the exact split
        raise RuntimeError(
            f"per-tenant conservation violated: books={bad_tenants} "
            f"spend={tenant_cons}")
    snap = lat.snapshot()
    capacity_rps = base["completed"] / base_dt
    offered = (over["requests"] or 1) / over_dt
    shed_total = over["shed"] + over["rejected"]
    return {
        "value": snap["p99_ms"],
        "unit": "p99_ms_admitted_under_overload",
        "slo_ms": slo_ms,
        "slo_met_p99": bool(snap["p99_ms"] is not None
                            and snap["p99_ms"] <= slo_ms),
        "capacity_requests_per_sec": round(capacity_rps, 1),
        "offered_requests_per_sec": round(offered, 1),
        "overdrive_outstanding": round(clients / absorbable, 2),
        "completed_per_sec": round(over["completed"] / over_dt, 1),
        "shed_total": shed_total,
        "shed_rate": round(shed_total / max(over["requests"], 1), 4),
        "shed_by": m["shed_by"],
        "max_queue_depth": max_depth[0],
        "queue_capacity": queue_capacity,
        "queue_bounded": bool(max_depth[0] <= queue_capacity),
        "watchdog_stalled_components": stalled,
        "clients": clients,
        "p50_ms": snap["p50_ms"],
        "seconds": round(base_dt + over_dt, 3),
        # the continuous-judgment half: rule verdicts from the run
        # ledger (replay: cli slo --ledger <path> --check)
        "slo": {
            "ledger": ledger_path,
            "run_id": ledger.run_id,
            "rules": [r.name for r in ledger.rules.rules],
            "fired": slo_fired,
            "fired_errors": slo_fired_errors,
        },
        "slo_ok": not slo_fired_errors,
        # per-tenant half of the verdict: exact books per customer plus
        # the serving device-seconds each one actually received
        "tenants": m["tenants"],
        "tenant_spend": {
            t: round(
                spend1.get(t, {}).get("device_seconds", {}).get(
                    resourcemeter.TIER_SERVING, 0.0)
                - spend0.get(t, {}).get("device_seconds", {}).get(
                    resourcemeter.TIER_SERVING, 0.0), 4)
            for t in tenants},
        "tenant_conservation": tenant_cons,
    }


def bench_decode(n_slots=8, duration=6.0, vocab=32, hidden=64,
                 slo_ms=None, seed=0):
    """Continuous-batching autoregressive decode (serving/decode.py):
    a sustained soak of zipf-length char-LSTM generate requests from two
    tenants (weighted 3:1) against one DecodeEngine, with a LIVE weight
    swap fired mid-soak. Reported: tokens/sec/chip, per-token latency
    (inter-token p50/p99, time-to-first-token separately — first tokens
    carry queue wait by design), mean/max slot occupancy, and the swap
    verdict: the inter-token p99 inside the swap window must meet the
    same SLO as the whole soak (the no-blip claim), with zero failed
    requests and exact per-tenant conservation books.

    `vs_alternate` is the honesty arm: the same request shapes served by
    the naive per-request loop (sequential `rnn_time_step`, batch=1 —
    what a server without continuous batching would do), so the headline
    is engine-vs-loop, not engine-vs-nothing."""
    import threading

    from deeplearning4j_tpu.models.charlstm import char_lstm_network
    from deeplearning4j_tpu.serving.decode import DecodeEngine
    from deeplearning4j_tpu.utils import resourcemeter
    from deeplearning4j_tpu.utils.latency import LatencyTracker
    from deeplearning4j_tpu.utils.metrics import get_registry

    # arm tenant spend metering: the verdict embeds per-tenant
    # device-seconds, and the fairness probe judges the split
    resourcemeter.enable()

    def _dec_sec(table, tenant):
        return table.get(tenant, {}).get(
            "device_seconds", {}).get(resourcemeter.TIER_DECODE, 0.0)

    on_tpu = jax.default_backend() not in ("cpu",)
    if slo_ms is None:
        # per-token SLO: measured steady-state ITL p99 is ~1 ms on the
        # 2-core CPU box (~2.5 ms inside the swap window) — 50 ms gives
        # box-contention headroom while still catching a real blip
        slo_ms = 20.0 if on_tpu else 50.0
    net = char_lstm_network(vocab_size=vocab, hidden=hidden, layers=1,
                            tbptt_length=16,
                            precision="bf16" if on_tpu else "f32")
    engine = DecodeEngine(net, n_slots=n_slots,
                          tenant_weights={"gold": 3.0, "std": 1.0},
                          default_max_tokens=32, queue_capacity=256,
                          component_prefix="bench_decode")
    rng = np.random.default_rng(seed)

    def make_req(i):
        # zipf-ish request mix: mostly short, a heavy tail
        p_len = int(min(1 + rng.zipf(1.6), 12))
        n_new = int(min(2 + rng.zipf(1.4), 24))
        prompt = rng.integers(0, vocab, size=p_len).tolist()
        tenant = "gold" if i % 2 == 0 else "std"
        return prompt, n_new, tenant

    # ITL (inter-token) and TTFT trackers, plus a timeline of
    # (wall_time, itl_seconds) so the swap window is auditable
    itl = LatencyTracker(window=200_000)
    ttft = LatencyTracker(window=50_000)
    timeline = []
    tl_lock = threading.Lock()
    stop = threading.Event()
    client_errors = []

    def client(ci):
        j = 0
        try:
            while not stop.is_set():
                j += 1
                prompt, n_new, tenant = make_req(ci * 7919 + j)
                t_sub = time.perf_counter()
                last = [None]

                def on_token(_tok, _last=last, _t_sub=t_sub):
                    now = time.perf_counter()
                    if _last[0] is None:
                        ttft.record(now - _t_sub)
                    else:
                        gap = now - _last[0]
                        itl.record(gap)
                        with tl_lock:
                            timeline.append((now, gap))
                    _last[0] = now

                fut = engine.generate(prompt, max_new_tokens=n_new,
                                      tenant=tenant, on_token=on_token)
                fut.result(timeout=120)
        except BaseException as e:  # noqa: BLE001 - reported, fails run
            client_errors.append(f"{type(e).__name__}: {e}")

    # warmup: compile the step + reset programs before the clock starts
    engine.generate([1, 2, 3], max_new_tokens=2, tenant="gold").result(120)
    warm_cache = engine.program_cache_size()
    # the soak ledger: per-tenant spend series recorded like any other,
    # with the per-tenant chip-budget burn rules judged live (a whole
    # chip per tenant is the generous single-host bar). Attached AFTER
    # warmup so the rules only grade traffic.
    import tempfile

    from deeplearning4j_tpu.analysis.slo import default_rule_pack
    from deeplearning4j_tpu.utils import runledger as _runledger

    ledger_path = os.path.join(tempfile.gettempdir(),
                               f"BENCH_decode_ledger_{os.getpid()}.jsonl")
    se = max(0.25, duration / 8.0)
    ledger = _runledger.RunLedger(
        ledger_path, sample_every=se,
        rules=default_rule_pack(
            sample_every=se,
            tenants={"gold": 1.0, "std": 1.0}))
    _runledger.attach(ledger)
    before = engine.metrics()
    spend0 = resourcemeter.spend_table(get_registry().scalar_values())
    clients = n_slots + 2  # keep the pool saturated, the queue shallow
    threads = [threading.Thread(target=client, args=(i,), daemon=True,
                                name=f"dl4j-bench-dec-{i}")
               for i in range(clients)]
    occupancy = []
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    swap_at = duration / 2.0
    swap_t = None
    swap_version = None
    while time.perf_counter() - t0 < duration:
        occupancy.append(engine.metrics()["slots_in_use"])
        if swap_t is None and time.perf_counter() - t0 >= swap_at:
            # the live swap: v+1 committed beside v on THIS thread, the
            # engine flips between steps — traffic never pauses
            perturbed = jax.tree_util.tree_map(
                lambda a: a * 1.001, net.params_list)
            swap_version = engine.load_version(perturbed)
            swap_t = time.perf_counter()
        time.sleep(0.02)
    stop.set()
    for t in threads:
        t.join(timeout=60.0)
        if t.is_alive():
            client_errors.append(f"{t.name}: wedged past join budget")
    dt = time.perf_counter() - t0
    after = engine.metrics()
    final_cache = engine.program_cache_size()
    spend1 = resourcemeter.spend_table(get_registry().scalar_values())
    # close (final sample) BEFORE the verdict: the replayable artifact
    # must hold everything the live verdict judged
    ledger.close()
    slo_fired = ledger.rules.ever_fired()
    slo_fired_errors = ledger.rules.ever_fired("error")
    engine.shutdown()
    if client_errors:
        raise RuntimeError(f"decode client died: {client_errors[:3]}")
    if not after["conservation_ok"]:
        raise RuntimeError(f"decode books violated: {after['tenants']}")
    tokens = after["tokens"] - before["tokens"]
    completed = after["completed"] - before["completed"]
    # the swap window: inter-token gaps landing just after the flip —
    # a blip would show up as a p99 spike HERE even if the whole-soak
    # p99 hides it
    with tl_lock:
        window = [g for (ts, g) in timeline
                  if swap_t is not None and swap_t - 0.5 <= ts <= swap_t + 1.0]
    swap_p99_ms = (None if len(window) < 10 else
                   round(sorted(window)[int(0.99 * (len(window) - 1))]
                         * 1e3, 3))
    itl_snap = itl.snapshot()
    slo_met = bool(itl_snap["p99_ms"] is not None
                   and itl_snap["p99_ms"] <= slo_ms
                   and (swap_p99_ms is None or swap_p99_ms <= slo_ms)
                   and after["failed"] == 0)

    # -- vs_alternate: the naive per-request loop -----------------------------
    def naive_tokens_per_sec(n_reqs=12):
        net.clear_rnn_state()
        reqs = [make_req(10_000 + i) for i in range(n_reqs)]
        # warmup the batch-1 streaming traces
        oh = np.zeros((1, vocab), np.float32)
        oh[0, 1] = 1.0
        net.rnn_time_step(oh)
        net.clear_rnn_state()
        n_tok = 0
        t0 = time.perf_counter()
        for prompt, n_new, _ in reqs:
            net.clear_rnn_state()
            out = None
            for t in prompt:
                oh = np.zeros((1, vocab), np.float32)
                oh[0, t] = 1.0
                out = np.asarray(net.rnn_time_step(oh))
            for _ in range(n_new):
                g = int(np.argmax(out[0]))
                n_tok += 1
                oh = np.zeros((1, vocab), np.float32)
                oh[0, g] = 1.0
                out = np.asarray(net.rnn_time_step(oh))
        return n_tok / (time.perf_counter() - t0)

    # -- fused decode steps: K steps scanned into ONE jitted dispatch --------
    def fused_probe(k, n_reqs=16):
        """Mini-soak at fused_steps=k on a fresh engine (own metric
        prefix — the books above must not be polluted): a FIXED request
        set, client-side ITL tracking, tokens/sec from the engine's own
        counters. K=1 is the per-step dispatch baseline; the K>1 arm
        shows what amortizing host dispatch overhead buys."""
        eng = DecodeEngine(net, n_slots=n_slots,
                           tenant_weights={"gold": 3.0, "std": 1.0},
                           default_max_tokens=32, queue_capacity=256,
                           component_prefix=f"bench_decode_f{k}")
        try:
            eng.set_fused_steps(k)
            eng.generate([1, 2, 3], max_new_tokens=2,
                         tenant="gold").result(120)
            tr = LatencyTracker(window=50_000)
            last = {}

            def mk_cb(i):
                def cb(_tok):
                    now = time.perf_counter()
                    if i in last:
                        tr.record(now - last[i])
                    last[i] = now
                return cb

            reqs = [make_req(50_000 + i) for i in range(n_reqs)]
            tok0 = eng.metrics()["tokens"]
            tp0 = time.perf_counter()
            futs = [eng.generate(p, max_new_tokens=nn, tenant=ten,
                                 on_token=mk_cb(i))
                    for i, (p, nn, ten) in enumerate(reqs)]
            for f in futs:
                f.result(timeout=120)
            dtp = time.perf_counter() - tp0
            n_tok = eng.metrics()["tokens"] - tok0
        finally:
            eng.shutdown()
        snap = tr.snapshot()
        return {"tokens_per_sec": round(n_tok / dtp, 1),
                "itl_p50_ms": snap["p50_ms"],
                "itl_p99_ms": snap["p99_ms"]}

    # -- weighted-fair spend probe: both tenants fully backlogged ------------
    def fairness_probe(secs=2.5):
        """The main soak's clients pick a tenant per request, so neither
        tenant stays backlogged and stride scheduling has nothing to
        arbitrate. Here each tenant keeps n_slots clients outstanding on
        a fresh engine — under dual backlog the 3:1 weights must show up
        as a ~3:1 decode device-seconds split in the resource meter."""
        eng = DecodeEngine(net, n_slots=n_slots,
                           tenant_weights={"gold": 3.0, "std": 1.0},
                           default_max_tokens=32, queue_capacity=256,
                           component_prefix="bench_decode_fair")
        errs = []
        try:
            eng.generate([1, 2, 3], max_new_tokens=2,
                         tenant="gold").result(120)
            s0 = resourcemeter.spend_table(get_registry().scalar_values())
            stop_f = threading.Event()

            def fclient(tenant, ci):
                j = 0
                try:
                    while not stop_f.is_set():
                        j += 1
                        prompt, n_new, _ = make_req(90_000 + ci * 7919 + j)
                        eng.generate(prompt, max_new_tokens=n_new,
                                     tenant=tenant).result(timeout=120)
                except BaseException as e:  # noqa: BLE001 - reported
                    errs.append(f"{type(e).__name__}: {e}")

            ths = [threading.Thread(target=fclient, args=(ten, i),
                                    daemon=True,
                                    name=f"dl4j-bench-fair-{ten}-{i}")
                   for ten in ("gold", "std") for i in range(n_slots)]
            for th in ths:
                th.start()
            time.sleep(secs)
            stop_f.set()
            for th in ths:
                th.join(timeout=60.0)
        finally:
            eng.shutdown()
        if errs:
            raise RuntimeError(f"fairness client died: {errs[:3]}")
        s1 = resourcemeter.spend_table(get_registry().scalar_values())
        gold = _dec_sec(s1, "gold") - _dec_sec(s0, "gold")
        std = _dec_sec(s1, "std") - _dec_sec(s0, "std")
        ratio = gold / max(std, 1e-9)
        want = 3.0  # the engine's gold:std weight ratio
        return {
            "device_seconds": {"gold": round(gold, 4),
                               "std": round(std, 4)},
            "ratio": round(ratio, 2),
            "want_ratio": want,
            # generous 2x band: stride scheduling is exact on admissions
            # but request lengths are zipf, so spend only approximates it
            "ok": bool(std > 0 and want / 2 <= ratio <= want * 2),
        }

    fair = fairness_probe()
    if not fair["ok"]:
        raise RuntimeError(
            f"weighted-fair spend violated: gold:std device-seconds "
            f"ratio {fair['ratio']} (want ~{fair['want_ratio']}): {fair}")

    fused_k = 4
    f_base = fused_probe(1)
    f_fused = fused_probe(fused_k)

    naive_tps = naive_tokens_per_sec()
    engine_tps = tokens / dt
    return {
        "value": round(engine_tps, 1),
        "unit": "tokens/sec/chip",
        "devices": 1,
        "slots": n_slots,
        "clients": clients,
        "seconds": round(dt, 3),
        "tokens": tokens,
        "requests_completed": completed,
        "itl_p50_ms": itl_snap["p50_ms"],
        "itl_p99_ms": itl_snap["p99_ms"],
        "ttft_p50_ms": ttft.snapshot()["p50_ms"],
        "ttft_p99_ms": ttft.snapshot()["p99_ms"],
        "slot_occupancy_mean": round(float(np.mean(occupancy)), 2)
        if occupancy else None,
        "slot_occupancy_max": int(max(occupancy)) if occupancy else None,
        "slo_ms_per_token": slo_ms,
        "slo_met_through_swap": slo_met,
        "swap": {
            "fired": swap_t is not None,
            "version": swap_version,
            "itl_p99_ms_in_window": swap_p99_ms,
            "window_samples": len(window),
            "swaps_counted": after["swaps"] - before["swaps"],
        },
        "zero_retraces": bool(final_cache == warm_cache),
        # K decode steps per dispatch (serving/decode.set_fused_steps):
        # same fixed request set both arms, fresh engine each
        "fused_steps": {
            "k": fused_k,
            "tokens_per_sec": f_fused["tokens_per_sec"],
            "itl_p50_ms": f_fused["itl_p50_ms"],
            "itl_p99_ms": f_fused["itl_p99_ms"],
            "unfused_tokens_per_sec": f_base["tokens_per_sec"],
            "unfused_itl_p50_ms": f_base["itl_p50_ms"],
            "unfused_itl_p99_ms": f_base["itl_p99_ms"],
            "speedup": round(f_fused["tokens_per_sec"]
                             / max(f_base["tokens_per_sec"], 1e-9), 2),
        },
        "books": {k: after[k] for k in ("admitted", "completed", "shed",
                                        "failed", "rejected")},
        "tenants": after["tenants"],
        # per-tenant chip spend over the soak (utils/resourcemeter) and
        # the dual-backlog weighted-fair verdict
        "tenant_spend": {
            t: {"decode_device_seconds":
                round(_dec_sec(spend1, t) - _dec_sec(spend0, t), 4),
                "tokens": round(
                    spend1.get(t, {}).get("tokens", 0.0)
                    - spend0.get(t, {}).get("tokens", 0.0))}
            for t in ("gold", "std")},
        "weighted_fair": fair,
        # the recorded half: per-tenant series + burn rules in a
        # replayable artifact (cli tenants --ledger <path> reproduces
        # tenant_spend; cli slo --ledger <path> --check re-judges it)
        "slo": {
            "ledger": ledger_path,
            "run_id": ledger.run_id,
            "rules": [r.name for r in ledger.rules.rules],
            "fired": slo_fired,
            "fired_errors": slo_fired_errors,
        },
        "slo_ok": not slo_fired_errors,
        "vs_alternate": {
            "alternate": "naive_per_request_rnn_time_step_loop",
            "alternate_tokens_per_sec": round(naive_tps, 1),
            "speedup": round(engine_tps / max(naive_tps, 1e-9), 2),
        },
    }


def bench_input_pipeline(n_batches=48, batch=64, img=24, classes=10,
                         workers=4, io_ms=12.0):
    """Input-bound training, the one workload where ETL is deliberately ON
    the books (every other workload excludes it per the BASELINE.md
    protocol): each record batch costs a simulated storage/codec latency
    (the I/O wait a real decode pays) plus genuine per-pixel host math,
    then normalization + random flip augmentation. A/Bs the staged
    pipeline against the same logical work run synchronously:

      off — decode + normalize + augment inline on the fit thread,
            async_prefetch=False (no overlap anywhere);
      on  — ParallelDataSetIterator(workers) decodes concurrently,
            DevicePrefetchIterator stages batches to the device ahead of
            the step, and normalize+flip run as a jitted on-device
            DeviceBatchTransform in the prefetch worker.

    The acceptance bar is speedup >= 2x on CPU: the pipeline must hide
    ETL behind compute, not just shave it."""
    from deeplearning4j_tpu.data.iterators import DataSetIterator
    from deeplearning4j_tpu.data.prefetch import ParallelDataSetIterator
    from deeplearning4j_tpu.data.transforms import DeviceBatchTransform
    from deeplearning4j_tpu.nn.conf import (
        DenseLayer,
        InputType,
        NeuralNetConfiguration,
        OutputLayer,
        SubsamplingLayer,
        Updater,
    )
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    on_tpu = jax.default_backend() not in ("cpu",)
    mean, std = 0.48, 0.27
    rng = np.random.default_rng(0)
    # a small pool of distinct "encoded" records, cycled to n_batches —
    # decode cost is per-batch, so aliasing the raw bytes is free
    pool = [(rng.integers(0, 256, (batch, img, img, 3), dtype=np.uint8),
             _onehot(rng, batch, classes)) for _ in range(8)]
    records = [pool[i % len(pool)] for i in range(n_batches)]

    def decode(item):
        raw, y = item
        time.sleep(io_ms / 1e3)  # storage/codec latency (releases the GIL)
        x = np.sqrt(raw.astype(np.float32) / 255.0)  # gamma-ish host work
        return DataSet(x, y)

    def host_augment(ds, step):
        x = (ds.features - mean) / std
        r = np.random.default_rng(step)
        flip = r.random(x.shape[0]) < 0.5
        x = np.where(flip[:, None, None, None], x[:, :, ::-1, :], x)
        return DataSet(x.astype(np.float32), ds.labels)

    class SyncEtlIterator(DataSetIterator):
        """Pipeline off: the full ETL chain inline on the fit thread."""

        def __iter__(self):
            for step, item in enumerate(records):
                yield host_augment(decode(item), step)

    def make_net():
        # deliberately tiny model: the workload measures the INPUT
        # pipeline, so compute must not be the bottleneck (pool + dense —
        # a conv here would be compute-bound on a 2-core CPU smoke box)
        conf = (
            NeuralNetConfiguration.builder().seed(7).updater(Updater.SGD)
            .learning_rate(0.01).weight_init("xavier")
            .precision("bf16" if on_tpu else "f32").list()
            .layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2),
                                    stride=(2, 2)))
            .layer(DenseLayer(n_out=32, activation="relu"))
            .layer(OutputLayer(n_out=classes, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.convolutional(img, img, 3)).build()
        )
        return MultiLayerNetwork(conf).init()

    from deeplearning4j_tpu.utils.metrics import get_registry

    data_wait = get_registry().histogram(
        "fit_data_wait_seconds",
        "time blocked on the data iterator (ETL) before a "
        "dispatch").labels()

    def timed(fit_once):
        fit_once()  # warmup: compile every program the timed pass uses
        times = []
        c0, s0 = data_wait.count, data_wait.sum
        for _ in range(3):
            t0 = time.perf_counter()
            net = fit_once()
            _sync(net)
            times.append(time.perf_counter() - t0)
        times.sort()
        # per-variant slice of the process-global data-wait histogram:
        # the A/B shares one registry, so deltas are the honest per-arm
        # numbers (the snapshot's merged histogram is both arms at once)
        wait_ms = (data_wait.sum - s0) / max(1, data_wait.count - c0) * 1e3
        return batch * n_batches / times[1], wait_ms

    net_off = make_net()
    ips_off, wait_off = timed(lambda: net_off.fit(
        SyncEtlIterator(), epochs=1, async_prefetch=False))

    net_on = make_net().set_input_transform(DeviceBatchTransform(
        normalize=(mean, std), random_flip=True, seed=0))
    make_it = lambda: ParallelDataSetIterator(
        records, transform=decode, workers=workers, queue_size=2 * workers)
    ips_on, wait_on = timed(lambda: net_on.fit(
        make_it(), epochs=1, async_prefetch=True))
    return {
        "value": round(ips_on, 1),
        "unit": "images/sec/chip",
        "pipeline_off": round(ips_off, 1),
        "speedup_vs_sync": round(ips_on / ips_off, 2),
        "fit_data_wait_mean_ms": {"pipeline_off": round(wait_off, 3),
                                  "pipeline_on": round(wait_on, 3)},
        "batch": batch,
        "n_batches": n_batches,
        "image_size": img,
        "etl_workers": workers,
        "simulated_io_ms": io_ms,
        "stages": "ParallelDataSetIterator -> DevicePrefetchIterator -> "
                  "DeviceBatchTransform(normalize+flip)",
    }


# -- multi-chip mode ----------------------------------------------------------


def _n_multichip_devices() -> int:
    return int(os.environ.get("BENCH_MULTICHIP_DEVICES", "8"))


def _legacy_param_averaging_fit(make_net, shard_datasets, steps):
    """The vs_alternate arm: DL4J ParallelWrapper semantics — each
    "worker" trains a full replica step on its own shard from the same
    start params (the real `_fit_dataset` machinery, so TBPTT nets run
    their real segment dispatch), then parameters + updater state are
    averaged THROUGH THE HOST every interval
    (ParallelWrapper.java:417-424, frequency 1). This is exactly the
    per-interval params-to-host round-trip the in-graph all-reduce
    removes; measuring it next to the sharded step is the honesty
    mechanism. Replicas dispatch sequentially — what a GIL-bound host
    orchestrator does on one box — so the arm is a mechanism A/B, not a
    tuned rival."""
    import jax.numpy as jnp

    net = make_net()
    # REAL buffer copies, not aliases: on device backends the step jit
    # donates argnums (0, 2), so each replica must dispatch its OWN
    # copy of the start params/updater — an aliased p0 would be deleted
    # by the first replica's donation (and the legacy semantics DO copy
    # the source model into every replica)
    copy_tree = lambda t: jax.tree_util.tree_map(jnp.copy, t)
    avg = lambda trees: jax.tree_util.tree_map(
        lambda *xs: np.mean([np.asarray(x) for x in xs], axis=0), *trees)
    t_total = None
    for _ in range(2):  # warmup pass (compile), then the timed pass
        t0 = time.perf_counter()
        for _ in range(steps):
            p0 = copy_tree(net.params_list)
            u0 = copy_tree(net.upd_state)
            s0 = list(net.state_list)
            it0 = net.iteration
            outs = []
            for ds in shard_datasets:
                net.params_list = copy_tree(p0)
                net.upd_state = copy_tree(u0)
                net.state_list, net.iteration = list(s0), it0
                net._fit_dataset(ds)
                outs.append((net.params_list, net.upd_state))
            # the legacy averaging interval: every replica's params and
            # updater state round-trip to host numpy, mean, re-upload
            net.params_list = avg([o[0] for o in outs])
            net.upd_state = avg([o[1] for o in outs])
        _sync(net)
        t_total = time.perf_counter() - t0
    return t_total


def _bench_multichip(workload: str):
    """Multi-chip training A/B on an n-device mesh (CPU boxes force the
    host-platform device count — the same virtual-mesh strategy as the
    MULTICHIP_r0x dryruns; the numbers are mechanism evidence there, not
    silicon claims — `backend` says which). Three arms per workload:

      sharded         — the mainline path: fit() with set_mesh, global
                        batch = n × per-chip batch, ONE jitted SPMD step,
                        in-graph gradient all-reduce.
      single_chip     — the same per-chip batch on one device: the
                        scaling-efficiency denominator.
      param_averaging — the legacy DL4J semantics (vs_alternate): per-
                        replica steps + host-side parameter averaging.

    Reported: per-chip throughput, scaling efficiency (sharded per-chip
    / single-chip), and the legacy arm under `vs_alternate` — the same
    A/B honesty mechanism as the kernel benches. MFU is per-chip-correct:
    model FLOPs divide by the data-axis size (`flops_source` recorded)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.parallel.mesh import data_parallel_mesh
    from deeplearning4j_tpu.utils.metrics import get_registry

    n = jax.device_count()
    on_tpu = jax.default_backend() not in ("cpu",)
    rng = np.random.default_rng(0)

    if workload == "resnet50":
        from deeplearning4j_tpu.models.resnet import resnet50_conf
        from deeplearning4j_tpu.nn.compgraph import ComputationGraph

        per_chip, steps, image_size, classes = (
            (128, 8, 224, 1000) if on_tpu else (4, 2, 64, 10))
        conf = resnet50_conf(num_classes=classes, image_size=image_size,
                             precision="bf16" if on_tpu else "f32")
        refusal = _doctor_refusal(conf, "images/sec/chip")
        if refusal is not None:
            return refusal
        make_net = lambda: ComputationGraph(conf).init()
        gb = per_chip * n
        x = rng.random((gb, image_size, image_size, 3), np.float32)
        ds = DataSet(x, _onehot(rng, gb, classes))
        unit, per_step_examples, timesteps = "images/sec/chip", gb, 16
    elif workload == "char_lstm":
        from deeplearning4j_tpu.models.charlstm import char_lstm_conf
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        vocab = 77
        per_chip, seq_len, tbptt, hidden, steps = (
            (64, 200, 50, 200, 8) if on_tpu else (2, 32, 16, 48, 2))
        conf = char_lstm_conf(vocab_size=vocab, hidden=hidden,
                              tbptt_length=tbptt,
                              precision="bf16" if on_tpu else "f32")
        refusal = _doctor_refusal(conf, "tokens/sec/chip")
        if refusal is not None:
            return refusal
        make_net = lambda: MultiLayerNetwork(conf).init()
        gb = per_chip * n
        idx = rng.integers(0, vocab, (gb, seq_len))
        x = np.eye(vocab, dtype=np.float32)[idx]
        yidx = rng.integers(0, vocab, (gb, seq_len))
        ds = DataSet(x, np.eye(vocab, dtype=np.float32)[yidx])
        unit = "tokens/sec/chip"
        per_step_examples = gb * seq_len
        timesteps = seq_len
    else:
        raise SystemExit(f"unknown multichip workload {workload!r}")

    # model FLOPs from an unsharded throwaway trace (mesh-independent);
    # the PER-CHIP figure divides by the data-axis size — the accounting
    # fix that keeps multi-chip MFU honest
    step_flops, flops_source = _step_flops(make_net, gb,
                                           timesteps=timesteps)
    per_chip_flops = step_flops / n if step_flops else None

    reg = get_registry()

    def timed_sharded(bucket_bytes=None, grad_dtype=None, block_scan=None):
        """One sharded arm under explicit collective knobs. Reports the
        throughput AND the per-arm evidence: allreduce wire-byte delta,
        the chosen bucket schedule, `graph_block` body-trace count and
        the first dispatch's trace+compile wall time (where the
        scan-over-blocks collapse shows up)."""
        mesh = data_parallel_mesh()
        net = make_net().set_mesh(mesh, bucket_bytes=bucket_bytes,
                                  grad_dtype=grad_dtype)
        if block_scan is not None and hasattr(net, "set_block_scan"):
            net.set_block_scan(block_scan)
        if per_chip_flops:
            net.set_model_flops_per_example(step_flops / gb, flops_source)
        plan = net._mesh_plan
        # pre-shard ONCE onto the mesh: the prefetch placement then
        # detects the committed sharding and passes through zero-copy
        # (the contract tests/test_sharded_step.py pins; the measured
        # fit_data_wait_mean_ms is REPORTED in the artifact — on a
        # contended CPU box per-epoch thread spin-up keeps it nonzero)
        staged = plan.shard_batch(ds)
        wait = reg.histogram(
            "fit_data_wait_seconds",
            "time blocked on the data iterator (ETL) before a "
            "dispatch").labels()
        gb_notes = reg.counter(
            "compile_total", "jit cache insertions (fresh traces)",
            ("kind",)).labels("graph_block")
        ar = reg.counter(
            "allreduce_bytes_total",
            "gradient bytes all-reduced in-graph by the sharded "
            "train step (logical payload: summed gradient leaf "
            "bytes per optimizer step)").labels()
        c0, s0, ar0, gb0 = wait.count, wait.sum, ar.value, gb_notes.value
        # first fit = trace + compile + one step: the compile-collapse
        # measurement (latency-cancelled throughput timing comes after)
        t0 = time.perf_counter()
        net.fit(ExistingDataSetIterator([staged]), epochs=1,
                async_prefetch=False)
        _sync(net)
        first_s = time.perf_counter() - t0
        dt, n_steps = _time_fit(
            net, lambda k: ExistingDataSetIterator([staged] * k), steps,
            reps=3 if on_tpu else 1)
        wait_ms = ((wait.sum - s0) / max(1, wait.count - c0)) * 1e3
        steps_total = net.iteration
        return {
            "dt": dt,
            "n_steps": n_steps,
            "wait_ms": wait_ms,
            "allreduce_bytes": int(ar.value - ar0),
            "allreduce_bytes_per_step": int(
                round((ar.value - ar0) / max(1, steps_total))),
            "graph_block_body_traces": int(gb_notes.value - gb0),
            "first_dispatch_seconds": round(first_s, 3),
            "collective": plan.collective_describe(net),
        }

    def timed_single():
        net = make_net()
        shard_ds = DataSet(
            jax.device_put(np.asarray(ds.features)[:per_chip]),
            jax.device_put(np.asarray(ds.labels)[:per_chip]))
        dt, n_steps = _time_fit(
            net, lambda k: ExistingDataSetIterator([shard_ds] * k), steps,
            reps=3 if on_tpu else 1)
        return dt, n_steps

    # Three collective arms (the A/B the bucketed path must win or tie):
    #   bucketed        — headline: default bucket schedule, and on graph
    #                     nets the scan-over-identical-blocks compile
    #                     collapse switched on.
    #   monolithic      — bucket_bytes=0 (single tail-end all-reduce) with
    #                     block runs force-unrolled: the old mainline.
    #   bucketed_bf16   — bucketed schedule + opt-in bf16 wire payload
    #                     (f32 accumulate): halves allreduce bytes.
    # (block_scan is hasattr-gated inside timed_sharded: MultiLayerNetwork
    # has no graph topology to scan, so the knob is a no-op there.)
    # Monolithic runs FIRST: the first arm absorbs one-time process
    # warmup (allocator growth, op registries) into its
    # first_dispatch_seconds, and charging that to the headline arm
    # would fake a compile-collapse regression — or hide a real one.
    arm_mono = timed_sharded(bucket_bytes=0, block_scan="unroll")
    arm_bucketed = timed_sharded(block_scan=True)
    arm_bf16 = timed_sharded(grad_dtype="bf16", block_scan=True)
    sh_dt, sh_steps = arm_bucketed["dt"], arm_bucketed["n_steps"]
    sh_wait_ms = arm_bucketed["wait_ms"]
    allreduce_bytes = arm_bucketed["allreduce_bytes"]
    si_dt, si_steps = timed_single()

    # legacy arm: per-shard device-resident batches, host averaging
    shards = []
    for s in range(n):
        sl = slice(s * per_chip, (s + 1) * per_chip)
        shards.append(DataSet(
            jnp.asarray(np.asarray(ds.features)[sl]),
            jnp.asarray(np.asarray(ds.labels)[sl])))
    vs_alt_err = None
    try:
        avg_dt = _legacy_param_averaging_fit(make_net, shards, steps)
    except Exception as e:
        avg_dt, vs_alt_err = None, f"{type(e).__name__}: {e}"

    # per-chip throughput: the sharded arm consumed gb examples/step
    def per_chip_rate(arm):
        return per_step_examples / n * arm["n_steps"] / arm["dt"]

    def arm_summary(arm):
        return {
            "value": round(per_chip_rate(arm), 2),
            "allreduce_bytes": arm["allreduce_bytes"],
            "allreduce_bytes_per_step": arm["allreduce_bytes_per_step"],
            "graph_block_body_traces": arm["graph_block_body_traces"],
            "first_dispatch_seconds": arm["first_dispatch_seconds"],
            "collective": arm["collective"],
        }

    sharded_per_chip = per_chip_rate(arm_bucketed)
    single_chip = per_step_examples / n * si_steps / si_dt
    efficiency = sharded_per_chip / single_chip if single_chip else None
    mfu = (per_chip_flops * sh_steps / sh_dt / peak_flops_per_chip()
           if on_tpu and per_chip_flops else None)
    vs_alt = {
        "collective_monolithic": round(per_chip_rate(arm_mono), 2),
        "collective_bucketed_bf16": round(per_chip_rate(arm_bf16), 2),
    }
    if avg_dt is not None:
        vs_alt["param_averaging_host"] = round(
            per_step_examples / n * steps / avg_dt, 2)
    out = {
        "value": round(sharded_per_chip, 2),
        "unit": unit,
        "devices": n,
        "per_chip_batch": per_chip,
        "global_batch": gb,
        "steps_timed": sh_steps,
        "single_chip_value": round(single_chip, 2),
        "scaling_efficiency": (None if efficiency is None
                               else round(efficiency, 3)),
        "kernel": "sharded_step_allreduce",
        "vs_alternate": vs_alt,
        **({"vs_alternate_errors": {"param_averaging_host": vs_alt_err}}
           if vs_alt_err else {}),
        # the three-arm collective A/B: bucketed is the headline arm
        # above; the per-arm evidence (wire bytes, bucket schedule,
        # graph_block trace counts, first-dispatch trace+compile wall)
        # is what makes the bucketed/bf16/scan claims falsifiable
        "collective_ab": {
            "bucketed": arm_summary(arm_bucketed),
            "monolithic": arm_summary(arm_mono),
            "bucketed_bf16": arm_summary(arm_bf16),
        },
        "fit_data_wait_mean_ms": round(sh_wait_ms, 3),
        "allreduce_bytes_total": allreduce_bytes,
        "model_flops_per_step": step_flops,
        "model_flops_per_chip": per_chip_flops,
        "flops_source": flops_source,
        "mfu": None if mfu is None else round(mfu, 4),
        "seconds": round(
            arm_bucketed["dt"] + arm_mono["dt"] + arm_bf16["dt"]
            + si_dt + (avg_dt or 0.0), 3),
    }
    return out


def bench_recsys(vocab=800_000, dim=64, hidden=192, batch=1024,
                 steps=40, warmup=6, endpoints=2, cache_rows=65536,
                 alpha=1.1, lr=0.05, seed=0, ledger_path=None):
    """Sparse-embedding recsys training over the sharded paramserver
    (parallel/sparse.SparseEmbeddingPipeline): a jitted dense tower
    (pure-jax step — runs unchanged under set_mesh, the embeddings are
    a plain [batch, dim] input) over a host-sharded multi-hundred-MB
    embedding table split across N in-process endpoints, fed synthetic
    zipf id traffic. Pull latency is INJECTED via the `paramserver_rpc`
    faultpoint (calibrated to the measured dense-step time, identical
    in both arms) so the overlap claim is about hiding the wire, not
    about localhost being fast.

    `vs_alternate` is the honesty arm: the SAME step, id stream, and
    injected latency run synchronously — no prefetch, no cache — so the
    pipelined/synchronous examples/sec ratio is the measured value of
    the overlap + hot-id cache. Coherence is graded too: both arms must
    finish with BYTE-IDENTICAL dense-tower params (the pipeline's
    write-through/dirty protocol makes cache + prefetch transparent),
    the cache books must conserve exactly (pull_rows == cache_hit +
    cache_miss), and the pull spend books per tenant under the
    paramserver tier with the process-total conservation check."""
    import tempfile
    import threading

    import jax.numpy as jnp

    from deeplearning4j_tpu.analysis.slo import (
        ERROR,
        SLORule,
        default_rule_pack,
    )
    from deeplearning4j_tpu.data.recsys import zipf_cdf, zipf_ids
    from deeplearning4j_tpu.parallel.paramserver import (
        EmbeddingParameterServer,
        EmbeddingPSClient,
    )
    from deeplearning4j_tpu.parallel.sparse import (
        SPARSE_THREAD_PREFIX,
        SparseEmbeddingPipeline,
    )
    from deeplearning4j_tpu.utils import faultpoints as _faults
    from deeplearning4j_tpu.utils import resourcemeter
    from deeplearning4j_tpu.utils import runledger as _runledger
    from deeplearning4j_tpu.utils.metrics import get_registry

    tenant = "recsys"
    if not resourcemeter.is_enabled():
        resourcemeter.enable()

    # -- the dense tower ------------------------------------------------------
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    params0 = {
        "w1": jax.random.normal(ks[0], (dim, hidden), jnp.float32)
        * np.sqrt(2.0 / dim),
        "b1": jnp.zeros((hidden,), jnp.float32),
        "w2": jax.random.normal(ks[1], (hidden, hidden), jnp.float32)
        * np.sqrt(2.0 / hidden),
        "b2": jnp.zeros((hidden,), jnp.float32),
        "w3": jax.random.normal(ks[2], (hidden, 2), jnp.float32)
        * np.sqrt(2.0 / hidden),
        "b3": jnp.zeros((2,), jnp.float32),
    }

    def _loss(p, emb, y):
        h = jnp.maximum(emb @ p["w1"] + p["b1"], 0.0)
        h = jnp.maximum(h @ p["w2"] + p["b2"], 0.0)
        logits = h @ p["w3"] + p["b3"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    @jax.jit
    def _step(p, emb, y):
        loss, (gp, gemb) = jax.value_and_grad(
            _loss, argnums=(0, 1))(p, emb, y)
        new_p = jax.tree_util.tree_map(lambda a, g: a - lr * g, p, gp)
        return new_p, (-lr) * gemb, loss

    # calibrate: the injected pull latency tracks the measured dense
    # step so the overlap is a real hiding problem on ANY box (too-fast
    # compute would make both arms wire-bound; too-slow would hide the
    # wire for free in the synchronous arm too)
    emb_d = jnp.zeros((batch, dim), jnp.float32)
    y_d = jnp.zeros((batch,), jnp.int32)
    p_c, g_c, _ = _step(params0, emb_d, y_d)
    jax.block_until_ready(g_c)
    t0 = time.perf_counter()
    for _ in range(5):
        p_c, g_c, _ = _step(params0, emb_d, y_d)
    jax.block_until_ready(g_c)
    compute_ms = (time.perf_counter() - t0) / 5 * 1e3
    lat_ms = float(min(60.0, max(10.0, compute_ms)))

    # -- ledger + SLO rule pack ----------------------------------------------
    if ledger_path is None:
        ledger_path = os.path.join(
            tempfile.gettempdir(), f"BENCH_recsys_ledger_{os.getpid()}.jsonl")
    sample_every = 0.5
    rules = default_rule_pack(sample_every=sample_every,
                              tenants={tenant: 1.0})
    rules.append(SLORule(
        name="paramserver_push_dropped",
        kind="rate_of_change",
        series="paramserver_client_push_dropped_total",
        op=">", value=0.0, severity=ERROR,
        component="paramserver", for_seconds=0.0))
    rules.append(SLORule(
        name="sparse_prefetch_unhealthy",
        kind="threshold",
        series='component_health{component="sparse_prefetch"}',
        op=">=", value=2.0, severity=ERROR,
        component="sparse_prefetch", for_seconds=0.0))
    ledger = _runledger.RunLedger(ledger_path, sample_every=sample_every,
                                  rules=rules)
    _runledger.attach(ledger)

    # identical id/label streams for both arms (seeded zipf)
    cdf = zipf_cdf(vocab, alpha)
    n_batches = warmup + steps + 1
    batches = [zipf_ids(batch, vocab, alpha, seed=seed * 1000 + k, cdf=cdf)
               for k in range(n_batches)]
    labels = [jnp.asarray((ids & 1).astype(np.int32)) for ids in batches]

    def run_arm(prefetch, arm_cache_rows):
        servers = [EmbeddingParameterServer(
            {"emb": np.zeros((vocab, dim), np.float32)})
            for _ in range(endpoints)]
        ports = [s.start() for s in servers]
        client = EmbeddingPSClient(
            [f"http://127.0.0.1:{pt}" for pt in ports], tenant=tenant)
        try:
            pipe = SparseEmbeddingPipeline(
                client, "emb", cache_rows=arm_cache_rows,
                prefetch=prefetch)
            p = params0
            dt = None
            rows_seen = 0
            with pipe:
                if prefetch:
                    pipe.prefetch(batches[0])
                t_start = time.perf_counter()
                for k in range(warmup + steps):
                    if k == warmup:
                        t_start = time.perf_counter()
                    emb = pipe.lookup(batches[k])
                    if prefetch:
                        pipe.prefetch(batches[k + 1])
                    p, delta, _ = _step(p, jnp.asarray(emb), labels[k])
                    delta = np.asarray(delta)  # blocks: compute is in dt
                    pipe.push(batches[k], delta)
                    if k >= warmup:
                        rows_seen += batches[k].size
                dt = time.perf_counter() - t_start
                stats = pipe.stats()
                pulls = sorted(pipe.pull_seconds)
            if not client.flush(timeout=60.0):
                raise RuntimeError("recsys arm: paramserver flush "
                                   "timed out")
            p = jax.tree_util.tree_map(np.asarray, p)
        finally:
            client.close()
            for s in servers:
                s.stop()
        pull_p50 = (float(np.percentile(pulls, 50)) * 1e3) if pulls else None
        pull_p99 = (float(np.percentile(pulls, 99)) * 1e3) if pulls else None
        if stats["pull_rows"] != stats["cache_hit"] + stats["cache_miss"]:
            raise RuntimeError(f"cache books violated: {stats}")
        return {
            "examples_per_sec": round(rows_seen / dt, 1),
            "step_ms": round(dt / steps * 1e3, 3),
            "pull_p50_ms": None if pull_p50 is None else round(pull_p50, 3),
            "pull_p99_ms": None if pull_p99 is None else round(pull_p99, 3),
            "cache_hit_rate": round(stats["hit_rate"], 4),
            "stats": stats,
        }, p

    plan = _faults.FaultPlan(seed=seed, rules=[_faults.FaultRule(
        point="paramserver_rpc", kind="latency", p=1.0,
        latency_ms=lat_ms)])
    spend0 = resourcemeter.spend_table(get_registry().scalar_values())
    with _faults.active(plan):
        piped, p_piped = run_arm(True, cache_rows)
        sync, p_sync = run_arm(False, 0)
    spend1 = resourcemeter.spend_table(get_registry().scalar_values())
    tenant_cons = resourcemeter.conservation(get_registry().scalar_values())
    ledger.close()
    slo_fired = ledger.rules.ever_fired()
    slo_fired_errors = ledger.rules.ever_fired("error")
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith(SPARSE_THREAD_PREFIX)]
    if leaked:
        raise RuntimeError(f"leaked sparse threads: {leaked}")
    if not tenant_cons["ok"]:
        # the per-tenant spend must sum to the process totals per tier —
        # a leak is a correctness bug, not a perf number
        raise RuntimeError(f"tenant spend conservation violated: "
                           f"{tenant_cons}")
    identical = all(
        np.asarray(a).tobytes() == np.asarray(b).tobytes()
        for a, b in zip(jax.tree_util.tree_leaves(p_piped),
                        jax.tree_util.tree_leaves(p_sync)))
    speedup = (piped["examples_per_sec"]
               / max(sync["examples_per_sec"], 1e-9))
    return {
        "value": piped["examples_per_sec"],
        "unit": "examples_per_sec_pipelined",
        "vocab": vocab,
        "dim": dim,
        "table_mb": round(vocab * dim * 4 / 2**20, 1),
        "endpoints": endpoints,
        "batch": batch,
        "steps": steps,
        "cache_rows": cache_rows,
        "zipf_alpha": alpha,
        "compute_ms": round(compute_ms, 3),
        "injected_pull_latency_ms": round(lat_ms, 3),
        "pipelined": piped,
        "vs_alternate": {
            "alternate": "synchronous_pull_no_prefetch_no_cache",
            **sync,
        },
        "speedup_vs_synchronous": round(speedup, 2),
        "overlap_win": bool(speedup >= 2.0),
        "trajectory_identical": bool(identical),
        "slo": {
            "ledger": ledger_path,
            "run_id": ledger.run_id,
            "rules": [r.name for r in ledger.rules.rules],
            "fired": slo_fired,
            "fired_errors": slo_fired_errors,
        },
        "slo_ok": not slo_fired_errors,
        "tenant_spend_paramserver_s": round(
            spend1.get(tenant, {}).get("device_seconds", {}).get(
                resourcemeter.TIER_PARAMSERVER, 0.0)
            - spend0.get(tenant, {}).get("device_seconds", {}).get(
                resourcemeter.TIER_PARAMSERVER, 0.0), 4),
        "tenant_conservation": tenant_cons,
    }


WORKLOADS = {
    "resnet50": bench_resnet50,
    "lenet": bench_lenet,
    "char_lstm": bench_char_lstm,
    "word2vec": bench_word2vec,
    "vgg16_keras_import": bench_vgg16,
    "parallel_inference": bench_parallel_inference,
    "parallel_inference_overload": bench_parallel_inference_overload,
    "input_pipeline": bench_input_pipeline,
    "decode": bench_decode,
    "recsys": bench_recsys,
}

# Per-workload subprocess timeouts (seconds). The big convnets get
# headroom for two cold compiles (warmup shape + timed shape share one,
# but bf16 ResNet-50 compiles are the slowest thing we run).
TIMEOUTS = {
    "resnet50": 600,
    "lenet": 420,
    "char_lstm": 600,
    "word2vec": 600,
    "vgg16_keras_import": 600,
    "parallel_inference": 420,
    "parallel_inference_overload": 240,
    "input_pipeline": 300,
    "decode": 300,
    "recsys": 420,
}
OVERALL_DEADLINE = float(os.environ.get("BENCH_DEADLINE_SEC", 1500))


def _child_env():
    env = dict(os.environ)
    # Persistent compilation cache, shared by all of a command's children:
    # where JAX_COMPILATION_CACHE_DIR says if it is set, else one fixed
    # path in the checkout (the path is part of the cache key, so it may
    # never be a temporary name). No other cache path is set anywhere.
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".jax_cache"))
    return env


def _run_child(args, timeout, extra_env=None):
    """Run `python bench.py <args>` with a hard timeout; return
    (parsed-last-json-line | None, error | None)."""
    cmd = [sys.executable, os.path.abspath(__file__)] + args
    env = _child_env()
    if extra_env:
        env.update(extra_env)
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout, env=env)
    except subprocess.TimeoutExpired:
        return None, "timeout"
    if proc.returncode != 0:
        tail = (proc.stderr or "").strip().splitlines()[-3:]
        return None, f"rc={proc.returncode}: " + " | ".join(tail)[-400:]
    for line in reversed((proc.stdout or "").strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line), None
            except json.JSONDecodeError:
                continue
    return None, "no JSON on stdout"


def _prior_bench():
    """Newest committed BENCH_r*.json next to this file — the perf
    trajectory's previous point. The committed files are driver-wrapped
    ({"n", "cmd", "rc", "tail"}) with this script's final JSON line inside
    "tail"; a bare bench result (this script's own output saved directly)
    is accepted too. Returns (basename, result) or (None, None)."""
    import glob
    import re

    def round_no(p):  # numeric, not lexicographic: r6 < r10 < r100
        m = re.search(r"BENCH_r(\d+)\.json$", p)
        return int(m.group(1)) if m else -1

    here = os.path.dirname(os.path.abspath(__file__))
    for path in sorted(glob.glob(os.path.join(here, "BENCH_r*.json")),
                       key=round_no, reverse=True):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        if not isinstance(doc, dict):
            continue
        if "workloads" in doc:
            return os.path.basename(path), doc
        for line in reversed(str(doc.get("tail", "")).strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    result = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if "workloads" in result:
                    return os.path.basename(path), result
    return None, None


def _vs_baseline(workloads, backend):
    """Per-workload speedup vs the newest prior BENCH_r*.json, so the
    trajectory is self-reporting. (The reference itself publishes no
    numbers — BASELINE.md — so the prior round is the only honest
    baseline there is; `source` names it.) Ratios are only computed
    against a prior run on the SAME backend — a CPU smoke run vs a TPU
    round would report nonsense 0.00x "slowdowns"."""
    prior_name, prior = _prior_bench()
    if not prior:
        return None
    prior_backend = prior.get("backend")
    if backend != prior_backend:
        result = {"source": prior_name,
                  "note": f"backend mismatch ({backend} vs prior "
                          f"{prior_backend}): ratios omitted"}
        # Speedup ratios are backend-bound, but the FLOP-accounting
        # question is not: "does today's cost model price the PRIOR
        # round's dims the way that round recorded?" is answerable on
        # any host by recomputing the static model at the prior dims
        # (cli perf's vs-prior check; each workload child does it, see
        # `_workload` — the orchestrator traces nothing). Without this, a pending
        # accounting change could hide behind a backend switch and
        # resurface as a phantom MFU jump later.
        drift = {name: out["flop_drift_at_prior_dims"]
                 for name, out in workloads.items()
                 if (out or {}).get("flop_drift_at_prior_dims")}
        if drift:
            result["flop_model_changed"] = drift
            result["flop_model_note"] = (
                "model_flops_per_step of the prior round differs from "
                "the current cost model evaluated at the prior round's "
                "own dims — MFU is not comparable across the two "
                "accountings")
            _ack_known_repricing(result, drift)
        return result
    ratios = {}
    flop_drift = {}
    for name, out in workloads.items():
        prior_wl = (prior.get("workloads") or {}).get(name) or {}
        pv = prior_wl.get("value")
        cv = out.get("value")
        if pv and cv:
            ratios[name] = round(cv / pv, 3)
        # FLOP-model drift (non-fatal warning): a speedup ratio is only
        # meaningful when both rounds agree on what a step COSTS — an
        # MFU "improvement" caused by a FLOP-accounting change must
        # surface as accounting, never as performance
        pf = prior_wl.get("model_flops_per_step")
        cf = out.get("model_flops_per_step")
        if pf and cf and abs(cf / pf - 1.0) > 0.01:
            flop_drift[name] = {
                "prior": pf,
                "current": cf,
                "ratio": round(cf / pf, 4),
                "prior_source": prior_wl.get("flops_source", "analytic"),
                "current_source": out.get("flops_source"),
            }
    result = {
        "source": prior_name,
        "headline": ratios.get("resnet50"),
        "speedup": ratios,
    }
    if flop_drift:
        result["flop_model_changed"] = flop_drift
        result["flop_model_note"] = (
            "model_flops_per_step differs from the prior round for these "
            "workloads — their MFU numbers are not comparable across "
            "rounds until the accounting change is acknowledged")
        _ack_known_repricing(result, flop_drift)
    return result


def _flop_drift_at_prior_dims(prior, workloads):
    """Cross-backend FLOP-drift detail for `_vs_baseline`, computed in the
    workload's child (it traces the cost model): for each workload
    measured THIS run that the prior round priced, recompute the static
    cost model at the prior round's recorded dims and compare with what
    it recorded. Only runs when the current round actually carries model
    FLOPs."""
    if not any((out or {}).get("model_flops_per_step")
               for out in workloads.values()):
        return {}
    from deeplearning4j_tpu.cli import _perf_vs_prior

    drift = {}
    for name, preset in (("resnet50", "resnet50"),
                         ("char_lstm", "charlstm")):
        if name not in workloads:
            continue
        if not ((prior.get("workloads") or {}).get(name) or {}).get(
                "model_flops_per_step"):
            continue
        try:
            vp = _perf_vs_prior(preset)
        except Exception as e:  # the drift check must never kill a round
            drift[name] = {"note": f"recompute failed: "
                                   f"{type(e).__name__}: {e}"}
            continue
        if vp and vp.get("drifted"):
            drift[name] = {
                "prior": vp["prior_model_flops_per_step"],
                "current_at_prior_dims": vp["costmodel_flops_per_step"],
                "ratio": vp["ratio"],
                "prior_source": vp.get("prior_flops_source", "analytic"),
                "current_source": "costmodel",
            }
    return drift


def _ack_known_repricing(result, drift):
    """Acknowledge the one known accounting change in the artifact
    itself: every drifted workload moved from the analytic per-layer
    estimate to the costmodel jaxpr trace (the PR 9 switch). The flag
    still fires — this note rides NEXT to it so the committed round
    records both the drift and its cause, and the chain is clean from
    the next round on (both sides costmodel => no drift)."""
    entries = [d for d in drift.values() if "ratio" in d]
    if entries and all(d.get("prior_source") in (None, "analytic")
                       and d.get("current_source") == "costmodel"
                       for d in entries):
        result["flop_model_ack"] = (
            "expected one-time repricing: the prior round recorded the "
            "analytic per-layer FLOP estimate; model FLOPs are now the "
            "cost-model jaxpr trace (HLO valid-pair conv accounting). "
            "MFU baselines reset at this round and are comparable again "
            "from the next round on.")


def _prior_multichip():
    """Newest committed MULTICHIP_r*.json next to this file — the
    multi-chip trajectory's previous point. Same tolerance as
    _prior_bench: driver-wrapped ({"tail": ...}) or bare result JSON.
    Returns (basename, result) or (None, None)."""
    import glob
    import re

    def round_no(p):
        m = re.search(r"MULTICHIP_r(\d+)\.json$", p)
        return int(m.group(1)) if m else -1

    here = os.path.dirname(os.path.abspath(__file__))
    for path in sorted(glob.glob(os.path.join(here, "MULTICHIP_r*.json")),
                       key=round_no, reverse=True):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        if not isinstance(doc, dict):
            continue
        if "workloads" in doc:
            return os.path.basename(path), doc
        for line in reversed(str(doc.get("tail", "")).strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    result = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if "workloads" in result:
                    return os.path.basename(path), result
    return None, None


def _vs_multichip_baseline(workloads, backend, devices):
    """Multi-chip analogue of _vs_baseline: the comparable number across
    MULTICHIP rounds is `scaling_efficiency` (a within-round ratio, so it
    survives box-speed noise that raw img/s does not); the raw per-chip
    `value` ratio rides along as secondary evidence. Ratios only against
    a prior round on the SAME backend and device count, with the same
    FLOP-drift tripwire as the kernel benches."""
    prior_name, prior = _prior_multichip()
    if not prior:
        return None
    prior_backend = prior.get("backend")
    prior_devices = prior.get("devices")
    if backend != prior_backend or devices != prior_devices:
        return {"source": prior_name,
                "note": f"setup mismatch ({backend}/{devices}dev vs prior "
                        f"{prior_backend}/{prior_devices}dev): "
                        "ratios omitted"}
    eff_ratios, val_ratios, flop_drift = {}, {}, {}
    for name, out in workloads.items():
        prior_wl = (prior.get("workloads") or {}).get(name) or {}
        pe, ce = prior_wl.get("scaling_efficiency"), out.get(
            "scaling_efficiency")
        if pe and ce:
            eff_ratios[name] = round(ce / pe, 3)
        pv, cv = prior_wl.get("value"), out.get("value")
        if pv and cv:
            val_ratios[name] = round(cv / pv, 3)
        pf = prior_wl.get("model_flops_per_step")
        cf = out.get("model_flops_per_step")
        if pf and cf and abs(cf / pf - 1.0) > 0.01:
            flop_drift[name] = {
                "prior": pf, "current": cf, "ratio": round(cf / pf, 4),
                "prior_source": prior_wl.get("flops_source", "analytic"),
                "current_source": out.get("flops_source"),
            }
    result = {
        "source": prior_name,
        "headline": eff_ratios.get("resnet50"),
        "efficiency_ratio": eff_ratios,
        "value_ratio": val_ratios,
    }
    if flop_drift:
        result["flop_model_changed"] = flop_drift
        result["flop_model_note"] = (
            "model_flops_per_step differs from the prior round for these "
            "workloads — an accounting change, never a speedup")
    return result


def _run_checked(fn, name):
    """Child mode: run one workload and refuse what it measured when a
    helper fell back behind its back — an auto-disable, a helper fn or a
    probe that raised (the fused-ReLU shortcut books there too), or a
    helper left disabled at the end (the A/B arms restore the switches
    they flip). The row would carry the kernel's name over the built-in
    path's number."""
    from deeplearning4j_tpu.ops.helpers import (
        helper_books,
        helper_names,
        hidden_fallbacks,
    )

    since = helper_books()
    out = fn(name)
    problems = hidden_fallbacks(since, expect_enabled=tuple(helper_names()))
    if problems:
        raise RuntimeError(f"{name}: hidden fallback — " + "; ".join(problems))
    dev = jax.devices()[0]
    out["backend"] = jax.default_backend()
    out["device"] = dev.device_kind
    return out


def _workload_multichip(name):
    """Child mode: one multi-chip workload on this process's full device
    set (the orchestrator forced the virtual device count for CPU hosts).
    Auto-mesh is pinned OFF here because the A/B needs all three arms
    explicit — the sharded arm calls set_mesh itself, and the single-chip
    baseline must NOT silently shard over the forced mesh (the t1.sh
    smoke covers the auto-engagement default)."""
    os.environ["DL4J_AUTO_MESH"] = "0"
    print(json.dumps(_run_checked(_bench_multichip, name)))


def _exit_code(result) -> int:
    """Non-zero whenever a workload errored, timed out or was skipped:
    a round with a hole in it is not a result."""
    return 1 if result.get("errors") or result.get("infra_error") else 0


def _collect(name, out, err, workloads, errors, seen):
    """Book one child's outcome. The first child that reports fixes the
    backend and device of the round; a later child on another backend
    (a silent CPU fallback) is an error, not a row."""
    if out is None:
        errors[name] = err
        print(f"[bench] {name}: ERROR {err}", file=sys.stderr)
        return
    backend, device = out.pop("backend", None), out.pop("device", None)
    seen.setdefault("backend", backend)
    seen.setdefault("device", device)
    if backend != seen["backend"]:
        errors[name] = (f"backend mismatch: child ran on {backend}, "
                        f"earlier children on {seen['backend']}")
        print(f"[bench] {name}: ERROR {errors[name]}", file=sys.stderr)
        return
    workloads[name] = out
    print(f"[bench] {name}: {json.dumps(out)}", file=sys.stderr)


def main_multichip(devices=None) -> int:
    """Multi-chip orchestrator: per-workload subprocesses like main(),
    with the host-platform device count forced for CPU hosts (the flag
    only concerns the CPU platform; a TPU host uses its real chips).
    Prints ONE JSON line — the committed MULTICHIP_r0x artifact format —
    and returns the exit code."""
    devices = devices or _n_multichip_devices()
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={devices}")
    extra = {"XLA_FLAGS": " ".join(flags)}
    workloads, errors, seen = {}, {}, {}
    for name in ("resnet50", "char_lstm"):
        # 1500s: the three-arm collective A/B compiles three distinct
        # SPMD programs per workload; on a 1-core box forcing 8 virtual
        # devices the resnet50 child alone measures ~800s
        out, err = _run_child(["--workload-multichip", name], 1500,
                              extra_env=extra)
        _collect(name, out, err, workloads, errors, seen)
    backend = seen.get("backend")
    # report the device count the workloads ACTUALLY ran on: off-cpu no
    # forcing happens, so a 4-chip box must not headline "devices": 8
    ran_on = {wl.get("devices") for wl in workloads.values()
              if wl.get("devices")}
    result = {
        "metric": "multichip_scaling_efficiency",
        "mode": "multichip",
        "devices": ran_on.pop() if len(ran_on) == 1 else devices,
        "backend": backend,
        "device": seen.get("device"),
        "note": ("cpu backend = virtual host-platform devices (mechanism "
                 "evidence, not silicon perf)" if backend == "cpu"
                 else None),
        "workloads": workloads,
    }
    vs = _vs_multichip_baseline(workloads, backend, result["devices"])
    if vs is not None:
        result["vs_baseline"] = vs
    if errors:
        result["errors"] = errors
    print(json.dumps(result))
    return _exit_code(result)


def _workload(name):
    """Child mode: run one workload, print its JSON dict. The shared
    metrics-registry snapshot rides along so compile counts, helper
    hit/fallback/auto-disable events, and step-phase histograms land in
    the committed BENCH_r*.json next to the perf numbers they explain."""
    out = _run_checked(lambda n: WORKLOADS[n](), name)
    # the cross-backend FLOP-accounting check traces the cost model, so
    # it runs here and not in the orchestrator (see `_vs_baseline`)
    _, prior = _prior_bench()
    if prior and prior.get("backend") != out["backend"]:
        drift = _flop_drift_at_prior_dims(prior, {name: out}).get(name)
        if drift:
            out["flop_drift_at_prior_dims"] = drift
    try:
        from deeplearning4j_tpu.utils.metrics import get_registry

        out["metrics_registry"] = get_registry().snapshot()
    except Exception as e:  # a metrics bug must never sink a bench run
        out["metrics_registry"] = {"error": f"{type(e).__name__}: {e}"}
    print(json.dumps(out))


def main() -> int:
    # monotonic: the budget must not move when NTP slews the wall clock
    # mid-run (lint CC007)
    t0 = time.monotonic()
    remaining = lambda: OVERALL_DEADLINE - (time.monotonic() - t0)

    workloads, errors, seen = {}, {}, {}
    # --only a,b runs a subset (regenerating one round's artifact without
    # paying for every workload); unknown names fail loudly, not silently
    selected = dict(WORKLOADS)
    if "--only" in sys.argv:
        only = sys.argv[sys.argv.index("--only") + 1].split(",")
        unknown = [n for n in only if n not in WORKLOADS]
        if unknown:
            raise SystemExit(f"--only: unknown workloads {unknown}; "
                             f"known: {sorted(WORKLOADS)}")
        selected = {n: WORKLOADS[n] for n in only}

    for name in selected:
        budget = min(TIMEOUTS[name], remaining())
        if budget < 60:
            errors[name] = "skipped: overall deadline"
            continue
        t_wl = time.monotonic()
        out, err = _run_child(["--workload", name], budget)
        if out is not None:
            out["elapsed_sec"] = round(time.monotonic() - t_wl, 1)
        _collect(name, out, err, workloads, errors, seen)

    head = workloads.get("resnet50", {})
    backend = seen.get("backend")
    result = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": head.get("value"),
        "unit": head.get("unit", "images/sec/chip"),
        # per-workload speedup vs the newest prior BENCH_r*.json; the
        # reference itself publishes no numbers (BASELINE.md), hence the
        # explicit null vs_reference rather than a self-graded 1.0
        "vs_baseline": _vs_baseline(workloads, backend),
        "vs_reference": None,
        "mfu": head.get("mfu"),
        "backend": backend,
        "device": seen.get("device"),
        "workloads": workloads,
    }
    if errors:
        result["errors"] = errors
    if not seen:
        result["infra_error"] = "no workload reached a device"
    print(json.dumps(result))
    return _exit_code(result)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--multichip":
        n_dev = None
        if "--devices" in sys.argv:
            n_dev = int(sys.argv[sys.argv.index("--devices") + 1])
        sys.exit(main_multichip(n_dev))
    elif len(sys.argv) > 1 and sys.argv[1] == "--workload-multichip":
        _workload_multichip(sys.argv[2])
    elif len(sys.argv) > 1 and sys.argv[1] == "--workload":
        name = sys.argv[2]
        if "--overload" in sys.argv[3:]:
            # `bench.py --workload parallel_inference --overload` is
            # the graceful-degradation variant of a serving workload
            name = f"{name}_overload"
        _workload(name)
    else:
        sys.exit(main())

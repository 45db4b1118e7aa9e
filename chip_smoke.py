"""Does the main path still start on the chip?

    python chip_smoke.py               one TPU chip: train ResNet-50 and the
                                       char-LSTM a few steps each through
                                       fit(), then serve /predict and
                                       /generate from an in-process server
    python chip_smoke.py --multichip   all chips of the host (four): ResNet-50
                                       through fit()'s auto-attached
                                       data-parallel mesh, against the same
                                       net pinned to one of those devices —
                                       and nothing else

Widths are the shipped models' own (ResNet-50 at 224², batch 128, bf16; the
char-LSTM at vocab 77, hidden 200, 2 layers, TBPTT 50, batch 64); weights
and data are random from a seed; steps and requests are a few. Each phase
prints one JSON line (`phase`, `ok`, `seconds`, of which `compile_seconds`
lowering and compiling and `run_seconds` the rest, what it checked), and
the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Any failed phase makes the exit code non-zero and that line is not printed.
Without a TPU the script exits non-zero at once: there is no CPU branch and
no shrunken configuration. One process owns the chip from start to end.

A phase proves that the KERNEL path ran, not that something ran: helper
hits per kernel family, no auto-disable and no raised helper or probe
(`ops/helpers.hidden_fallbacks`), `tpu_custom_call` in the compiled step,
and a first-step score that agrees with the same net on the built-in XLA
lowering. Seconds are printed for orientation; no performance is claimed.

Compile cache: `JAX_COMPILATION_CACHE_DIR` if set, else `.jax_cache` next to
this file; no other path is ever set. A second run finds the first run's
programs there (`cache_hits` per phase).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
import traceback
import urllib.request
from typing import Callable, List, Optional

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
CONV_OPS = ("conv2d", "batch_norm", "bn_backward")
LSTM_OPS = ("lstm_sequence",)

# Tolerances. bf16 keeps 8 bits of mantissa (eps 2^-8 = 3.9e-3): two
# lowerings of a 50-layer bf16 forward differ by a few eps in the loss. The
# char-LSTM is f32, but the XLA path multiplies f32 matrices in bf16 passes
# by default on the TPU while the kernel multiplies in f32, so the two
# losses also differ at the bf16 level.
SCORE_RTOL = 2e-2
# The second step's score has been through one optimizer update, so the
# backward kernels are in it; the update also amplifies the forward's
# bf16-level differences. A wrong backward (a dropped term, a sign) moves
# it by far more than this.
STEP2_RTOL = 1e-1
PREDICT_RTOL, PREDICT_ATOL = 1e-2, 1e-5
# a greedy token may differ from the reference only where the reference's
# two best probabilities are this close (a tie that another batch shape's
# rounding may break the other way)
TIE_MARGIN = 1e-3


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What a run is sized by. `_full_sizes()` is the only configuration
    the program runs; tests/test_chip_smoke.py hands a tiny one to
    `run_phases` directly."""
    resnet_conf: Callable[[], object]
    resnet_batch: int
    resnet_image: int
    resnet_classes: int
    resnet_steps: int
    lstm_net: Callable[[], object]
    lstm_vocab: int
    lstm_batch: int
    lstm_seq: int
    lstm_batches: int
    predict_sizes: tuple
    predict_steps: int
    predict_max_batch: int
    prompts: tuple
    gen_tokens: int
    decode_slots: int
    kernel_marker: Optional[str]   # in the compiled step's text on the TPU
    seed: int = 0


def _full_sizes() -> Sizes:
    from deeplearning4j_tpu.models.charlstm import char_lstm_network
    from deeplearning4j_tpu.models.resnet import resnet50_conf

    return Sizes(
        resnet_conf=lambda: resnet50_conf(num_classes=1000, image_size=224,
                                          precision="bf16"),
        resnet_batch=128, resnet_image=224, resnet_classes=1000,
        resnet_steps=3,
        lstm_net=char_lstm_network,  # vocab 77, hidden 200, 2 layers, TBPTT 50
        lstm_vocab=77, lstm_batch=64, lstm_seq=200, lstm_batches=2,
        predict_sizes=(1, 3, 8, 5, 2, 6), predict_steps=16,
        predict_max_batch=8,
        prompts=((5, 17, 3), (60,), (1, 2, 3, 4, 5, 6, 7), (33, 9),
                 (70, 0, 12, 44), (8, 8)),
        gen_tokens=12, decode_slots=4,
        kernel_marker="tpu_custom_call",
    )


# -- measuring ----------------------------------------------------------------

class CompileMeter:
    """Seconds jax spent lowering and compiling (reading the persistent
    cache included), and what that cache answered, from jax's own
    monitoring events. Tracing is left out: jax reports nested traces
    one inside the other, and their sum exceeds the wall clock."""
    _DURATIONS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

    def install(self) -> "CompileMeter":
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event: str, duration: float, **_):
        if event in self._DURATIONS:
            self.seconds += duration

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self):
        return self.seconds, self.cache_hits, self.cache_misses


class PhaseFailed(Exception):
    """A check of a phase did not hold."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def run_phase(name: str, fn: Callable[[], dict], meter: CompileMeter) -> dict:
    """Run one phase, print its JSON line and return it."""
    t0 = time.perf_counter()
    c0, h0, m0 = meter.snapshot()
    line = {"phase": name, "ok": False}
    try:
        line.update(fn())
        line["ok"] = True
    except Exception as e:  # the phase's failure is its result
        traceback.print_exc(file=sys.stderr)
        line["error"] = f"{type(e).__name__}: {e}"[:600]
    c1, h1, m1 = meter.snapshot()
    seconds = time.perf_counter() - t0
    line.update(seconds=round(seconds, 2),
                compile_seconds=round(c1 - c0, 2),
                run_seconds=round(max(seconds - (c1 - c0), 0.0), 2),
                cache_hits=h1 - h0, cache_misses=m1 - m0)
    print(json.dumps(line), flush=True)
    return line


def _check_helpers(since: dict, ops, expected_families) -> dict:
    """The no-hidden-fallback rule of a phase: nothing auto-disabled,
    nothing raised, the phase's helpers still enabled, and a hit in every
    family the phase expects."""
    from deeplearning4j_tpu.ops.helpers import helper_books, hidden_fallbacks

    problems = hidden_fallbacks(since, expect_enabled=ops)
    _check(not problems, "hidden fallback: " + "; ".join(problems))
    delta = helper_books(since)
    missing = sorted(set(expected_families) - set(delta["hits"]))
    _check(not missing, f"no helper hit in families {missing} "
                        f"(hits: {delta['hits']})")
    return {"helper_hits": delta["hits"],
            "helper_fallbacks": delta["fallbacks"],
            "helper_auto_disable_total": sum(delta["auto_disable"].values())}


class _RecordedProgram:
    """A jitted step that remembers the shapes of its first call, so the
    program that ran can be lowered again and read."""

    def __init__(self, jitted):
        self.jitted = jitted
        self.abstract_args = None

    def __call__(self, *args):
        if self.abstract_args is None:
            import jax

            # only a committed array's placement is part of the call
            self.abstract_args = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype,
                    sharding=a.sharding if getattr(a, "committed", False)
                    else None)
                if hasattr(a, "shape") else a, args)
        return self.jitted(*args)

    def compiled_text(self) -> str:
        # the persistent cache answers this second compile
        return self.jitted.lower(*self.abstract_args).compile().as_text()


def _record_step_programs(net) -> List[_RecordedProgram]:
    """Every step program `net` builds from now on, through the one place
    all of them get their jit (`netbase._jit_step`)."""
    programs: List[_RecordedProgram] = []
    jit_step = net._jit_step

    def recording(step, **kw):
        programs.append(_RecordedProgram(jit_step(step, **kw)))
        return programs[-1]

    net._jit_step = recording
    return programs


def _step_text(programs: List[_RecordedProgram]) -> str:
    ran = [p for p in programs if p.abstract_args is not None]
    _check(bool(ran), "no step program was dispatched")
    return "\n".join(p.compiled_text() for p in ran)


def _fit_scores(net, datasets) -> List[float]:
    """fit() over the batches once; the score of every optimizer step."""
    from deeplearning4j_tpu.data.iterators import ExistingDataSetIterator
    from deeplearning4j_tpu.train.listeners import (
        CollectScoresIterationListener,
    )

    scores = CollectScoresIterationListener()
    net.set_listeners(scores)
    net.fit(ExistingDataSetIterator(list(datasets)), epochs=1,
            async_prefetch=True)
    return [s for _, s in scores.scores]


def _builtin_arm(make_net, datasets) -> List[float]:
    """The comparison arm: the same net, seed and batches on the built-in
    lowering, the kernel helpers switched off (and restored after). Its
    books must show that no kernel ran in it, or the comparison compares
    a path with itself."""
    from deeplearning4j_tpu.ops.helpers import helper_books
    from deeplearning4j_tpu.utils.flops import _helpers_disabled

    since = helper_books()
    with _helpers_disabled():
        scores = _fit_scores(make_net(), datasets)
    delta = helper_books(since)
    _check(not delta["hits"] and bool(delta["fallbacks"].get("disabled")),
           f"comparison arm: hits {delta['hits']}, fallbacks "
           f"{delta['fallbacks']}")
    return scores


def _compare_scores(scores, ref_scores, ref_name: str) -> dict:
    """Step 1 is the forward alone; step 2 has the backward in it."""
    for step, rtol in ((0, SCORE_RTOL), (1, STEP2_RTOL)):
        _check(math.isclose(scores[step], ref_scores[step], rel_tol=rtol),
               f"step {step + 1} score {scores[step]} vs "
               f"{ref_scores[step]} on {ref_name}: more than rtol {rtol} "
               "apart")
    return {"scores": scores, f"{ref_name}_scores": ref_scores[:2],
            "score_rtol": [SCORE_RTOL, STEP2_RTOL],
            "score_rel_diff": [
                abs(a - b) / max(abs(a), abs(b))
                for a, b in zip(scores[:2], ref_scores[:2])]}


# -- phases -------------------------------------------------------------------

def phase_device() -> dict:
    """What jax runs on; on a TPU (`main` lets nothing else through, the
    tests hand the phases a CPU), that interpret mode is off and that the
    peak tables answer for this chip by table match, not by default."""
    import jax

    dev = jax.devices()[0]
    out = {"platform": dev.platform, "kind": dev.device_kind,
           "count": len(jax.devices()),
           "cache_dir": jax.config.jax_compilation_cache_dir}
    if dev.platform != "tpu":
        return out
    from deeplearning4j_tpu.ops import pallas_conv_bn, pallas_lstm
    from deeplearning4j_tpu.utils import flops

    _check(not pallas_conv_bn._INTERPRET and not pallas_lstm._INTERPRET,
           "a Pallas interpret flag is on with a TPU backend")
    out["interpret_flags"] = False
    gen = flops.DEVICE_KINDS.get(dev.device_kind)
    _check(gen is not None, f"device kind {dev.device_kind!r} not in "
                            "utils/flops.DEVICE_KINDS")
    peaks = {"peak_flops": flops.peak_flops_per_chip(),
             "hbm_bytes": flops.peak_hbm_bytes_per_chip(),
             "hbm_bandwidth": flops.hbm_bandwidth_per_chip(),
             "ici_bandwidth": flops.ici_bandwidth_per_chip()}
    _check(peaks == {"peak_flops": flops.TPU_PEAK_FLOPS[gen],
                     "hbm_bytes": flops.TPU_HBM_BYTES[gen],
                     "hbm_bandwidth": flops.TPU_HBM_BANDWIDTH[gen],
                     "ici_bandwidth": flops.TPU_ICI_BANDWIDTH[gen]},
           f"peaks {peaks} are not the {gen} rows")
    out.update(chip=gen, **peaks)
    return out


def _resnet_batch(sizes: Sizes):
    from deeplearning4j_tpu.data.dataset import DataSet

    rng = np.random.default_rng(sizes.seed)
    n, s, k = sizes.resnet_batch, sizes.resnet_image, sizes.resnet_classes
    x = rng.random((n, s, s, 3), np.float32)
    y = np.zeros((n, k), np.float32)
    y[np.arange(n), rng.integers(0, k, n)] = 1.0
    return DataSet(x, y)


def _covered_conv_families(conf, batch: int, dtype) -> set:
    """The conv families the live router (`conv_decision`, not its
    planning mode) covers at this net's shapes — and with them the BN
    kernels their outputs feed."""
    from deeplearning4j_tpu.analysis.kernelcoverage import conv_instances
    from deeplearning4j_tpu.ops.pallas_conv_bn import conv_decision

    fams = set()
    for _, ctx in conv_instances(conf, batch=batch):
        d = conv_decision(dtype=dtype, **ctx)
        if d["status"] == "covered":
            fams.add(d["family"])
    return fams


def phase_resnet50_train(sizes: Sizes) -> dict:
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.compgraph import ComputationGraph
    from deeplearning4j_tpu.ops.helpers import helper_books

    conf = sizes.resnet_conf()
    ds = _resnet_batch(sizes)
    dtype = jnp.bfloat16 if conf.net_conf.precision == "bf16" \
        else jnp.float32
    since = helper_books()
    net = ComputationGraph(conf).init()
    programs = _record_step_programs(net)
    scores = _fit_scores(net, [ds] * sizes.resnet_steps)
    _check(len(scores) == sizes.resnet_steps,
           f"{len(scores)} steps ran, not {sizes.resnet_steps}")
    _check(all(math.isfinite(s) for s in scores), f"scores {scores}")

    covered = _covered_conv_families(conf, sizes.resnet_batch, dtype)
    _check(bool(covered), "the router covers no conv family of this net")
    out = _check_helpers(since, CONV_OPS, covered | {"bn_apply", "bn_bwd"})
    if sizes.kernel_marker:
        text = _step_text(programs)
        _check(sizes.kernel_marker in text,
               f"no {sizes.kernel_marker} in the compiled train step")
        out["kernels_in_step"] = text.count(sizes.kernel_marker)

    ref_scores = _builtin_arm(lambda: ComputationGraph(conf).init(),
                              [ds] * 2)
    out.update(_compare_scores(scores, ref_scores, "xla"))
    out["covered_conv_families"] = sorted(covered)
    return out


def _lstm_batches(sizes: Sizes):
    from deeplearning4j_tpu.data.dataset import DataSet

    rng = np.random.default_rng(sizes.seed + 1)
    eye = np.eye(sizes.lstm_vocab, dtype=np.float32)
    shape = (sizes.lstm_batch, sizes.lstm_seq)
    return [DataSet(eye[rng.integers(0, sizes.lstm_vocab, shape)],
                    eye[rng.integers(0, sizes.lstm_vocab, shape)])
            for _ in range(sizes.lstm_batches)]


def phase_char_lstm_train(sizes: Sizes) -> dict:
    from deeplearning4j_tpu.ops.helpers import helper_books

    batches = _lstm_batches(sizes)
    since = helper_books()
    net = sizes.lstm_net()
    programs = _record_step_programs(net)
    scores = _fit_scores(net, batches)
    _check(bool(scores) and all(math.isfinite(s) for s in scores),
           f"scores {scores}")
    out = _check_helpers(since, LSTM_OPS, {"lstm_seq"})
    if sizes.kernel_marker:
        text = _step_text(programs)
        _check(sizes.kernel_marker in text,
               f"no {sizes.kernel_marker} in the compiled TBPTT step")
        out["kernels_in_step"] = text.count(sizes.kernel_marker)

    ref_scores = _builtin_arm(sizes.lstm_net, batches[:1])
    out.update(_compare_scores(scores, ref_scores, "scan"))
    out["steps"] = len(scores)
    return out


def _post(port: int, path: str, body: dict) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as resp:
        return json.loads(resp.read())


def _reference_decode(net, prompt, max_new: int, vocab: int):
    """The sequential reference of the decode engine (README "Continuous
    batching"): one request alone, one token per `rnn_time_step` at batch
    1. Returns the tokens and, per emitted token, the margin between the
    two best probabilities it was chosen from."""
    def feed(tok):
        oh = np.zeros((1, vocab), np.float32)
        oh[0, tok] = 1.0
        return np.asarray(net.rnn_time_step(oh))[0]

    net.clear_rnn_state()
    for t in prompt:
        probs = feed(t)
    toks, margins = [], []
    while len(toks) < max_new:
        top2 = np.sort(probs)[-2:]
        margins.append(float(top2[1] - top2[0]))
        toks.append(int(np.argmax(probs)))
        probs = feed(toks[-1])
    net.clear_rnn_state()
    return toks, margins


def phase_serve(sizes: Sizes) -> dict:
    from deeplearning4j_tpu.ops.helpers import helper_books
    from deeplearning4j_tpu.serving.inference_server import InferenceServer

    rng = np.random.default_rng(sizes.seed + 2)
    eye = np.eye(sizes.lstm_vocab, dtype=np.float32)
    requests = [eye[rng.integers(0, sizes.lstm_vocab,
                                 (n, sizes.predict_steps))]
                for n in sizes.predict_sizes]
    since = helper_books()
    net = sizes.lstm_net()
    srv = InferenceServer(
        net, port=0, max_batch_size=sizes.predict_max_batch,
        warmup_shape=(sizes.predict_steps, sizes.lstm_vocab),
        decode_slots=sizes.decode_slots, decode_max_tokens=sizes.gen_tokens)
    port = srv.start()
    try:
        buckets = list(srv.inference.buckets)
        warm = net.output_compile_count
        _check(warm <= len(buckets),
               f"warmup compiled {warm} forwards for buckets {buckets}")
        answers = [np.asarray(_post(port, "/predict",
                                    {"features": x.tolist()})["predictions"],
                              np.float32) for x in requests]
        _check(net.output_compile_count == warm,
               "mixed-size /predict traffic compiled "
               f"{net.output_compile_count - warm} more forwards")
        generated = [_post(port, "/generate",
                           {"prompt": list(p),
                            "max_tokens": sizes.gen_tokens})["tokens"]
                     for p in sizes.prompts]
        programs = srv.decode.program_cache_size()
        _check(programs <= 2, f"decode engine holds {programs} programs")
        m = srv.metrics()
    finally:
        srv.stop()
    _check(m["oversized"] == 0 and m["requests"] == len(requests),
           f"serving books {m['requests']} requests, {m['oversized']} "
           "oversized")

    worst = 0.0
    for x, got in zip(requests, answers):
        want = np.asarray(net.output(x), np.float32)
        _check(got.shape == want.shape, f"{got.shape} vs {want.shape}")
        _check(bool(np.all(np.isfinite(got))), "non-finite prediction")
        worst = max(worst, float(np.max(np.abs(got - want))))
        _check(np.allclose(got, want, rtol=PREDICT_RTOL, atol=PREDICT_ATOL),
               f"/predict differs from net.output() by {worst}")

    near_ties = 0
    for prompt, got in zip(sizes.prompts, generated):
        want, margins = _reference_decode(net, prompt, sizes.gen_tokens,
                                          sizes.lstm_vocab)
        _check(len(got) == len(want), f"{len(got)} tokens for {prompt}")
        if got != want:
            first = next(i for i, (a, b) in enumerate(zip(got, want))
                         if a != b)
            _check(margins[first] < TIE_MARGIN,
                   f"/generate {got} != sequential reference {want} at "
                   f"token {first} (margin {margins[first]})")
            near_ties += 1

    out = _check_helpers(since, LSTM_OPS + ("lstm_decode_step",),
                         {"lstm_seq", "lstm_step"})
    out.update(predict_requests=len(requests), buckets=buckets,
               forward_compiles=warm, predict_max_abs_diff=worst,
               generate_requests=len(generated),
               tokens_equal_reference=len(generated) - near_ties,
               tokens_near_tie=near_ties, decode_programs=programs)
    return out


def phase_multichip(sizes: Sizes) -> dict:
    """fit()'s auto-attached data-parallel mesh over every device of the
    host, against the same net, seed and batch on a one-device mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from deeplearning4j_tpu.nn.compgraph import ComputationGraph
    from deeplearning4j_tpu.ops.helpers import helper_books
    from deeplearning4j_tpu.parallel.mesh import (
        DATA_AXIS,
        data_parallel_mesh,
    )
    from deeplearning4j_tpu.utils.metrics import get_registry

    devices = jax.devices()
    n = len(devices)
    _check(n > 1, f"{n} device: nothing to shard over")
    _check(sizes.resnet_batch % n == 0, f"batch not divisible by {n}")
    conf = sizes.resnet_conf()
    ds = _resnet_batch(sizes)
    allreduce = get_registry().counter(
        "allreduce_bytes_total",
        "logical gradient all-reduce payload bytes (in-graph collective)")
    bytes0 = allreduce.value
    since = helper_books()

    net = ComputationGraph(conf).init()
    programs = _record_step_programs(net)
    scores = _fit_scores(net, [ds] * sizes.resnet_steps)
    _check(all(math.isfinite(s) for s in scores), f"scores {scores}")
    plan = net._mesh_plan
    _check(plan is not None, "fit() attached no mesh")
    _check(set(plan.mesh.devices.flat) == set(devices),
           f"mesh over {plan.mesh.devices.size} of {n} devices")

    # params and updater state: replicated, a copy on every device
    replicated = NamedSharding(plan.mesh, PartitionSpec())
    for what, tree in (("params", net.params_list),
                       ("updater state", net.upd_state)):
        leaves = jax.tree_util.tree_leaves(tree)
        _check(bool(leaves), f"no {what}")
        for leaf in leaves:
            _check(leaf.sharding.is_equivalent_to(replicated, leaf.ndim)
                   and len(leaf.sharding.device_set) == n
                   and len(leaf.addressable_shards) == n,
                   f"{what} leaf {leaf.shape} on {leaf.sharding}, not "
                   f"replicated over {n} devices")
    # the batch, staged as fit() stages it: dim 0 split over "data"
    staged = net._batch_transform(ds)
    feats = staged.features[0] if isinstance(staged.features, (list, tuple)) \
        else staged.features
    batch_sh = NamedSharding(plan.mesh, PartitionSpec(DATA_AXIS))
    _check(feats.sharding.is_equivalent_to(batch_sh, feats.ndim)
           and len(feats.sharding.device_set) == n
           and {s.data.shape[0] for s in feats.addressable_shards}
           == {sizes.resnet_batch // n},
           f"batch on {feats.sharding}, not split {n} ways")

    text = _step_text(programs)
    _check("all-reduce" in text, "no all-reduce in the compiled step")
    wire = allreduce.value - bytes0
    _check(wire > 0, "allreduce_bytes_total did not move")
    # kernel helpers decline a partitioned program, by rule and booked
    delta = helper_books(since)
    declined = delta["fallbacks"].get("partitioned_program", {})
    _check(not delta["hits"] and bool(declined),
           f"helpers under the mesh: hits {delta['hits']}, declined "
           f"{declined}")

    one = ComputationGraph(conf).init()
    one.set_mesh(data_parallel_mesh(devices[:1]))
    one_scores = _fit_scores(one, [ds] * 2)
    return {
        "devices": n, "per_device_batch": sizes.resnet_batch // n,
        **_compare_scores(scores, one_scores, "one_device"),
        "param_leaves_replicated": len(
            jax.tree_util.tree_leaves(net.params_list)),
        "all_reduce_ops_in_step": text.count("all-reduce("),
        "allreduce_bytes_total": wire,
        "collective": plan.collective_describe(net),
        "helpers_declined_partitioned_program": declined,
    }


# -- the program --------------------------------------------------------------

def _place_compile_cache() -> None:
    """JAX_COMPILATION_CACHE_DIR if it is set (jax reads it itself), else
    one fixed directory in the checkout. The path is part of the cache
    key, so it is never a temporary name."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO, ".jax_cache"))


def run_phases(sizes: Sizes, multichip: bool) -> Optional[dict]:
    """All phases in order; the device dict when every phase passed."""
    meter = CompileMeter().install()
    device = run_phase("device", phase_device, meter)
    if not device["ok"]:
        return None
    phases = ([("multichip", phase_multichip)] if multichip else
              [("resnet50_train", phase_resnet50_train),
               ("char_lstm_train", phase_char_lstm_train),
               ("serve", phase_serve)])
    ok = True
    for name, fn in phases:  # a failed phase does not hide the next one
        ok = run_phase(name, lambda fn=fn: fn(sizes), meter)["ok"] and ok
    if not ok:
        return None
    return {"platform": device["platform"], "kind": device["kind"],
            "count": device["count"]}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--multichip", action="store_true",
                        help="run only the sharded phase and its "
                             "one-device comparison, on all chips")
    args = parser.parse_args(argv)
    _place_compile_cache()
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {platform!r}); this script "
              "has no CPU mode", file=sys.stderr)
        return 2
    want = 4 if args.multichip else 1
    if len(jax.devices()) != want:
        print(f"chip_smoke: this mode needs {want} chip(s), jax found "
              f"{len(jax.devices())} (one chip with no arguments, four "
              "with --multichip)", file=sys.stderr)
        return 2
    device = run_phases(_full_sizes(), multichip=args.multichip)
    if device is None:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

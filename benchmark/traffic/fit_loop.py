"""The general generator for training jobs: `net.fit()` over a pool of host
batches until a deadline.

A traffic file (`benchmark/traffic/<name>.json`) with `"generator":
"fit_loop"` gives the batch a chip, the size of the pool and how many first
steps are checked. A configuration file gives the net: a factory of the
program (`module:function` and its arguments), the engine class, and the
name of its plain reference. Nothing here knows a model by name.

One run, in one process that holds the chips from start to end:

1. set-up: the pool and the weights from the seed, the net, the first steps
   through the window's own call and feed (`fit(iterator, epochs=1,
   async_prefetch=True)`, one batch a call, so that each step's loss and the
   state after the first step can be read), then a short warm `fit()`;
2. the window: one `fit()` over the pool, cycled until the deadline; it ends
   when the last step's score and parameters are ready;
3. after the window: peak memory, then the program's state is dropped and
   the plain reference follows the same first steps (`verify`).
"""

from __future__ import annotations

import importlib
import math
import os
import shutil
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from benchmark import compare, flops
from benchmark.reference import plain

def _resolve(spec: str):
    module, _, attr = spec.partition(":")
    return getattr(importlib.import_module(module), attr)


def load_reference(config: dict):
    return importlib.import_module(f"benchmark.reference.{config['reference']}")


# -- the feed -----------------------------------------------------------------

def make_pool(seed: int, n_batches: int, batch: int, config: dict):
    """`n_batches` distinct host batches from the seed: float32 NHWC images
    uniform in [0, 1) and uniform one-hot labels. Every seed gives the same
    shapes; only the values differ."""
    rng = np.random.default_rng(int(seed))
    size, ch, k = config["image_size"], config["channels"], \
        config["num_classes"]
    pool = []
    for _ in range(n_batches):
        x = rng.random((batch, size, size, ch), dtype=np.float32)
        y = np.zeros((batch, k), np.float32)
        y[np.arange(batch), rng.integers(0, k, batch)] = 1.0
        pool.append((x, y))
    return pool


def pool_iterator(pool, *, deadline: Optional[float] = None,
                  max_batches: Optional[int] = None,
                  clock=time.perf_counter):
    """A plain host `DataSetIterator` of the program that cycles the pool
    and stops at the deadline (on `clock`) or after `max_batches`. The base
    class is the program's, because `fit()` asks for it."""
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import DataSetIterator

    class PoolIterator(DataSetIterator):
        def __init__(self):
            self.yielded = 0

        def __iter__(self):
            n = 0
            while True:
                if max_batches is not None and n >= max_batches:
                    return
                if deadline is not None and clock() >= deadline:
                    return
                x, y = pool[n % len(pool)]
                n += 1
                self.yielded += 1
                yield DataSet(x, y)

        def reset(self):
            pass

        def batch_size(self):
            return int(pool[0][0].shape[0])

        def total_examples(self):
            return None

    return PoolIterator()


# -- the net and its weights --------------------------------------------------

def layer_keys(net) -> List[str]:
    """The key of each slot of `net.params_list`: the vertex name in a
    graph, the position in a sequential net."""
    names = getattr(net, "layer_vertex_names", None)
    return list(names) if names else [str(i)
                                      for i in range(len(net.params_list))]


def install_weights(net, weights: Dict[str, Dict]) -> None:
    """Hand the benchmark's weights to the program, leaf for leaf."""
    new, used = [], set()
    for key, old in zip(layer_keys(net), net.params_list):
        if not old:
            new.append(old)
            continue
        mine = weights.get(key)
        if mine is None or set(mine) != set(old):
            raise ValueError(f"layer {key!r}: the program holds "
                             f"{sorted(old)}, the reference "
                             f"{sorted(mine) if mine else None}")
        for name in old:
            if tuple(mine[name].shape) != tuple(old[name].shape):
                raise ValueError(
                    f"layer {key!r} {name}: the program's shape "
                    f"{tuple(old[name].shape)} is not the reference's "
                    f"{tuple(mine[name].shape)}")
        new.append({name: mine[name].astype(old[name].dtype)
                    for name in old})
        used.add(key)
    unused = sorted(set(weights) - used)
    if unused:
        raise ValueError(f"the reference's layers {unused} are not in the "
                         "program's net")
    net.params_list = new


def build_net(config: dict, seed: int):
    conf = _resolve(config["factory"])(**config["factory_args"])
    net = _resolve(config["engine"])(conf).init()
    install_weights(net, load_reference(config).init_params(seed, config))
    return net


def _host_leaves(net) -> Dict[str, np.ndarray]:
    """{"layer/param": host copy} of the net's parameters."""
    import jax

    out = {}
    for key, leaves in zip(layer_keys(net), jax.device_get(net.params_list)):
        for name, a in leaves.items():
            out[f"{key}/{name}"] = np.asarray(a)
    return out


def _norm(a) -> float:
    return float(np.sqrt(np.sum(np.square(a, dtype=np.float64))))


def _first_gradient_norms(net, updater: dict, p0, p1) -> Dict[str, float]:
    """The norm of the first gradient as the optimizer got it, worked out
    from the net's state after one step: Nesterov's velocity is -lr g after
    the first step; plain SGD keeps no state, so the parameters' change
    is."""
    import jax

    lr = updater["learning_rate"]
    if updater["name"] == "nesterovs":
        out = {}
        state = jax.device_get(net.upd_state)
        for key, leaves in zip(layer_keys(net), state):
            for name, s in leaves.items():
                out[f"{key}/{name}"] = _norm(np.asarray(s["v"])) / lr
        return out
    if updater["name"] == "sgd":
        return {k: _norm(p1[k] - p0[k]) / lr for k in p0}
    raise ValueError(f"no rule to recover the first gradient from updater "
                     f"{updater['name']!r}")


def _fit(net, iterator) -> None:
    """The window's own call."""
    net.fit(iterator, epochs=1, async_prefetch=True)


def first_steps_of_program(net, pool, config: dict, n_steps: int) -> dict:
    """Drive the net through its first `n_steps` with the window's own
    call and feed, one batch a call, and read what `correct` compares."""
    updater = config["updater"]
    p0 = _host_leaves(net)
    losses, grad_norms = [], None
    for i in range(n_steps):
        _fit(net, pool_iterator(pool[i:i + 1], max_batches=1))
        losses.append(float(net._score))
        if i == 0:
            p1 = _host_leaves(net) if updater["name"] == "sgd" else None
            grad_norms = _first_gradient_norms(net, updater, p0, p1)
            del p1
    pn = _host_leaves(net)
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": {k: _norm(pn[k] - p0[k]) for k in p0}}


def first_steps_of_reference(config: dict, seed: int, pool, n_steps: int,
                             precision: str = "f32", rows=None) -> dict:
    """The plain reference over the same first steps, from the same seed.
    `precision` other than "f32" and `rows` (a slice of each batch) are the
    control and the planted fault that `correct` has to fail."""
    ref = load_reference(config)
    params = ref.init_params(seed, config)
    batches = [(x[rows], y[rows]) if rows is not None else (x, y)
               for x, y in pool[:n_steps]]
    return plain.first_steps(
        lambda p, x, y: ref.loss(p, x, y, config, precision),
        params, batches, config["updater"])


# -- the traced slice of the window --------------------------------------------

def _stop_profiler(trace_dir: str) -> None:
    """Stop the profiler and keep the `.xplane.pb` alone. `jax.profiler.
    stop_trace` also converts the trace to `trace.json.gz`, which took 163 s
    after three traced seconds on the chip (PERF.md, PR 24); the session
    hands over the serialized trace without that."""
    import jax
    from jax._src import profiler as jax_profiler

    state = getattr(jax_profiler, "_profile_state", None)
    session = getattr(state, "profile_session", None)
    if session is None or not hasattr(session, "stop"):
        jax.profiler.stop_trace()
        return
    with state.lock:
        xspace = session.stop()
        state.reset()
    out_dir = os.path.join(trace_dir, "plugins", "profile", "bench")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "bench.xplane.pb"), "wb") as f:
        f.write(xspace)


class _Tracer(threading.Thread):
    """Turns the JAX profiler on for a slice inside the window, from a
    thread of its own (the fit loop holds the main thread), and counts the
    optimizer steps dispatched in the slice. The host's own events are left
    out: the 16 transpose threads of the host-to-device copy alone write
    5 million of them a second, and stopping the profiler then takes two
    minutes (PERF.md, PR 24)."""

    def __init__(self, trace_dir: str, start_at: float, seconds: float,
                 steps_counter):
        super().__init__(name="bench-tracer", daemon=True)
        self.trace_dir, self.start_at, self.seconds = \
            trace_dir, start_at, seconds
        self.steps_counter = steps_counter
        self.steps = None
        self.wall_s = None
        self.stop_s = None
        self.error = None

    def run(self):
        import jax

        try:
            time.sleep(max(0.0, self.start_at - time.perf_counter()))
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=options)
            t0, s0 = time.perf_counter(), self.steps_counter.value
            time.sleep(self.seconds)
            self.steps = int(self.steps_counter.value - s0)
            self.wall_s = time.perf_counter() - t0
            t1 = time.perf_counter()
            _stop_profiler(self.trace_dir)
            self.stop_s = time.perf_counter() - t1
        except Exception as e:  # reported by the run, which then fails
            self.error = e


def _compile_counter():
    """Counts the programs jax compiles (cache misses included, cache hits
    not), from jax's own monitoring events."""
    from jax import monitoring

    box = {"compiles": 0}

    def on_duration(event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            box["compiles"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    return box


def device_peak_bytes(stats: Optional[dict]) -> int:
    """The most of a chip's memory the process held: the allocator's peak
    (`peak_bytes_in_use`: parameters, updater state, staged batches) plus
    what the runtime reserved beside it for the loaded programs' scratch
    (`peak_bytes_reserved`; on the v5e the step's temporaries live there
    and are no part of `peak_bytes_in_use`; PERF.md, PR 24)."""
    stats = stats or {}
    return int(stats.get("peak_bytes_in_use", 0)) \
        + int(stats.get("peak_bytes_reserved", 0))


def _all_finite(tree) -> bool:
    import jax
    import jax.numpy as jnp

    leaves = [l for l in jax.tree_util.tree_leaves(tree)
              if hasattr(l, "dtype")]
    ok = jax.jit(lambda ls: jnp.all(jnp.stack(
        [jnp.all(jnp.isfinite(l)) for l in ls])))(leaves)
    return bool(ok)


# -- one run ------------------------------------------------------------------

def run(ctx: dict) -> dict:
    """Set-up, the window, and what was read after it. `ctx` holds the
    cell (`chips`), the `config`, the `traffic`, `seed`, `seconds`, `trace`,
    the process's start on `time.perf_counter` (`t_start`) and the
    directory for the trace (`trace_dir`)."""
    import jax

    from deeplearning4j_tpu.ops.helpers import helper_books
    from deeplearning4j_tpu.utils.metrics import get_registry

    config, traffic = ctx["config"], ctx["traffic"]
    chips = int(ctx["chips"])
    batch = int(traffic["batch_per_chip"]) * chips
    n_checked = int(traffic["checked_steps"])
    compiles = _compile_counter()

    marks = [("imports", time.perf_counter())]
    pool = make_pool(ctx["seed"], int(traffic["pool_batches"]), batch, config)
    marks.append(("pool", time.perf_counter()))
    net = build_net(config, ctx["seed"])
    marks.append(("net_and_weights", time.perf_counter()))
    program = first_steps_of_program(net, pool, config, n_checked)
    marks.append(("first_steps", time.perf_counter()))
    _fit(net, pool_iterator(pool, max_batches=int(traffic["warm_batches"])))
    jax.block_until_ready((net._score, net.params_list))
    marks.append(("warm_fit", time.perf_counter()))
    setup_parts = {name: t - prev for (name, t), prev in zip(
        marks, [ctx["t_start"]] + [t for _, t in marks])}

    registry = get_registry()
    steps_counter = registry.counter("fit_step_total",
                                     "optimizer steps run").labels()
    tracer = None
    books0 = helper_books()
    compiles0 = compiles["compiles"]
    before = registry.scalar_values()
    t0 = time.perf_counter()
    setup_s = t0 - ctx["t_start"]
    if ctx["trace"]:
        shutil.rmtree(ctx["trace_dir"], ignore_errors=True)
        os.makedirs(ctx["trace_dir"], exist_ok=True)
        seconds = float(ctx["seconds"])
        length = min(float(traffic["trace_seconds"]), 0.4 * seconds)
        before_end = min(float(traffic["trace_before_end_s"]),
                         0.5 * seconds)
        tracer = _Tracer(ctx["trace_dir"],
                         t0 + seconds - before_end - length, length,
                         steps_counter)
        tracer.start()
    error = None
    try:
        _fit(net, pool_iterator(pool, deadline=t0 + float(ctx["seconds"])))
        jax.block_until_ready((net._score, net.params_list))
    except Exception as e:  # a step that raised: the run is not correct
        error = f"{type(e).__name__}: {e}"[:400]
    window_s = time.perf_counter() - t0
    after = registry.scalar_values()
    compiles_in_window = compiles["compiles"] - compiles0
    if tracer is not None:
        tracer.join(timeout=240)
        if tracer.is_alive():
            tracer.error = TimeoutError("stop_trace did not return in 240 s")
    books = helper_books(books0)

    delta = lambda name: after.get(name, 0.0) - before.get(name, 0.0)
    steps = int(delta("fit_step_total"))
    last_loss = float(net._score) if net._score is not None else math.nan
    if error is not None:
        failed = 1
    elif not (math.isfinite(last_loss) and _all_finite(net.params_list)):
        # a loss that is not finite poisons every later step, and which
        # step was the first is not known without a host read a step
        failed = steps
    else:
        failed = 0
    memory_peak = max(device_peak_bytes(d.memory_stats())
                      for d in jax.local_devices())
    hidden = sum(books["auto_disable"].values()) + sum(
        n for reason in ("raised", "probe_error")
        for n in books["fallbacks"].get(reason, {}).values())
    facts = {
        "setup_s": setup_s, "setup_parts_s": setup_parts,
        "window_s": window_s, "chips": chips,
        "steps": steps, "examples": int(delta("fit_examples_total")),
        "attempted": steps, "failed": failed,
        "error": error, "last_loss": last_loss,
        "first_losses": program["losses"],
        "memory_peak_bytes": memory_peak,
        "registry_before": before, "registry_after": after,
        "helper_books": books, "hidden_fallbacks": hidden,
        "compiles_in_window": compiles_in_window,
        "pool_bytes": sum(x.nbytes + y.nbytes for x, y in pool),
        "flops_per_example": flops.train_flops_per_example(
            load_reference(config).layers(config)),
        "program_first_steps": program,
        "trace_dir": ctx["trace_dir"] if tracer is not None else None,
        "trace_steps": tracer.steps if tracer is not None else None,
        "trace_wall_s": tracer.wall_s if tracer is not None else None,
        "trace_stop_s": tracer.stop_s if tracer is not None else None,
        "memory_stats": {str(d): d.memory_stats()
                         for d in jax.local_devices()},
        "helper_books_total": helper_books(),
        "trace_error": (repr(tracer.error)
                        if tracer is not None and tracer.error else None),
        "_pool": pool,
    }
    # the program's state goes before the reference comes
    del net
    return facts


def verify(ctx: dict, facts: dict) -> dict:
    """The numbers `correct` compares, each beside its limit. Runs once the
    window has closed, peak memory has been read and the program's state is
    dropped."""
    config = ctx["config"]
    n_checked = int(ctx["traffic"]["checked_steps"])
    reference = first_steps_of_reference(config, ctx["seed"],
                                         facts.pop("_pool"), n_checked)
    gaps = compare.first_step_gaps(facts["program_first_steps"], reference)
    numbers = {
        "loss1_gap": gaps["loss1_gap"], "loss_gap": gaps["loss_gap"],
        "grad_gap": gaps["grad_gap"],
        "grad_median_gap": gaps["grad_median_gap"],
        "delta_gap": gaps["delta_gap"],
        "delta_median_gap": gaps["delta_median_gap"],
        "failed_steps": float(facts["failed"]),
        "hidden_fallbacks": float(facts["hidden_fallbacks"]),
        "compiles_in_window": float(facts["compiles_in_window"]),
    }
    limits = dict(config["limits"], failed_steps=0.0, hidden_fallbacks=0.0,
                  compiles_in_window=0.0)
    verdict = compare.judge(numbers, limits)
    verdict["worst_leaves"] = gaps["worst"]
    verdict["leaves"] = gaps["leaves"]
    verdict["leaves_left_out"] = gaps["leaves_left_out"]
    verdict["reference_losses"] = reference["losses"]
    return verdict

"""The wait before the first step, from inside the program: the always-on
lifecycle spans (`net/init`, `fit/run` > `fit/setup`, `fit/teardown`), every
jit trace, lowering, compile and cache load as a span that names its program
and the step it fell in, and their sums in the registry. CPU only."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.analysis import tracecrit
from deeplearning4j_tpu.nn.compgraph import ComputationGraph
from deeplearning4j_tpu.nn.conf import (
    DenseLayer,
    NeuralNetConfiguration,
    OutputLayer,
    Updater,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.utils import tracing
from deeplearning4j_tpu.utils.metrics import get_registry

BACKEND_COUNT = 'jit_compile_seconds{phase="backend"}:count'
TRACE_SUM = 'jit_compile_seconds{phase="trace"}:sum'


@pytest.fixture(autouse=True)
def _a_clean_ring_and_the_tracer_off():
    tracing.enable(False)
    tracing.get_tracer().clear()
    yield
    tracing.enable(False)
    tracing.get_tracer().clear()


def _builder(seed):
    return (NeuralNetConfiguration.builder().seed(seed)
            .updater(Updater.SGD).learning_rate(0.05).weight_init("xavier"))


def _chain(seed=7, hidden=16):
    return MultiLayerNetwork(
        _builder(seed).list()
        .layer(DenseLayer(n_in=12, n_out=hidden, activation="tanh"))
        .layer(OutputLayer(n_in=hidden, n_out=4, activation="softmax",
                           loss="mcxent"))
        .build())


def _graph(seed=7, hidden=16):
    return ComputationGraph(
        _builder(seed).graph_builder().add_inputs("in")
        .add_layer("h", DenseLayer(n_in=12, n_out=hidden, activation="tanh"),
                   "in")
        .add_layer("out", OutputLayer(n_in=hidden, n_out=4,
                                      activation="softmax", loss="mcxent"),
                   "h")
        .set_outputs("out").build())


def _xy(n, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 12)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, n)]
    return x, y


def _named(events, name):
    return [e for e in events if e["name"] == name]


def _values():
    return get_registry().scalar_values()


# -- the lifecycle of one fit(), tracer off ---------------------------------------

@pytest.mark.parametrize("make,hidden", [(_chain, 20), (_graph, 28)],
                         ids=["chain", "graph"])
def test_a_fit_with_the_tracer_off_leaves_its_lifecycle_in_the_ring(make,
                                                                    hidden):
    # a width of its own, so that init()'s programs are new to the process
    before = _values()
    t0 = tracing.now_ns()
    net = make(hidden=hidden).init()
    x, y = _xy(24)
    net.fit(x, y, epochs=1, batch_size=8, async_prefetch=False)
    t1 = tracing.now_ns()
    assert not tracing.is_enabled()
    events = tracing.get_tracer().recent()

    (init,) = _named(events, "net/init")
    assert init["parent"] is None
    assert init["args"]["engine"] == type(net).__name__
    assert init["args"]["n_leaves"] == 4
    assert init["args"]["param_bytes"] == 4 * (
        12 * hidden + hidden + hidden * 4 + 4)

    (run,) = _named(events, "fit/run")
    (setup,) = _named(events, "fit/setup")
    (teardown,) = _named(events, "fit/teardown")
    assert run["parent"] is None and run["trace"] != init["trace"]
    for part in (setup, teardown):
        assert part["parent"] == run["id"] and part["trace"] == run["trace"]
    assert setup["args"] == {"async_prefetch": False, "mesh": None}
    assert run["args"]["fit_call"] == 1 and run["args"]["epochs"] == 1
    # fit/run's iterations are the step timeline's
    steps = [r[0] for r in tracing.get_step_timeline().records(t0)]
    assert steps == [0, 1, 2]
    assert (run["args"]["first_iteration"],
            run["args"]["last_iteration"]) == (steps[0], steps[-1])
    # every span on the one clock, children inside their parent
    for e in events:
        assert t0 <= e["start_ns"] <= e["start_ns"] + e["dur_ns"] <= t1
    assert run["start_ns"] <= setup["start_ns"]
    assert setup["start_ns"] + setup["dur_ns"] <= teardown["start_ns"]
    assert teardown["start_ns"] + teardown["dur_ns"] \
        <= run["start_ns"] + run["dur_ns"]

    # the step program's compile: named, attributed to its step, under
    # fit/run; init()'s programs under net/init with no step
    compiles = _named(events, "compile/backend")
    step = [e for e in compiles if "step" in e["args"]["fun_name"]]
    assert step and step[0]["args"]["iteration"] == 0
    assert step[0]["parent"] == run["id"] and step[0]["trace"] == run["trace"]
    assert step[0]["args"]["cache"] == "off"   # no cache directory here
    under_init = [e for e in compiles if e["parent"] == init["id"]]
    assert under_init and all(e["args"]["iteration"] is None
                              for e in under_init)
    for e in events:
        if e["name"].startswith("compile/"):
            assert e["args"]["fun_name"] and "iteration" in e["args"]
            assert 0.0 <= e["args"]["self_s"] <= e["dur_ns"] * 1e-9 + 1e-9

    # the sums a scrape holds
    after = _values()
    delta = lambda key: after[key] - before.get(key, 0.0)
    assert delta("net_init_seconds:count") == 1
    assert delta("net_init_seconds:sum") == pytest.approx(
        init["dur_ns"] * 1e-9)
    for phase, span in (("setup", setup), ("teardown", teardown)):
        key = f'fit_phase_seconds{{phase="{phase}"}}'
        assert delta(key + ":count") == 1
        assert delta(key + ":sum") == pytest.approx(span["dur_ns"] * 1e-9)
    assert delta(BACKEND_COUNT) == len(compiles)
    assert {'jit_cache_total{result="hit"}',
            'jit_cache_total{result="miss"}'} <= set(after)


def test_a_second_fit_is_the_second_call_and_starts_where_the_first_ended():
    net = _chain().init()
    x, y = _xy(16)
    for _ in range(2):
        net.fit(x, y, epochs=1, batch_size=8, async_prefetch=False)
    runs = _named(tracing.get_tracer().recent(), "fit/run")
    assert [(r["args"]["fit_call"], r["args"]["first_iteration"],
             r["args"]["last_iteration"]) for r in runs] == [
        (1, 0, 1), (2, 2, 3)]
    assert runs[0]["trace"] != runs[1]["trace"]


def test_a_fit_that_raises_still_closes_its_spans():
    class Boom:
        def __iter__(self):
            raise RuntimeError("iterator died")

        def reset(self):
            pass

    net = _chain().init()
    with pytest.raises(RuntimeError, match="iterator died"):
        net._run_fit(Boom(), epochs=1, async_prefetch=False)
    events = tracing.get_tracer().recent()
    (run,) = _named(events, "fit/run")
    (teardown,) = _named(events, "fit/teardown")
    assert teardown["parent"] == run["id"]
    # and the thread's parent stack is empty again
    with tracing.phase("after") as after:
        pass
    assert after.parent is None


def test_the_books_read_is_a_span_under_the_teardown():
    from deeplearning4j_tpu.models.smallthinker import tiny_smallthinker_conf

    net = ComputationGraph(tiny_smallthinker_conf(seq_len=16)).init()
    ids = np.random.default_rng(0).integers(0, 128, (4, 17)).astype(np.int32)
    net.fit(ids[:, :-1], ids[:, 1:], epochs=1, batch_size=4,
            async_prefetch=False)
    events = tracing.get_tracer().recent()
    (teardown,) = _named(events, "fit/teardown")
    (books,) = _named(events, "fit/publish_books")
    assert books["parent"] == teardown["id"]
    assert books["args"]["n_slots"] >= 1
    assert _values()['fit_phase_seconds{phase="publish_books"}:count'] >= 1


# -- which step recompiled ------------------------------------------------------

def test_a_batch_of_another_shape_is_a_compile_attributed_to_its_step():
    net = _chain().init()
    before = _values()
    x, y = _xy(16)
    net.fit(x, y, epochs=1, batch_size=8, async_prefetch=False)  # 8, 8
    mid = _values()
    tracing.get_tracer().clear()
    x, y = _xy(20)
    net.fit(x, y, epochs=1, batch_size=8, async_prefetch=False)  # 8, 8, 4
    after = _values()
    compiles = [e for e in _named(tracing.get_tracer().recent(),
                                  "compile/backend")
                if "step" in e["args"]["fun_name"]]
    # steps 2 and 3 ran the program the first fit left; step 4 is the
    # batch of four
    assert [e["args"]["iteration"] for e in compiles] == [4]
    assert after[BACKEND_COUNT] > mid[BACKEND_COUNT]
    # the net's own count saw one insertion, whatever jax compiled
    key = 'compile_total{kind="train_step"}'
    assert after[key] - before.get(key, 0.0) == 1
    assert after[key] == mid[key]


# -- self time ----------------------------------------------------------------

@pytest.mark.parametrize("enabled", [False, True],
                         ids=["tracer_off", "tracer_on"])
def test_booked_trace_seconds_are_self_times(enabled):
    """An inner jit traced inside an outer one emits a duration of its own
    inside the outer's: the histogram holds the union, and with the tracer
    on the inner span hangs under the outer `compile/trace`."""
    tracing.watch_compiles()
    tracing.enable(enabled)

    @jax.jit
    def inner_fn(x):
        return jnp.tanh(x) * 2.0

    @jax.jit
    def outer_fn(x):
        return inner_fn(x) + inner_fn(x * 3.0).sum()

    x = jnp.ones((3, 5))   # its own program first
    before = _values()
    tracing.get_tracer().clear()
    with tracing.phase("wall") as wall:
        outer_fn(x).block_until_ready()
    tracing.enable(False)
    booked = _values()[TRACE_SUM] - before.get(TRACE_SUM, 0.0)
    events = tracing.get_tracer().recent()
    traces = _named(events, "compile/trace")
    outer = [e for e in traces if e["args"]["fun_name"] == "outer_fn"]
    assert len(outer) == 1 and outer[0]["parent"] == wall.id
    outer = outer[0]
    assert booked <= outer["dur_ns"] * 1e-9 + 1e-6
    assert booked == pytest.approx(sum(e["args"]["self_s"] for e in traces))
    inner = [e for e in traces if e["args"]["fun_name"] == "inner_fn"]
    if not enabled:
        # nested traces get no span: their seconds stay the outer's
        assert traces == [outer]
        assert outer["args"]["self_s"] == pytest.approx(
            outer["dur_ns"] * 1e-9)
        return
    assert inner and all(e["trace"] == outer["trace"] for e in inner)
    by_id = {e["id"]: e for e in traces}
    for e in inner:
        # under the outer trace, directly or through a nested one
        up = e
        while up["parent"] in by_id:
            up = by_id[up["parent"]]
        assert up is outer
    children = [e for e in traces if e["parent"] == outer["id"]]
    assert outer["args"]["self_s"] == pytest.approx(
        (outer["dur_ns"] - sum(e["dur_ns"] for e in children)) * 1e-9)
    assert outer["args"]["self_s"] < outer["dur_ns"] * 1e-9


def test_the_stages_of_one_program_add_up_to_the_wall_it_held():
    tracing.watch_compiles()

    @jax.jit
    def program(x):
        return jnp.cumsum(jnp.sin(x) @ x.T, axis=0)

    x = jnp.ones((4, 4))   # its own programs first
    before = _values()
    tracing.get_tracer().clear()
    t0 = time.perf_counter()
    program(x).block_until_ready()
    wall = time.perf_counter() - t0
    after = _values()
    stages = {s: after[f'jit_compile_seconds{{phase="{s}"}}:sum']
              - before.get(f'jit_compile_seconds{{phase="{s}"}}:sum', 0.0)
              for s in ("trace", "lower", "backend")}
    assert all(v > 0 for v in stages.values())
    assert sum(stages.values()) <= wall
    names = [e["name"] for e in tracing.get_tracer().recent()]
    assert names == ["compile/trace", "compile/lower", "compile/backend"]


# -- the persistent cache ---------------------------------------------------------

def test_a_cache_miss_then_a_hit_with_its_load(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    tracing.watch_compiles()
    saved = {name: getattr(jax.config, name) for name in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    compilation_cache.reset_cache()
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        def cached_program(x):
            return jnp.sqrt(jnp.abs(x) + 41.0).sum()

        x = jnp.arange(6.0)
        before = _values()
        jax.jit(cached_program)(x).block_until_ready()
        jax.clear_caches()
        jax.jit(cached_program)(x).block_until_ready()
        after = _values()
    finally:
        for name, value in saved.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
    events = tracing.get_tracer().recent()
    mine = [e for e in _named(events, "compile/backend")
            if "cached_program" in e["args"]["fun_name"]]
    assert [e["args"]["cache"] for e in mine] == ["miss", "hit"]
    hit = mine[1]
    assert "saved_s" in hit["args"]
    (load,) = [e for e in _named(events, "compile/cache_load")
               if e["parent"] == hit["id"]]
    assert load["args"]["fun_name"] == hit["args"]["fun_name"]
    # the load is inside the backend stage and is not booked twice
    assert hit["args"]["self_s"] == pytest.approx(
        (hit["dur_ns"] - load["dur_ns"]) * 1e-9)
    delta = lambda key: after[key] - before.get(key, 0.0)
    assert delta('jit_cache_total{result="hit"}') >= 1
    assert delta('jit_cache_total{result="miss"}') >= 1
    assert delta('jit_compile_seconds{phase="cache_load"}:count') >= 1


def test_a_stage_whose_start_was_not_seen_is_recorded_when_it_ends():
    """Another jax, or a listener installed between the two events: the
    span ends now and lasts what jax says, under whatever is open."""
    before = _values().get(BACKEND_COUNT, 0.0)
    with tracing.phase("outer") as outer:
        tracing._on_jax_duration(
            "/jax/core/compile/backend_compile_duration", 0.25,
            fun_name="jit_late")
        end = tracing.now_ns()
    (late,) = _named(tracing.get_tracer().recent(), "compile/backend")
    assert late["parent"] == outer.id and late["trace"] == outer.trace
    assert late["dur_ns"] == 250_000_000
    assert abs(late["start_ns"] + late["dur_ns"] - end) < 50_000_000
    assert late["args"] == {"fun_name": "jit_late", "iteration": None,
                            "self_s": 0.25}
    assert _values()[BACKEND_COUNT] == before + 1


def test_a_listener_that_fails_does_not_fail_the_compile(monkeypatch, caplog):
    tracing.watch_compiles()
    monkeypatch.setattr(tracing, "_compile_books",
                        lambda: 1 / 0)
    with caplog.at_level("ERROR", logger="deeplearning4j_tpu"):
        out = jax.jit(lambda x: x * 5.0 + 1.0)(jnp.ones(2))
    assert out.tolist() == [6.0, 6.0]
    assert "listener of jax's event" in caplog.text


# -- the export -----------------------------------------------------------------

def test_the_export_of_one_fit_is_one_tree_rooted_at_fit_run():
    net = _chain().init()
    tracing.get_tracer().clear()
    x, y = _xy(16)
    net.fit(x, y, epochs=1, batch_size=8, async_prefetch=False)
    events = tracecrit.parse_jsonl(tracing.get_tracer().to_jsonl())
    traces = tracecrit.group_traces(events)
    assert len(traces) == 1
    (trace_id, spans), = traces.items()
    roots = tracecrit._roots(spans)
    assert [r["name"] for r in roots] == ["fit/run"]
    path = tracecrit.critical_path(spans)
    assert path[0]["name"] == "fit/run"
    assert {"compile/backend", "fit/teardown"} <= {p["name"] for p in path}
    report = tracecrit.analyze_trace(trace_id, spans)
    assert report["root"] == "fit/run" and report["n_spans"] == len(spans)
    # the stages' self times add up to the call
    assert report["critical_path_us"] == pytest.approx(
        path[0]["dur_us"], rel=1e-3)
    assert "fit/run" in tracecrit.format_report(tracecrit.analyze(events))


def test_the_steps_nest_under_fit_run_when_the_tracer_is_on():
    net = _chain().init()
    x, y = _xy(16)
    tracing.enable(True)
    net.fit(x, y, epochs=1, batch_size=8, async_prefetch=False)
    tracing.enable(False)
    events = tracing.get_tracer().recent()
    (run,) = _named(events, "fit/run")
    steps = _named(events, "fit/step")
    assert len(steps) == 2
    assert all(s["parent"] == run["id"] and s["trace"] == run["trace"]
               for s in steps)
    dispatches = {e["id"] for e in _named(events, "fit/dispatch")}
    step_compiles = [e for e in _named(events, "compile/backend")
                     if "step" in e["args"]["fun_name"]]
    assert step_compiles and all(e["parent"] in dispatches
                                 for e in step_compiles)


def test_a_tracing_listeners_file_holds_fit_run(tmp_path):
    from deeplearning4j_tpu.train.listeners import TracingListener

    net = _chain().init()
    path = tmp_path / "spans.jsonl"
    net.set_listeners(TracingListener(jsonl_path=str(path)))
    x, y = _xy(16)
    net.fit(x, y, epochs=1, batch_size=8, async_prefetch=False)
    names = [e["name"] for e in tracecrit.parse_jsonl(path.read_text())]
    assert {"fit/run", "fit/setup", "fit/teardown", "fit/step"} <= set(names)


# -- what it costs ---------------------------------------------------------------

def test_the_disabled_path_and_a_phase_cost_microseconds():
    assert not tracing.is_enabled()
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        tracing.span("hot/span")
    per_span = (time.perf_counter() - t0) / n
    t0 = time.perf_counter()
    for _ in range(n):
        tracing.current_context()
        tracing.current_traceparent()
        tracing.detach(tracing.attach(None))
        tracing.instant("nope")
        tracing.watch_compiles()
    per_hooks = (time.perf_counter() - t0) / n
    assert per_span < 10e-6, f"span() cost {per_span * 1e6:.2f}us"
    assert per_hooks < 10e-6, f"the hooks cost {per_hooks * 1e6:.2f}us"
    assert tracing.get_tracer().recent() == []
    # a phase is paid once a fit() call, not once a step: tens of
    # microseconds would still be nothing, and it is under ten
    t0 = time.perf_counter()
    for _ in range(n):
        with tracing.phase("cold/phase"):
            pass
    per_phase = (time.perf_counter() - t0) / n
    assert per_phase < 50e-6, f"phase() cost {per_phase * 1e6:.2f}us"
    assert len(tracing.get_tracer().recent()) == 8192   # the one ring's bound


def test_a_dispatch_reads_the_clock_three_times_as_before(monkeypatch):
    """`_timed_fit` is the step's path: dispatch start, dispatch end, end
    of the observers, and nothing added for the lifecycle spans."""
    net = _chain().init()
    x, y = _xy(16)
    net.fit(x, y, epochs=1, batch_size=8, async_prefetch=False)
    reads = []
    real = tracing.now_ns

    def counting():
        reads.append(1)
        return real()

    monkeypatch.setattr(tracing, "now_ns", counting)
    n_events = len(tracing.get_tracer().recent())
    net._timed_fit(lambda: None, 0.0, 0)
    assert len(reads) == 3
    assert len(tracing.get_tracer().recent()) == n_events

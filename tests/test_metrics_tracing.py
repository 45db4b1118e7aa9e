"""Observability-layer tests: the shared MetricsRegistry (thread safety,
histogram math vs numpy, Prometheus exposition format), the span tracer
(nesting, chrome-trace export, disabled-path cost), and the cross-layer
wiring — Helper SPI fallback counters (the PR 2 auto-disable regression),
fit-loop step-phase instruments with the zero-registry-lookups-per-step
overhead guard, and the inference server's strict-JSON /metrics plus the
one-scrape-sees-training-AND-serving Prometheus acceptance criterion."""

import json
import threading

import numpy as np
import pytest

import deeplearning4j_tpu as dl4j
from deeplearning4j_tpu.nn.conf import (
    DenseLayer,
    NeuralNetConfiguration,
    OutputLayer,
    Updater,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops import helpers
from deeplearning4j_tpu.utils import metrics as metrics_mod
from deeplearning4j_tpu.utils import tracing
from deeplearning4j_tpu.utils.jsonhttp import json_response
from deeplearning4j_tpu.utils.metrics import MetricsRegistry


@pytest.fixture(autouse=True)
def _tracing_off_after():
    """Tracing is process-global state; never leak an enabled tracer (or
    a dirty span buffer) into other tests."""
    yield
    tracing.enable(False)
    tracing.get_tracer().clear()


def _mlp_conf(seed=7, n_in=12):
    return (
        NeuralNetConfiguration.builder()
        .seed(seed)
        .updater(Updater.SGD)
        .learning_rate(0.05)
        .weight_init("xavier")
        .list()
        .layer(DenseLayer(n_in=n_in, n_out=16, activation="tanh"))
        .layer(OutputLayer(n_in=16, n_out=4, activation="softmax",
                           loss="mcxent"))
        .build()
    )


def _xy(n=32, n_in=12, n_out=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, n_in)).astype(np.float32)
    y = np.eye(n_out, dtype=np.float32)[rng.integers(0, n_out, n)]
    return x, y


def _count_blocking_reads(monkeypatch):
    """Count every `jax.block_until_ready` the process makes from here
    on (the fit loop's only way to wait for a step's score)."""
    import jax

    calls = []
    real = jax.block_until_ready

    def counting(x):
        calls.append(x)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    return calls


# -- registry core -----------------------------------------------------------

def test_counter_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("hits_total", "x", ("who",))
    child = c.labels("a")

    def worker():
        for _ in range(1000):
            child.inc()
            c.labels("b").inc(2)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert child.value == 8000
    assert c.labels("b").value == 16000


def test_counter_is_monotonic_and_typed():
    reg = MetricsRegistry()
    c = reg.counter("ops_total")
    with pytest.raises(ValueError):
        c.inc(-1)
    # get-or-create returns the same family; kind conflicts are errors
    assert reg.counter("ops_total") is c
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("ops_total")
    with pytest.raises(ValueError, match="labels"):
        reg.counter("ops_total", labelnames=("x",))


def test_gauge_set_function_and_dead_callback():
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    g.set(3)
    assert g.value == 3
    g.set_function(lambda: 7)
    assert g.value == 7
    g.set_function(lambda: 1 / 0)  # a dying callback must not kill a scrape
    snap = reg.snapshot()
    assert snap["depth"]["values"][0]["value"] is None  # NaN -> null
    json.dumps(snap, allow_nan=False)


def test_histogram_percentiles_vs_numpy():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", window=10_000)
    rng = np.random.default_rng(42)
    vals = rng.lognormal(mean=-5, sigma=1.0, size=2000)
    for v in vals:
        h.observe(float(v))
    child = h.labels()
    assert child.count == 2000
    assert child.sum == pytest.approx(vals.sum(), rel=1e-9)
    # nearest-rank percentile over the full window vs numpy's
    for q in (50, 90, 99):
        got = child.percentile(q)
        lo, hi = np.percentile(vals, max(q - 1, 0)), np.percentile(
            vals, min(q + 1, 100))
        assert lo <= got <= hi


def test_histogram_bucket_counts_exact():
    reg = MetricsRegistry()
    h = reg.histogram("d_seconds", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.01, 0.05, 0.5, 5.0):
        h.observe(v)
    cum = h.labels().cumulative_buckets()
    # le semantics: 0.01 counts the exact-boundary observation
    assert cum == [(0.01, 2), (0.1, 3), (1.0, 4), (float("inf"), 5)]


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("requests", "served requests", ("route",)) \
        .labels('with"quote\\and\nnewline').inc(3)
    reg.gauge("depth", "queue depth").set(2)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = reg.to_prometheus()
    # counters get the _total suffix when the name lacks it
    assert "# TYPE requests_total counter" in text
    assert 'requests_total{route="with\\"quote\\\\and\\nnewline"} 3' in text
    assert "# TYPE depth gauge" in text
    assert "depth 2" in text.splitlines()
    # histogram expansion: cumulative buckets incl +Inf, _sum, _count
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 2' in text
    assert "lat_seconds_count 2" in text.splitlines()
    assert any(line.startswith("lat_seconds_sum ")
               for line in text.splitlines())
    assert "# HELP requests_total served requests" in text


def test_snapshot_is_strict_json():
    reg = MetricsRegistry()
    reg.histogram("empty_seconds")  # family with no observations
    reg.histogram("one_seconds").observe(0.25)
    s = json.dumps(reg.snapshot(), allow_nan=False)
    doc = json.loads(s)
    one = doc["one_seconds"]["values"][0]
    assert one["count"] == 1 and one["p50"] == 0.25
    assert doc["empty_seconds"]["values"] == []


# -- tracing -----------------------------------------------------------------

def test_span_disabled_is_free_singleton():
    tracing.enable(False)
    tracing.get_tracer().clear()   # lifecycle spans of earlier fits
    s1, s2 = tracing.span("a"), tracing.span("b", k=1)
    assert s1 is s2 is tracing.NULL_SPAN
    with s1:
        pass
    tracing.instant("nope")
    assert tracing.get_tracer().recent() == []


def test_span_nesting_and_chrome_roundtrip(tmp_path):
    tracer = tracing.get_tracer()
    tracer.clear()
    tracing.enable(True)
    with tracing.span("outer", phase="x"):
        with tracing.span("inner"):
            pass
        tracing.instant("marker", it=3)
    evs = tracer.recent()
    tracing.enable(False)
    by_name = {e["name"]: e for e in evs}
    assert set(by_name) == {"outer", "inner", "marker"}
    # children close (and record) before the parent; parent ids link up
    assert by_name["inner"]["parent"] == by_name["outer"]["id"]
    assert by_name["marker"]["parent"] == by_name["outer"]["id"]
    assert by_name["outer"]["parent"] is None
    assert by_name["outer"]["dur"] >= by_name["inner"]["dur"] >= 0
    # chrome-trace export round-trips through strict JSON
    path = tmp_path / "trace.json"
    tracer.write_chrome_trace(str(path))
    doc = json.loads(path.read_text())
    names = [e["name"] for e in doc["traceEvents"]]
    assert set(names) == {"outer", "inner", "marker"}
    marker = next(e for e in doc["traceEvents"] if e["name"] == "marker")
    assert marker["ph"] == "i" and marker["args"]["it"] == 3
    # JSONL export: one strict-JSON object per line
    for line in tracer.to_jsonl().strip().splitlines():
        json.loads(line)


def test_tracing_listener_writes_artifacts(tmp_path, monkeypatch):
    from deeplearning4j_tpu.train.listeners import TracingListener

    tracing.get_tracer().clear()
    net = MultiLayerNetwork(_mlp_conf()).init()
    jsonl = tmp_path / "spans.jsonl"
    chrome = tmp_path / "spans.chrome.json"
    lst = TracingListener(jsonl_path=str(jsonl), chrome_path=str(chrome))
    # construction must NOT flip the process-global flag (that would
    # impose the spans' host work on every other net in the process)
    assert not tracing.is_enabled()
    net.set_listeners(lst)
    x, y = _xy(n=16)
    blocking_reads = _count_blocking_reads(monkeypatch)
    net.fit(x, y, epochs=2, batch_size=8, async_prefetch=False)
    assert not tracing.is_enabled()  # restored
    lines = [json.loads(l) for l in jsonl.read_text().strip().splitlines()]
    names = {e["name"] for e in lines}
    assert "fit/step" in names and "iteration" in names
    # tracing was on: the observers' phase has its span, and no span
    # waited for the device
    assert {"fit/dispatch", "fit/observe"} <= names
    assert blocking_reads == []
    step = next(e for e in lines if e["name"] == "fit/step")
    for e in lines:
        if e["name"] in ("fit/dispatch", "fit/observe") \
                and e["parent"] == step["id"]:
            assert step["start_ns"] <= e["start_ns"]
            assert e["start_ns"] + e["dur_ns"] \
                <= step["start_ns"] + step["dur_ns"]
    # restore_on_epoch_end must NOT leave later epochs untraced: all 4
    # steps (2 epochs x 2 batches) recorded spans
    assert sum(e["name"] == "fit/step" for e in lines) == 4
    iters = {e["args"]["iteration"] for e in lines
             if e["name"] == "iteration"}
    assert iters == {0, 1, 2, 3}
    doc = json.loads(chrome.read_text())
    assert any(e["name"] == "fit/step" for e in doc["traceEvents"])


def test_tracing_listener_restores_when_fit_raises():
    from deeplearning4j_tpu.train.listeners import TracingListener

    class _Boom:
        def __iter__(self):
            raise RuntimeError("iterator died")

        def reset(self):
            pass

    net = MultiLayerNetwork(_mlp_conf()).init()
    net.set_listeners(TracingListener())
    with pytest.raises(RuntimeError, match="iterator died"):
        net._run_fit(_Boom(), epochs=1, async_prefetch=False)
    # the finally-hook restored the process-global flag despite the raise
    assert not tracing.is_enabled()


def test_recent_rejects_nonpositive_and_histogram_bucket_conflict():
    tracer = tracing.Tracer()  # local tracer: no global state
    tracer.enabled = True
    for i in range(5):
        with tracer.span(f"s{i}"):
            pass
    assert len(tracer.recent()) == 5
    assert [e["name"] for e in tracer.recent(2)] == ["s3", "s4"]
    assert tracer.recent(0) == []
    assert tracer.recent(-3) == []  # must not invert into "all but newest"
    reg = MetricsRegistry()
    reg.histogram("x_seconds", buckets=(0.1, 1.0))
    reg.histogram("x_seconds")  # no explicit buckets: existing family ok
    with pytest.raises(ValueError, match="buckets"):
        reg.histogram("x_seconds", buckets=(0.001, 0.01))


# -- helper SPI counters (PR 2 auto-disable regression) ----------------------

def _counter_value(name, **labels):
    fam = metrics_mod.get_registry().get(name)
    if fam is None:
        return 0.0
    return fam.labels(**labels).value


def test_helper_fallback_counters_on_auto_disable():
    op = "metrics_test_op"

    def boom(*a, **k):
        raise RuntimeError("kernel exploded at trace time")

    helpers.register_helper(op, boom, name="boomer")
    try:
        # no family= at registration: the kernel-family label defaults
        # to the op name (bounded — one value per op)
        before_dis = _counter_value("helper_auto_disable_total",
                                    op=op, helper="boomer", family=op)
        before_raised = _counter_value("helper_fallback_total",
                                       op=op, helper="boomer", family=op,
                                       reason="raised")
        fn = helpers.get_helper(op)
        assert fn is not None
        assert _counter_value("helper_hit_total",
                              op=op, helper="boomer", family=op) >= 1
        with pytest.raises(helpers.HelperError):
            fn(1, 2)
        assert _counter_value("helper_auto_disable_total", op=op,
                              helper="boomer", family=op) == before_dis + 1
        assert _counter_value("helper_fallback_total", op=op,
                              helper="boomer", family=op,
                              reason="raised") == before_raised + 1
        # the helper is now disabled: the next lookup falls back, counted
        assert helpers.get_helper(op) is None
        assert _counter_value("helper_fallback_total", op=op,
                              helper="boomer", family=op,
                              reason="disabled") >= 1
    finally:
        helpers._HELPERS.pop(op, None)


def test_helper_unsupported_fallback_counted():
    op = "metrics_test_unsup"
    helpers.register_helper(op, lambda: None,
                            supported=lambda **ctx: False, name="picky")
    try:
        before = _counter_value("helper_fallback_total", op=op,
                                helper="picky", family=op,
                                reason="unsupported")
        assert helpers.get_helper(op) is None
        assert _counter_value("helper_fallback_total", op=op,
                              helper="picky", family=op,
                              reason="unsupported") == before + 1
    finally:
        helpers._HELPERS.pop(op, None)


def test_helper_counter_family_label_cardinality_bounded():
    """The kernel-family label on helper_* counters must stay bounded:
    one slug per kernel family, or the op name when the registration
    carries no family fn — never a per-shape or per-instance value
    (which would blow up the scrape cardinality)."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops import pallas_conv_bn  # noqa: F401 (registers)

    # exercise several conv contexts so the conv family slugs materialize
    # on the fallback counters (CPU: everything falls back, labeled)
    for kernel, stride in (((1, 1), (1, 1)), ((3, 3), (1, 1)),
                           ((3, 3), (2, 2)), ((7, 7), (2, 2)),
                           ((5, 5), (1, 1))):
        helpers.get_helper(
            "conv2d", kernel=kernel, stride=stride, dilation=(1, 1),
            same=True, has_bias=False, activation="identity",
            dtype=jnp.float32, n_in=64, n_out=64,
            x_shape=(2, 8, 8, 64), training=True)

    allowed_slugs = {"conv1x1", "conv1x1s2", "conv3x3", "conv3x3s2",
                     "conv7x7s2", "conv_other", "bn_apply", "bn_bwd",
                     "lstm_seq", "lstm_step",
                     # attention's and the expert layers' slots, where a
                     # decoder test ran in this process before
                     "full", "window", "gated", "two_matrix"}
    reg = metrics_mod.get_registry()
    seen = 0
    for name in ("helper_hit_total", "helper_fallback_total",
                 "helper_auto_disable_total"):
        fam = reg.get(name)
        if fam is None:
            continue
        assert "family" in fam.labelnames
        f_idx = fam.labelnames.index("family")
        op_idx = fam.labelnames.index("op")
        for key in list(fam._children):
            seen += 1
            fam_label, op_label = key[f_idx], key[op_idx]
            assert fam_label in allowed_slugs or fam_label == op_label, (
                f"{name}: unbounded family label {fam_label!r} "
                f"(op={op_label!r})")
    assert seen > 0  # the probes above must have produced labeled samples


# -- fit-loop wiring + overhead guard ----------------------------------------

def test_fit_step_metrics_recorded():
    reg = metrics_mod.get_registry()
    steps0 = _counter_value("fit_step_total")
    net = MultiLayerNetwork(_mlp_conf()).init()
    x, y = _xy(n=40)
    net.fit(x, y, epochs=2, batch_size=10, async_prefetch=False)
    assert _counter_value("fit_step_total") == steps0 + 8
    disp = reg.get("fit_dispatch_seconds").labels()
    wait = reg.get("fit_data_wait_seconds").labels()
    assert disp.count >= 8 and wait.count >= 8
    assert _counter_value("compile_total", kind="train_step") >= 1


def test_fit_hot_path_no_registry_lookups_when_disabled(monkeypatch):
    """The overhead guard, asserted structurally (iteration counts, not
    wall clock): with tracing disabled and no listeners, a fit's
    per-step path performs ZERO registry lookups (instruments resolve
    once) and ZERO blocking reads of a step's score — and turning the
    tracer on adds none."""
    assert not tracing.is_enabled()
    reg = metrics_mod.get_registry()
    lookups = []
    orig = MetricsRegistry._get_or_create

    def counting(self, name, *a, **k):
        lookups.append(name)
        return orig(self, name, *a, **k)

    net = MultiLayerNetwork(_mlp_conf()).init()
    blocking_reads = _count_blocking_reads(monkeypatch)
    x, y = _xy(n=200)
    monkeypatch.setattr(MetricsRegistry, "_get_or_create", counting)
    net.fit(x, y, epochs=1, batch_size=4, async_prefetch=False)  # 50 steps
    fit_lookups = [n for n in lookups if n.startswith("fit_")]
    # instruments resolved at most once each (6 families: steps/
    # examples/examples_unknown/data_wait/dispatch and the fit call's
    # own phases), NOT once per 50 steps
    assert len(fit_lookups) <= 6, fit_lookups
    # a second fit reuses the cached children: no new lookups at all
    lookups.clear()
    net.fit(x, y, epochs=1, batch_size=4, async_prefetch=False)
    assert [n for n in lookups if n.startswith("fit_")] == []
    # no step's score was waited for (tier-1 runs devprof's sampled
    # read off), and the tracer turned on changes nothing about that
    assert blocking_reads == []
    tracing.enable(True)
    net.fit(x, y, epochs=1, batch_size=4, async_prefetch=False)
    tracing.enable(False)
    assert blocking_reads == []
    assert reg.get("fit_device_sync_seconds") is None


def test_performance_listener_reports_window_etl():
    from deeplearning4j_tpu.train.listeners import PerformanceListener

    out = []
    lst = PerformanceListener(frequency=3, print_fn=out.append)
    for i in range(7):
        lst.iteration_done(None, i, {"batch_size": 8, "etl_ms": 12.0})
    assert out, "listener never printed"
    # averaged over the window, not the last batch's value
    assert "etl 12.0 ms/iter" in out[0]


# -- satellites: logging + strict JSON ---------------------------------------

def test_library_logger_has_null_handler():
    import logging

    lg = logging.getLogger("deeplearning4j_tpu")
    assert any(isinstance(h, logging.NullHandler) for h in lg.handlers)


def test_configure_logging_json_lines(capsys):
    import io
    import logging

    buf = io.StringIO()
    lg = dl4j.configure_logging(level=logging.INFO, json_lines=True,
                                stream=buf)
    try:
        lg.info("hello %s", "world")
        rec = json.loads(buf.getvalue().strip().splitlines()[-1])
        assert rec["message"] == "hello world"
        assert rec["level"] == "INFO"
        assert rec["logger"] == "deeplearning4j_tpu"
        # reconfiguring replaces, not stacks, the handler
        buf2 = io.StringIO()
        lg = dl4j.configure_logging(json_lines=False, stream=buf2)
        assert sum(getattr(h, "_dl4j_tpu_configured", False)
                   for h in lg.handlers) == 1
    finally:
        for h in list(lg.handlers):
            if getattr(h, "_dl4j_tpu_configured", False):
                lg.removeHandler(h)


def test_json_response_replaces_non_finite():
    code, ctype, payload = json_response(
        {"p50": float("nan"), "p99": float("inf"), "ok": 1.5})
    doc = json.loads(
        payload.decode(),
        parse_constant=lambda c: pytest.fail(f"non-strict token {c}"))
    assert doc == {"p50": None, "p99": None, "ok": 1.5}


# -- inference server: strict JSON with zero traffic + shared scrape ---------

def _http_get(port, path):
    import urllib.request

    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30) as r:
        return r.read().decode()


def test_inference_server_metrics_strict_json_zero_traffic():
    from deeplearning4j_tpu.serving import InferenceServer

    net = MultiLayerNetwork(_mlp_conf()).init()
    server = InferenceServer(net, port=0)
    port = server.start()
    try:
        body = _http_get(port, "/metrics")
        doc = json.loads(
            body,
            parse_constant=lambda c: pytest.fail(
                f"non-strict JSON token {c} in /metrics with zero traffic"))
        assert doc["requests"] == 0
        assert doc["latency_ms"]["p50_ms"] is None
    finally:
        server.stop()


def test_prometheus_scrape_spans_training_and_serving():
    """Acceptance: ONE registry — a /metrics?format=prometheus scrape
    returns training-side (fit_step_*, helper_*, compile_total) and
    serving-side (bucket hits, request latency histogram) series from
    the same process."""
    from deeplearning4j_tpu.serving import InferenceServer

    # training side (same process)
    net = MultiLayerNetwork(_mlp_conf()).init()
    x, y = _xy(n=16)
    net.fit(x, y, epochs=1, batch_size=8, async_prefetch=False)
    # a helper event (any op) so helper_* series exist
    helpers.register_helper("scrape_demo", lambda v: v, name="demo")
    try:
        helpers.get_helper("scrape_demo")("ok")
    finally:
        helpers._HELPERS.pop("scrape_demo", None)

    serve_net = MultiLayerNetwork(_mlp_conf(seed=11)).init()
    server = InferenceServer(serve_net, port=0, max_batch_size=8)
    port = server.start()
    try:
        import urllib.request

        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/predict",
            data=json.dumps(
                {"features": np.zeros((3, 12)).tolist()}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            assert json.loads(r.read())["predictions"]
        text = _http_get(port, "/metrics?format=prometheus")
    finally:
        server.stop()
    for family in ("fit_step_total", "compile_total",
                   "helper_hit_total{helper=\"demo\"",
                   "serving_requests_total", "serving_bucket_hits_total",
                   "serving_request_seconds_bucket",
                   "serving_request_seconds_count", "serving_queue_depth"):
        assert family.split("{")[0] in text, f"{family} missing from scrape"
    # and the serving series actually moved
    assert "serving_requests_total " in text
    line = next(l for l in text.splitlines()
                if l.startswith("serving_requests_total"))
    assert float(line.split()[-1]) >= 1


def test_trace_route_serves_recent_spans():
    from deeplearning4j_tpu.serving import InferenceServer

    tracing.get_tracer().clear()
    tracing.enable(True)
    net = MultiLayerNetwork(_mlp_conf()).init()
    server = InferenceServer(net, port=0)
    port = server.start()
    try:
        import urllib.request

        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/predict",
            data=json.dumps(
                {"features": np.zeros((2, 12)).tolist()}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            r.read()
        body = _http_get(port, "/trace")
        names = {json.loads(l)["name"]
                 for l in body.strip().splitlines() if l}
        assert "serve/predict" in names
        chrome = json.loads(_http_get(port, "/trace?format=chrome"))
        assert any(e["name"] == "serve/predict"
                   for e in chrome["traceEvents"])
    finally:
        tracing.enable(False)
        server.stop()


# -- checkpoint + paramserver wiring ----------------------------------------

def test_checkpoint_save_metrics(tmp_path):
    from deeplearning4j_tpu.train.checkpoint import CheckpointListener

    reg = metrics_mod.get_registry()
    before = 0.0
    fam = reg.get("checkpoint_saves_total")
    if fam is not None:
        before = fam.labels(reason="manual").value
    net = MultiLayerNetwork(_mlp_conf()).init()
    lst = CheckpointListener(str(tmp_path), every_n_epochs=None)
    assert lst.save(net, reason="manual") is not None
    assert reg.get("checkpoint_saves_total").labels(
        reason="manual").value == before + 1
    # the save histogram is phase-split: `snapshot` (fit-thread blocking
    # capture) and `write` (serialize + atomic rename)
    assert reg.get("checkpoint_save_seconds").labels("snapshot").count >= 1
    assert reg.get("checkpoint_save_seconds").labels("write").count >= 1


def test_paramserver_rpc_metrics():
    from deeplearning4j_tpu.parallel.paramserver import (
        EmbeddingParameterServer,
        EmbeddingPSClient,
    )

    reg = metrics_mod.get_registry()
    server = EmbeddingParameterServer(
        {"syn0": np.zeros((10, 4), np.float32)})
    port = server.start()
    try:
        client = EmbeddingPSClient([f"http://127.0.0.1:{port}"])
        rows = np.array([1, 3])
        got = client.pull("syn0", rows)
        assert got.shape == (2, 4)
        client.push_async("syn0", rows, np.ones((2, 4), np.float32))
        client.flush()
        assert server.pushes_applied == 1
        assert reg.get("paramserver_rpc_total").labels(
            route="pull.bin").value >= 1
        assert reg.get("paramserver_rpc_total").labels(
            route="push.bin").value >= 1
        assert reg.get("paramserver_rpc_seconds").labels(
            route="pull.bin").count >= 1
        assert reg.get("paramserver_client_rpc_total").labels(
            route="pull.bin").value >= 1
    finally:
        server.stop()


# -- cli ---------------------------------------------------------------------

def test_cli_metrics_local_dump(tmp_path, capsys):
    from deeplearning4j_tpu.cli import main

    metrics_mod.get_registry().counter("cli_demo_total").inc(5)
    assert main(["metrics"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["cli_demo_total"]["values"][0]["value"] == 5
    out = tmp_path / "m.prom"
    assert main(["metrics", "--format", "prometheus",
                 "--output", str(out)]) == 0
    assert "cli_demo_total 5" in out.read_text().splitlines()


# -- exposition edge cases (blackbox/health PR satellites) --------------------

def test_exposition_gauge_family_with_zero_samples():
    """A registered family whose labels() was never called must still
    expose a well-formed TYPE (and HELP) block with no sample lines —
    and survive the snapshot path. component_health before the first
    watchdog transition is the live trigger for this shape."""
    reg = MetricsRegistry()
    reg.gauge("empty_gauge", "no children yet", ("component",))
    text = reg.to_prometheus()
    assert "# TYPE empty_gauge gauge" in text
    assert "# HELP empty_gauge no children yet" in text
    assert not [l for l in text.splitlines()
                if l.startswith("empty_gauge") and not l.startswith("#")]
    snap = reg.snapshot()
    assert snap["empty_gauge"]["values"] == []
    # strict-JSON safe even with zero samples
    json.dumps(snap, allow_nan=False)


def test_exposition_label_escaping_roundtrip():
    """Label values with backslashes, quotes, and newlines survive the
    text exposition and parse back to the original strings."""
    import re

    reg = MetricsRegistry()
    nasty = 'a\\b"c\nd'
    reg.counter("esc_total", "", ("component",)).labels(nasty).inc(3)
    text = reg.to_prometheus()
    line = [l for l in text.splitlines() if l.startswith("esc_total{")][0]
    assert "\n" not in line  # the newline was escaped, not emitted
    m = re.match(r'esc_total\{component="((?:[^"\\]|\\.)*)"\} 3', line)
    assert m, line
    unescaped = (m.group(1).replace("\\\\", "\x00").replace('\\"', '"')
                 .replace("\\n", "\n").replace("\x00", "\\"))
    assert unescaped == nasty
    # scalar_values (the --watch / flight-recorder view) uses the same
    # escaping, so the series key is unambiguous too
    assert f'esc_total{{component="{metrics_mod.escape_label_value(nasty)}"}}' \
        in reg.scalar_values()


def test_exposition_under_concurrent_registry_mutation():
    """/metrics must stay well-formed while other threads register new
    families and children mid-scrape (a live serving process does this
    constantly: warmup compiles, first paramserver push, watchdog
    transitions)."""
    reg = MetricsRegistry()
    stop = threading.Event()
    errs = []

    def mutate(k):
        i = 0
        try:
            while not stop.is_set():
                fam = reg.counter(f"mut_{k}_{i % 17}_total", "x", ("l",))
                fam.labels(f"v{i % 5}").inc()
                reg.gauge(f"mutg_{k}_{i % 13}", "x").set(i)
                reg.histogram(f"muth_{k}_{i % 7}_seconds", "x").observe(
                    0.001 * (i % 50))
                i += 1
        except Exception as e:  # pragma: no cover - failure reporting
            errs.append(e)

    threads = [threading.Thread(target=mutate, args=(k,), daemon=True,
                                name=f"dl4j-test-mut-{k}")
               for k in range(3)]
    for t in threads:
        t.start()
    try:
        for _ in range(30):
            text = reg.to_prometheus()
            # every non-comment line is "name{labels} value" with a
            # parseable numeric value
            for line in text.splitlines():
                if not line or line.startswith("#"):
                    continue
                name_part, _, value = line.rpartition(" ")
                assert name_part, line
                float(value)
            json.dumps(reg.snapshot(), allow_nan=False)
            reg.scalar_values()
    finally:
        stop.set()
        for t in threads:
            t.join(5)
    assert not errs


# -- one clock, and the always-on step timeline --------------------------------

def test_now_ns_is_monotonic_and_on_the_unix_epoch():
    import time

    reads = [tracing.now_ns() for _ in range(1000)]
    assert all(isinstance(r, int) for r in reads)
    assert all(b >= a for a, b in zip(reads, reads[1:]))
    assert abs(tracing.now_ns() - time.time_ns()) < 1_000_000  # 1 ms
    # a perf_counter reading lands on the same clock (record_complete's
    # callers hand those in)
    assert abs(tracing.perf_to_ns(time.perf_counter())
               - tracing.now_ns()) < 1_000_000


def test_spans_are_stored_on_the_shared_clock():
    import time

    tracer = tracing.get_tracer()
    tracer.clear()
    tracing.enable(True)
    before = tracing.now_ns()
    with tracing.span("on_the_clock"):
        pass
    t0 = time.perf_counter()
    tracing.record_complete("handed_in", t0, t0 + 0.25)
    tracing.instant("mark")
    after = tracing.now_ns()
    tracing.enable(False)
    evs = {e["name"]: e for e in tracer.recent()}
    for ev in evs.values():
        assert isinstance(ev["start_ns"], int)
        assert isinstance(ev["dur_ns"], int)
        assert before <= ev["start_ns"] <= after
        # the exports' microseconds are derived from the nanoseconds
        assert ev["ts"] == ev["start_ns"] / 1e3
        assert ev["dur"] == ev["dur_ns"] / 1e3
    assert evs["handed_in"]["dur_ns"] == pytest.approx(250e6, abs=2)
    assert evs["mark"]["dur_ns"] == 0
    line = json.loads(tracer.to_jsonl().splitlines()[0])
    assert {"start_ns", "dur_ns", "ts", "dur"} <= set(line)


def _fit_records(net, x, y, **fit_args):
    t0 = tracing.now_ns()
    net.fit(x, y, async_prefetch=False, **fit_args)
    return tracing.get_step_timeline().records(since_ns=t0), t0


def test_step_timeline_phases_tile_the_fit_threads_time():
    net = MultiLayerNetwork(_mlp_conf()).init()
    x, y = _xy(n=40)
    records, t0 = _fit_records(net, x, y, epochs=2, batch_size=10)
    assert len(records) == 8
    assert [r[0] for r in records] == list(range(8))  # the iteration
    for (it, n, w0, d0, d1, end, s0, s1, cpu_d, cpu_o, score) in records:
        assert n == 1 and t0 <= w0 <= d0 <= d1 <= end
        assert (s0, s1) == (0, 0)          # tier-1: devprof's read is off
        assert 0 <= cpu_d <= (d1 - d0) + 1_000_000
        assert 0 <= cpu_o <= (end - d1) + 1_000_000
        assert score is not None
    # within an epoch a record starts where the one before it ended
    for a, b in zip(records, records[1:]):
        if b[0] % 4:
            assert b[2] == a[5]
        else:                               # the next epoch's first
            assert b[2] >= a[5]
    spans = tracing.step_timeline(since_ns=t0)
    steps = [s for s in spans if s["name"] == "fit/step"]
    assert [s["step"] for s in steps] == list(range(8))
    for step in steps:
        kids = [s for s in spans if s["step"] == step["step"]
                and s["parent"] == "fit/step"]
        assert [k["name"] for k in kids] == [
            "fit/data_wait", "fit/dispatch", "fit/observe"]
        assert kids[0]["start_ns"] == step["start_ns"]
        assert kids[-1]["end_ns"] == step["end_ns"]
        for a, b in zip(kids, kids[1:]):
            assert a["end_ns"] == b["start_ns"]
        assert step["cpu_ns"] == kids[1]["cpu_ns"] + kids[2]["cpu_ns"]


def test_step_timeline_records_devprofs_blocking_read(monkeypatch):
    from deeplearning4j_tpu.utils import devprof

    profiler = devprof.get_profiler()
    monkeypatch.setattr(profiler, "sample_every", 2)
    blocking_reads = _count_blocking_reads(monkeypatch)
    net = MultiLayerNetwork(_mlp_conf()).init()
    x, y = _xy(n=40)
    records, t0 = _fit_records(net, x, y, epochs=1, batch_size=10)
    sampled = [r for r in records if r[7] > r[6]]
    assert len(sampled) == len(blocking_reads) == 2   # steps 2 and 4 of 4
    for (_, _, _, _, d1, end, s0, s1, *_rest) in sampled:
        assert d1 <= s0 <= s1 <= end   # inside the observers' phase
    spans = tracing.step_timeline(since_ns=t0)
    samples = [s for s in spans if s["name"] == "devprof/sample"]
    assert len(samples) == 2
    assert all(s["parent"] == "fit/observe" for s in samples)


def test_step_timeline_hot_path_cost_is_microseconds():
    """What a dispatch pays for the timeline: three clock reads, three
    reads of the thread's CPU time, one tuple, one append."""
    import time

    timeline = tracing.StepTimeline()
    now_ns, cpu_ns, append = tracing.now_ns, time.thread_time_ns, \
        timeline.append
    n = 20_000
    t0 = time.perf_counter()
    for i in range(n):
        a, c0 = now_ns(), cpu_ns()
        b, c1 = now_ns(), cpu_ns()
        end = now_ns()
        append((i, 1, a, a, b, end, 0, 0, c1 - c0, cpu_ns() - c1, None))
    per_dispatch = (time.perf_counter() - t0) / n
    assert per_dispatch < 10e-6, f"{per_dispatch * 1e6:.2f}us a dispatch"
    assert len(timeline.records()) == 4096   # bounded


def test_flight_recorder_reads_the_one_ring(tmp_path):
    """The process's recorder keeps no step ring of its own: its final
    steps are the timeline's newest records, under the dump's old keys."""
    from deeplearning4j_tpu.utils import blackbox

    rec = blackbox.get_recorder()
    assert rec.timeline is tracing.get_step_timeline()
    from collections import deque

    assert sorted(k for k, v in vars(rec).items()
                  if isinstance(v, deque)) == ["_events", "_metrics_deltas"]
    net = MultiLayerNetwork(_mlp_conf()).init()
    x, y = _xy(n=30)
    records, _ = _fit_records(net, x, y, epochs=1, batch_size=10)
    with open(rec.dump(str(tmp_path / "bb.json"), reason="keys")) as f:
        doc = json.load(f)
    last = doc["steps"][-3:]
    assert [r["step"] for r in last] == [0, 1, 2]
    for got, (_, _, w0, d0, d1, end, *_rest) in zip(last, records):
        assert set(got) == {"ts", "step", "score", "data_wait", "dispatch"}
        assert got["data_wait"] == pytest.approx((d0 - w0) * 1e-9, abs=1e-6)
        assert got["dispatch"] == pytest.approx((d1 - d0) * 1e-9, abs=1e-6)
        assert got["ts"] == pytest.approx(end * 1e-9, abs=1e-3)
        # resolved without a blocking read: a step still in flight
        # reads "pending"
        assert isinstance(got["score"], float) or got["score"] == "pending"
    assert len(doc["steps"]) <= 256
    text = blackbox.render_dump(doc)
    assert "data_wait   dispatch" in text and "sync" not in text.split(
        "events")[0]

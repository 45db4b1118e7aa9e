"""Bucketed, pipelined inference-serving tests: compile-cache stability
(the shape-keyed output cache + retrace counter), correctness of fused
mixed-size dispatch, the two ParallelInference admission races, and the
REST InferenceServer (reference: ParallelInferenceTest.java +
inference/observers/BatchedInferenceObservable tests — extended with the
trace-count assertions the reference had no equivalent of)."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import (
    DenseLayer,
    NeuralNetConfiguration,
    OutputLayer,
    Updater,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel import (
    InferenceMode,
    ParallelInference,
    data_parallel_mesh,
    data_shards,
    power_of_two_buckets,
)
from deeplearning4j_tpu.serving import InferenceServer


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


def _requests(sizes, n_in=12, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((s, n_in)).astype(np.float32)
            for s in sizes]


def _assert_same_rows(actual, desired):
    """Served rows vs a per-request `net.output`: within one f32 ulp of
    the softmax's range (eps = 2^-23 = 1.2e-7, absolute). The two run as
    differently shaped XLA:CPU programs (the request padded up to its
    bucket vs its own batch size), whose dot/softmax vectorisation may
    round the last bit differently (jax 0.9 does: 6e-8 measured);
    anything a padding or row-mapping bug would produce is off by whole
    values, not by a last bit."""
    np.testing.assert_allclose(np.asarray(actual), np.asarray(desired),
                               rtol=0, atol=np.finfo(np.float32).eps)


def _expected_traces(buckets, n_shards):
    """Distinct jit shapes: each bucket is padded up to a multiple of the
    shard count before dispatch, so buckets below n_shards collapse."""
    return len({b + (-b) % n_shards for b in buckets})


# -- bucket policy ----------------------------------------------------------

def test_default_bucket_set():
    assert power_of_two_buckets(64) == [1, 2, 4, 8, 16, 32, 64]
    assert power_of_two_buckets(48) == [1, 2, 4, 8, 16, 32, 48]
    assert power_of_two_buckets(1) == [1]


def test_custom_buckets_validated():
    net = MultiLayerNetwork(_mlp_conf()).init()
    with pytest.raises(ValueError, match="bucket"):
        ParallelInference(net, data_parallel_mesh(), max_batch_size=32,
                          buckets=[4, 8])  # largest < max_batch_size
    with pytest.raises(ValueError, match="max_batch_size"):
        ParallelInference(net, data_parallel_mesh(), max_batch_size=0)
    pi = ParallelInference(net, data_parallel_mesh(), max_batch_size=32,
                           buckets=[8, 32, 16],
                           inference_mode=InferenceMode.SEQUENTIAL)
    assert pi.buckets == [8, 16, 32]


def test_empty_request_rejected():
    """A 0-row request must be rejected at admission: 0 is a multiple of
    every bucket, so it would otherwise compile a fresh 0-shape trace."""
    net = MultiLayerNetwork(_mlp_conf()).init()
    pi = ParallelInference(net, data_parallel_mesh(), max_batch_size=8)
    try:
        compiles = net.output_compile_count
        with pytest.raises(ValueError, match="empty"):
            pi.output(np.zeros((0, 12), np.float32))
        assert net.output_compile_count == compiles
    finally:
        pi.shutdown()


# -- compile-cache stability (the tentpole claim) ---------------------------

def test_mixed_sizes_bounded_compiles_and_exact_results():
    """≥6 distinct concurrent request sizes through BATCHED mode: the
    number of forward compiles equals the number of distinct bucket
    shapes (NOT the number of distinct request/group sizes), warmup
    precompiles all of them so traffic itself compiles nothing, and every
    caller gets the rows of a per-request model.output (to one ulp)."""
    net = MultiLayerNetwork(_mlp_conf()).init()
    mesh = data_parallel_mesh()
    pi = ParallelInference(net, mesh, max_batch_size=16)
    try:
        assert pi.buckets == [1, 2, 4, 8, 16]
        pi.warmup((12,))
        compiles_warm = net.output_compile_count
        assert compiles_warm == _expected_traces(pi.buckets,
                                                 data_shards(mesh))
        assert compiles_warm <= len(pi.buckets)

        sizes = [1, 2, 3, 5, 8, 11, 16, 4, 7, 13]  # 10 distinct sizes
        xs = _requests(sizes)
        results = {}

        def call(i):
            results[i] = np.asarray(pi.output(xs[i]))

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(len(xs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # traffic with 10 distinct request sizes compiled NOTHING new
        assert net.output_compile_count == compiles_warm
        m = pi.metrics()
        assert m["requests"] == len(sizes)
        assert m["examples"] == sum(sizes)
        assert m["oversized"] == 0
        assert sum(m["bucket_hits"].values()) == m["batches"] > 0
    finally:
        pi.shutdown()
    # equal to per-request output to one ulp (row results are independent of
    # the fused batch around them; pad rows are sliced off) — computed
    # after the counter assertions since these calls add new trace shapes
    for i, x in enumerate(xs):
        _assert_same_rows(results[i], net.output(x))


def test_sequential_mode_is_bucketed_too():
    net = MultiLayerNetwork(_mlp_conf()).init()
    ref = MultiLayerNetwork(_mlp_conf()).init()  # same seed: same params
    pi = ParallelInference(net, data_parallel_mesh(), max_batch_size=16,
                           inference_mode=InferenceMode.SEQUENTIAL)
    pi.warmup((12,))
    compiles_warm = net.output_compile_count
    for x in _requests([3, 5, 9, 13, 16, 1]):
        _assert_same_rows(pi.output(x), ref.output(x))
    assert net.output_compile_count == compiles_warm


def test_oversized_request_served_alone():
    net = MultiLayerNetwork(_mlp_conf()).init()
    pi = ParallelInference(net, data_parallel_mesh(), max_batch_size=8)
    try:
        x = _requests([24])[0]
        out = np.asarray(pi.output(x))
        assert out.shape == (24, 4)
        assert pi.metrics()["oversized"] == 1
    finally:
        pi.shutdown()


def test_output_cache_is_shape_keyed_multilayer():
    net = MultiLayerNetwork(_mlp_conf()).init()
    assert net.output_compile_count == 0
    x8, x16 = _requests([8, 16])
    net.output(x8)
    net.output(x8)  # same shape: cache hit
    assert net.output_compile_count == 1
    net.output(x16)
    assert net.output_compile_count == 2
    net.output(x8, training=True)  # distinct trace per training flag
    assert net.output_compile_count == 3


def test_output_cache_is_shape_keyed_compgraph():
    from deeplearning4j_tpu.nn.compgraph import ComputationGraph

    conf = (NeuralNetConfiguration.builder()
            .seed(7).updater(Updater.SGD).learning_rate(0.05)
            .weight_init("xavier").graph_builder()
            .add_inputs("in")
            .add_layer("d", DenseLayer(n_in=12, n_out=16, activation="tanh"),
                       "in")
            .add_layer("out", OutputLayer(n_in=16, n_out=4,
                                          activation="softmax",
                                          loss="mcxent"),
                       "d")
            .set_outputs("out")
            .build())
    g = ComputationGraph(conf).init()
    x8, x16 = _requests([8, 16])
    g.output(x8)
    g.output(x8)
    assert g.output_compile_count == 1
    g.output(x16)
    assert g.output_compile_count == 2


def _two_head_graph():
    from deeplearning4j_tpu.nn.compgraph import ComputationGraph

    conf = (NeuralNetConfiguration.builder()
            .seed(7).updater(Updater.SGD).learning_rate(0.05)
            .weight_init("xavier").graph_builder()
            .add_inputs("in")
            .add_layer("d", DenseLayer(n_in=12, n_out=16, activation="tanh"),
                       "in")
            .add_layer("outA", OutputLayer(n_in=16, n_out=4,
                                           activation="softmax",
                                           loss="mcxent"), "d")
            .add_layer("outB", OutputLayer(n_in=16, n_out=2,
                                           activation="softmax",
                                           loss="mcxent"), "d")
            .set_outputs("outA", "outB")
            .build())
    return ComputationGraph(conf).init()


def test_multi_output_graph_through_parallel_inference():
    """A multi-output ComputationGraph returns a LIST from output(); the
    batch slice/scatter must apply per output array, not to the list."""
    g = _two_head_graph()
    ref = _two_head_graph()  # same seed: same params
    pi = ParallelInference(g, data_parallel_mesh(), max_batch_size=8)
    try:
        results = {}
        xs = _requests([3, 5, 2])

        def call(i):
            results[i] = pi.output(xs[i])

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(len(xs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, x in enumerate(xs):
            out = results[i]
            assert isinstance(out, list) and len(out) == 2
            assert out[0].shape == (x.shape[0], 4)
            assert out[1].shape == (x.shape[0], 2)
            ref_a, ref_b = ref.output(x)
            # ULP-tolerance, not byte-equality: XLA does not guarantee
            # bitwise row-position invariance for the fused two-head
            # graph (the second head drifts 1 ULP when the request sits
            # at a nonzero row offset inside the fused batch)
            np.testing.assert_allclose(out[0], np.asarray(ref_a),
                                       rtol=2e-6, atol=1e-7)
            np.testing.assert_allclose(out[1], np.asarray(ref_b),
                                       rtol=2e-6, atol=1e-7)
    finally:
        pi.shutdown()


def test_multi_output_graph_through_inference_server():
    """/predict on a multi-output graph returns one predictions entry per
    output head instead of a mis-stacked tensor or a spurious 400."""
    g = _two_head_graph()
    server = InferenceServer(g, max_batch_size=8, warmup_shape=(12,))
    port = server.start()
    try:
        x = _requests([3])[0]
        preds = _http(f"http://127.0.0.1:{port}/predict",
                      {"features": x.tolist()})["predictions"]
        assert len(preds) == 2
        ref_a, ref_b = g.output(x)
        np.testing.assert_allclose(np.asarray(preds[0], np.float32),
                                   np.asarray(ref_a), rtol=2e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(preds[1], np.float32),
                                   np.asarray(ref_b), rtol=2e-6, atol=1e-7)
    finally:
        server.stop()


# -- admission races (satellite regressions) --------------------------------

def test_first_request_shape_race():
    """Two shapes racing to be the first request: exactly ONE wins (the
    admission lock fixes `_expected_shape` atomically) and every loser is
    rejected at admission with ValueError — mismatched shapes can never
    share a fused group. Before the fix, two concurrent first callers
    could both see None, co-admit, and fail the whole fused group with
    collateral errors for correctly-shaped callers."""
    for attempt in range(4):
        net = MultiLayerNetwork(_mlp_conf()).init()
        pi = ParallelInference(net, data_parallel_mesh(), max_batch_size=32)
        try:
            n_each = 6
            xs = (_requests([4] * n_each, n_in=12)
                  + _requests([4] * n_each, n_in=7, seed=1))
            start = threading.Barrier(2 * n_each)
            outcomes = {}

            def call(i):
                start.wait()
                try:
                    outcomes[i] = np.asarray(pi.output(xs[i])).shape
                except ValueError:
                    outcomes[i] = "rejected"  # lost the admission race
                except Exception as e:  # model-level failure (winner != 12)
                    outcomes[i] = ("failed", type(e).__name__)

            threads = [threading.Thread(target=call, args=(i,))
                       for i in range(len(xs))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            assert not any(t.is_alive() for t in threads)
            for i, x in enumerate(xs):
                o = outcomes[i]
                if x.shape[1:] == (12,):
                    # a model-compatible request either lost an admission
                    # race (clean reject) or got a CORRECT result — never
                    # collateral failure from the other shape in its group
                    assert o in ("rejected", (4, 4)), (i, o)
                else:
                    # the model-incompatible shape can win the pin (and
                    # then fail at the model, unpinning) but must never
                    # produce a result
                    assert o == "rejected" or (
                        isinstance(o, tuple) and o[0] == "failed"), (i, o)
            # at least one caller was served or cleanly rejected — and if
            # the bad shape won the provisional pin, its forward failure
            # unpinned it, so the endpoint is never poisoned:
            x_ok = _requests([4])[0]
            assert np.asarray(pi.output(x_ok)).shape == (4, 4)
        finally:
            pi.shutdown()


def test_bad_first_request_does_not_poison_endpoint():
    """A malformed FIRST request (feature width the model rejects) pins
    the expected shape only provisionally: its forward failure unpins,
    so later well-formed requests are served instead of being rejected
    forever."""
    net = MultiLayerNetwork(_mlp_conf()).init()
    pi = ParallelInference(net, data_parallel_mesh(), max_batch_size=8)
    try:
        with pytest.raises(Exception):
            pi.output(np.zeros((2, 7), np.float32))  # model wants n_in=12
        x = _requests([3])[0]
        out = np.asarray(pi.output(x))
        assert out.shape == (3, 4)
        _assert_same_rows(out, net.output(x))
    finally:
        pi.shutdown()


def test_shutdown_under_load_no_hung_futures():
    """Requests racing shutdown(): every caller either gets a result or a
    fast RuntimeError — the enqueue-after-drain window that used to leave
    a Future unresolved forever is closed by the admission lock."""
    net = MultiLayerNetwork(_mlp_conf()).init()
    pi = ParallelInference(net, data_parallel_mesh(), max_batch_size=8,
                           batch_timeout_ms=1.0)
    pi.warmup((12,))
    x = _requests([2])[0]
    served, rejected, hung = [], [], []

    def client(i):
        try:
            out = pi.output(x)
            assert np.asarray(out).shape == (2, 4)
            served.append(i)
        except RuntimeError:
            rejected.append(i)
        except BaseException:
            hung.append(i)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(32)]
    for j, t in enumerate(threads):
        t.start()
        if j == 12:  # shut down mid-stream
            killer = threading.Thread(target=pi.shutdown)
            killer.start()
    killer.join(timeout=15)
    for t in threads:
        t.join(timeout=15)
    assert not any(t.is_alive() for t in threads), "caller hung on shutdown"
    assert not hung
    assert len(served) + len(rejected) == 32
    with pytest.raises(RuntimeError, match="shut down"):
        pi.output(x)


# -- REST server ------------------------------------------------------------

def _http(url, payload=None, timeout=15):
    if payload is None:
        resp = urllib.request.urlopen(url, timeout=timeout)
    else:
        req = urllib.request.Request(
            url, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        resp = urllib.request.urlopen(req, timeout=timeout)
    return json.loads(resp.read())


def test_inference_server_routes():
    net = MultiLayerNetwork(_mlp_conf()).init()
    server = InferenceServer(net, max_batch_size=8, warmup_shape=(12,))
    port = server.start()
    base = f"http://127.0.0.1:{port}"
    try:
        h = _http(f"{base}/health")
        assert h["status"] == "ok"
        assert h["model"] == "MultiLayerNetwork"
        assert h["feature_shape"] == [12]

        x = _requests([3])[0]
        preds = np.asarray(
            _http(f"{base}/predict", {"features": x.tolist()})["predictions"],
            np.float32)
        np.testing.assert_allclose(preds, np.asarray(net.output(x)),
                                   rtol=1e-5, atol=1e-6)
        # single flat example: one row back
        single = np.asarray(
            _http(f"{base}/predict",
                  {"features": x[0].tolist()})["predictions"], np.float32)
        np.testing.assert_allclose(single, preds[0], rtol=1e-5, atol=1e-6)

        m = _http(f"{base}/metrics")
        assert m["requests"] == 2
        assert m["latency_ms"]["count"] == 2
        assert m["latency_ms"]["p50_ms"] is not None
        assert m["latency_ms"]["p99_ms"] is not None
        assert set(m["bucket_hits"]) == {"1", "2", "4", "8"}
        assert m["forward_compiles"] >= 1
        assert m["queue_depth"] == 0

        # client errors are 4xx with a JSON body, and the server survives
        for payload in ({"features": [[1.0, 2.0]]},  # wrong width
                        {"features": 3.5},           # scalar
                        {"features": []},            # empty
                        {}):                         # missing key
            bad = urllib.request.Request(
                f"{base}/predict", data=json.dumps(payload).encode())
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(bad, timeout=15)
            assert ei.value.code == 400, payload
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/nosuch", timeout=15)
        assert ei.value.code == 404
        assert _http(f"{base}/health")["status"] == "ok"

        # server-side faults are 5xx (retryable), not mislabeled 400s:
        # kill the inference engine under the still-serving HTTP layer
        server.inference.shutdown()
        good = urllib.request.Request(
            f"{base}/predict", data=json.dumps({"features": x.tolist()}).encode())
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(good, timeout=15)
        assert ei.value.code == 500
    finally:
        server.stop()


@pytest.mark.slow
def test_inference_server_concurrent_load():
    """Serving load test: many clients, mixed sizes, through the full
    REST + fused-dispatch + bucket-padding stack; all responses correct,
    no compiles after warmup, metrics consistent."""
    net = MultiLayerNetwork(_mlp_conf()).init()
    server = InferenceServer(net, max_batch_size=16, warmup_shape=(12,),
                             batch_timeout_ms=1.0)
    port = server.start()
    base = f"http://127.0.0.1:{port}"
    compiles_warm = net.output_compile_count
    rng = np.random.default_rng(0)
    sizes = [int(s) for s in rng.integers(1, 17, size=64)]
    xs = _requests(sizes)
    errors = []

    def client(i):
        try:
            preds = np.asarray(
                _http(f"{base}/predict",
                      {"features": xs[i].tolist()})["predictions"],
                np.float32)
            if preds.shape != (sizes[i], 4):
                errors.append((i, preds.shape))
        except BaseException as e:
            errors.append((i, repr(e)))

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(xs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    dt = time.perf_counter() - t0
    try:
        assert not errors, errors[:5]
        assert net.output_compile_count == compiles_warm
        m = _http(f"{base}/metrics")
        assert m["requests"] == len(xs)
        assert m["examples"] == sum(sizes)
        assert m["latency_ms"]["count"] == len(xs)
        assert dt < 60
    finally:
        server.stop()

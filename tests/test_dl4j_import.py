"""DL4J model-zip import (modelimport/dl4j.py).

Round-trip strategy (the reference's own regressiontest/ approach needs
release-era zip artifacts; none ship in-tree): export writes the exact
reference layouts — f-order flat views per nn/params/*, IFOG gate order
with DL4J's candidate/input-gate block semantics, Graves peephole columns
— and import must reconstruct a network whose forward output matches the
original to float precision. A hand-built coefficients buffer additionally
pins the gate permutation itself (not just invertibility).
"""

import io
import numpy as np
import pytest

from deeplearning4j_tpu.modelimport.dl4j import (
    export_dl4j_zip,
    import_dl4j_multilayer,
    read_nd4j_array,
    write_nd4j_array,
    _perm_ifog,
)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    BatchNormalization,
    DenseLayer,
    GravesLSTM,
    LSTM,
    OutputLayer,
    RnnOutputLayer,
    SubsamplingLayer,
    ConvolutionLayer,
)
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork


def test_nd4j_binary_round_trip():
    rng = np.random.default_rng(0)
    for arr in (rng.standard_normal(17).astype(np.float32),
                rng.standard_normal((3, 5)).astype(np.float64)):
        buf = io.BytesIO()
        write_nd4j_array(arr, buf)
        buf.seek(0)
        back = read_nd4j_array(buf)
        np.testing.assert_array_equal(back.reshape(-1), arr.reshape(-1))


def test_perm_ifog_blocks():
    """DL4J [I,F,O,G] -> framework [i,f,g,o] means [G,F,I,O]."""
    H = 2
    cols = np.array([[10, 11, 20, 21, 30, 31, 40, 41]], np.float32)
    out = _perm_ifog(cols, H)
    np.testing.assert_array_equal(
        out[0], [40, 41, 20, 21, 10, 11, 30, 31])


def _mlp_net(seed=5):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .weight_init("xavier").list()
            .layer(DenseLayer(n_out=9, activation="tanh"))
            .layer(BatchNormalization())
            .layer(OutputLayer(n_out=4, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(6)).build())
    return MultiLayerNetwork(conf).init()


def test_mlp_zip_round_trip(tmp_path):
    net = _mlp_net()
    # give BN non-trivial running stats
    x = np.random.default_rng(0).standard_normal((32, 6)).astype(np.float32)
    y = np.zeros((32, 4), np.float32)
    y[np.arange(32), np.random.default_rng(1).integers(0, 4, 32)] = 1.0
    net.fit(x, y, batch_size=16, epochs=1, async_prefetch=False)

    path = str(tmp_path / "mlp.zip")
    export_dl4j_zip(net, path)
    back = import_dl4j_multilayer(path)
    np.testing.assert_allclose(
        np.asarray(back.output(x)), np.asarray(net.output(x)),
        rtol=1e-5, atol=1e-6)


def test_graves_lstm_zip_round_trip_golden_forward(tmp_path):
    """The headline case: gate permutation + peephole
    column mapping proven by forward equality on a Graves LSTM."""
    conf = (NeuralNetConfiguration.builder().seed(11)
            .weight_init("xavier").list()
            .layer(GravesLSTM(n_out=7, activation="tanh"))
            .layer(RnnOutputLayer(n_out=3, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(5)).build())
    net = MultiLayerNetwork(conf).init()
    x = np.random.default_rng(2).standard_normal((4, 10, 5)).astype(np.float32)
    golden = np.asarray(net.output(x))

    path = str(tmp_path / "graves.zip")
    export_dl4j_zip(net, path)
    back = import_dl4j_multilayer(path)
    np.testing.assert_allclose(np.asarray(back.output(x)), golden,
                               rtol=1e-5, atol=1e-6)
    # peephole vectors landed in the right slots
    for k in ("pI", "pF", "pO"):
        np.testing.assert_allclose(np.asarray(back.params_list[0][k]),
                                   np.asarray(net.params_list[0][k]),
                                   rtol=1e-6)


def test_vanilla_lstm_zip_round_trip(tmp_path):
    conf = (NeuralNetConfiguration.builder().seed(3)
            .weight_init("xavier").list()
            .layer(LSTM(n_out=6, activation="tanh"))
            .layer(RnnOutputLayer(n_out=2, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(4)).build())
    net = MultiLayerNetwork(conf).init()
    x = np.random.default_rng(4).standard_normal((3, 8, 4)).astype(np.float32)
    path = str(tmp_path / "lstm.zip")
    export_dl4j_zip(net, path)
    back = import_dl4j_multilayer(path)
    np.testing.assert_allclose(np.asarray(back.output(x)),
                               np.asarray(net.output(x)),
                               rtol=1e-5, atol=1e-6)


def test_length_mismatch_detected(tmp_path):
    net = _mlp_net()
    path = str(tmp_path / "bad.zip")
    export_dl4j_zip(net, path)
    import zipfile, json

    with zipfile.ZipFile(path) as zf:
        conf = zf.read("configuration.json")
        coeff = zf.read("coefficients.bin")
    # truncate the flat buffer: drop the final 4 bytes (one float)
    buf = io.BytesIO(coeff)
    arr = read_nd4j_array(buf)
    short = np.asarray(arr).reshape(-1)[:-1]
    out = io.BytesIO()
    write_nd4j_array(short, out)
    with zipfile.ZipFile(path, "w") as zf:
        zf.writestr("configuration.json", conf)
        zf.writestr("coefficients.bin", out.getvalue())
    with pytest.raises(ValueError, match="too short|mismatch"):
        import_dl4j_multilayer(path)


# -- ComputationGraph zips ----------------------------------------------------

from deeplearning4j_tpu.modelimport.dl4j import (
    export_dl4j_graph,
    import_dl4j_computation_graph,
    _dl4j_topo_names,
)
from deeplearning4j_tpu.nn.compgraph import ComputationGraph
from deeplearning4j_tpu.nn.conf.graph import (
    ElementWiseVertex,
    MergeVertex,
)


def _graph_net(seed=11):
    """Diamond graph: dense branches -> merge, plus a residual elementwise
    add and a BN layer — exercises vertex mapping AND the topological flat
    walk (branch params interleave)."""
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .weight_init("xavier").graph_builder()
            .add_inputs("in")
            .add_layer("a", DenseLayer(n_in=8, n_out=6, activation="tanh"),
                       "in")
            .add_layer("b", DenseLayer(n_in=8, n_out=6, activation="relu"),
                       "in")
            .add_vertex("add", ElementWiseVertex(op="add"), "a", "b")
            .add_vertex("m", MergeVertex(), "a", "add")
            .add_layer("bn", BatchNormalization(n_in=12), "m")
            .add_layer("out", OutputLayer(n_in=12, n_out=3,
                                          activation="softmax",
                                          loss="mcxent"), "bn")
            .set_outputs("out").build())
    return ComputationGraph(conf).init()


def test_graph_zip_round_trip(tmp_path):
    net = _graph_net()
    x = np.random.default_rng(3).standard_normal((5, 8)).astype(np.float32)
    want = np.asarray(net.output(x))
    path = tmp_path / "graph.zip"
    export_dl4j_graph(net, str(path))
    back = import_dl4j_computation_graph(str(path))
    got = np.asarray(back.output(x))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_graph_import_frozen_reference_fixture():
    """Byte-frozen fixture zip in the exact Jackson shape (WRAPPER_OBJECT
    vertices, networkInputs/vertexInputs names, vertices deliberately
    listed OUT of topological order, Adam updaterState.bin) — the
    reference's regressiontest discipline (RegressionTest080.java loads
    release-era artifacts) rather than JSON built adjacent to the code
    under test. Regenerate ONLY with tests/fixtures/make_cg_fixture.py
    and only for deliberate format-version bumps."""
    import os as _os

    from deeplearning4j_tpu.modelimport.dl4j import updater_state_to_flat

    fixtures = _os.path.join(_os.path.dirname(_os.path.abspath(__file__)),
                             "fixtures")
    path = _os.path.join(fixtures, "cg_adam_v1.zip")
    expected = np.load(_os.path.join(fixtures, "cg_adam_v1_expected.npz"))

    net = import_dl4j_computation_graph(path)
    np.testing.assert_allclose(np.asarray(net.output(expected["x"])),
                               expected["out"], rtol=1e-5, atol=1e-6)
    # resume state: iteration counter + the Adam [m|v] block view survive
    assert net.iteration == int(expected["iteration"])
    assert net.net_conf.updater == "adam"
    # flat-walk order: FIFO Kahn over JSON-order vertex numbers -> b, a, out
    np.testing.assert_allclose(
        updater_state_to_flat(
            net, indexed_layer_confs=[
                (net._pidx[n], net.conf.vertices[n].layer)
                for n in ("b", "a", "out")]),
        expected["updater_state"], atol=0, rtol=0)


def test_dl4j_topo_matches_reference_kahn():
    """FIFO Kahn with ascending-index tie-break: inputs first, then both
    ready children in vertex-number order, etc."""
    order = _dl4j_topo_names(
        ["in"], ["z", "a", "out"],
        {"z": ["in"], "a": ["in"], "out": ["z", "a"]})
    assert order == ["in", "z", "a", "out"]
    # diamond where JSON order disagrees with readiness
    order = _dl4j_topo_names(
        ["x"], ["c", "b"], {"c": ["b"], "b": ["x"]})
    assert order == ["x", "b", "c"]


def test_bn_lock_gamma_beta_import(tmp_path):
    """lockGammaBeta zips carry only mean/var (2*nOut floats); gamma/beta
    come from the conf constants (ADVICE r3 + reference
    BatchNormalizationParamInitializer)."""
    import io as _io
    import json as _json
    import zipfile as _zipfile
    from deeplearning4j_tpu.modelimport.dl4j import write_nd4j_array

    rng = np.random.default_rng(9)
    n = 4
    W = rng.standard_normal((n, 2)).astype(np.float32)
    b = rng.standard_normal(2).astype(np.float32)
    mean = rng.standard_normal(n).astype(np.float32)
    var = (rng.random(n).astype(np.float32) + 0.5)
    conf = {"confs": [
        {"layer": {"batchNormalization": {
            "nin": n, "nout": n, "eps": 1e-5, "decay": 0.9,
            "lockGammaBeta": True, "gamma": 2.0, "beta": 0.5}}},
        {"layer": {"output": {"nin": n, "nout": 2,
                              "activationFn": "softmax",
                              "lossFn": "mcxent"}}},
    ]}
    flat = np.concatenate([mean, var, W.reshape(-1, order="F"), b])
    buf = _io.BytesIO()
    write_nd4j_array(flat, buf)
    p = tmp_path / "bn_locked.zip"
    with _zipfile.ZipFile(p, "w") as zf:
        zf.writestr("configuration.json", _json.dumps(conf))
        zf.writestr("coefficients.bin", buf.getvalue())
    net = import_dl4j_multilayer(str(p))
    p0 = net.params_list[0]
    np.testing.assert_allclose(np.asarray(p0["gamma"]), np.full(n, 2.0))
    np.testing.assert_allclose(np.asarray(p0["beta"]), np.full(n, 0.5))
    st = net.state_list[0]
    np.testing.assert_allclose(np.asarray(st["mean"]), mean, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(st["var"]), var, rtol=1e-6)
    # and the forward APPLIES the locked constants (gamma*xhat + beta),
    # matching the reference's lockGammaBeta semantics
    x = rng.standard_normal((6, n)).astype(np.float32)
    xhat = (x - mean) / np.sqrt(var + 1e-5)
    logits = (2.0 * xhat + 0.5) @ W + b
    e = np.exp(logits - logits.max(axis=1, keepdims=True))
    want = e / e.sum(axis=1, keepdims=True)
    np.testing.assert_allclose(np.asarray(net.output(x)), want,
                               rtol=1e-4, atol=1e-5)

"""ComputationGraph engine tests.

Mirrors the reference's graph test coverage (SURVEY.md §4:
deeplearning4j-core/src/test/.../nn/graph/ +
gradientcheck/GradientCheckTestsComputationGraph.java): vertex-type
semantics, topo order, multi-input/multi-output training, fan-out gradient
accumulation, serde round trip, and gradient checks on small DAGs.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import MultiDataSet
from deeplearning4j_tpu.nn.compgraph import ComputationGraph
from deeplearning4j_tpu.nn.conf import (
    DenseLayer,
    DuplicateToTimeSeriesVertex,
    ElementWiseVertex,
    InputType,
    L2NormalizeVertex,
    L2Vertex,
    LastTimeStepVertex,
    LSTM,
    MergeVertex,
    NeuralNetConfiguration,
    OutputLayer,
    ReshapeVertex,
    RnnOutputLayer,
    ScaleVertex,
    ShiftVertex,
    StackVertex,
    SubsetVertex,
    UnstackVertex,
)
from deeplearning4j_tpu.nn.conf.graph import ComputationGraphConfiguration
from deeplearning4j_tpu.train.gradientcheck import check_gradients_graph


def _gb(seed=7, lr=0.05, updater="sgd"):
    return (
        NeuralNetConfiguration.builder()
        .seed(seed)
        .updater(updater)
        .learning_rate(lr)
        .weight_init("xavier")
        .graph_builder()
    )


def _xy(n=16, nin=8, nout=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, nin)).astype(np.float32)
    y = np.zeros((n, nout), np.float32)
    y[np.arange(n), rng.integers(0, nout, n)] = 1.0
    return x, y


# -- topology / build --------------------------------------------------------

def test_topological_order_diamond():
    conf = (
        _gb()
        .add_inputs("in")
        .add_layer("a", DenseLayer(n_out=4, activation="tanh"), "in")
        .add_layer("b", DenseLayer(n_out=4, activation="tanh"), "in")
        .add_vertex("m", MergeVertex(), "a", "b")
        .add_layer("out", OutputLayer(n_out=2, activation="softmax"), "m")
        .set_outputs("out")
        .set_input_types(InputType.feed_forward(8))
        .build()
    )
    order = conf.topological_order()
    assert order.index("in") < order.index("a")
    assert order.index("a") < order.index("m")
    assert order.index("b") < order.index("m")
    assert order.index("m") < order.index("out")
    # shape inference wired n_in through the merge
    assert conf.vertices["out"].layer.n_in == 8


def test_unknown_input_rejected():
    with pytest.raises(ValueError, match="unknown input"):
        _gb().add_inputs("in").add_layer(
            "a", DenseLayer(n_out=4), "nonexistent"
        )


def test_cycle_impossible_by_construction():
    # vertices may only reference already-added names, so cycles can't be
    # expressed through the builder — the config-level check still guards
    # hand-built configs
    conf = ComputationGraphConfiguration(
        inputs=["in"],
        outputs=["a"],
        vertices={"a": None, "b": None},
        vertex_inputs={"a": ["b"], "b": ["a"]},
    )
    with pytest.raises(ValueError, match="unreachable or cyclic"):
        conf.topological_order()


def test_serde_round_trip():
    conf = (
        _gb()
        .add_inputs("in")
        .add_layer("a", DenseLayer(n_out=4, activation="tanh"), "in")
        .add_vertex("s", ScaleVertex(scale=0.5), "a")
        .add_layer("out", OutputLayer(n_out=2, activation="softmax"), "s")
        .set_outputs("out")
        .set_input_types(InputType.feed_forward(6))
        .build()
    )
    conf2 = ComputationGraphConfiguration.from_json(conf.to_json())
    assert conf2.vertex_inputs == conf.vertex_inputs
    assert conf2.vertices["s"].scale == 0.5
    assert conf2.vertices["out"].layer.n_in == 4
    # the rebuilt conf drives an identical network
    net1 = ComputationGraph(conf).init()
    net2 = ComputationGraph(conf2).init()
    x, _ = _xy(4, 6, 2)
    np.testing.assert_allclose(
        np.asarray(net1.output(x)), np.asarray(net2.output(x)), rtol=1e-6
    )


# -- vertex semantics --------------------------------------------------------

def test_vertex_forwards():
    x = jnp.asarray(np.arange(12, dtype=np.float32).reshape(2, 6))
    y = jnp.asarray(np.ones((2, 6), np.float32))
    env = {}
    assert MergeVertex().forward([x, y], env).shape == (2, 12)
    np.testing.assert_allclose(
        ElementWiseVertex(op="add").forward([x, y], env), np.asarray(x) + 1
    )
    np.testing.assert_allclose(
        ElementWiseVertex(op="subtract").forward([x, y], env), np.asarray(x) - 1
    )
    np.testing.assert_allclose(
        ElementWiseVertex(op="product").forward([x, y], env), np.asarray(x)
    )
    np.testing.assert_allclose(
        ElementWiseVertex(op="average").forward([x, y], env),
        (np.asarray(x) + 1) / 2,
    )
    np.testing.assert_allclose(
        ElementWiseVertex(op="max").forward([x, y], env),
        np.maximum(np.asarray(x), 1),
    )
    np.testing.assert_allclose(
        SubsetVertex(from_=1, to=3).forward([x], env), np.asarray(x)[:, 1:4]
    )
    st = StackVertex().forward([x, y], env)
    assert st.shape == (4, 6)
    np.testing.assert_allclose(
        UnstackVertex(from_=1, stack_size=2).forward([st], env), np.asarray(y)
    )
    np.testing.assert_allclose(
        ScaleVertex(scale=2.0).forward([x], env), 2 * np.asarray(x)
    )
    np.testing.assert_allclose(
        ShiftVertex(shift=1.5).forward([x], env), np.asarray(x) + 1.5
    )
    assert ReshapeVertex(new_shape=(2, 3)).forward([x], env).shape == (2, 2, 3)
    d = L2Vertex().forward([x, y], env)
    assert d.shape == (2, 1)
    expected = np.sqrt(np.sum((np.asarray(x) - 1) ** 2, axis=1) + 1e-8)
    np.testing.assert_allclose(np.asarray(d)[:, 0], expected, rtol=1e-5)
    nz = L2NormalizeVertex().forward([x], env)
    norms = np.linalg.norm(np.asarray(nz), axis=1)
    np.testing.assert_allclose(norms, 1.0, rtol=1e-4)


def test_rnn_vertices():
    xt = jnp.asarray(np.random.default_rng(0).standard_normal((2, 5, 3)).astype(np.float32))
    xf = jnp.asarray(np.ones((2, 3), np.float32))
    env = {"activations": {"seq": xt}, "input_masks": {}}
    last = LastTimeStepVertex().forward([xt], env)
    np.testing.assert_allclose(last, np.asarray(xt)[:, -1])
    # masked: example 0 has 3 valid steps, example 1 has 5
    mask = jnp.asarray(np.array([[1, 1, 1, 0, 0], [1, 1, 1, 1, 1]], np.float32))
    env_m = {"activations": {"seq": xt}, "input_masks": {"in": mask}}
    last_m = LastTimeStepVertex(mask_input="in").forward([xt], env_m)
    np.testing.assert_allclose(last_m[0], np.asarray(xt)[0, 2])
    np.testing.assert_allclose(last_m[1], np.asarray(xt)[1, 4])
    dup = DuplicateToTimeSeriesVertex(ref_input="seq").forward([xf], env)
    assert dup.shape == (2, 5, 3)
    np.testing.assert_allclose(dup[:, 2], np.asarray(xf))


# -- training ----------------------------------------------------------------

def test_fanout_gradient_accumulation():
    """A vertex consumed by two branches must receive the SUM of both
    branch gradients (reference: ComputationGraph.java:1480-1502 epsilon
    accumulation) — checked against finite differences."""
    conf = (
        _gb()
        .add_inputs("in")
        .add_layer("shared", DenseLayer(n_out=5, activation="tanh"), "in")
        .add_layer("b1", DenseLayer(n_out=5, activation="sigmoid"), "shared")
        .add_layer("b2", DenseLayer(n_out=5, activation="tanh"), "shared")
        .add_vertex("add", ElementWiseVertex(op="add"), "b1", "b2")
        .add_layer("out", OutputLayer(n_out=3, activation="softmax"), "add")
        .set_outputs("out")
        .set_input_types(InputType.feed_forward(4))
        .build()
    )
    net = ComputationGraph(conf).init()
    x, y = _xy(8, 4, 3)
    assert check_gradients_graph(net, [x], [y], max_checks=60)


def test_multi_input_multi_output_training():
    conf = (
        _gb(updater="adam", lr=0.01)
        .add_inputs("inA", "inB")
        .add_layer("dA", DenseLayer(n_out=8, activation="relu"), "inA")
        .add_layer("dB", DenseLayer(n_out=8, activation="relu"), "inB")
        .add_vertex("m", MergeVertex(), "dA", "dB")
        .add_layer("trunk", DenseLayer(n_out=8, activation="tanh"), "m")
        .add_layer("out1", OutputLayer(n_out=3, activation="softmax"), "trunk")
        .add_layer("out2", OutputLayer(n_out=2, activation="softmax"), "trunk")
        .set_outputs("out1", "out2")
        .set_input_types(InputType.feed_forward(6), InputType.feed_forward(4))
        .build()
    )
    net = ComputationGraph(conf).init()
    rng = np.random.default_rng(3)
    xa = rng.standard_normal((32, 6)).astype(np.float32)
    xb = rng.standard_normal((32, 4)).astype(np.float32)
    y1 = np.zeros((32, 3), np.float32)
    y1[np.arange(32), rng.integers(0, 3, 32)] = 1.0
    y2 = np.zeros((32, 2), np.float32)
    y2[np.arange(32), rng.integers(0, 2, 32)] = 1.0
    mds = MultiDataSet([xa, xb], [y1, y2])
    s0 = net.score(mds)
    net.fit(mds, epochs=40, batch_size=32, async_prefetch=False)
    s1 = net.score(mds)
    assert s1 < s0 * 0.5
    o1, o2 = net.output(xa, xb)
    assert o1.shape == (32, 3) and o2.shape == (32, 2)


def test_seq2vec_with_rnn_vertices():
    """LSTM encoder -> LastTimeStep -> classifier, with masking — the
    reference's rnn-vertex pattern (LastTimeStepVertex.java)."""
    conf = (
        _gb(updater="adam", lr=0.02)
        .add_inputs("seq")
        .add_layer("lstm", LSTM(n_out=8, activation="tanh"), "seq")
        .add_vertex("last", LastTimeStepVertex(mask_input="seq"), "lstm")
        .add_layer("out", OutputLayer(n_out=2, activation="softmax"), "last")
        .set_outputs("out")
        .set_input_types(InputType.recurrent(4))
        .build()
    )
    net = ComputationGraph(conf).init()
    rng = np.random.default_rng(5)
    x = rng.standard_normal((16, 6, 4)).astype(np.float32)
    y = np.zeros((16, 2), np.float32)
    y[np.arange(16), rng.integers(0, 2, 16)] = 1.0
    mask = np.ones((16, 6), np.float32)
    mask[:8, 4:] = 0.0
    mds = MultiDataSet([x], [y], [mask], None)
    s0 = net.score(mds)
    net.fit(mds, epochs=30, batch_size=16, async_prefetch=False)
    assert net.score(mds) < s0


def test_gradcheck_merge_subset_scale():
    conf = (
        _gb()
        .add_inputs("in")
        .add_layer("a", DenseLayer(n_out=4, activation="tanh"), "in")
        .add_layer("b", DenseLayer(n_out=6, activation="sigmoid"), "in")
        .add_vertex("m", MergeVertex(), "a", "b")
        .add_vertex("sub", SubsetVertex(from_=2, to=7), "m")
        .add_vertex("sc", ScaleVertex(scale=1.5), "sub")
        .add_layer("out", OutputLayer(n_out=3, activation="softmax"), "sc")
        .set_outputs("out")
        .set_input_types(InputType.feed_forward(5))
        .build()
    )
    net = ComputationGraph(conf).init()
    x, y = _xy(6, 5, 3, seed=2)
    assert check_gradients_graph(net, [x], [y], max_checks=60)


def test_l2_vertices_gradcheck():
    conf = (
        _gb()
        .add_inputs("a", "b")
        .add_layer("ea", DenseLayer(n_out=6, activation="tanh"), "a")
        .add_layer("eb", DenseLayer(n_out=6, activation="tanh"), "b")
        .add_vertex("na", L2NormalizeVertex(), "ea")
        .add_vertex("nb", L2NormalizeVertex(), "eb")
        .add_vertex("dist", L2Vertex(), "na", "nb")
        .add_layer("out", OutputLayer(n_out=2, activation="softmax"), "dist")
        .set_outputs("out")
        .set_input_types(InputType.feed_forward(4), InputType.feed_forward(4))
        .build()
    )
    net = ComputationGraph(conf).init()
    rng = np.random.default_rng(4)
    xa = rng.standard_normal((6, 4)).astype(np.float32)
    xb = rng.standard_normal((6, 4)).astype(np.float32)
    y = np.zeros((6, 2), np.float32)
    y[np.arange(6), rng.integers(0, 2, 6)] = 1.0
    assert check_gradients_graph(net, [xa, xb], [y], max_checks=50)


def test_evaluate_single_output():
    conf = (
        _gb(updater="adam", lr=0.05)
        .add_inputs("in")
        .add_layer("d", DenseLayer(n_out=16, activation="relu"), "in")
        .add_layer("out", OutputLayer(n_out=3, activation="softmax"), "d")
        .set_outputs("out")
        .set_input_types(InputType.feed_forward(8))
        .build()
    )
    net = ComputationGraph(conf).init()
    x, y = _xy(64, 8, 3)
    net.fit(x, y, epochs=60, batch_size=32, async_prefetch=False)
    ev = net.evaluate(x, y)
    assert ev.accuracy() > 0.8


def test_auto_merge_on_multi_input_layer():
    """add_layer with >1 input auto-inserts a MergeVertex (reference:
    ComputationGraphConfiguration.java:580-584) — ADVICE r2 medium."""
    conf = (
        _gb()
        .add_inputs("in")
        .add_layer("a", DenseLayer(n_out=5, activation="tanh"), "in")
        .add_layer("b", DenseLayer(n_out=7, activation="tanh"), "in")
        .add_layer("out", OutputLayer(n_out=3, activation="softmax"), "a", "b")
        .set_outputs("out")
        .set_input_types(InputType.feed_forward(4))
        .build()
    )
    assert "out-merge" in conf.vertices
    assert isinstance(conf.vertices["out-merge"], MergeVertex)
    assert conf.vertex_inputs["out"] == ["out-merge"]
    # the output layer sees the concatenated width (5 + 7 = 12)
    assert conf.vertices["out"].layer.n_in == 12
    net = ComputationGraph(conf).init()
    x, y = _xy(8, 4, 3)
    out = net.output(x)
    assert out.shape == (8, 3)
    net.fit(x, y, epochs=2, batch_size=8, async_prefetch=False)


def test_output_with_input_masks():
    """output(input_masks=...) threads masks to LastTimeStepVertex so
    inference matches training on variable-length sequences (ADVICE r2)."""
    conf = (
        _gb()
        .add_inputs("in")
        .add_layer("lstm", LSTM(n_out=6, activation="tanh"), "in")
        .add_vertex("last", LastTimeStepVertex(mask_input="in"), "lstm")
        .add_layer("out", OutputLayer(n_out=2, activation="softmax"), "last")
        .set_outputs("out")
        .set_input_types(InputType.recurrent(3))
        .build()
    )
    net = ComputationGraph(conf).init()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((4, 5, 3)).astype(np.float32)
    mask = np.ones((4, 5), np.float32)
    mask[0, 3:] = 0.0  # example 0 has length 3
    out_masked = np.asarray(net.output(x, input_masks=[mask]))
    out_plain = np.asarray(net.output(x))
    # example 0 must use step 2's state, not the padded last step
    x_trunc = x.copy()
    x_trunc[0, 3:] = 123.0  # garbage past the mask must not matter
    out_masked2 = np.asarray(net.output(x_trunc, input_masks=[mask]))
    np.testing.assert_allclose(out_masked[0], out_masked2[0], atol=2e-4)
    assert not np.allclose(out_masked[0], out_plain[0])


def test_clone_carries_updater_and_counters():
    conf = (
        _gb(updater="adam", lr=0.05)
        .add_inputs("in")
        .add_layer("d", DenseLayer(n_out=8, activation="relu"), "in")
        .add_layer("out", OutputLayer(n_out=3, activation="softmax"), "d")
        .set_outputs("out")
        .set_input_types(InputType.feed_forward(8))
        .build()
    )
    net = ComputationGraph(conf).init()
    x, y = _xy(16, 8, 3)
    net.fit(x, y, epochs=3, batch_size=16, async_prefetch=False)
    other = net.clone()
    assert other.iteration == net.iteration
    assert other.epoch == net.epoch
    a = np.concatenate([np.ravel(l) for l in
                        __import__("jax").tree_util.tree_leaves(net.upd_state)])
    b = np.concatenate([np.ravel(l) for l in
                        __import__("jax").tree_util.tree_leaves(other.upd_state)])
    np.testing.assert_array_equal(a, b)
    # continued training must be bit-identical between original and clone
    net.fit(x, y, epochs=1, batch_size=16, async_prefetch=False)
    other.fit(x, y, epochs=1, batch_size=16, async_prefetch=False)
    np.testing.assert_allclose(
        np.asarray(net.params()), np.asarray(other.params()), atol=0
    )


# -- round-3 parity: TBPTT / rnnTimeStep / CenterLoss / transfer -------------


def _chain_rnn_mln_and_cg(seed=21, tbptt=True, fwd=4, bwd=None):
    """The same LSTM chain as an MLN and as a CG (identical seeds =>
    identical init, since both fold_in layer index 0,1)."""
    from deeplearning4j_tpu.nn.conf import BackpropType
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    def base():
        return (
            NeuralNetConfiguration.builder()
            .seed(seed)
            .updater("sgd")
            .learning_rate(0.1)
            .weight_init("xavier")
        )

    lb = (
        base().list()
        .layer(LSTM(n_out=6, activation="tanh"))
        .layer(RnnOutputLayer(n_out=2, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.recurrent(3))
    )
    gb = (
        base().graph_builder()
        .add_inputs("in")
        .add_layer("lstm", LSTM(n_out=6, activation="tanh"), "in")
        .add_layer("out", RnnOutputLayer(n_out=2, activation="softmax",
                                         loss="mcxent"), "lstm")
        .set_outputs("out")
        .set_input_types(InputType.recurrent(3))
    )
    if tbptt:
        lb = lb.backprop_type(BackpropType.TRUNCATED_BPTT).t_bptt_lengths(fwd, bwd)
        gb = gb.backprop_type("tbptt").t_bptt_lengths(fwd, bwd)
    return MultiLayerNetwork(lb.build()).init(), ComputationGraph(gb.build()).init()


def _rnn_xy(n=8, t=12, nin=3, nout=2, seed=5):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, t, nin)).astype(np.float32)
    y = np.zeros((n, t, nout), np.float32)
    idx = rng.integers(0, nout, (n, t))
    for i in range(n):
        y[i, np.arange(t), idx[i]] = 1.0
    return x, y


def test_cg_tbptt_matches_mln():
    """CG TBPTT segment loop == MLN TBPTT on the same chain (reference:
    ComputationGraph.doTruncatedBPTT mirrors the MLN path)."""
    mln, cg = _chain_rnn_mln_and_cg(tbptt=True, fwd=4)
    np.testing.assert_allclose(np.asarray(mln.params()),
                               np.asarray(cg.params()), atol=0)
    x, y = _rnn_xy()
    mln.fit(x, y, epochs=2, batch_size=8, async_prefetch=False)
    cg.fit(x, y, epochs=2, batch_size=8, async_prefetch=False)
    assert mln.iteration == cg.iteration  # same number of segment steps
    np.testing.assert_allclose(np.asarray(mln.params()),
                               np.asarray(cg.params()), rtol=2e-5, atol=2e-6)


def test_cg_tbptt_bwd_truncation_matches_mln():
    mln, cg = _chain_rnn_mln_and_cg(tbptt=True, fwd=6, bwd=3)
    x, y = _rnn_xy(t=12)
    mln.fit(x, y, epochs=1, batch_size=8, async_prefetch=False)
    cg.fit(x, y, epochs=1, batch_size=8, async_prefetch=False)
    np.testing.assert_allclose(np.asarray(mln.params()),
                               np.asarray(cg.params()), rtol=2e-5, atol=2e-6)


def test_cg_rnn_time_step_streaming_equivalence():
    """Streaming chunks through rnn_time_step == one full-sequence output
    (reference: ComputationGraph.rnnTimeStep)."""
    _, cg = _chain_rnn_mln_and_cg(tbptt=False)
    x, _ = _rnn_xy(n=4, t=10)
    full = np.asarray(cg.output(x))
    cg.rnn_clear_previous_state()
    c1 = np.asarray(cg.rnn_time_step(x[:, :4]))
    c2 = np.asarray(cg.rnn_time_step(x[:, 4:7]))
    c3 = np.asarray(cg.rnn_time_step(x[:, 7:]))
    streamed = np.concatenate([c1, c2, c3], axis=1)
    np.testing.assert_allclose(streamed, full, rtol=2e-5, atol=2e-6)
    # single-step [b, nin] form
    cg.rnn_clear_previous_state()
    s = np.asarray(cg.rnn_time_step(x[:, 0]))
    np.testing.assert_allclose(s, full[:, 0], rtol=2e-5, atol=2e-6)


def test_cg_center_loss_head():
    """CenterLossOutputLayer as a CG head: trains, centers move (reference:
    CenterLossOutputLayer.java wired through the graph path)."""
    from deeplearning4j_tpu.nn.conf import CenterLossOutputLayer

    conf = (
        _gb(updater="adam", lr=0.05)
        .add_inputs("in")
        .add_layer("d", DenseLayer(n_out=8, activation="relu"), "in")
        .add_layer("out", CenterLossOutputLayer(
            n_out=3, activation="softmax", loss="mcxent",
            lambda_=0.1, alpha=0.3), "d")
        .set_outputs("out")
        .set_input_types(InputType.feed_forward(6))
        .build()
    )
    net = ComputationGraph(conf).init()
    x, y = _xy(48, 6, 3)
    pidx = net._pidx["out"]
    centers0 = np.asarray(net.state_list[pidx]["centers"])
    net.fit(x, y, epochs=40, batch_size=48, async_prefetch=False)
    centers1 = np.asarray(net.state_list[pidx]["centers"])
    assert not np.allclose(centers0, centers1)  # EMA updates happened
    assert net.evaluate(x, y).accuracy() > 0.8
    # the center term shapes the features: same run with lambda_=0 must
    # leave larger within-class scatter (relative to feature scale) than
    # the center-pulled run
    def within_scatter(trained):
        feats = np.asarray(trained.feed_forward(x)["d"])
        labels = y.argmax(1)
        scale = np.linalg.norm(feats - feats.mean(0), axis=1).mean() + 1e-12
        return np.mean([
            np.linalg.norm(
                feats[labels == k] - feats[labels == k].mean(0), axis=1
            ).mean()
            for k in range(3)
        ]) / scale

    conf0 = (
        _gb(updater="adam", lr=0.05)
        .add_inputs("in")
        .add_layer("d", DenseLayer(n_out=8, activation="relu"), "in")
        .add_layer("out", CenterLossOutputLayer(
            n_out=3, activation="softmax", loss="mcxent",
            lambda_=0.0, alpha=0.3), "d")
        .set_outputs("out")
        .set_input_types(InputType.feed_forward(6))
        .build()
    )
    net0 = ComputationGraph(conf0).init()
    net0.fit(x, y, epochs=40, batch_size=48, async_prefetch=False)
    assert within_scatter(net) < within_scatter(net0)


def test_cg_transfer_learning():
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.transferlearning import TransferLearning

    conf = (
        _gb(updater="sgd", lr=0.1)
        .add_inputs("in")
        .add_layer("f1", DenseLayer(n_out=10, activation="relu"), "in")
        .add_layer("f2", DenseLayer(n_out=8, activation="relu"), "f1")
        .add_layer("out", OutputLayer(n_out=3, activation="softmax"), "f2")
        .set_outputs("out")
        .set_input_types(InputType.feed_forward(6))
        .build()
    )
    src = ComputationGraph(conf).init()
    x, y = _xy(32, 6, 3)
    src.fit(x, y, epochs=3, batch_size=32, async_prefetch=False)

    # freeze the feature front, swap the head for a 4-class one
    new = (
        TransferLearning.GraphBuilder(src)
        .set_feature_extractor("f2")
        .remove_vertex_and_connections("out")
        .add_layer("newout", L.OutputLayer(n_in=8, n_out=4,
                                           activation="softmax"), "f2")
        .set_outputs("newout")
        .build()
    )
    # surviving params are shared/copied
    np.testing.assert_array_equal(
        np.asarray(new.params_list[new._pidx["f1"]]["W"]),
        np.asarray(src.params_list[src._pidx["f1"]]["W"]),
    )
    # frozen front must not move during fit
    w_before = np.asarray(new.params_list[new._pidx["f1"]]["W"]).copy()
    y4 = np.zeros((32, 4), np.float32)
    y4[np.arange(32), np.random.default_rng(1).integers(0, 4, 32)] = 1.0
    new.fit(x, y4, epochs=3, batch_size=32, async_prefetch=False)
    np.testing.assert_array_equal(
        np.asarray(new.params_list[new._pidx["f1"]]["W"]), w_before
    )
    assert new.output(x).shape == (32, 4)


# -- scan-over-identical-blocks (PR 16) ---------------------------------------
# Runs of identically-configured residual blocks compile as ONE scanned
# body over stacked params instead of N unrolled copies. The contract:
# outputs and training trajectories are BIT-identical to the unrolled
# walk (jax.lax.scan over stacked slots traces the same per-unit body;
# fold_in on a traced row index equals the concrete fold_in), and
# compile_total{kind="graph_block"} drops from one count per block to
# one per run.


def _scan_resnet(block_scan):
    from deeplearning4j_tpu.models.resnet import resnet_conf

    conf = resnet_conf(blocks=(3, 3), widths=(2, 4), num_classes=3,
                       image_size=8, stem_width=4)
    net = ComputationGraph(conf).init()
    net.set_block_scan(block_scan)
    return net


def _scan_xy(n=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 8, 8, 3)).astype(np.float32)
    y = np.zeros((n, 3), np.float32)
    y[np.arange(n), rng.integers(0, 3, n)] = 1.0
    return x, y


def test_block_scan_detects_identity_runs():
    """blocks=(3,3) has two runs of 2 identity blocks each (the stage
    entry block projects, so it can't join); blocks=(1,1) has none."""
    from deeplearning4j_tpu.models.resnet import resnet_conf, tiny_resnet_conf

    net = _scan_resnet(True)
    runs = net._block_runs()
    assert len(runs) == 2
    assert all(r["count"] == 2 for r in runs)
    tiny = ComputationGraph(tiny_resnet_conf()).init()
    assert tiny._block_runs() == []


def test_block_scan_output_and_training_bit_identical():
    """Scanned forward == unrolled forward bit for bit, eager and jitted.
    Training is compared with a stated tolerance: the scanned backward
    sums the same per-unit gradient terms in another f32 order than the
    unrolled one (two differently shaped XLA:CPU programs). After ONE
    step the params agree to a last bit (measured 3.5e-7 abs under jax
    0.9; 8 f32 eps = 9.5e-7 allowed). This net moves its params by more
    than 1.0 per step (batch-8 BatchNorm over 2-4 channels), so that bit
    grows about tenfold per step (measured 1.0e-5, 1.1e-4 after steps 2
    and 3); after 3 steps atol 1e-3 is allowed — a thousandth of one
    step's movement, where a wrong block order, a dropped unit or state
    not threaded between steps is off by the movement itself."""
    x, y = _scan_xy()
    a, b = _scan_resnet("unroll"), _scan_resnet(True)
    np.testing.assert_array_equal(np.asarray(a.output(x)),
                                  np.asarray(b.output(x)))

    def params(net):
        return [(k, np.asarray(p[k])) for p in net.params_list for k in p]

    for net in (a, b):
        net.fit(x, y, epochs=1, batch_size=8, async_prefetch=False)
    for (k, p1), (_, p2) in zip(params(a), params(b)):
        np.testing.assert_allclose(p1, p2, rtol=0, err_msg=k,
                                   atol=8 * np.finfo(np.float32).eps)
    for net in (a, b):
        net.fit(x, y, epochs=2, batch_size=8, async_prefetch=False)
    for (k, p1), (_, p2) in zip(params(a), params(b)):
        np.testing.assert_allclose(p1, p2, rtol=0, atol=1e-3, err_msg=k)


def test_block_scan_collapses_graph_block_compile_counter():
    """compile_total{kind="graph_block"} counts traced block bodies:
    4 for the unrolled walk (2 runs x 2 blocks), 2 when scanned (one
    per run) — the collapse the bench artifact records."""
    from deeplearning4j_tpu.utils.metrics import get_registry

    gb = get_registry().counter(
        "compile_total", "jit cache insertions (fresh traces)",
        ("kind",)).labels("graph_block")
    x, y = _scan_xy()

    c0 = gb.value
    _scan_resnet("unroll").fit(x, y, epochs=1, batch_size=8,
                               async_prefetch=False)
    unrolled = gb.value - c0
    c0 = gb.value
    _scan_resnet(True).fit(x, y, epochs=1, batch_size=8,
                           async_prefetch=False)
    scanned = gb.value - c0
    assert (unrolled, scanned) == (4, 2)

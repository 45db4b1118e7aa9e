"""Multi-batch fused fit equivalence (NetworkBase.set_fused_steps): K
minibatches per jitted dispatch must produce the SAME trajectory —
params, updater state, iteration count — as the per-batch loop, for
MultiLayerNetwork (standard + cross-batch TBPTT programs) and
ComputationGraph. Ragged tails and mid-stream shape changes must fall
back to per-batch fits, not crash or skip data.

This is the dispatch-latency amortizer playing the reference's
AsyncDataSetIterator throughput role (MultiLayerNetwork.java:1023-1025)
at the XLA level."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.data import DataSet
from deeplearning4j_tpu.data.iterators import ListDataSetIterator
from deeplearning4j_tpu.nn.compgraph import ComputationGraph
from deeplearning4j_tpu.nn.conf import (
    DenseLayer,
    InputType,
    LSTM,
    NeuralNetConfiguration,
    OutputLayer,
    RnnOutputLayer,
)
from deeplearning4j_tpu.nn.conf.network import BackpropType
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork


def _max_tree_diff(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return max(
        (float(jnp.max(jnp.abs(jnp.asarray(x, jnp.float32)
                               - jnp.asarray(y, jnp.float32))))
         for x, y in zip(la, lb)),
        default=0.0,
    )


def _mlp_conf(dropout=0.0):
    return (
        NeuralNetConfiguration.builder()
        .seed(11)
        .updater("adam")
        .learning_rate(0.01)
        .list()
        .layer(DenseLayer(n_out=16, activation="relu", dropout=dropout))
        .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.feed_forward(8))
        .build()
    )


def _cls_data(n=96, nin=8, k=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, nin)).astype(np.float32)
    y = np.zeros((n, k), np.float32)
    y[np.arange(n), rng.integers(0, k, n)] = 1.0
    return x, y


def _pair(conf_fn, fused_k):
    a = MultiLayerNetwork(conf_fn()).init()
    b = MultiLayerNetwork(conf_fn()).init().set_fused_steps(fused_k)
    return a, b


def test_fused_std_matches_loop_exact_chunks():
    x, y = _cls_data(96)  # batch 24 -> 4 batches: one K=4 chunk per epoch
    loop, fused = _pair(_mlp_conf, 4)
    for net in (loop, fused):
        net.fit(x, y, epochs=3, batch_size=24, async_prefetch=False)
    assert fused.iteration == loop.iteration == 12
    assert _max_tree_diff(loop.params_list, fused.params_list) < 1e-6
    assert _max_tree_diff(loop.upd_state, fused.upd_state) < 1e-6
    assert abs(float(loop._score) - float(fused._score)) < 1e-6


def test_fused_std_ragged_tail_falls_back():
    # 96 examples / batch 20 -> 4 full batches (one fused K=4 chunk) + 1
    # ragged batch of 16 whose signature break sends it down the per-step
    # path: same trajectory as the loop, nothing dropped.
    x, y = _cls_data(96)
    loop, fused = _pair(_mlp_conf, 4)
    for net in (loop, fused):
        net.fit(x, y, epochs=2, batch_size=20, async_prefetch=False)
    assert fused.iteration == loop.iteration == 10
    assert _max_tree_diff(loop.params_list, fused.params_list) < 1e-6


def test_fused_std_dropout_rng_matches():
    x, y = _cls_data(96)
    loop, fused = _pair(lambda: _mlp_conf(dropout=0.5), 4)
    for net in (loop, fused):
        net.fit(x, y, epochs=2, batch_size=24, async_prefetch=False)
    assert _max_tree_diff(loop.params_list, fused.params_list) < 1e-6


def test_fused_chunk_smaller_than_k_falls_back():
    x, y = _cls_data(48)  # 2 batches of 24 < K=8 -> per-step path
    loop, fused = _pair(_mlp_conf, 8)
    for net in (loop, fused):
        net.fit(x, y, epochs=2, batch_size=24, async_prefetch=False)
    assert fused.iteration == loop.iteration == 4
    assert _max_tree_diff(loop.params_list, fused.params_list) < 1e-6


def _rnn_conf():
    return (
        NeuralNetConfiguration.builder()
        .seed(5)
        .updater("adam")
        .learning_rate(0.02)
        .list()
        .layer(LSTM(n_out=8, activation="tanh"))
        .layer(RnnOutputLayer(n_out=2, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.recurrent(3))
        .backprop_type(BackpropType.TRUNCATED_BPTT)
        .t_bptt_lengths(4)
        .build()
    )


@pytest.mark.parametrize("engine", ["multilayer", "graph"])
def test_fused_tbptt_cross_batch_matches_loop(engine):
    rng = np.random.default_rng(2)
    n, t = 64, 12  # batch 16 -> 4 fit batches x 3 segments each
    x = rng.normal(size=(n, t, 3)).astype(np.float32)
    cs = np.cumsum(x[..., 0], axis=1)
    y = np.zeros((n, t, 2), np.float32)
    y[..., 0] = (cs <= 0).astype(np.float32)
    y[..., 1] = (cs > 0).astype(np.float32)

    # the graph reaches the cross-batch program since nn/trainstep
    make = ((lambda: MultiLayerNetwork(_rnn_conf())) if engine == "multilayer"
            else (lambda: ComputationGraph(_rnn_graph_conf())))
    loop = make().init()
    fused = make().init().set_fused_steps(2)
    for net in (loop, fused):
        net.fit(x, y, epochs=2, batch_size=16, async_prefetch=False)
    assert ("tbptt_batched", (2, 3, 4, 4)) in fused._step_programs
    # 2 epochs x 4 batches x 3 segments
    assert fused.iteration == loop.iteration == 24
    assert _max_tree_diff(loop.params_list, fused.params_list) < 1e-6
    assert _max_tree_diff(loop.upd_state, fused.upd_state) < 1e-6
    assert abs(float(loop._score) - float(fused._score)) < 1e-6


def _graph_conf():
    return (
        NeuralNetConfiguration.builder()
        .seed(3)
        .updater("adam")
        .learning_rate(0.01)
        .graph_builder()
        .add_inputs("in")
        .add_layer("d", DenseLayer(n_out=16, activation="relu"), "in")
        .add_layer("out",
                   OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"), "d")
        .set_outputs("out")
        .set_input_types(InputType.feed_forward(8))
        .build()
    )


def test_fused_graph_matches_loop():
    x, y = _cls_data(96)
    loop = ComputationGraph(_graph_conf()).init()
    fused = ComputationGraph(_graph_conf()).init().set_fused_steps(4)
    for net in (loop, fused):
        net.fit(x, y, epochs=3, batch_size=24, async_prefetch=False)
    assert fused.iteration == loop.iteration == 12
    assert _max_tree_diff(loop.params_list, fused.params_list) < 1e-6
    assert _max_tree_diff(loop.upd_state, fused.upd_state) < 1e-6


def _rnn_graph_conf(fwd=4, bwd=4):
    return (
        NeuralNetConfiguration.builder().seed(5)
        .updater("adam").learning_rate(0.02)
        .graph_builder().add_inputs("seq")
        .add_layer("lstm", LSTM(n_out=8, activation="tanh"), "seq")
        .add_layer("out", RnnOutputLayer(n_out=2, activation="softmax",
                                         loss="mcxent"), "lstm")
        .set_outputs("out")
        .set_input_types(InputType.recurrent(3))
        .backprop_type("tbptt")
        .t_bptt_lengths(fwd, bwd)
        .build()
    )


def _seq_xy(n=32, t=12, seed=2):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, t, 3)).astype(np.float32)
    cs = np.cumsum(x[..., 0], axis=1)
    y = np.zeros((n, t, 2), np.float32)
    y[..., 0] = (cs <= 0).astype(np.float32)
    y[..., 1] = (cs > 0).astype(np.float32)
    return x, y


class _NoOp:
    def iteration_done(self, model, iteration, info):
        pass

    def on_epoch_start(self, model, epoch):
        pass

    def on_epoch_end(self, model, epoch):
        pass


@pytest.mark.parametrize("fwd,bwd", [(4, 4), (6, 3)])
def test_graph_tbptt_fused_matches_loop(fwd, bwd):
    """CG fused-TBPTT (all segments one dispatch) == per-segment loop,
    incl. the bwd<fwd truncated builder — the ComputationGraph twin of
    tests/test_tbptt_fused.py (a listener forces the loop path)."""
    x, y = _seq_xy(t=12)
    loop = ComputationGraph(_rnn_graph_conf(fwd, bwd)).init()
    loop.add_listener(_NoOp())
    fused = ComputationGraph(_rnn_graph_conf(fwd, bwd)).init()
    for net in (loop, fused):
        net.fit(x, y, epochs=2, batch_size=16, async_prefetch=False)
    assert fused.iteration == loop.iteration
    assert _max_tree_diff(loop.params_list, fused.params_list) < 1e-6
    assert _max_tree_diff(loop.upd_state, fused.upd_state) < 1e-6
    assert abs(float(loop._score) - float(fused._score)) < 1e-6


def test_graph_tbptt_ragged_tail_falls_back():
    x, y = _seq_xy(t=10)  # 10 % 4 != 0 -> loop path on both
    loop = ComputationGraph(_rnn_graph_conf(4, 4)).init()
    loop.add_listener(_NoOp())
    fused = ComputationGraph(_rnn_graph_conf(4, 4)).init()
    for net in (loop, fused):
        net.fit(x, y, epochs=1, batch_size=16, async_prefetch=False)
    assert fused.iteration == loop.iteration == 2 * 3
    assert _max_tree_diff(loop.params_list, fused.params_list) < 1e-6


def test_fused_listeners_disable_fusion():
    from deeplearning4j_tpu.train.listeners import CollectScoresIterationListener

    x, y = _cls_data(96)
    net = MultiLayerNetwork(_mlp_conf()).init().set_fused_steps(4)
    collector = CollectScoresIterationListener()
    net.add_listener(collector)
    net.fit(x, y, epochs=1, batch_size=24, async_prefetch=False)
    # listeners force the per-step path: one callback per iteration
    assert len(collector.scores) == 4

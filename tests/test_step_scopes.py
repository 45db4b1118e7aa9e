"""The names the step program gives the device's work: every layer's
forward under `jax.named_scope("L<key>_<kind>")` (so its backward carries
`transpose(jvp(L<key>_<kind>))` by JAX's own rule), `loss`, `update`, and a
`name` on every Pallas kernel. Read from the lowered program's text: the
names are metadata, and a device trace shows what the lowering holds."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.compgraph import ComputationGraph
from deeplearning4j_tpu.nn.conf import (
    BatchNormalization,
    ConvolutionLayer,
    DenseLayer,
    InputType,
    NeuralNetConfiguration,
    OutputLayer,
    SubsamplingLayer,
)
from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork, layer_scope
from deeplearning4j_tpu.ops import pallas_conv_bn as pcb
from deeplearning4j_tpu.ops import pallas_lstm


def _builder():
    return (NeuralNetConfiguration.builder().seed(3).updater("sgd")
            .learning_rate(0.05).weight_init("xavier"))


def _tiny_multilayer():
    conf = (_builder().list()
            .layer(ConvolutionLayer(n_in=1, n_out=2, kernel_size=(3, 3),
                                    activation="relu"))
            .layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
            .layer(DenseLayer(n_out=6, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.convolutional(8, 8, 1))
            .build())
    return MultiLayerNetwork(conf).init(), [
        "L0_convolution", "L1_subsampling", "L2_dense", "L3_output"]


def _tiny_graph():
    conf = (_builder().graph_builder()
            .add_inputs("in")
            .add_layer("a", DenseLayer(n_out=4, activation="tanh"), "in")
            .add_layer("a_bn", BatchNormalization(), "a")
            .add_layer("b", DenseLayer(n_out=4, activation="tanh"), "in")
            .add_vertex("sum", ElementWiseVertex(op="add"), "a_bn", "b")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                          loss="mcxent"), "sum")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(8))
            .build())
    return ComputationGraph(conf).init(), [
        "La_dense", "La_bn_batchnorm", "Lb_dense", "Lsum_elementwise",
        "Lout_output"]


def _lowered_step_text(net, x, y):
    """The text of the program `fit()` runs for one step, as lowered."""
    step = net._build_train_step()
    seen = {}

    def spy(*args):
        seen["text"] = step.lower(*args).as_text(debug_info=True)
        return step(*args)

    net._train_step_fn = spy
    net.fit(x, y, epochs=1, batch_size=x.shape[0], async_prefetch=False)
    return seen["text"]


def _op_names(text):
    return set(re.findall(r'loc\("([^"]*)"', text))


@pytest.mark.parametrize("build", [_tiny_multilayer, _tiny_graph],
                         ids=["multilayer", "graph"])
def test_the_lowered_step_holds_every_layers_scope(build):
    net, scopes = build()
    rng = np.random.default_rng(0)
    shape = (4, 8, 8, 1) if build is _tiny_multilayer else (4, 8)
    x = rng.standard_normal(shape).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 4)]
    text = _lowered_step_text(net, x, y)
    assert "module @jit_step" in text   # PERF.md finds the step by name
    names = _op_names(text)
    for scope in scopes:
        assert any(f"jvp({scope})/" in n for n in names), scope
    # a layer with parameters has a backward pass, named by JAX's rule
    for scope in (s for s in scopes if "dense" in s or "conv" in s):
        assert any(f"transpose(jvp({scope}))/" in n for n in names), scope
    assert any("jvp(loss)/" in n for n in names)
    assert any("/update/" in n for n in names)
    # nothing of a layer's forward or backward is named `update`
    assert not any("update" in n and "jvp(" in n for n in names)


# -- one optimizer-step family for both engines (nn/trainstep) ----------------

STEP_FAMILY = (
    "_make_step_body", "_lr_mult_tree", "_trainable_mask",
    "_std_loss_builder", "_trunc_loss_builder", "_make_step", "_jit_step",
    "_build_train_step", "_build_truncated_bwd_step", "_run_step",
    "_fit_step", "_fit_step_truncated", "_make_seg_data", "_fit_tbptt",
    "_build_tbptt_fused_step", "_fit_tbptt_fused", "_build_multi_fit_step",
    "_fit_datasets_fused", "_build_tbptt_batched_step",
    "_fit_tbptt_batched", "_reset_step_programs",
)


def _tiny_rnn(engine, fwd, bwd):
    from deeplearning4j_tpu.nn.conf import LSTM, RnnOutputLayer

    b = (NeuralNetConfiguration.builder().seed(5).updater("adam")
         .learning_rate(0.02))
    lstm = LSTM(n_out=8, activation="tanh")
    out = RnnOutputLayer(n_out=2, activation="softmax", loss="mcxent")
    if engine is MultiLayerNetwork:
        conf = (b.list().layer(lstm).layer(out)
                .set_input_type(InputType.recurrent(3))
                .backprop_type("tbptt").t_bptt_lengths(fwd, bwd).build())
    else:
        conf = (b.graph_builder().add_inputs("seq")
                .add_layer("lstm", lstm, "seq").add_layer("out", out, "lstm")
                .set_outputs("out").set_input_types(InputType.recurrent(3))
                .backprop_type("tbptt").t_bptt_lengths(fwd, bwd).build())
    return engine(conf).init()


class _NoOp:
    """A listener pins `_fit_tbptt` to the per-segment loop."""

    def iteration_done(self, model, iteration, info):
        pass

    def on_epoch_start(self, model, epoch):
        pass

    def on_epoch_end(self, model, epoch):
        pass


def _max_diff(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return max(float(jnp.max(jnp.abs(x - y))) for x, y in zip(la, lb))


@pytest.mark.parametrize("engine", [MultiLayerNetwork, ComputationGraph],
                         ids=["multilayer", "graph"])
@pytest.mark.parametrize("case", ["one_family", "fused", "truncated",
                                  "fused_steps"])
def test_both_engines_run_the_one_step_family(engine, case):
    """The engines define no method of the family, and on each engine's
    tiny recurrent net every variant of the step ends where the
    per-segment loop does (tolerances: tests/test_tbptt_fused.py,
    tests/test_fused_fit.py)."""
    if case == "one_family":
        from deeplearning4j_tpu.nn.trainstep import TrainStep

        for name in STEP_FAMILY:
            assert name not in vars(engine), name
            assert getattr(MultiLayerNetwork, name) \
                is getattr(ComputationGraph, name) \
                is getattr(TrainStep, name), name
        return
    fwd, bwd = (6, 3) if case == "truncated" else (4, 4)
    rng = np.random.default_rng(2)
    x = rng.normal(size=(32, 12, 3)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[(np.cumsum(x[..., 0], 1) > 0) * 1]
    loop = _tiny_rnn(engine, fwd, bwd)
    loop.add_listener(_NoOp())
    other = _tiny_rnn(engine, fwd, bwd)
    if case == "fused_steps":
        other.set_fused_steps(2)
    for net in (loop, other):
        net.fit(x, y, epochs=2, batch_size=16, async_prefetch=False)
    assert other.iteration == loop.iteration == 2 * 2 * (12 // fwd)
    kinds = {kind for kind, _ in other._step_programs}
    assert kinds == {"fused_steps": {"tbptt_batched"}}.get(
        case, {"tbptt_fused"})
    assert {kind for kind, _ in loop._step_programs} == (
        {"truncated"} if case == "truncated" else set())
    assert _max_diff(loop.params_list, other.params_list) < 1e-6
    assert _max_diff(loop.upd_state, other.upd_state) < 1e-6
    assert abs(float(loop._score) - float(other._score)) < 1e-6


def test_layer_scope_names():
    assert layer_scope(2, ConvolutionLayer(n_out=1)) == "L2_convolution"
    assert layer_scope("stem_bn", BatchNormalization()) \
        == "Lstem_bn_batchnorm"
    assert layer_scope("add", ElementWiseVertex(op="add")) \
        == "Ladd_elementwise"


F32 = jnp.float32


def _mm_stats():
    return pcb._mm_stats_call, (jnp.ones((8, 4), F32), jnp.ones((4, 4), F32))


def _ck_stats():
    return (lambda x, w: pcb._ck_stats_call(x, w, (1, 1))), (
        jnp.ones((1, 4, 4, 2), F32), jnp.ones((3, 3, 2, 2), F32))


def _norm(relu):
    def make():
        row = jnp.ones((1, 4), F32)
        return (lambda x, m, s, b: pcb._norm_call(x, m, s, b, relu)), (
            jnp.ones((8, 4), F32), row, row, row)
    return make


def _bnb_reduce():
    return pcb._bnb_reduce_call, (jnp.ones((8, 4), F32),
                                  jnp.ones((8, 4), F32))


def _bnb_apply():
    row = jnp.ones((1, 4), F32)
    return pcb._bnb_apply_call, (jnp.ones((8, 4), F32),
                                 jnp.ones((8, 4), F32), row, row, row)


def _lstm_args(T=2, B=2, H=4):
    vec = jnp.zeros((H,), F32)
    return (jnp.ones((T, B, 4 * H), F32), jnp.ones((H, 4 * H), F32),
            vec, vec, vec, jnp.zeros((B, H), F32), jnp.zeros((B, H), F32))


def _lstm_fwd():
    return pallas_lstm._fwd_call, _lstm_args()


def _lstm_bwd():
    T, B, H = 2, 2, 4
    _, rw, pI, pF, pO, h0, _ = _lstm_args(T, B, H)
    seq = jnp.ones((T, B, H), F32)
    return pallas_lstm._bwd_call, (jnp.ones((T, B, 4 * H), F32), seq, seq,
                                   rw, pI, pF, pO, seq, h0)


def _lstm_step():
    xg, rw, pI, pF, pO, h0, c0 = _lstm_args()
    return pallas_lstm.lstm_step, (xg[0], rw, pI, pF, pO, h0, c0)


KERNELS = {
    "conv_bn_stats": _mm_stats, "convk_bn_stats": _ck_stats,
    "bn_apply": _norm(False), "bn_apply_relu": _norm(True),
    "bn_bwd_reduce": _bnb_reduce, "bn_bwd_apply": _bnb_apply,
    "lstm_seq": _lstm_fwd, "lstm_seq_bwd": _lstm_bwd,
    "lstm_step": _lstm_step,
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_each_pallas_kernel_carries_its_name(name, monkeypatch):
    """Nine kernel bodies behind the eight `pallas_call`s (the normalize
    call picks one of two), each under its own stable name, forward and
    backward apart. Interpret mode lowers the same name scope."""
    monkeypatch.setattr(pcb, "_INTERPRET", True)
    monkeypatch.setattr(pallas_lstm, "_INTERPRET", True)
    fn, args = KERNELS[name]()
    names = _op_names(jax.jit(fn).lower(*args).as_text(debug_info=True))
    assert any(f"/{name}/" in n or n.endswith(f"/{name}") for n in names), \
        sorted(names)[:20]
    others = [k for k in KERNELS if k != name and not (
        k.startswith(name) or name.startswith(k))]
    assert not any(f"/{k}/" in n for k in others for n in names)

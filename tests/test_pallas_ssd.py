"""The Mamba-2 chunked scan's kernels (`ops/pallas_ssd.py`) in interpret
mode on the CPU: value and the gradients of `x`, `dt`, `A`, `B` and `C`
against `ssm.ssd_chunked_xla`, the XLA body they replace, at tiny shapes;
the probe; and a tiny Nemotron net whose mixers take the kernels through
`fit()`, against the same net on the XLA body. The file is kept small: a
chunk of an interpreted kernel is a slow loop body on the CPU."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import ListDataSetIterator
from deeplearning4j_tpu.models.nemotron_h import tiny_nemotron_h_conf
from deeplearning4j_tpu.nn.compgraph import ComputationGraph
from deeplearning4j_tpu.nn.layers import ssm
from deeplearning4j_tpu.ops import pallas_ssd
from deeplearning4j_tpu.ops.helpers import (
    get_helper,
    helper_books,
    partitioned_program,
)

BF16 = jnp.bfloat16

# (b, t, heads, head_dim, groups, chunk): r P and the state (128) of whole
# lanes, as the probe asks
CASES = {
    # 200 positions: padded to 4 chunks of 64; r = 4 heads of 32 a group
    "ragged_r4_f32": ((2, 200, 8, 32, 2, 64), jnp.float32, False),
    # one chunk; r = 1 head of 128
    "one_chunk_r1_bf16": ((1, 128, 2, 128, 2, 128), BF16, False),
    # three chunks, r = 4, a third of the positions with dt = 0
    "three_chunks_r4_bf16_dt0": ((1, 384, 4, 32, 1, 128), BF16, True),
    "three_chunks_r1_f32_dt0": ((1, 96, 2, 128, 2, 32), jnp.float32, True),
}
# f32: both sides float32, the order of the sums differs; bf16: the
# cotangents are rounded to bf16 at other points of the backward pass
TOL = {jnp.float32: (1e-5, 1e-4), BF16: (1e-5, 2e-2)}


def _scan_inputs(b, t, H, P, G, dtype, zero_dt, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, t, H, P)).astype(dtype)
    # a chunk's decay exp(s_end) of about 0.01-0.9: the state carried
    # across chunks counts
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, H)) - 4.0)
    if zero_dt:
        dt = dt.at[:, t // 3:2 * t // 3].set(0.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=-1.0, maxval=1.5))
    B = (0.3 * jax.random.normal(ks[3], (b, t, G, 128))).astype(dtype)
    C = (0.3 * jax.random.normal(ks[4], (b, t, G, 128))).astype(dtype)
    return (x, dt, A, B, C), jax.random.normal(ks[5], (b, t, H, P))


def _rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm((a - b).ravel())
                 / jnp.maximum(jnp.linalg.norm(b.ravel()), 1e-30))


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernels_against_the_xla_body(case, monkeypatch):
    monkeypatch.setattr(pallas_ssd, "_INTERPRET", True)
    (b, t, H, P, G, Q), dtype, zero_dt = CASES[case]
    args, ct = _scan_inputs(b, t, H, P, G, dtype, zero_dt)

    def run(scan):
        y, pull = jax.vjp(lambda *a: scan(*a, Q), *args)
        return (y,) + pull(ct)

    got = jax.jit(lambda: run(pallas_ssd.ssd_scan))()
    want = jax.jit(lambda: run(ssm.ssd_chunked_xla))()
    y_tol, grad_tol = TOL[dtype]
    assert got[0].shape == (b, t, H, P) and got[0].dtype == jnp.float32
    assert _rel(got[0], want[0]) < y_tol
    for name, g, w in zip(("x", "dt", "A", "B", "C"), got[1:], want[1:]):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert _rel(g, w) < grad_tol, (name, _rel(g, w))


# -- the probe ---------------------------------------------------------------------

NEMOTRON = dict(x_shape=(4, 4096, 64, 64), dtype=BF16, n_groups=8,
                state_size=128, chunk=128)


def _ask(**ctx):
    before = helper_books()
    helper = get_helper("ssd_scan", **ctx)
    return helper, helper_books(before)


@pytest.mark.parametrize("change,takes", [
    ({}, True),
    ({"dtype": jnp.float32}, True),
    ({"state_size": 64}, False),              # half a lane of state
    ({"x_shape": (4, 4096, 64, 8)}, False),   # a group: 8 heads of 8
    ({"x_shape": (4, 4096, 128, 8)}, False),  # 16 heads of 8: half a tile
    ({"chunk": 100}, False),                  # no whole 8-row blocks
    ({"chunk": 2048}, False),                 # past the VMEM a step gets
    ({"dtype": jnp.float16}, False),
], ids=["nemotron", "float32", "state_64", "group_64_lanes", "head_dim_8",
        "chunk_100", "chunk_2048", "float16"])
def test_probe_by_shape_and_dtype(change, takes, monkeypatch):
    monkeypatch.setattr(pallas_ssd, "_INTERPRET", True)
    ctx = {**NEMOTRON, **change}
    helper, moved = _ask(**ctx)
    assert (helper is not None) is takes
    assert moved == ({"hits": {"ssd_scan": 1}, "auto_disable": {},
                      "fallbacks": {}} if takes else
                     {"hits": {}, "auto_disable": {},
                      "fallbacks": {"unsupported": {"ssd_scan": 1}}})


def test_probe_declines_the_cpu_and_a_partitioned_program(monkeypatch):
    assert jax.default_backend() == "cpu" and not pallas_ssd._INTERPRET
    assert _ask(**NEMOTRON)[1]["fallbacks"] == {"unsupported": {"ssd_scan": 1}}
    monkeypatch.setattr(pallas_ssd, "_INTERPRET", True)
    with partitioned_program(4):
        helper, moved = _ask(**NEMOTRON)
    assert helper is None
    assert moved["fallbacks"] == {"partitioned_program": {"ssd_scan": 1}}


# -- a tiny Nemotron net through fit() ------------------------------------------------

SEQ, BATCH = 32, 4


def test_a_tiny_nemotron_fits_on_the_kernels_as_on_the_xla_body(monkeypatch):
    """The tiny preset with mixers the probe takes (2 groups of 2 heads of
    64, state 128, chunks of 8): loss and every gradient leaf within the
    bf16 tolerances of `test_nemotron_h.py` (2e-4, 0.15) of the same net on
    the XLA body, every mixer on the kernels and none on the fallback; then
    a step of `fit()` on the kernels, whose score is that loss."""
    net = ComputationGraph(tiny_nemotron_h_conf(
        precision="bf16", mamba_num_heads=4, mamba_head_dim=64,
        ssm_state_size=128)).init()
    ids = np.random.default_rng(5).integers(0, 128, (BATCH, SEQ + 1),
                                            dtype=np.int32)
    x, y = ids[:, :-1], ids[:, 1:]

    def loss_and_grads(interpret):
        monkeypatch.setattr(pallas_ssd, "_INTERPRET", interpret)
        before = helper_books()
        got = jax.jit(jax.value_and_grad(lambda p: net._loss(
            p, net.state_list, [jnp.asarray(x)], [jnp.asarray(y)], None,
            None, None)[0]))(net.params_list)
        return got, helper_books(before)

    (want, want_grads), fallback = loss_and_grads(False)
    assert fallback["fallbacks"]["unsupported"]["ssd_scan"] == 2
    (loss, grads), books = loss_and_grads(True)
    assert books["hits"]["ssd_scan"] == 2           # two mixers, one trace
    assert "ssd_scan" not in str(books["fallbacks"])
    assert not books["auto_disable"]
    assert abs(float(loss) - float(want)) / float(want) < 2e-4
    for got, ref in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert _rel(got, ref) < 0.15

    before = helper_books()
    net.fit(ListDataSetIterator(DataSet(x, y), BATCH), epochs=1)
    books = helper_books(before)
    assert books["hits"]["ssd_scan"] == 2
    assert "ssd_scan" not in str(books["fallbacks"])
    assert abs(float(net._score) - float(want)) / float(want) < 2e-4

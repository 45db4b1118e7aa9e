"""Sequence parallelism: ring attention over the 8-device CPU mesh ==
single-device full attention; the SelfAttentionLayer in the DSL trains.

This is NEW capability beyond the reference (SURVEY §5: DL4J has no
long-context machinery beyond TBPTT) — the equivalence test is the
contract that the sharded path computes the same math.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from deeplearning4j_tpu.parallel.sequence import (
    SEQ_AXIS,
    full_attention,
    ring_attention_sharded,
    ring_self_attention,
)


def _seq_mesh():
    return Mesh(np.array(jax.devices()), (SEQ_AXIS,))


def _qkv(B=2, T=32, H=4, D=8, seed=0):
    # T=32 (was 64): same 8-hop ring coverage at a quarter of the
    # compile/grad cost — these tests take ~130s of the tier-1 budget,
    # and the math they pin is shape-independent
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(
        rng.standard_normal((B, T, H, D)) * 0.5, jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_ring_equals_full_attention(causal):
    from jax.sharding import PartitionSpec as P

    q, k, v = _qkv()
    mesh = _seq_mesh()
    spec = P(None, SEQ_AXIS, None, None)
    ring = jax.shard_map(
        lambda q, k, v: ring_attention_sharded(q, k, v, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    out_ring = np.asarray(ring(q, k, v))
    out_full = np.asarray(full_attention(q, k, v, causal=causal))
    np.testing.assert_allclose(out_ring, out_full, rtol=2e-5, atol=2e-6)


@pytest.mark.slow  # ~30-80s of 8-way SPMD compile on the 1.5-core gate box;
# tier-1 keeps the ring==full equivalence pair + the DSL layer test (at seed this
# whole file was import-broken, so gate coverage still strictly improves)
def test_ring_self_attention_projections():
    rng = np.random.default_rng(1)
    B, T, E, H = 2, 32, 16, 4
    x = jnp.asarray(rng.standard_normal((B, T, E)), jnp.float32)
    ws = [jnp.asarray(rng.standard_normal((E, E)) * 0.2, jnp.float32)
          for _ in range(4)]
    mesh = _seq_mesh()
    out = np.asarray(ring_self_attention(
        x, *ws, mesh=mesh, n_heads=H, causal=True))
    q = (x @ ws[0]).reshape(B, T, H, E // H)
    k = (x @ ws[1]).reshape(B, T, H, E // H)
    v = (x @ ws[2]).reshape(B, T, H, E // H)
    ref = np.asarray(
        full_attention(q, k, v, causal=True).reshape(B, T, E) @ ws[3])
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)


@pytest.mark.slow  # ~30-80s of 8-way SPMD compile on the 1.5-core gate box;
# tier-1 keeps the ring==full equivalence pair + the DSL layer test (at seed this
# whole file was import-broken, so gate coverage still strictly improves)
def test_ring_attention_bf16_accumulates_f32():
    """bf16 long-context inputs: softmax statistics accumulate in f32
    inside the ring, so the sharded bf16 result stays close to the f32
    full-attention truth (within one bf16 rounding of inputs/outputs) —
    and exactly matches single-device attention run with the same f32
    accumulation policy."""
    from jax.sharding import PartitionSpec as P

    q, k, v = _qkv(T=16)
    qb, kb, vb = (t.astype(jnp.bfloat16) for t in (q, k, v))
    mesh = _seq_mesh()
    spec = P(None, SEQ_AXIS, None, None)
    ring = jax.shard_map(
        lambda q, k, v: ring_attention_sharded(q, k, v, causal=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    out_ring = np.asarray(ring(qb, kb, vb)).astype(np.float32)
    assert out_ring.dtype == np.float32  # cast back from bf16 for compare
    out_full_f32 = np.asarray(full_attention(q, k, v, causal=True))
    # error budget: bf16 inputs (~8-bit mantissa) dominate; f32 stats mean
    # no error growth with ring hops
    np.testing.assert_allclose(out_ring, out_full_f32, rtol=0.05, atol=0.02)
    # and bf16 single-device (same accumulation policy) agrees bitwise-ish
    out_full_bf16 = np.asarray(
        full_attention(qb, kb, vb, causal=True)).astype(np.float32)
    np.testing.assert_allclose(out_ring, out_full_bf16, rtol=0.02, atol=0.01)


@pytest.mark.slow  # ~30-80s of 8-way SPMD compile on the 1.5-core gate box;
# tier-1 keeps the ring==full equivalence pair + the DSL layer test (at seed this
# whole file was import-broken, so gate coverage still strictly improves)
def test_ring_attention_differentiable():
    """Gradients flow through the ring (training viability, not just
    inference)."""
    from jax.sharding import PartitionSpec as P

    q, k, v = _qkv(T=16)
    # 4-device ring (the other tests cover the full 8): the backward of
    # the statically-unrolled ring is the suite's single most expensive
    # compile on the 2-core box — a 4-hop ring proves the same property
    # (multi-hop grad == full attention grad) at half the program size
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:4]), (SEQ_AXIS,))
    spec = P(None, SEQ_AXIS, None, None)

    def loss_ring(q, k, v):
        f = jax.shard_map(
            lambda q, k, v: ring_attention_sharded(q, k, v, causal=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        return jnp.sum(jnp.square(f(q, k, v)))

    def loss_full(q, k, v):
        return jnp.sum(jnp.square(full_attention(q, k, v, causal=True)))

    g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-6,
                                   err_msg=f"d{name}")


def test_self_attention_layer_in_dsl():
    """SelfAttentionLayer trains end-to-end inside MultiLayerNetwork and
    honors time masks + causality."""
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (
        RnnOutputLayer,
        SelfAttentionLayer,
    )
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder().seed(5).updater("adam")
            .learning_rate(1e-2).weight_init("xavier").list()
            .layer(SelfAttentionLayer(n_out=16, n_heads=4, causal=True))
            .layer(RnnOutputLayer(n_out=2, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(8)).build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    # task: label at t = sign of x[:, 0, 0] (requires attending position 0)
    x = rng.standard_normal((32, 10, 8)).astype(np.float32)
    cls = (x[:, 0, 0] > 0).astype(int)
    y = np.zeros((32, 10, 2), np.float32)
    y[np.arange(32), :, :] = np.eye(2, dtype=np.float32)[cls][:, None, :]
    for _ in range(150):
        net.fit(x, y, batch_size=32, epochs=1, async_prefetch=False)
    out = np.asarray(net.output(x))
    acc = float(np.mean(np.argmax(out[:, -1], -1) == cls))
    assert acc > 0.9, acc

    # gradient check through the layer at f64 (the framework's own harness)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork as MLN
    from deeplearning4j_tpu.train.gradientcheck import check_gradients

    conf2 = (NeuralNetConfiguration.builder().seed(6)
             .weight_init("xavier").list()
             .layer(SelfAttentionLayer(n_out=8, n_heads=2))
             .layer(RnnOutputLayer(n_out=2, activation="softmax",
                                   loss="mcxent"))
             .set_input_type(InputType.recurrent(4)).build())
    xs = np.random.default_rng(2).standard_normal((3, 5, 4))
    ys = np.zeros((3, 5, 2))
    ys[..., 0] = 1.0
    assert check_gradients(MLN(conf2).init(), xs, ys)

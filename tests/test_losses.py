"""Loss function tests (reference: LossFunctions / ILossFunction impls,
exercised by LossFunctionGradientCheck.java)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops.losses import LossFunction, loss_value

ALL = [
    "mse", "l1", "l2", "xent", "mcxent", "squared_loss",
    "negativeloglikelihood", "kl_divergence", "cosine_proximity", "hinge",
    "squared_hinge", "poisson", "mean_absolute_error",
    "mean_absolute_percentage_error", "mean_squared_logarithmic_error",
    "reconstruction_crossentropy", "rmse_xent",
]


def _probs(key, shape):
    x = jax.random.uniform(key, shape, minval=0.05, maxval=1.0)
    return x / jnp.sum(x, axis=-1, keepdims=True)


@pytest.mark.parametrize("name", ALL)
def test_shape_and_finite(name):
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    preout = jax.random.normal(k1, (4, 3))
    if name in ("hinge", "squared_hinge"):
        labels = jnp.sign(jax.random.normal(k2, (4, 3)))
        act = "identity"
    elif name in ("xent", "kl_divergence", "reconstruction_crossentropy"):
        labels = _probs(k2, (4, 3))
        act = "sigmoid"
    elif name in ("mcxent", "negativeloglikelihood"):
        labels = jax.nn.one_hot(jnp.array([0, 1, 2, 1]), 3)
        act = "softmax"
    elif name == "poisson":
        labels = jnp.abs(jax.random.normal(k2, (4, 3)))
        act = "softplus"
    elif name == "mean_absolute_percentage_error":
        labels = 1.0 + jnp.abs(jax.random.normal(k2, (4, 3)))
        act = "identity"
    elif name == "mean_squared_logarithmic_error":
        labels = jnp.abs(jax.random.normal(k2, (4, 3)))
        act = "softplus"
    else:
        labels = jax.random.normal(k2, (4, 3))
        act = "identity"
    v = loss_value(name, labels, preout, act)
    assert v.shape == (4,)
    assert bool(jnp.all(jnp.isfinite(v)))
    # loss must be differentiable end-to-end
    g = jax.grad(lambda p: jnp.mean(loss_value(name, labels, p, act)))(preout)
    assert g.shape == preout.shape
    assert bool(jnp.all(jnp.isfinite(g)))


def test_mse_known_value():
    labels = jnp.array([[1.0, 2.0]])
    preout = jnp.array([[0.0, 0.0]])
    v = loss_value("mse", labels, preout, "identity")
    np.testing.assert_allclose(v, [(1.0 + 4.0) / 2.0])
    # l2 = SSE without the 1/n
    v2 = loss_value("l2", labels, preout, "identity")
    np.testing.assert_allclose(v2, [5.0])


def test_mcxent_matches_manual_softmax_ce():
    key = jax.random.PRNGKey(3)
    logits = jax.random.normal(key, (6, 5))
    labels = jax.nn.one_hot(jnp.arange(6) % 5, 5)
    v = loss_value("mcxent", labels, logits, "softmax")
    manual = -jnp.sum(labels * jnp.log(jax.nn.softmax(logits, -1)), axis=-1)
    np.testing.assert_allclose(v, manual, rtol=1e-4, atol=1e-5)


def test_mcxent_stable_at_extreme_logits():
    logits = jnp.array([[1000.0, -1000.0, 0.0]])
    labels = jnp.array([[0.0, 1.0, 0.0]])
    v = loss_value("mcxent", labels, logits, "softmax")
    assert bool(jnp.isfinite(v[0]))
    assert float(v[0]) > 100  # huge but finite loss


def test_xent_stable_from_logits():
    logits = jnp.array([[800.0, -800.0]])
    labels = jnp.array([[0.0, 1.0]])
    v = loss_value("xent", labels, logits, "sigmoid")
    assert bool(jnp.isfinite(v[0]))


def test_masking_zeroes_out_elements():
    labels = jnp.ones((2, 4))
    preout = jnp.zeros((2, 4))
    mask = jnp.array([[1.0, 1.0, 0.0, 0.0], [1.0, 1.0, 1.0, 1.0]])
    v = loss_value("l2", labels, preout, "identity", mask)
    np.testing.assert_allclose(v, [2.0, 4.0])


def test_cosine_proximity():
    a = jnp.array([[1.0, 0.0]])
    v = loss_value("cosine_proximity", a, a, "identity")
    np.testing.assert_allclose(v, [-1.0], atol=1e-6)


def test_time_series_loss_reduces_over_time():
    # [batch, time, features] per-example score sums over time+features
    labels = jnp.ones((2, 3, 4))
    preout = jnp.zeros((2, 3, 4))
    v = loss_value("l2", labels, preout, "identity")
    np.testing.assert_allclose(v, [12.0, 12.0])


def test_enum_names_resolve():
    for name in vars(LossFunction):
        if not name.startswith("_"):
            # the one loss on integer labels takes the class of each row
            labels = jnp.ones((2,), jnp.int32) if name == "SPARSE_MCXENT" \
                else jnp.ones((2, 2)) * 0.5
            loss_value(
                getattr(LossFunction, name),
                labels,
                jnp.zeros((2, 2)),
                "sigmoid",
            )

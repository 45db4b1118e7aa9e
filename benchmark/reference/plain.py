"""The blocks every plain reference is written in: jax.numpy and lax in
float32, no kernels, nothing of the program.

`precision` names how the matrix products (convolutions and dense layers)
are computed; everything else is float32 in every mode:

* "f32"  — float32 operands at `lax.Precision.HIGHEST`. The reference.
* "bf16" — operands rounded to bfloat16, float32 accumulation: what the
  configurations state. A second witness, never the reference.
* "fp8"  — operands rounded to float8_e4m3 with one scale per tensor
  (amax / 448): the nearest precision below bf16, the control that
  `correct` has to fail.

The rounded modes round whatever a program of that compute type holds in
it (`store`): the operands of every convolution, its result, the bias and
their sum, every activation a layer hands on, and on the way back the
cotangent of each of those, the weights' among them. The products themselves
are exact (float32 at HIGHEST of values that the narrow type holds), and
statistics, parameters, the dense layers' results, the updater and the loss
stay float32, as in the configurations.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

PRECISIONS = ("f32", "bf16", "fp8")
_DIMS = ("NHWC", "HWIO", "NHWC")
_FP8_MAX = 448.0


def seed_key(seed):
    """A PRNG key from any whole-number seed (the driver's pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _straight_through(a, rounded):
    return a + lax.stop_gradient(rounded - a)


def _to_fp8(a):
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / _FP8_MAX
    return _straight_through(
        a, (a / scale).astype(jnp.float8_e4m3fn).astype(a.dtype) * scale)


def _to_bf16(a):
    return _straight_through(a, a.astype(jnp.bfloat16).astype(a.dtype))


def _round(a, precision):
    return _to_bf16(a) if precision == "bf16" else _to_fp8(a)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _store(a, precision):
    return _round(a, precision)


def _store_fwd(a, precision):
    return _round(a, precision), None


def _store_bwd(precision, _, g):
    return (_round(g, precision),)


_store.defvjp(_store_fwd, _store_bwd)


def store(a, precision):
    """An activation as a layer of that precision stores it: rounded on the
    way forward, and its cotangent rounded on the way back."""
    if precision == "f32":
        return a
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}; one of "
                         f"{PRECISIONS}")
    return _store(a, precision)


def conv2d(x, w, stride, padding, precision, b=None):
    """NHWC x HWIO -> NHWC float32; `padding` is "SAME" or "VALID". The
    product, and the sum with the bias `b`, are each stored once."""
    z = store(lax.conv_general_dilated(
        store(x, precision), store(w, precision), (stride, stride), padding,
        dimension_numbers=_DIMS, precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32), precision)
    return z if b is None else store(z + store(b, precision), precision)


def dense(x, w, b, precision):
    """x @ w + b. The programs multiply a narrow activation by the float32
    weights and keep the float32 result, so only the input is rounded."""
    return jnp.matmul(store(x, precision), w,
                      precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32) + b


def batch_norm_train(x, gamma, beta, eps):
    """Batch statistics over every axis but the last, biased variance."""
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(x, axis=axes)
    var = jnp.mean(jnp.square(x - mean), axis=axes)
    return (x - mean) * lax.rsqrt(var + eps) * gamma + beta


def max_pool(x, window, stride, padding):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, window, window, 1),
                             (1, stride, stride, 1), padding)


def softmax_cross_entropy(logits, onehot):
    """Mean over the rows of -sum(y * log softmax(z))."""
    return jnp.mean(-jnp.sum(onehot * jax.nn.log_softmax(logits), axis=-1))


# -- updaters: (params, state, grads) -> (params, state) ----------------------

def sgd(hyper):
    lr = hyper["learning_rate"]

    def init(params):
        return None

    def apply(params, state, grads):
        return jax.tree_util.tree_map(lambda p, g: p - lr * g,
                                      params, grads), None

    return init, apply


def nesterovs(hyper):
    """nd4j's formulation: v' = mu v - lr g; p' = p - mu v + (1 + mu) v'."""
    lr, mu = hyper["learning_rate"], hyper["momentum"]

    def init(params):
        return jax.tree_util.tree_map(jnp.zeros_like, params)

    def apply(params, state, grads):
        v_new = jax.tree_util.tree_map(lambda v, g: mu * v - lr * g,
                                       state, grads)
        new = jax.tree_util.tree_map(
            lambda p, v, vn: p - mu * v + (1.0 + mu) * vn,
            params, state, v_new)
        return new, v_new

    return init, apply


UPDATERS = {"sgd": sgd, "nesterovs": nesterovs}


# -- the first steps ----------------------------------------------------------

def leaf_norms(tree):
    """{"layer/param": l2 norm} of a {layer: {param: array}} tree."""
    return {f"{layer}/{name}": jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32))))
            for layer, leaves in tree.items() for name, a in leaves.items()}


def first_steps(loss_fn, params, batches, updater_hyper):
    """Drive `loss_fn(params, x, y)` through one optimizer step for each
    of `batches` and return what `correct` compares: every step's loss,
    the per-leaf norm of the first gradient, and the per-leaf norm of the
    parameters' change over all the steps."""
    init, apply = UPDATERS[updater_hyper["name"]](updater_hyper)

    @jax.jit
    def step(p, s, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(p, x, y)
        new_p, new_s = apply(p, s, grads)
        return loss, leaf_norms(grads), new_p, new_s

    @jax.jit
    def change(a, b):
        return leaf_norms(jax.tree_util.tree_map(jnp.subtract, a, b))

    p, s = params, init(params)
    losses, grad_norms = [], None
    for x, y in batches:
        loss, gn, p, s = step(p, s, jnp.asarray(x), jnp.asarray(y))
        losses.append(loss)
        if grad_norms is None:
            grad_norms = gn
    out = jax.device_get({"losses": losses, "grad_norms": grad_norms,
                          "delta_norms": change(p, params)})
    return {"losses": [float(v) for v in out["losses"]],
            "grad_norms": {k: float(v) for k, v in out["grad_norms"].items()},
            "delta_norms": {k: float(v)
                            for k, v in out["delta_norms"].items()}}

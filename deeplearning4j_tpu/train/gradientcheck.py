"""Numeric gradient checking.

Analog of the reference's GradientCheckUtil
(gradientcheck/GradientCheckUtil.java, 515 LoC): central-difference
numerical gradients vs the analytic ones, per parameter, in f64. The
reference enforces global double precision and a whitelist of smooth
activations (:48-91); here f64 runs on the CPU backend via the enable_x64
context (TPUs don't do f64 — the check is a host-side correctness tool,
exactly like the reference runs it on the CPU backend).

Where the reference compares hand-written backprop against finite
differences, here the analytic side is jax.grad — so this harness validates
layer forward implementations + loss wiring (a wrong forward still yields a
consistent-but-wrong gradient pair only if the forward itself is what we
meant; any non-differentiable kink or masking bug shows up as a mismatch).
"""

from __future__ import annotations

import logging
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger("deeplearning4j_tpu")


def enable_x64():
    """The f64 context manager — the check is useless without it (f64 is
    the whole point, see module docstring)."""
    return jax.enable_x64()


def numeric_gradient(f: Callable, flat: np.ndarray, epsilon: float = 1e-6,
                     indices=None, chunk: int = 128) -> np.ndarray:
    """Central differences: (f(x+eps e_i) - f(x-eps e_i)) / (2 eps).

    Vectorized: perturbation rows are evaluated through jit(vmap(f)) in
    chunks — the whole sweep is a handful of compiled batched evaluations
    instead of 2N eager forward passes."""
    flat = np.asarray(flat, dtype=np.float64)
    idx = np.fromiter(
        (range(flat.size) if indices is None else indices), dtype=np.int64
    )
    fv = jax.jit(jax.vmap(f))
    out = np.zeros(flat.size, dtype=np.float64)
    for start in range(0, idx.size, chunk):
        sel = idx[start : start + chunk]
        base = np.broadcast_to(flat, (sel.size, flat.size)).copy()
        plus = base.copy()
        plus[np.arange(sel.size), sel] += epsilon
        minus = base
        minus[np.arange(sel.size), sel] -= epsilon
        fp = np.asarray(fv(jnp.asarray(plus)))
        fm = np.asarray(fv(jnp.asarray(minus)))
        out[sel] = (fp - fm) / (2.0 * epsilon)
    return out


def check_gradients_fn(
    loss_of_flat: Callable,
    flat_params: np.ndarray,
    epsilon: float = 1e-6,
    max_rel_error: float = 1e-5,
    min_abs_error: float = 1e-8,
    max_checks: Optional[int] = None,
    seed: int = 0,
    verbose: bool = False,
) -> bool:
    """Check d(loss)/d(flat) analytic vs numeric. `loss_of_flat` must be a
    pure function of a flat f64 vector. Mirrors the reference's pass
    criterion: relative error (|a-n| / (|a|+|n|)) <= max_rel_error, with an
    absolute-error floor for near-zero gradients
    (GradientCheckUtil.java:161-180)."""
    with enable_x64():
        flat64 = jnp.asarray(np.asarray(flat_params, dtype=np.float64))
        analytic = np.asarray(jax.grad(lambda p: loss_of_flat(p))(flat64))

        n = flat64.size
        if max_checks is not None and max_checks < n:
            rng = np.random.default_rng(seed)
            indices = rng.choice(n, size=max_checks, replace=False)
        else:
            indices = range(n)

        numeric = numeric_gradient(loss_of_flat, np.asarray(flat64), epsilon, indices)

        fails = 0
        for i in indices:
            a, m = analytic[i], numeric[i]
            denom = abs(a) + abs(m)
            rel = abs(a - m) / denom if denom > 0 else 0.0
            if rel > max_rel_error and abs(a - m) > min_abs_error:
                fails += 1
                if verbose:
                    logger.info("param %d: analytic=%.8g numeric=%.8g "
                                "rel=%.3g", i, a, m, rel)
        if verbose:
            logger.info("gradient check: %d/%d ok",
                        len(list(indices)) - fails, len(list(indices)))
        return fails == 0


def check_gradients(net, x, y, features_mask=None, labels_mask=None,
                    epsilon: float = 1e-6, max_rel_error: float = 1e-5,
                    min_abs_error: float = 1e-8, max_checks: Optional[int] = None,
                    verbose: bool = False) -> bool:
    """Gradient-check a MultiLayerNetwork's full loss (data term + l1/l2)
    against its flattened parameter vector (reference:
    GradientCheckUtil.checkGradients(MultiLayerNetwork, ...))."""
    from deeplearning4j_tpu.common.dtypes import PrecisionPolicy
    from deeplearning4j_tpu.nn.params import flat_to_params

    net._require_init()
    # the network's normal policy would downcast to its compute dtype; the
    # check must run end-to-end f64 (reference: GradientCheckUtil enforces
    # global double precision, :77-91)
    saved_policy = net.policy
    net.policy = PrecisionPolicy(
        param_dtype=jnp.float64, compute_dtype=jnp.float64, output_dtype=jnp.float64
    )
    try:
        return _check_gradients_x64(net, x, y, features_mask, labels_mask,
                                    epsilon, max_rel_error, min_abs_error,
                                    max_checks, verbose)
    finally:
        net.policy = saved_policy


def check_gradients_graph(net, xs, ys, features_masks=None, labels_masks=None,
                          epsilon: float = 1e-6, max_rel_error: float = 1e-5,
                          min_abs_error: float = 1e-8,
                          max_checks: Optional[int] = None,
                          verbose: bool = False) -> bool:
    """Gradient-check a ComputationGraph (reference:
    GradientCheckUtil.checkGradients(ComputationGraph, ...) and the
    GradientCheckTestsComputationGraph suite). xs/ys are lists aligned with
    the graph's inputs/outputs."""
    from deeplearning4j_tpu.common.dtypes import PrecisionPolicy
    from deeplearning4j_tpu.nn.params import flat_to_params, params_to_flat

    net._require_init()
    saved_policy = net.policy
    net.policy = PrecisionPolicy(
        param_dtype=jnp.float64, compute_dtype=jnp.float64, output_dtype=jnp.float64
    )
    try:
        with enable_x64():
            confs = net._ordered_layer_confs()
            params64 = jax.tree_util.tree_map(
                lambda a: jnp.asarray(np.asarray(a, dtype=np.float64)),
                net.params_list,
            )
            states64 = [
                None if s is None else {k: jnp.asarray(np.asarray(v, np.float64))
                                        for k, v in s.items()}
                for s in net.state_list
            ]
            xs64 = [jnp.asarray(np.asarray(x, np.float64)) for x in xs]
            ys64 = [jnp.asarray(np.asarray(y, np.float64)) for y in ys]
            as64 = lambda ms: None if ms is None else [
                None if m is None else jnp.asarray(np.asarray(m, np.float64))
                for m in ms
            ]
            fms, lms = as64(features_masks), as64(labels_masks)

            def loss_of_flat(flat):
                plist = flat_to_params(confs, params64, flat)
                s, _ = net._loss(plist, states64, xs64, ys64, fms, lms,
                                 rng=None, training=True)
                return s

            flat0 = params_to_flat(confs, params64)
            return check_gradients_fn(
                loss_of_flat, np.asarray(flat0), epsilon=epsilon,
                max_rel_error=max_rel_error, min_abs_error=min_abs_error,
                max_checks=max_checks, verbose=verbose,
            )
    finally:
        net.policy = saved_policy


def _check_gradients_x64(net, x, y, features_mask, labels_mask, epsilon,
                         max_rel_error, min_abs_error, max_checks, verbose):
    from deeplearning4j_tpu.nn.params import flat_to_params

    with enable_x64():
        params64 = jax.tree_util.tree_map(
            lambda a: jnp.asarray(np.asarray(a, dtype=np.float64)), net.params_list
        )
        states64 = [
            None if s is None else {k: jnp.asarray(np.asarray(v, np.float64))
                                    for k, v in s.items()}
            for s in net.state_list
        ]
        x64 = jnp.asarray(np.asarray(x, np.float64))
        y64 = jnp.asarray(np.asarray(y, np.float64))
        fm = None if features_mask is None else jnp.asarray(np.asarray(features_mask, np.float64))
        lm = None if labels_mask is None else jnp.asarray(np.asarray(labels_mask, np.float64))

        def loss_of_flat(flat):
            plist = flat_to_params(net.layer_confs, params64, flat)
            # training=True exercises the train-path math but with no rng =>
            # deterministic (dropout inactive), matching the reference's
            # gradient-check preconditions (no dropout, smooth activations)
            s, _ = net._loss(plist, states64, x64, y64, fm, lm, rng=None,
                             training=True)
            return s

        from deeplearning4j_tpu.nn.params import params_to_flat

        flat0 = params_to_flat(net.layer_confs, params64)
        return check_gradients_fn(
            loss_of_flat, np.asarray(flat0), epsilon=epsilon,
            max_rel_error=max_rel_error, min_abs_error=min_abs_error,
            max_checks=max_checks, verbose=verbose,
        )

"""MultiLayerNetwork — the sequential network.

Analog of the reference's nn/multilayer/MultiLayerNetwork.java (2,853 LoC).
The capability map (SURVEY.md §3.1) translates TPU-first:

- reference: per-minibatch Solver.optimize -> feedForward (per-layer JNI
  ops) -> backprop (hand-written) -> updater -> step.
- here: ONE jitted train step = forward + loss + autodiff backward +
  gradient normalization + updater + parameter update, compiled by XLA into
  a single TPU program with donated buffers. Host code only feeds batches
  and reads back the score when a listener asks.

Parameters are a list of per-layer dicts (pytree); the flattened view
(reference: flattenedParams, MultiLayerNetwork.java:102-104) is provided by
nn/params.py for serialization/averaging APIs. Mutable non-trainable state
(batchnorm running stats; LSTM h/c during TBPTT and rnnTimeStep streaming)
is a parallel list, threaded functionally through the step.

TBPTT (reference: :1074-1076, truncatedBPTTGradient :1333) segments the
time axis host-side and carries RNN state between segment steps.
"""

from __future__ import annotations

import logging
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.common.dtypes import policy_from_name
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import (
    AsyncDataSetIterator,
    DataSetIterator,
    ListDataSetIterator,
)
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.network import BackpropType, MultiLayerConfiguration
from deeplearning4j_tpu.nn.layers.core import rnn_output_preout
from deeplearning4j_tpu.nn.layers.registry import (
    LayerContext,
    forward_layer,
    init_layer_params,
    init_layer_state,
)
from deeplearning4j_tpu.nn.netbase import NetworkBase
from deeplearning4j_tpu.ops.losses import example_presence, masked_example_mean, loss_value
from deeplearning4j_tpu.train.evaluation import Evaluation, RegressionEvaluation
from deeplearning4j_tpu.train.updaters import (
    normalize_gradients,
    schedule_lr,
    updater_from_conf,
)

logger = logging.getLogger("deeplearning4j_tpu")

_OUTPUT_LAYER_TYPES = (L.OutputLayer, L.RnnOutputLayer, L.LossLayer,
                       L.CenterLossOutputLayer)


def _is_recurrent(conf) -> bool:
    inner = conf.inner if isinstance(conf, L.FrozenLayer) else conf
    return isinstance(inner, (L.LSTM, L.GravesLSTM))


def _is_frozen(conf) -> bool:
    return isinstance(conf, L.FrozenLayer)


def _regularizable(name: str) -> bool:
    """Weight-style params get l1/l2; biases and batchnorm affine params do
    not (reference: each ParamInitializer flags regularizable params;
    BatchNormalizationParamInitializer marks gamma/beta non-regularizable)."""
    if name in ("gamma", "beta"):
        return False
    base = name.rsplit("_", 1)[-1]
    return base in ("W", "RW", "pI", "pF", "pO")


def _l1_l2_penalty(confs, params):
    """L1/L2 penalties (reference: BaseLayer.calcL1/calcL2 added to score;
    gradients come from differentiating this same expression)."""
    reg = 0.0
    for conf, p in zip(confs, params):
        inner = conf.inner if isinstance(conf, L.FrozenLayer) else conf
        l1 = getattr(inner, "l1", 0.0) or 0.0
        l2 = getattr(inner, "l2", 0.0) or 0.0
        if l1 == 0.0 and l2 == 0.0:
            continue
        for name, w in p.items():
            if _regularizable(name):
                if l1:
                    reg = reg + l1 * jnp.sum(jnp.abs(w))
                if l2:
                    reg = reg + 0.5 * l2 * jnp.sum(w * w)
    return reg


def layer_scope(key, conf) -> str:
    """The `jax.named_scope` of one layer or vertex in the step program:
    `L<key>_<kind>`, key as in `params_list` (position in a sequential
    net, vertex name in a graph), kind the conf class in lower case
    without its `Layer`/`Vertex` suffix (`L2_convolution`,
    `Lstem_bn_batchnorm`). Device-trace events carry it in their op name
    (backward ops as `transpose(jvp(L2_convolution))`), so a trace
    reduction finds a layer's device time after any refactor."""
    kind = type(conf).__name__.lower().replace("normalization", "norm")
    for suffix in ("layer", "vertex"):
        if kind.endswith(suffix) and kind != suffix:
            kind = kind[:-len(suffix)]
    return f"L{key}_{kind}"


def _preout_of_output_layer(conf, params, x):
    """Pre-activation of the final (output) layer — the quantity losses
    consume (reference: BaseOutputLayer.preOutput2d)."""
    if isinstance(conf, L.LossLayer):
        return x
    if isinstance(conf, L.RnnOutputLayer):
        return rnn_output_preout(params, x)
    return x @ params["W"] + params["b"]


class MultiLayerNetwork(NetworkBase):
    """Sequential network. API mirrors the reference: init, fit, output,
    score, evaluate, params/set_params, rnn_time_step."""

    def __init__(self, conf: MultiLayerConfiguration):
        super().__init__()
        self.conf = conf
        self.layer_confs: List[L.LayerConf] = list(conf.layers)
        if self.layer_confs and getattr(
                self.layer_confs[-1], "head_rows_block", None):
            raise ValueError(
                "RnnOutputLayer.head_rows_block is honoured by "
                "ComputationGraph only: MultiLayerNetwork takes the head "
                "and its loss over the whole batch at once")
        self.net_conf = conf.net_conf
        self.policy = policy_from_name(self.net_conf.precision)
        self.updater_def = updater_from_conf(self.net_conf)
        self._rnn_states = None  # streaming inference state (rnn_time_step)
        self._train_step_fn = None
        self._output_fn = None

    def _ordered_layer_confs(self):
        return self.layer_confs

    # -- init ----------------------------------------------------------------

    def init(self) -> "MultiLayerNetwork":
        key = jax.random.PRNGKey(self.net_conf.seed)
        dtype = self.policy.param_dtype
        self.params_list = []
        self.state_list = []
        for i, conf in enumerate(self.layer_confs):
            self.params_list.append(
                init_layer_params(jax.random.fold_in(key, i), conf, dtype)
            )
            self.state_list.append(init_layer_state(conf, dtype))
        self.upd_state = self.updater_def.init_tree(self.params_list)
        return self

    # -- forward -------------------------------------------------------------

    def _forward(self, params, states, x, *, training, rng, f_mask=None,
                 stateful=False, preout_last=False, to_layer=None):
        """Pure forward. Returns (out, new_states). Used under jit."""
        confs = self.layer_confs
        pps = self.conf.preprocessors
        new_states: List[Optional[dict]] = [None] * len(confs)
        timesteps = x.shape[1] if x.ndim == 3 else None
        n = len(confs) if to_layer is None else to_layer
        for i in range(n):
            conf = confs[i]
            with jax.named_scope(layer_scope(i, conf)):
                pp = pps.get(str(i))
                if pp is not None:
                    x = pp(x, {"timesteps": timesteps})
                if hasattr(x, "ndim") and x.ndim == 3:
                    timesteps = x.shape[1]
                st = states[i]
                if stateful and _is_recurrent(conf) and st is None:
                    st = {}  # empty: zero-state seed + state return
                ctx = LayerContext(
                    training=training,
                    rng=(jax.random.fold_in(rng, i)
                         if rng is not None else None),
                    mask=(f_mask if hasattr(x, "ndim") and x.ndim == 3
                          else None),
                    timesteps=timesteps,
                    state=st,
                    compute_dtype=self.policy.compute_dtype,
                )
                is_last = i == len(confs) - 1
                if (preout_last and is_last
                        and isinstance(conf, _OUTPUT_LAYER_TYPES)):
                    # input dropout applies to the output layer too
                    # (reference: BaseOutputLayer preOutput applies
                    # Dropout to its input)
                    from deeplearning4j_tpu.nn.layers.core import (
                        apply_dropout,
                    )

                    x = apply_dropout(x, conf.dropout, ctx)
                    x = _preout_of_output_layer(conf, params[i], x)
                    ns = None
                else:
                    x, ns = forward_layer(conf, params[i], x, ctx)
            new_states[i] = ns
        return x, new_states

    def _merge_states(self, old, new):
        return [n if n is not None else o for o, n in zip(old, new)]

    # -- loss ----------------------------------------------------------------

    def _loss(self, params, states, x, y, f_mask, l_mask, rng, training=True):
        last = self.layer_confs[-1]
        if not isinstance(last, _OUTPUT_LAYER_TYPES):
            raise ValueError(
                "the final layer must be an OutputLayer/RnnOutputLayer/"
                "LossLayer to compute a training loss"
            )
        x = self.policy.cast_input(x)
        if isinstance(last, L.CenterLossOutputLayer):
            score, new_states = self._center_loss(
                params, states, x, y, f_mask, l_mask, rng, training
            )
        else:
            preout, new_states = self._forward(
                params, states, x, training=training, rng=rng, f_mask=f_mask,
                preout_last=True,
            )
            with jax.named_scope("loss"):
                preout = self.policy.cast_output(preout)
                per_ex = loss_value(last.loss, y, preout, last.activation,
                                    l_mask)
                score = masked_example_mean(per_ex, l_mask)
        with jax.named_scope("loss"):
            return score + _l1_l2_penalty(self.layer_confs, params), \
                new_states

    def _center_loss(self, params, states, x, y, f_mask, l_mask, rng, training):
        """Center loss (reference: nn/layers/training/CenterLossOutputLayer
        .java): base loss + lambda/2 * ||f - c_y||^2 on the output layer's
        input features, with the per-class centers EMA-updated toward the
        batch class means (alpha) as non-trainable state."""
        from deeplearning4j_tpu.nn.layers.core import apply_dropout

        last: L.CenterLossOutputLayer = self.layer_confs[-1]
        n = len(self.layer_confs)
        feats, new_states = self._forward(
            params, states, x, training=training, rng=rng, f_mask=f_mask,
            to_layer=n - 1,
        )
        ctx_last = LayerContext(
            training=training,
            rng=jax.random.fold_in(rng, n - 1) if rng is not None else None,
        )
        with jax.named_scope(layer_scope(n - 1, last)):
            feats = apply_dropout(feats, last.dropout, ctx_last)
            preout = _preout_of_output_layer(last, params[-1], feats)
        with jax.named_scope("loss"):
            preout = self.policy.cast_output(preout)
            per_ex = loss_value(last.loss, y, preout, last.activation, l_mask)

            centers = states[-1]["centers"].astype(feats.dtype)  # [classes, nIn]
            y32 = y.astype(feats.dtype)
            per_example_center = y32 @ centers  # one-hot pick
            diff = feats - per_example_center
            center_per_ex = 0.5 * jnp.sum(diff * diff, axis=-1)
            present = example_presence(per_ex, l_mask)
            score = (masked_example_mean(per_ex, l_mask)
                     + last.lambda_ * jnp.sum(center_per_ex * present)
                     / jnp.maximum(jnp.sum(present), 1.0))

            if training:
                # EMA update: c_k <- (1-alpha) c_k + alpha * mean(f_i : y_i = k),
                # only for classes present in the batch; gradients do not flow
                # into the centers (they are state, not params)
                f_sg = jax.lax.stop_gradient(feats)
                yw = y32 * present[:, None]  # pad rows excluded from the EMA
                counts = jnp.sum(yw, axis=0)[:, None]  # [classes, 1]
                sums = yw.T @ f_sg  # [classes, nIn]
                means = sums / jnp.maximum(counts, 1.0)
                updated = jnp.where(
                    counts > 0, (1.0 - last.alpha) * centers + last.alpha * means,
                    centers,
                )
                new_states[-1] = {"centers": updated.astype(states[-1]["centers"].dtype)}
        return score, new_states

    # -- train step ----------------------------------------------------------

    def _lr_mult_tree(self):
        """Per-leaf learning-rate multiplier (per-layer learning_rate and
        bias_learning_rate overrides, reference: layer conf learningRate)."""
        base = self.net_conf.learning_rate
        out = []
        for conf, p in zip(self.layer_confs, self.params_list):
            inner = conf.inner if isinstance(conf, L.FrozenLayer) else conf
            layer_lr = getattr(inner, "learning_rate", None)
            bias_lr = getattr(inner, "bias_learning_rate", None)
            mult = {}
            for name in p:
                if name == "b" and bias_lr is not None:
                    mult[name] = bias_lr / base
                elif layer_lr is not None:
                    mult[name] = layer_lr / base
                else:
                    mult[name] = 1.0
            out.append(mult)
        return out

    def _trainable_mask(self):
        return [
            {k: (0.0 if _is_frozen(conf) else 1.0) for k in p}
            for conf, p in zip(self.layer_confs, self.params_list)
        ]

    def _make_step_body(self, loss_builder, collect: bool = False):
        """Unjitted optimizer-step body around a loss builder
        (p, states, data, rng) -> (score, new_states). The tail — gradient
        masking/normalization, per-leaf lr, updater, param update — is
        shared by the standard, truncated-backward and fused-TBPTT steps.

        Returns (params, states, upd_state, score, diag[, stats]): `diag`
        is the in-graph divergence diagnostic `[loss, global grad norm]`
        — a 2-vector fused into the same program (a few elementwise
        reductions next to a full backward pass), so the sentinel's
        per-step judgment costs ONE device read that rides the score
        fetch instead of a second sync."""
        gnorm = self.net_conf.gradient_normalization
        gthresh = self.net_conf.gradient_normalization_threshold
        mults = self._lr_mult_tree()
        tmask = self._trainable_mask()
        updater = self.updater_def
        minimize = self.net_conf.minimize
        # mesh-attached nets pin the gradient reduction IN-GRAPH here:
        # constraining the grads to the parameter shardings makes GSPMD
        # insert the cross-device psum/mean at the grad site (replicated
        # params x data-sharded batch), replacing the reference's
        # host-side parameter averaging. The plan emits it BUCKETED
        # (reverse-topo flat payloads, parallel/sharded.CollectivePlan):
        # each bucket's collective depends only on its own leaves, so the
        # scheduler can overlap early buckets with the remaining backward
        plan = self._mesh_plan

        def step(params, states, upd_state, data, lr, t, rng):
            def loss_fn(p):
                return loss_builder(p, states, data, rng)

            (score, new_states), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params)
            if plan is not None:
                with jax.named_scope("reduce_grads"):
                    grads = plan.reduce_grads(self, grads)
            merged = self._merge_states(states, new_states)
            with jax.named_scope("update"):
                # global grad norm of the RAW gradient (before masking/
                # clipping — clipping would hide exactly the explosion the
                # sentinel watches for), accumulated in f32
                gsq = jnp.float32(0.0)
                for g in jax.tree_util.tree_leaves(grads):
                    gsq = gsq + jnp.sum(jnp.square(g.astype(jnp.float32)))
                diag = jnp.stack([score.astype(jnp.float32), jnp.sqrt(gsq)])
                if not minimize:
                    grads = jax.tree_util.tree_map(lambda g: -g, grads)
                grads = [
                    {k: g[k] * m[k] for k in g} for g, m in zip(grads, tmask)
                ]
                grads = normalize_gradients(grads, gnorm, gthresh)
                lr_tree = [
                    {k: lr * m[k] for k in g} for g, m in zip(grads, mults)
                ]
                updates, new_upd = updater.apply_tree(grads, upd_state,
                                                      lr_tree, t)
                new_params = jax.tree_util.tree_map(jnp.add, params, updates)
                if collect:
                    # per-layer mean |x| scalars for the stats pipeline
                    # (reference: BaseStatsListener param/grad/update mean
                    # magnitudes) — fused into the step; tiny reductions
                    mm = lambda tree: [
                        {k: jnp.mean(jnp.abs(v)) for k, v in p.items()}
                        for p in tree
                    ]
                    stats = {"grad_mm": mm(grads), "update_mm": mm(updates),
                             "param_mm": mm(new_params)}
                    return new_params, merged, new_upd, score, diag, stats
            return new_params, merged, new_upd, score, diag

        return step

    def _make_step(self, loss_builder):
        """Jitted single-minibatch optimizer step (donated params/updater
        buffers on device backends; sharded signature under a mesh plan —
        see netbase._jit_step)."""
        step = self._make_step_body(
            loss_builder, collect=bool(getattr(self, "_collect_stats", False))
        )
        return self._jit_step(step)

    def _std_loss_builder(self):
        def loss_builder(p, states, data, rng):
            x, y, f_mask, l_mask = data
            return self._loss(p, states, x, y, f_mask, l_mask, rng)

        return loss_builder

    def _trunc_loss_builder(self):
        """TBPTT loss with tbptt_bwd_length < tbptt_fwd_length: the
        segment's leading (fwd-bwd) timesteps run under stop_gradient
        (state advances, loss counts, but no gradient flows back through
        them), truncating backprop depth to bwd_length (reference:
        tBPTTBackwardLength, MultiLayerNetwork.java:1333; the reference
        zeroes epsilons past bwd steps of the reverse walk — here the cut
        is a stop_gradient on the carried state at the boundary)."""

        def loss_builder(p, states, data, rng):
            xA, yA, fmA, lmA, xB, yB, fmB, lmB = data
            lossA, statesA = self._loss(p, states, xA, yA, fmA, lmA, rng)
            carried = self._merge_states(states, statesA)
            carried = jax.tree_util.tree_map(jax.lax.stop_gradient, carried)
            lossB, statesB = self._loss(
                p, carried, xB, yB, fmB, lmB,
                None if rng is None else jax.random.fold_in(rng, 1),
            )
            nA, nB = xA.shape[1], xB.shape[1]
            # slice A contributes to the reported score but NOT to the
            # gradient (stop_gradient lets XLA prune its whole backward
            # pass) — backprop depth is exactly bwd_length
            score = (
                jax.lax.stop_gradient(lossA) * nA + lossB * nB
            ) / (nA + nB)
            return score, self._merge_states(carried, statesB)

        return loss_builder

    def _build_train_step(self):
        return self._make_step(self._std_loss_builder())

    def _build_truncated_bwd_step(self):
        return self._make_step(self._trunc_loss_builder())

    @staticmethod
    def _make_seg_data(seg: int, bwd: int):
        """TBPTT time-segmentation under jit: returns seg_data(x, y, fm,
        lm, i) -> the step-body data tuple for segment i (the 8-tuple
        A/B split when bwd < seg, the plain 4-tuple otherwise). Uses
        dynamic_slice so `i` may be a traced scan index."""

        def seg_slice(a, start, length):
            return jax.lax.dynamic_slice_in_dim(a, start, length, axis=1)

        def seg_data(x, y, fm, lm, i):
            start = i * seg
            cut_m = lambda m, s0, ln: (
                None if m is None else (m if m.ndim == 1
                                        else seg_slice(m, s0, ln))
            )
            cut_y = lambda s0, ln: (seg_slice(y, s0, ln) if y.ndim == 3 else y)
            if bwd < seg:
                nA = seg - bwd
                return (
                    seg_slice(x, start, nA), cut_y(start, nA),
                    cut_m(fm, start, nA), cut_m(lm, start, nA),
                    seg_slice(x, start + nA, bwd), cut_y(start + nA, bwd),
                    cut_m(fm, start + nA, bwd), cut_m(lm, start + nA, bwd),
                )
            return (seg_slice(x, start, seg), cut_y(start, seg),
                    cut_m(fm, start, seg), cut_m(lm, start, seg))

        return seg_data

    def _build_tbptt_fused_step(self, n_seg: int, seg: int, bwd: int):
        """ALL of a batch's TBPTT segments in ONE jitted dispatch.

        The per-segment loop in `_fit_tbptt` costs several host->device
        dispatches per segment (time-slices + the step); through a
        high-latency device link that overhead dwarfs the compute for
        small recurrent cells (measured: 9.5ms/segment dispatched vs 93us
        of device time on the char-rnn bench). Here segment 0 runs inline
        (populating the RNN-state carry structure) and segments 1..n-1 run
        under `lax.scan`, so the whole fit batch is one dispatch. Exact
        same math as the loop: same per-segment lr/t/rng, same optimizer
        tail (equivalence pinned by tests/test_tbptt_fused.py).

        Callers must guarantee T == n_seg * seg (no ragged tail — the
        fixed-size `dynamic_slice` segmentation cannot express one; the
        loop path handles it) and that per-iteration stats collection is
        off (the body is built without `collect`).
        """
        assert not getattr(self, "_collect_stats", False), (
            "fused TBPTT does not collect per-iteration stats; "
            "_fit_tbptt must use the loop path when collection is on"
        )
        body = self._make_step_body(
            self._trunc_loss_builder() if bwd < seg
            else self._std_loss_builder()
        )
        seed_key_base = self.net_conf.seed ^ 0x5EED
        seg_data = self._make_seg_data(seg, bwd)

        def step(params, states, upd_state, data, lrs, t0, _rng_unused):
            x, y, fm, lm = data
            key = jax.random.PRNGKey(seed_key_base)

            def run_seg(params, states, upd_state, i):
                rng, t = self._step_rng_and_t(key, t0, i)
                return body(params, states, upd_state,
                            seg_data(x, y, fm, lm, i), lrs[i], t, rng)

            # segment 0 inline: its merged states establish the carry
            # pytree (zero-state {} -> populated h/c) for the scan
            params, states, upd_state, s0, d0 = run_seg(
                params, states, upd_state, 0)
            if n_seg == 1:
                return params, states, upd_state, s0[None], s0, d0

            def scan_body(carry, i):
                p, st, us = carry
                p, st, us, score, dg = run_seg(p, st, us, i)
                return (p, st, us), (score, dg)

            (params, states, upd_state), (scores, diags) = jax.lax.scan(
                scan_body, (params, states, upd_state),
                jnp.arange(1, n_seg))
            # the final score returned separately so the host can keep a
            # scalar _score without an extra device-indexing dispatch
            last = scores[-1]
            # whole-batch diagnostic: final score, worst grad norm of
            # any segment (a NaN segment poisons later params, so the
            # final loss carries the non-finite signal regardless)
            diag = jnp.stack([diags[-1, 0],
                              jnp.maximum(d0[1], jnp.max(diags[:, 1]))])
            scores = jnp.concatenate([s0[None], scores])
            return params, states, upd_state, scores, last, diag

        return self._jit_step(step)

    def _run_step(self, step_fn, data, stateful_states=None):
        lr = schedule_lr(self.net_conf, self.iteration)
        rng = jax.random.fold_in(
            jax.random.PRNGKey(self.net_conf.seed ^ 0x5EED), self.iteration
        )
        states = stateful_states if stateful_states is not None else self.state_list
        out = step_fn(
            self.params_list, states, self.upd_state,
            tuple(None if a is None else jnp.asarray(a) for a in data),
            jnp.asarray(lr, jnp.float32), jnp.asarray(float(self.iteration)),
            rng,
        )
        params, states, upd, score = out[:4]
        self._step_diag = out[4]
        self._last_stats = out[5] if len(out) > 5 else None
        self.params_list = params
        self.upd_state = upd
        self._score = score
        self.iteration += 1
        return states, score

    def _fit_step(self, x, y, f_mask, l_mask, stateful_states=None):
        """One optimizer step. Returns the (device) score."""
        if self._train_step_fn is None:
            self._train_step_fn = self._build_train_step()
            self._note_compile("train_step")
        return self._run_step(
            self._train_step_fn, (x, y, f_mask, l_mask), stateful_states
        )

    def _fit_step_truncated(self, dataA, dataB, stateful_states):
        """One TBPTT segment step with a backward-truncation boundary
        between slice A (state-carry, stop-gradient) and slice B."""
        if getattr(self, "_trunc_step_fn", None) is None:
            self._trunc_step_fn = self._build_truncated_bwd_step()
            self._note_compile("train_step_truncated")
        return self._run_step(
            self._trunc_step_fn, dataA + dataB, stateful_states
        )

    # -- pretraining ---------------------------------------------------------

    _PRETRAINABLE = (L.AutoEncoder, L.VariationalAutoencoder, L.RBM)

    def pretrain(self, data, *, epochs: int = 1, batch_size: int = 32):
        """Layerwise unsupervised pretraining: each pretrainable layer
        (AutoEncoder / VAE / RBM) trains on the activations of the frozen
        stack below it (reference: MultiLayerNetwork.pretrain/pretrainLayer
        :210-287)."""
        self._require_init()
        for i, conf in enumerate(self.layer_confs):
            if isinstance(conf, self._PRETRAINABLE):
                self.pretrain_layer(i, data, epochs=epochs, batch_size=batch_size)
        return self

    def pretrain_layer(self, idx: int, data, *, epochs: int = 1,
                       batch_size: int = 32):
        """Unsupervised fit of one layer. Objectives: AutoEncoder =
        reconstruction loss through tied-weight decode; VAE = negative
        ELBO (special.py vae_elbo); RBM = CD-k (rbm.py rbm_cd_stats)."""
        conf = self.layer_confs[idx]
        if not isinstance(conf, self._PRETRAINABLE):
            raise ValueError(
                f"layer {idx} ({type(conf).__name__}) is not pretrainable"
            )
        if isinstance(data, DataSetIterator):
            iterator = data
        elif isinstance(data, DataSet):
            iterator = ListDataSetIterator(data, batch_size)
        else:  # raw features; labels are unused in unsupervised fit
            x = np.asarray(data)
            iterator = ListDataSetIterator(DataSet(x, x), batch_size)
        feed = jax.jit(
            lambda params, states, x: self._forward(
                params, states, self.policy.cast_input(x),
                training=False, rng=None, to_layer=idx,
            )[0]
        )
        step = self._build_pretrain_step(conf)
        upd_state = self.updater_def.init_tree(self.params_list[idx])
        it_count = 0
        for _ in range(epochs):
            for ds in iterator:
                x_in = feed(self.params_list, self.state_list,
                            jnp.asarray(ds.features))
                lr = schedule_lr(self.net_conf, it_count)
                rng = jax.random.fold_in(
                    jax.random.PRNGKey(self.net_conf.seed ^ (0xBEEF + idx)),
                    it_count,
                )
                new_p, upd_state, score = step(
                    self.params_list[idx], upd_state, x_in,
                    jnp.asarray(lr, jnp.float32),
                    jnp.asarray(float(it_count)), rng,
                )
                self.params_list = (
                    self.params_list[:idx] + [new_p] + self.params_list[idx + 1:]
                )
                self._score = score
                it_count += 1
            iterator.reset()
        return self

    def _build_pretrain_step(self, conf):
        updater = self.updater_def

        def step(layer_params, upd_state, x_in, lr, t, rng):
            if isinstance(conf, L.RBM):
                from deeplearning4j_tpu.nn.layers.rbm import rbm_cd_stats

                grads, per_ex = rbm_cd_stats(conf, layer_params, x_in, rng)
                score = jnp.mean(per_ex)
            else:
                def objective(p):
                    if isinstance(conf, L.VariationalAutoencoder):
                        from deeplearning4j_tpu.nn.layers.special import vae_elbo

                        return jnp.mean(vae_elbo(conf, p, x_in, rng))
                    from deeplearning4j_tpu.nn.layers.core import (
                        autoencoder_reconstruct,
                    )

                    ctx = LayerContext(training=True, rng=rng)
                    recon = autoencoder_reconstruct(conf, p, x_in, ctx)
                    per_ex = loss_value(conf.loss, x_in, recon, "identity", None)
                    return jnp.mean(per_ex)

                score, grads = jax.value_and_grad(objective)(layer_params)
            updates, new_upd = updater.apply_tree(grads, upd_state, lr, t)
            new_params = jax.tree_util.tree_map(jnp.add, layer_params, updates)
            return new_params, new_upd, score

        return jax.jit(step)

    # -- fit -----------------------------------------------------------------

    def fit(self, data, labels=None, *, epochs: int = 1, batch_size: int = 32,
            async_prefetch: bool = True, prefetch_buffer: int = 4,
            hang_timeout: float = None, resume_from: str = None,
            run_ledger=None):
        """Train. Accepts (features, labels) arrays, a DataSet, or a
        DataSetIterator (reference: MultiLayerNetwork.fit overloads
        :1019). If the configuration sets pretrain=True, layerwise
        unsupervised pretraining runs once before the first backprop epoch
        (reference: fit() pretrain dispatch :210). With async_prefetch the
        staged input pipeline (host ETL thread -> device prefetch, see
        nn/netbase._stage_input_pipeline) feeds the loop; prefetch_buffer
        is the host stage's queue depth. `hang_timeout` (seconds) arms the
        hang watchdog: a step making no progress for that long raises
        utils.health.StepHangError carrying a flight-recorder dump path
        instead of blocking forever. Pick it above the worst-case single
        phase — the first step's trace+compile and the longest legitimate
        data wait both count as "no progress" if they exceed it.
        `resume_from` names a checkpoint directory (CheckpointListener):
        the newest checkpoint is loaded into this net, the iterator is
        fast-forwarded to the saved mid-epoch position, and training
        continues to the same loss curve as an uninterrupted run; an
        empty directory starts fresh, so the same command line works on
        first boot and after a preemption. `epochs` stays the TOTAL
        target — already-completed epochs are not re-run. `run_ledger`
        opts this fit into persistent metrics recording + SLO judgment
        (utils/runledger): a path records a per-run ledger artifact
        there, a RunLedger instance is attached for the fit's duration;
        None (the default) keeps the fit-loop ledger hook at one flag
        check per step."""
        self._require_init()
        if self.conf.pretrain and not getattr(self, "_pretrained", False):
            self.pretrain(data, batch_size=batch_size)
            self._pretrained = True
        iterator = self._as_iterator(data, labels, batch_size)
        return self._run_fit(iterator, epochs, async_prefetch,
                             prefetch_buffer, hang_timeout=hang_timeout,
                             resume_from=resume_from,
                             run_ledger=run_ledger)

    def _as_iterator(self, data, labels, batch_size) -> DataSetIterator:
        if isinstance(data, DataSetIterator):
            return data
        if isinstance(data, DataSet):
            return ListDataSetIterator(data, batch_size)
        x = np.asarray(data)
        y = np.asarray(labels)
        return ListDataSetIterator(DataSet(x, y), batch_size)

    def _fit_dataset(self, ds: DataSet):
        algo = self.net_conf.optimization_algo
        if algo != "sgd":
            self._fit_line_search(ds, algo)
            return
        tbptt = (
            self.conf.backprop_type == BackpropType.TRUNCATED_BPTT
            and ds.features.ndim == 3
        )
        if tbptt:
            self._fit_tbptt(ds)
        else:
            states, score = self._fit_step(
                ds.features, ds.labels, ds.features_mask, ds.labels_mask
            )
            self.state_list = states
            self._notify(getattr(ds, "reported_examples", None)
                         or ds.num_examples(), ds)

    def _fit_line_search(self, ds: DataSet, algo: str):
        """Line-search optimizer path (LBFGS/CG/line GD): host-side search
        loop around the compiled value+gradient function (reference:
        BaseOptimizer.optimize :182-230). One optimize() call per batch."""
        from deeplearning4j_tpu.nn.params import flat_to_params, params_to_flat
        from deeplearning4j_tpu.train.solvers import (
            _FlatProblem,
            make_line_search_optimizer,
        )

        if getattr(self, "_solver", None) is None or self._solver.name != algo:
            self._solver = make_line_search_optimizer(algo)
            self._flat_problem = _FlatProblem(self)
        x = jnp.asarray(ds.features)
        y = jnp.asarray(ds.labels)
        fm = None if ds.features_mask is None else jnp.asarray(ds.features_mask)
        lm = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask)
        rng = jax.random.fold_in(
            jax.random.PRNGKey(self.net_conf.seed ^ 0x5EED), self.iteration
        )
        problem = self._flat_problem.bind(self.state_list, x, y, fm, lm, rng)
        flat = params_to_flat(self.layer_confs, self.params_list)
        step0 = schedule_lr(self.net_conf, self.iteration)
        new_flat, f_new = self._solver.optimize(problem, flat, step0)
        self.params_list = flat_to_params(self.layer_confs, self.params_list, new_flat)
        self._score = jnp.asarray(f_new)
        # no in-graph diagnostic on the line-search path: the sentinel
        # degrades to the finite check on the score alone
        self._step_diag = None
        self.iteration += 1
        self._notify(getattr(ds, "reported_examples", None)
                         or ds.num_examples(), ds)

    def _fit_tbptt(self, ds: DataSet):
        """Truncated BPTT: split time into segments of tbptt_fwd_length and
        carry RNN state across segments (reference:
        MultiLayerNetwork.doTruncatedBPTT :1333). When tbptt_bwd_length <
        tbptt_fwd_length, each segment's gradient is truncated to its last
        bwd_length timesteps (config tBPTTBackwardLength).

        When the batch has no ragged tail (T divisible by seg), no
        listeners are attached, and stats collection is off, all segments
        run in ONE jitted dispatch (`_build_tbptt_fused_step`) — same math,
        ~n_seg fewer host->device round-trips. Listeners keep the loop path
        so per-iteration callbacks observe the params of *their* iteration.
        """
        T = ds.features.shape[1]
        seg = int(self.conf.tbptt_fwd_length)
        bwd = int(self.conf.tbptt_bwd_length)
        n_seg = -(-T // seg)
        if (
            T == n_seg * seg
            and not self.listeners
            and not getattr(self, "_collect_stats", False)
        ):
            self._fit_tbptt_fused(ds, n_seg, seg, bwd)
            return
        # seed zero RNN state for recurrent layers
        states = list(self.state_list)
        for i, conf in enumerate(self.layer_confs):
            if _is_recurrent(conf) and states[i] is None:
                states[i] = {}

        def cut_mask(m, sl):
            if m is None:
                return None
            return m if m.ndim == 1 else m[:, sl]  # 1-D = per-example mask

        def cut(sl):
            fm = cut_mask(ds.features_mask, sl)
            lm = cut_mask(ds.labels_mask, sl)
            labels = ds.labels[:, sl] if ds.labels.ndim == 3 else ds.labels
            return (ds.features[:, sl], labels, fm, lm)

        for start in range(0, T, seg):
            end = min(start + seg, T)
            if bwd < end - start:
                boundary = end - bwd
                states, _ = self._fit_step_truncated(
                    cut(slice(start, boundary)), cut(slice(boundary, end)),
                    stateful_states=states,
                )
            else:
                states, _ = self._fit_step(
                    *cut(slice(start, end)), stateful_states=states
                )
            self._notify(getattr(ds, "reported_examples", None)
                         or ds.num_examples(), ds)
        # persist only non-RNN state (running stats); RNN carry is per-batch
        self.state_list = [
            st if not _is_recurrent(conf) else self.state_list[i]
            for i, (conf, st) in enumerate(zip(self.layer_confs, states))
        ]

    def _fit_tbptt_fused(self, ds: DataSet, n_seg: int, seg: int, bwd: int):
        """Run one TBPTT fit batch through the single-dispatch fused step
        (see `_build_tbptt_fused_step`). Host work: the lr schedule values
        for the n_seg optimizer steps and one call."""
        sig = (n_seg, seg, bwd)
        cached = getattr(self, "_fused_tbptt_fn", None)
        if cached is None or cached[0] != sig:
            self._fused_tbptt_fn = (
                sig, self._build_tbptt_fused_step(n_seg, seg, bwd)
            )
        step_fn = self._fused_tbptt_fn[1]
        states = list(self.state_list)
        for i, conf in enumerate(self.layer_confs):
            if _is_recurrent(conf) and states[i] is None:
                states[i] = {}
        lrs = jnp.asarray(
            [schedule_lr(self.net_conf, self.iteration + i)
             for i in range(n_seg)],
            jnp.float32,
        )
        data = tuple(
            None if a is None else jnp.asarray(a)
            for a in (ds.features, ds.labels, ds.features_mask,
                      ds.labels_mask)
        )
        params, states, upd, _scores, last, diag = step_fn(
            self.params_list, states, self.upd_state, data, lrs,
            jnp.asarray(self.iteration, jnp.uint32), None,
        )
        self.params_list = params
        self.upd_state = upd
        self._score = last
        self._step_diag = diag
        self._last_stats = None
        self.iteration += n_seg
        # persist only non-RNN state (running stats); RNN carry is per-batch
        self.state_list = [
            st if not _is_recurrent(conf) else self.state_list[i]
            for i, (conf, st) in enumerate(zip(self.layer_confs, states))
        ]

    # -- multi-batch fused fit (set_fused_steps) -----------------------------

    def _fused_fit_supported(self) -> bool:
        return self.net_conf.optimization_algo == "sgd"

    def _fit_datasets_fused(self, ds_list):
        """K same-shape minibatches in ONE jitted dispatch (see
        NetworkBase.set_fused_steps). Dispatches to the cross-batch TBPTT
        program for 3-d TBPTT batches, the stacked-scan program otherwise;
        anything ineligible (ragged TBPTT tail) falls back per-batch."""
        d0 = ds_list[0]
        if (
            self.conf.backprop_type == BackpropType.TRUNCATED_BPTT
            and d0.features.ndim == 3
        ):
            T = d0.features.shape[1]
            seg = int(self.conf.tbptt_fwd_length)
            bwd = int(self.conf.tbptt_bwd_length)
            n_seg = -(-T // seg)
            if T != n_seg * seg:
                for d in ds_list:
                    self._fit_dataset(d)
                return
            self._fit_tbptt_batched(ds_list, n_seg, seg, bwd)
            return
        self._fit_std_batched(ds_list)

    @staticmethod
    def _stack_datasets(ds_list):
        stack = lambda vals: (
            None if vals[0] is None
            else jnp.stack([jnp.asarray(v) for v in vals])
        )
        return (
            stack([d.features for d in ds_list]),
            stack([d.labels for d in ds_list]),
            stack([d.features_mask for d in ds_list]),
            stack([d.labels_mask for d in ds_list]),
        )

    def _build_multi_fit_step(self, K: int):
        """K standard optimizer steps as one `lax.scan` over the stacked
        batches — same per-step lr/t/rng derivation as `_run_step`, K-1
        fewer dispatches (equivalence: tests/test_fused_fit.py)."""
        assert not getattr(self, "_collect_stats", False)
        body = self._make_step_body(self._std_loss_builder())
        seed_key_base = self.net_conf.seed ^ 0x5EED

        def step(params, states, upd_state, data_stack, lrs, t0):
            key = jax.random.PRNGKey(seed_key_base)

            def scan_body(carry, inp):
                p, st, us = carry
                data_i, lr, i = inp
                rng, t = self._step_rng_and_t(key, t0, i)
                p, st, us, sc, dg = body(p, st, us, data_i, lr, t, rng)
                return (p, st, us), (sc, dg)

            (params, states, upd_state), (scores, diags) = jax.lax.scan(
                scan_body, (params, states, upd_state),
                (data_stack, lrs, jnp.arange(K, dtype=jnp.uint32)))
            diag = jnp.stack([diags[-1, 0], jnp.max(diags[:, 1])])
            return params, states, upd_state, scores[-1], diag

        # stacked batches: [K, B, ...] — under a mesh plan the batch dim
        # (1, not 0) shards over the data axis
        return self._jit_step(step, stacked_data=True)

    def _fit_std_batched(self, ds_list):
        K = len(ds_list)
        cached = getattr(self, "_multi_fit_fn", None)
        if cached is None or cached[0] != K:
            self._multi_fit_fn = (K, self._build_multi_fit_step(K))
        fn = self._multi_fit_fn[1]
        data = self._stack_datasets(ds_list)
        lrs = jnp.asarray(
            [schedule_lr(self.net_conf, self.iteration + i)
             for i in range(K)], jnp.float32)
        params, states, upd, last, diag = fn(
            self.params_list, self.state_list, self.upd_state, data, lrs,
            jnp.asarray(self.iteration, jnp.uint32))
        self.params_list = params
        self.upd_state = upd
        self.state_list = states
        self._score = last
        self._step_diag = diag
        self._last_stats = None
        self.iteration += K

    def _build_tbptt_batched_step(self, K: int, n_seg: int, seg: int,
                                  bwd: int):
        """K TBPTT fit batches (each n_seg segments, RNN state reset at
        every batch boundary, BN stats carried throughout) in ONE jitted
        dispatch. Batch 0's segment 0 runs inline to bootstrap the RNN
        carry structure ({} -> {"h","c"}); batches 1..K-1 scan with a
        zeros reset — identical math to K calls of `_fit_tbptt` (the
        layer seeds zero state for {} exactly as `reset` writes zeros;
        equivalence: tests/test_fused_fit.py)."""
        assert not getattr(self, "_collect_stats", False)
        body = self._make_step_body(
            self._trunc_loss_builder() if bwd < seg
            else self._std_loss_builder()
        )
        seed_key_base = self.net_conf.seed ^ 0x5EED
        seg_data = self._make_seg_data(seg, bwd)
        rec = [_is_recurrent(c) for c in self.layer_confs]

        def reset_rnn(states):
            return [
                jax.tree_util.tree_map(jnp.zeros_like, st) if is_r else st
                for st, is_r in zip(states, rec)
            ]

        def step(params, states, upd_state, data_stack, lrs, t0,
                 _rng_unused):
            key = jax.random.PRNGKey(seed_key_base)
            pick = lambda b: tuple(
                None if a is None else a[b] for a in data_stack)

            def run_seg(p, st, us, data_b, i_seg, j):
                rng, t = self._step_rng_and_t(key, t0, j)
                x, y, fm, lm = data_b
                return body(p, st, us, seg_data(x, y, fm, lm, i_seg),
                            lrs[j], t, rng)

            # batch 0 / segment 0 inline: bootstraps the carry structure
            data0 = pick(0)
            params, states, upd_state, _, d00 = run_seg(
                params, states, upd_state, data0, 0, 0)
            gmax = d00[1]
            if n_seg > 1:
                def seg_scan0(carry, i):
                    p, st, us = carry
                    p, st, us, sc, dg = run_seg(p, st, us, data0, i, i)
                    return (p, st, us), dg

                (params, states, upd_state), dgs0 = jax.lax.scan(
                    seg_scan0, (params, states, upd_state),
                    jnp.arange(1, n_seg))
                gmax = jnp.maximum(gmax, jnp.max(dgs0[:, 1]))

            def batch_body(carry, b):
                p, st, us = carry
                st = reset_rnn(st)
                data_b = pick(b)

                def seg_scan(c2, s):
                    p2, st2, us2 = c2
                    p2, st2, us2, sc, dg = run_seg(
                        p2, st2, us2, data_b, s, b * n_seg + s)
                    return (p2, st2, us2), (sc, dg)

                (p, st, us), (scs, dgs) = jax.lax.scan(
                    seg_scan, (p, st, us), jnp.arange(n_seg))
                return (p, st, us), (scs[-1], jnp.max(dgs[:, 1]))

            (params, states, upd_state), (lasts, gmaxes) = jax.lax.scan(
                batch_body, (params, states, upd_state),
                jnp.arange(1, K))
            diag = jnp.stack([lasts[-1],
                              jnp.maximum(gmax, jnp.max(gmaxes))])
            return params, states, upd_state, lasts[-1], diag

        return self._jit_step(step, stacked_data=True)

    def _fit_tbptt_batched(self, ds_list, n_seg: int, seg: int, bwd: int):
        K = len(ds_list)
        if K == 1:
            self._fit_tbptt_fused(ds_list[0], n_seg, seg, bwd)
            return
        sig = (K, n_seg, seg, bwd)
        cached = getattr(self, "_tbptt_batched_fn", None)
        if cached is None or cached[0] != sig:
            self._tbptt_batched_fn = (
                sig, self._build_tbptt_batched_step(K, n_seg, seg, bwd))
        fn = self._tbptt_batched_fn[1]
        states = list(self.state_list)
        for i, conf in enumerate(self.layer_confs):
            if _is_recurrent(conf) and states[i] is None:
                states[i] = {}
        data = self._stack_datasets(ds_list)
        lrs = jnp.asarray(
            [schedule_lr(self.net_conf, self.iteration + j)
             for j in range(K * n_seg)], jnp.float32)
        params, states, upd, last, diag = fn(
            self.params_list, states, self.upd_state, data, lrs,
            jnp.asarray(self.iteration, jnp.uint32), None)
        self.params_list = params
        self.upd_state = upd
        self._score = last
        self._step_diag = diag
        self._last_stats = None
        self.iteration += K * n_seg
        self.state_list = [
            st if not _is_recurrent(conf) else self.state_list[i]
            for i, (conf, st) in enumerate(zip(self.layer_confs, states))
        ]

    # -- inference -----------------------------------------------------------

    def output(self, x, training: bool = False):
        """Full forward pass (reference: MultiLayerNetwork.output).
        training=True gives train-mode activations (dropout active, batch
        statistics) with a deterministic per-call rng.

        The jit cache is keyed on (training, input shape, dtype), and every
        insertion bumps `output_compile_count` — serving layers
        (ParallelInference /metrics) read it so that shape-driven compile
        storms show up as a counter instead of mystery tail latency."""
        self._require_init()
        xx = jnp.asarray(x)

        def make_fn():
            def fwd(params, states, xx, rng):
                xx = self.policy.cast_input(xx)
                out, _ = self._forward(params, states, xx,
                                       training=training, rng=rng)
                return self.policy.cast_output(out)

            return jax.jit(fwd)

        fn = self._cached_output_fn(
            (training, xx.shape, str(xx.dtype)), make_fn)
        rng = (
            jax.random.PRNGKey(self.net_conf.seed ^ 0xD0) if training else None
        )
        return fn(self.params_list, self.state_list, xx, rng)

    def feed_forward(self, x):
        """Per-layer activations list (reference: feedForward family
        :725-831). Not jitted — debugging/inspection path."""
        self._require_init()
        acts = []
        xx = jnp.asarray(x)
        timesteps = xx.shape[1] if xx.ndim == 3 else None
        for i, conf in enumerate(self.layer_confs):
            pp = self.conf.preprocessors.get(str(i))
            if pp is not None:
                xx = pp(xx, {"timesteps": timesteps})
            if xx.ndim == 3:
                timesteps = xx.shape[1]
            ctx = LayerContext(training=False, state=self.state_list[i],
                               timesteps=timesteps)
            xx, _ = forward_layer(conf, self.params_list[i], xx, ctx)
            acts.append(xx)
        return acts

    def score(self, data, labels=None) -> float:
        """Loss on a dataset without updating (reference:
        MultiLayerNetwork.score(DataSet))."""
        self._require_init()
        if isinstance(data, DataSet):
            ds = data
        else:
            ds = DataSet(np.asarray(data), np.asarray(labels))
        s, _ = self._loss(
            self.params_list, self.state_list,
            jnp.asarray(ds.features), jnp.asarray(ds.labels),
            None if ds.features_mask is None else jnp.asarray(ds.features_mask),
            None if ds.labels_mask is None else jnp.asarray(ds.labels_mask),
            rng=None, training=False,
        )
        return float(s)

    def evaluate(self, data, labels=None, batch_size: int = 256) -> Evaluation:
        """Classification evaluation (reference: evaluate/doEvaluation
        :2605-2646)."""
        ev = Evaluation()
        for ds in self._eval_batches(data, labels, batch_size):
            out = self.output(ds.features)
            ev.eval_batch(ds.labels, out, ds.labels_mask)
        return ev

    def evaluate_regression(self, data, labels=None, batch_size: int = 256):
        ev = RegressionEvaluation()
        for ds in self._eval_batches(data, labels, batch_size):
            out = self.output(ds.features)
            ev.eval_batch(ds.labels, out, ds.labels_mask)
        return ev

    def _eval_batches(self, data, labels, batch_size):
        if isinstance(data, DataSetIterator):
            yield from data
        elif isinstance(data, DataSet):
            yield from data.split_batches(batch_size)
        else:
            yield from DataSet(np.asarray(data), np.asarray(labels)).split_batches(batch_size)

    # -- rnn streaming inference ---------------------------------------------

    def _rnn_layer_size(self, i: int) -> int:
        conf = self.layer_confs[i]
        inner = conf.inner if isinstance(conf, L.FrozenLayer) else conf
        return int(inner.n_out)

    def rnn_zero_carry(self, batch: int) -> dict:
        """Zero recurrent carry for a `batch`-wide stream: {layer index
        -> {"h", "c"} [batch, H]} for every recurrent layer — the state
        a fresh rnn_time_step stream (or a freshly admitted decode slot)
        starts from. Dtype is the compute dtype, matching the zeros the
        scan itself would seed."""
        self._require_init()
        dt = self.policy.compute_dtype
        return {
            i: {"h": jnp.zeros((batch, self._rnn_layer_size(i)), dt),
                "c": jnp.zeros((batch, self._rnn_layer_size(i)), dt)}
            for i, c in enumerate(self.layer_confs) if _is_recurrent(c)
        }

    def _rnn_seed_states(self, carry: dict, batch: int):
        """Full state list for a streaming step: recurrent layers from
        `carry` (zero-seeded when absent — host-side, so the jitted
        program's state STRUCTURE is constant and the first call shares
        the steady-state trace), everything else fresh from state_list
        (BN running stats must match output() even after an interleaved
        fit())."""
        dt = self.policy.compute_dtype
        states = []
        for i, c in enumerate(self.layer_confs):
            if _is_recurrent(c):
                st = carry.get(i)
                if st is None:
                    H = self._rnn_layer_size(i)
                    st = {"h": jnp.zeros((batch, H), dt),
                          "c": jnp.zeros((batch, H), dt)}
                states.append(st)
            else:
                states.append(self.state_list[i])
        return states

    def rnn_time_step(self, x):
        """Stateful streaming inference (reference:
        MultiLayerNetwork.rnnTimeStep). x: [batch, time, nIn] (or
        [batch, nIn] for a single step).

        The streaming step is jitted with a shape-keyed cache (the same
        discipline as `output()`: keyed on (batch, time, nIn, dtype),
        each insertion bumps `output_compile_count`) — a mixed-size
        stream costs one trace per shape, not one per call. A call whose
        batch size differs from the carried state starts a NEW stream:
        the stale carry is dropped (loudly) instead of leaking a
        previous caller's hidden state into this one."""
        self._require_init()
        xx = jnp.asarray(x)
        single = xx.ndim == 2
        if single:
            xx = xx[:, None, :]
        bsz = xx.shape[0]
        # only the recurrent carry persists between calls
        carry = self._rnn_states or {}
        if carry and any(v.shape[0] != bsz
                         for st in carry.values() for v in st.values()):
            logger.warning(
                "rnn_time_step batch size changed (carried %d, got %d): "
                "dropping the previous stream's state — call "
                "clear_rnn_state() between streams to silence this",
                next(iter(carry.values()))["h"].shape[0], bsz)
            carry = {}
            self._rnn_states = None
        states = self._rnn_seed_states(carry, bsz)

        def make_fn():
            def fwd(params, states, xx):
                out, new_states = self._forward(
                    params, states, self.policy.cast_input(xx),
                    training=False, rng=None, stateful=True,
                )
                return self.policy.cast_output(out), new_states

            return jax.jit(fwd)

        fn = self._cached_output_fn(
            ("rnn_step", xx.shape, str(xx.dtype)), make_fn)
        out, new_states = fn(self.params_list, states, xx)
        merged = self._merge_states(states, new_states)
        self._rnn_states = {
            i: merged[i]
            for i, c in enumerate(self.layer_confs) if _is_recurrent(c)
        }
        return out[:, 0] if single else out

    def rnn_clear_previous_state(self):
        self._rnn_states = None

    def clear_rnn_state(self):
        """Reset the streaming-inference state — the next rnn_time_step
        call starts a fresh stream (alias of rnn_clear_previous_state)."""
        self.rnn_clear_previous_state()

    def rnn_decode_step_fn(self):
        """Pure single-step decode function for the continuous-batching
        serving tier (serving/decode.py):

            (params, states, carry, x) -> (new_carry, out)

        `x` is ONE timestep [batch, nIn]; `carry` maps recurrent layer
        index -> {"h", "c"} [batch, H] (see `rnn_zero_carry`); `states`
        is the net's state_list (recurrent entries ignored in favor of
        `carry`); `out` is the post-activation output row [batch, nOut].
        Closed over the configuration only — params/states/carry are
        ARGUMENTS, which is what makes the decode engine's zero-downtime
        weight swap compile-free: the jitted program is keyed on shapes,
        not parameter values. jit-safe; the caller owns the jit and its
        cache."""
        self._require_init()
        rec = frozenset(
            i for i, c in enumerate(self.layer_confs) if _is_recurrent(c))

        def step(params, states, carry, x):
            xx = self.policy.cast_input(x)[:, None, :]
            st = [carry[i] if i in rec else states[i]
                  for i in range(len(self.layer_confs))]
            out, new_states = self._forward(
                params, st, xx, training=False, rng=None, stateful=True,
            )
            new_carry = {
                i: (new_states[i] if new_states[i] is not None else st[i])
                for i in rec
            }
            return new_carry, self.policy.cast_output(out[:, 0])

        return step

    def clone(self) -> "MultiLayerNetwork":
        import copy

        other = MultiLayerNetwork(copy.deepcopy(self.conf))
        if self.params_list is not None:
            other.init()
            other.params_list = jax.tree_util.tree_map(lambda a: a, self.params_list)
            other.state_list = [
                None if s is None else dict(s) for s in self.state_list
            ]
            # the clone resumes training equivalently: updater state
            # (momentum/Adam moments) + counters (LR schedule position)
            # travel with it (reference: MultiLayerNetwork.clone carries
            # the updater)
            other.upd_state = jax.tree_util.tree_map(lambda a: a, self.upd_state)
            other.iteration = self.iteration
            other.epoch = self.epoch
        return other

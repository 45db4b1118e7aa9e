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
from deeplearning4j_tpu.nn.conf.network import MultiLayerConfiguration
from deeplearning4j_tpu.nn.layers.core import rnn_output_preout
from deeplearning4j_tpu.nn.layers.registry import (
    LayerContext,
    forward_layer,
)
from deeplearning4j_tpu.nn.netbase import NetworkBase
from deeplearning4j_tpu.nn.trainstep import _is_recurrent
from deeplearning4j_tpu.ops.losses import example_presence, masked_example_mean, loss_value
from deeplearning4j_tpu.train.evaluation import Evaluation, RegressionEvaluation
from deeplearning4j_tpu.train.updaters import schedule_lr, updater_from_conf

logger = logging.getLogger("deeplearning4j_tpu")

_OUTPUT_LAYER_TYPES = (L.OutputLayer, L.RnnOutputLayer, L.LossLayer,
                       L.CenterLossOutputLayer)


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
        self._output_fn = None

    def _ordered_layer_confs(self):
        return self.layer_confs

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

    def _batch_data(self, ds: DataSet):
        return (ds.features, ds.labels, ds.features_mask, ds.labels_mask)

    def _fit_dataset(self, ds: DataSet):
        algo = self.net_conf.optimization_algo
        if algo != "sgd":
            self._fit_line_search(ds, algo)
            return
        data = self._batch_data(ds)
        if self._is_tbptt(data):
            self._fit_tbptt(ds)
        else:
            states, score = self._fit_step(*data)
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

    def _fused_fit_supported(self) -> bool:
        # the line-search solvers take one batch at a time
        return self.net_conf.optimization_algo == "sgd"

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

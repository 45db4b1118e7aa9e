"""The optimizer-step family, once, for both engines.

How an optimizer step is built from a loss, jitted, cached and run from
the host is decided here and nowhere else: the step body, its jit, the
truncated-backward / fused-TBPTT / multi-batch / cross-batch-TBPTT
programs and the host routines that dispatch them. `NetworkBase` mixes
`TrainStep` in; an engine supplies only

* `_loss(params, states, features, labels, f_masks, l_masks, rng,
  training=True) -> (score, new_states)`,
* `_ordered_layer_confs()` (aligned with `params_list`), and
* `_batch_data(ds)`: its batch as **`data`, one pytree `(features,
  labels, f_masks, l_masks)`** whose leaves are arrays or None — an
  array each for MultiLayerNetwork, a list each for ComputationGraph.

Everything below is written over that pytree with `jax.tree_util`; none
of it asks which engine is calling. Every program has the one signature
`step(params, states, upd_state, data, lr, t, rng)` (the fused ones take
`lrs, t0` and ignore `rng`): argument 3 is the batch, 0 and 2 are
donated, and the jitted function is called `step`, so the device's
program is `jit_step` whichever builder made it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.network import BackpropType
from deeplearning4j_tpu.train.updaters import normalize_gradients, schedule_lr

_tm = jax.tree_util.tree_map
_leaves = jax.tree_util.tree_leaves


def _is_recurrent(conf) -> bool:
    inner = conf.inner if isinstance(conf, L.FrozenLayer) else conf
    return isinstance(inner, (L.LSTM, L.GravesLSTM))


def _is_frozen(conf) -> bool:
    return isinstance(conf, L.FrozenLayer)


def _time_steps(features) -> int:
    """The time length of a batch: the longest axis 1 among its 3-d
    feature leaves (on a chain that is `x.shape[1]`)."""
    return max(a.shape[1] for a in _leaves(features) if a.ndim == 3)


def _cut_time(data, cut):
    """One slice in time of a batch: `cut(a)` of every leaf that has a
    time axis (a feature or label of 3 dims, a mask of 2), the leaf whole
    otherwise (2-d labels, 1-d per-example masks)."""
    x, y, fm, lm = data
    arr = lambda a: cut(a) if a.ndim == 3 else a
    mask = lambda m: m if m.ndim == 1 else cut(m)
    return _tm(arr, x), _tm(arr, y), _tm(mask, fm), _tm(mask, lm)


class TrainStep:
    """Mixin of NetworkBase: the step programs and the host code that
    runs them (see the module docstring for what an engine supplies)."""

    def _batch_data(self, ds):
        """The engine's batch as the `data` pytree."""
        raise NotImplementedError

    def _merge_states(self, old, new):
        return [n if n is not None else o for o, n in zip(old, new)]

    # -- the step body -------------------------------------------------------

    def _lr_mult_tree(self):
        """Per-leaf learning-rate multiplier (per-layer learning_rate and
        bias_learning_rate overrides, reference: layer conf learningRate)."""
        base = self.net_conf.learning_rate
        out = []
        for conf, p in zip(self._ordered_layer_confs(), self.params_list):
            inner = conf.inner if _is_frozen(conf) else conf
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
            for conf, p in zip(self._ordered_layer_confs(), self.params_list)
        ]

    def _make_step_body(self, loss_builder, collect: bool = False):
        """Unjitted optimizer-step body around a loss builder
        (p, states, data, rng) -> (score, new_states). The tail — gradient
        masking/normalization, per-leaf lr, updater, param update — is
        shared by the standard, truncated-backward, fused-TBPTT and
        multi-batch steps.

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
                for g in _leaves(grads):
                    gsq = gsq + jnp.sum(jnp.square(g.astype(jnp.float32)))
                diag = jnp.stack([score.astype(jnp.float32), jnp.sqrt(gsq)])
                if not minimize:
                    grads = _tm(lambda g: -g, grads)
                grads = [
                    {k: g[k] * m[k] for k in g} for g, m in zip(grads, tmask)
                ]
                grads = normalize_gradients(grads, gnorm, gthresh)
                lr_tree = [
                    {k: lr * m[k] for k in g} for g, m in zip(grads, mults)
                ]
                updates, new_upd = updater.apply_tree(grads, upd_state,
                                                      lr_tree, t)
                new_params = _tm(jnp.add, params, updates)
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

    def _std_loss_builder(self):
        def loss_builder(p, states, data, rng):
            return self._loss(p, states, *data, rng)

        return loss_builder

    def _trunc_loss_builder(self):
        """TBPTT loss with tbptt_bwd_length < tbptt_fwd_length: the
        segment's leading (fwd-bwd) timesteps run under stop_gradient
        (state advances, loss counts, but no gradient flows back through
        them), truncating backprop depth to bwd_length (reference:
        tBPTTBackwardLength, MultiLayerNetwork.java:1333; the reference
        zeroes epsilons past bwd steps of the reverse walk — here the cut
        is a stop_gradient on the carried state at the boundary). `data`
        is slice A's batch followed by slice B's."""

        def loss_builder(p, states, data, rng):
            dataA, dataB = data[:4], data[4:]
            lossA, statesA = self._loss(p, states, *dataA, rng)
            carried = self._merge_states(states, statesA)
            carried = _tm(jax.lax.stop_gradient, carried)
            lossB, statesB = self._loss(
                p, carried, *dataB,
                None if rng is None else jax.random.fold_in(rng, 1),
            )
            nA, nB = _time_steps(dataA[0]), _time_steps(dataB[0])
            # slice A contributes to the reported score but NOT to the
            # gradient (stop_gradient lets XLA prune its whole backward
            # pass) — backprop depth is exactly bwd_length
            score = (
                jax.lax.stop_gradient(lossA) * nA + lossB * nB
            ) / (nA + nB)
            return score, self._merge_states(carried, statesB)

        return loss_builder

    # -- the jit and the cache of programs -----------------------------------

    def _step_donate_argnums(self):
        """donate_argnums for jitted optimizer steps: params (0) and
        updater state (2) are donated on device backends so the update
        reuses their buffers instead of holding old+new copies; cpu
        makes donation a no-op (jax warns), so it is skipped there. The
        ONE definition every step builder uses — and records on the net,
        so analysis/jaxpr_audit's JX006 check audits the value the jits
        actually got, not a parallel reconstruction of this rule."""
        donate = (0, 2) if jax.default_backend() != "cpu" else ()
        self._donate_argnums = donate
        return donate

    def _jit_step(self, step, *, stacked_data=False):
        """jit an optimizer-step program — the ONE place every builder
        below gets its jit, so the donation rule AND the mesh sharding
        policy are single-sourced. Without a mesh plan this is plain
        `jax.jit(step, donate_argnums=...)`; with one the program is
        built with explicit NamedSharding in-shardings (the batch,
        argument 3, sharded on the data axis, params/updater per their
        live placement) and the same donation — the sharded signature
        JX006 audits via the recorded `_donate_argnums`."""
        donate = self._step_donate_argnums()
        plan = self._mesh_plan
        if plan is None:
            return jax.jit(step, donate_argnums=donate)
        return plan.jit_step(self, step, donate_argnums=donate,
                             stacked_data=stacked_data)

    def _make_step(self, loss_builder):
        """Jitted single-minibatch optimizer step (donated params/updater
        buffers on device backends; sharded signature under a mesh plan —
        see `_jit_step`)."""
        return self._jit_step(
            self._make_step_body(loss_builder, collect=self._collect_stats))

    def _build_train_step(self):
        return self._make_step(self._std_loss_builder())

    def _build_truncated_bwd_step(self):
        self._note_compile("train_step_truncated")
        return self._make_step(self._trunc_loss_builder())

    def _step_program(self, kind: str, key, build):
        """The jitted program of `kind` for the shape key `key`, built on
        first use. `_train_step_fn` apart (tests read and replace it),
        this mapping is every step program the net holds."""
        fn = self._step_programs.get((kind, key))
        if fn is None:
            fn = self._step_programs[(kind, key)] = build()
        return fn

    def _reset_step_programs(self):
        """Drop every cached jitted program (train steps, fused variants,
        output cache) — placement or signature changed."""
        self._train_step_fn = None
        self._output_fn = None
        self._step_programs.clear()

    # -- one step from the host ----------------------------------------------

    def _step_key(self):
        """The base of every optimizer step's rng: step `i` draws from
        fold_in(this, i), from the host or inside a fused program."""
        return jax.random.PRNGKey(self.net_conf.seed ^ 0x5EED)

    def _run_step(self, step_fn, data, stateful_states=None):
        lr = schedule_lr(self.net_conf, self.iteration)
        rng = jax.random.fold_in(self._step_key(), self.iteration)
        states = stateful_states if stateful_states is not None else self.state_list
        out = step_fn(
            self.params_list, states, self.upd_state,
            _tm(jnp.asarray, data),
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

    def _fit_step(self, features, labels, f_masks, l_masks,
                  stateful_states=None):
        """One optimizer step. Returns (states, the device score)."""
        if self._train_step_fn is None:
            self._train_step_fn = self._build_train_step()
            self._note_compile("train_step")
        return self._run_step(
            self._train_step_fn, (features, labels, f_masks, l_masks),
            stateful_states)

    def _fit_step_truncated(self, dataA, dataB, stateful_states):
        """One TBPTT segment step with a backward-truncation boundary
        between slice A (state-carry, stop-gradient) and slice B."""
        return self._run_step(
            self._step_program("truncated", None,
                               self._build_truncated_bwd_step),
            dataA + dataB, stateful_states)

    # -- truncated BPTT ------------------------------------------------------

    def _is_tbptt(self, data) -> bool:
        return (self.conf.backprop_type == BackpropType.TRUNCATED_BPTT
                and any(a.ndim == 3 for a in _leaves(data[0])))

    def _tbptt_segments(self, data):
        """(T, n_seg, seg, bwd, whole) of a TBPTT batch: `whole` when the
        segments tile the time axis with no ragged tail and every leaf
        with a time axis shares T — what the fixed-size `dynamic_slice`
        segmentation of the fused programs can express."""
        x, y, fm, lm = data
        T = _time_steps(x)
        seg = int(self.conf.tbptt_fwd_length)
        bwd = int(self.conf.tbptt_bwd_length)
        n_seg = -(-T // seg)
        timed = [a for a in _leaves((x, y)) if a.ndim == 3] \
            + [m for m in _leaves((fm, lm)) if m.ndim == 2]
        whole = T == n_seg * seg and all(a.shape[1] == T for a in timed)
        return T, n_seg, seg, bwd, whole

    def _seeded_states(self):
        """state_list copy with {} seeded for recurrent layers (the
        TBPTT zero-state trigger, shared by the loop and fused paths)."""
        states = list(self.state_list)
        for i, conf in enumerate(self._ordered_layer_confs()):
            if _is_recurrent(conf) and states[i] is None:
                states[i] = {}
        return states

    def _keep_non_recurrent(self, states):
        """What a TBPTT batch leaves in state_list: only non-RNN state
        (running stats) persists; the RNN carry is per-batch."""
        return [
            self.state_list[i] if _is_recurrent(conf) else st
            for i, (conf, st) in enumerate(
                zip(self._ordered_layer_confs(), states))
        ]

    def _fit_tbptt(self, ds):
        """Truncated BPTT: split time into segments of tbptt_fwd_length and
        carry RNN state across segments (reference:
        MultiLayerNetwork.doTruncatedBPTT :1333, ComputationGraph
        .doTruncatedBPTT). When tbptt_bwd_length < tbptt_fwd_length, each
        segment's gradient is truncated to its last bwd_length timesteps
        (config tBPTTBackwardLength).

        When the batch is `whole` (`_tbptt_segments`), no listeners are
        attached and stats collection is off, all segments run in ONE
        jitted dispatch (`_build_tbptt_fused_step`) — same math, ~n_seg
        fewer host->device round-trips. Listeners keep the loop path so
        per-iteration callbacks observe the params of *their* iteration."""
        data = self._batch_data(ds)
        T, n_seg, seg, bwd, whole = self._tbptt_segments(data)
        if whole and not self.listeners and not self._collect_stats:
            self._fit_tbptt_fused(data, n_seg, seg, bwd)
            return
        states = self._seeded_states()
        cut = lambda lo, hi: _cut_time(data, lambda a: a[:, lo:hi])
        n_examples = getattr(ds, "reported_examples", None) \
            or ds.num_examples()
        for start in range(0, T, seg):
            end = min(start + seg, T)
            if bwd < end - start:
                states, _ = self._fit_step_truncated(
                    cut(start, end - bwd), cut(end - bwd, end),
                    stateful_states=states)
            else:
                states, _ = self._fit_step(
                    *cut(start, end), stateful_states=states)
            self._notify(n_examples, ds)
        self.state_list = self._keep_non_recurrent(states)

    @staticmethod
    def _make_seg_data(seg: int, bwd: int):
        """TBPTT time-segmentation under jit: returns seg_data(data, i) ->
        the step-body data for segment i (slice A's batch followed by
        slice B's when bwd < seg, the plain batch otherwise). Uses
        dynamic_slice so `i` may be a traced scan index."""

        def cut(a, s0, ln):
            return jax.lax.dynamic_slice_in_dim(a, s0, ln, axis=1)

        def seg_data(data, i):
            start = i * seg
            if bwd < seg:
                nA = seg - bwd
                return (_cut_time(data, lambda a: cut(a, start, nA))
                        + _cut_time(data, lambda a: cut(a, start + nA, bwd)))
            return _cut_time(data, lambda a: cut(a, start, seg))

        return seg_data

    @staticmethod
    def _step_rng_and_t(key, t0, i):
        """Per-step (rng, t) inside a fused scan: t0 is the iteration
        counter as EXACT uint32 (float32 would collapse consecutive
        steps' dropout rng past 2^24 iterations), i the scan index. The
        ONE derivation every fused program shares with `_run_step`'s
        per-step fold_in(key, iteration)."""
        ti = t0 + jnp.asarray(i, t0.dtype)
        return jax.random.fold_in(key, ti), ti.astype(jnp.float32)

    def _fused_body(self, truncated: bool = False):
        """The step body of the fused programs: without stats collection
        (callers keep the loop path when collection is on)."""
        assert not self._collect_stats, (
            "fused programs do not collect per-iteration stats")
        return self._make_step_body(
            self._trunc_loss_builder() if truncated
            else self._std_loss_builder())

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
        tail (equivalence pinned by tests/test_tbptt_fused.py and
        tests/test_fused_fit.py). Callers guarantee a `whole` batch."""
        body = self._fused_body(bwd < seg)
        seg_data = self._make_seg_data(seg, bwd)

        def step(params, states, upd_state, data, lrs, t0, _rng_unused):
            key = self._step_key()

            def run_seg(params, states, upd_state, i):
                rng, t = self._step_rng_and_t(key, t0, i)
                return body(params, states, upd_state, seg_data(data, i),
                            lrs[i], t, rng)

            # segment 0 inline: its merged states establish the carry
            # pytree (zero-state {} -> populated h/c) for the scan
            params, states, upd_state, s0, d0 = run_seg(
                params, states, upd_state, 0)
            if n_seg == 1:
                return params, states, upd_state, s0, d0

            def scan_body(carry, i):
                p, st, us = carry
                p, st, us, score, dg = run_seg(p, st, us, i)
                return (p, st, us), (score, dg)

            (params, states, upd_state), (scores, diags) = jax.lax.scan(
                scan_body, (params, states, upd_state),
                jnp.arange(1, n_seg))
            # whole-batch diagnostic: final score, worst grad norm of
            # any segment (a NaN segment poisons later params, so the
            # final loss carries the non-finite signal regardless)
            diag = jnp.stack([diags[-1, 0],
                              jnp.maximum(d0[1], jnp.max(diags[:, 1]))])
            return params, states, upd_state, scores[-1], diag

        return self._jit_step(step)

    def _run_fused(self, step_fn, states, data, n_steps: int):
        """Host side of a fused dispatch of `n_steps` optimizer steps:
        their lr schedule values and one call. Returns the new states."""
        lrs = jnp.asarray(
            [schedule_lr(self.net_conf, self.iteration + i)
             for i in range(n_steps)], jnp.float32)
        params, states, upd, score, diag = step_fn(
            self.params_list, states, self.upd_state, data, lrs,
            jnp.asarray(self.iteration, jnp.uint32), None)
        self.params_list = params
        self.upd_state = upd
        self._score = score
        self._step_diag = diag
        self._last_stats = None
        self.iteration += n_steps
        return states

    def _fit_tbptt_fused(self, data, n_seg: int, seg: int, bwd: int):
        """Run one TBPTT fit batch through the single-dispatch fused step
        (see `_build_tbptt_fused_step`)."""
        key = (n_seg, seg, bwd)
        step_fn = self._step_program(
            "tbptt_fused", key, lambda: self._build_tbptt_fused_step(*key))
        states = self._run_fused(step_fn, self._seeded_states(),
                                 _tm(jnp.asarray, data), n_seg)
        self.state_list = self._keep_non_recurrent(states)

    # -- multi-batch fused fit (set_fused_steps) -----------------------------

    def _fused_fit_supported(self) -> bool:
        """Whether this network can run `_fit_datasets_fused`."""
        return True

    def _ds_signature(self, ds):
        """Shape/mask signature — only identically-shaped consecutive
        batches are stacked into one fused dispatch."""
        return _tm(lambda a: tuple(a.shape), self._batch_data(ds))

    def _fit_datasets_fused(self, ds_list):
        """K same-shape minibatches in ONE jitted dispatch (see
        NetworkBase.set_fused_steps). Dispatches to the cross-batch TBPTT
        program for TBPTT batches, the stacked-scan program otherwise;
        anything ineligible (ragged TBPTT tail) falls back per-batch."""
        datas = [self._batch_data(d) for d in ds_list]
        if not self._is_tbptt(datas[0]):
            self._fit_std_batched(datas)
            return
        _, n_seg, seg, bwd, whole = self._tbptt_segments(datas[0])
        if whole:
            self._fit_tbptt_batched(datas, n_seg, seg, bwd)
        else:
            for d in ds_list:
                self._fit_dataset(d)

    @staticmethod
    def _stack_datasets(datas):
        """K batches as one: every leaf [K, ...]."""
        return _tm(lambda *vals: jnp.stack([jnp.asarray(v) for v in vals]),
                   *datas)

    def _build_multi_fit_step(self, K: int):
        """K standard optimizer steps as one `lax.scan` over the stacked
        batches — same per-step lr/t/rng derivation as `_run_step`, K-1
        fewer dispatches (equivalence: tests/test_fused_fit.py)."""
        body = self._fused_body()

        def step(params, states, upd_state, data_stack, lrs, t0,
                 _rng_unused):
            key = self._step_key()

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

    def _fit_std_batched(self, datas):
        K = len(datas)
        fn = self._step_program(
            "multi_fit", K, lambda: self._build_multi_fit_step(K))
        self.state_list = self._run_fused(
            fn, self.state_list, self._stack_datasets(datas), K)

    def _build_tbptt_batched_step(self, K: int, n_seg: int, seg: int,
                                  bwd: int):
        """K TBPTT fit batches (each n_seg segments, RNN state reset at
        every batch boundary, BN stats carried throughout) in ONE jitted
        dispatch. Batch 0's segment 0 runs inline to bootstrap the RNN
        carry structure ({} -> {"h","c"}); batches 1..K-1 scan with a
        zeros reset — identical math to K calls of `_fit_tbptt` (the
        layer seeds zero state for {} exactly as `reset` writes zeros;
        equivalence: tests/test_fused_fit.py)."""
        body = self._fused_body(bwd < seg)
        seg_data = self._make_seg_data(seg, bwd)
        rec = [_is_recurrent(c) for c in self._ordered_layer_confs()]

        def reset_rnn(states):
            return [
                _tm(jnp.zeros_like, st) if is_r else st
                for st, is_r in zip(states, rec)
            ]

        def step(params, states, upd_state, data_stack, lrs, t0,
                 _rng_unused):
            key = self._step_key()
            pick = lambda b: _tm(lambda a: a[b], data_stack)

            def run_seg(p, st, us, data_b, i_seg, j):
                rng, t = self._step_rng_and_t(key, t0, j)
                return body(p, st, us, seg_data(data_b, i_seg),
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

    def _fit_tbptt_batched(self, datas, n_seg: int, seg: int, bwd: int):
        K = len(datas)
        if K == 1:
            self._fit_tbptt_fused(datas[0], n_seg, seg, bwd)
            return
        key = (K, n_seg, seg, bwd)
        fn = self._step_program(
            "tbptt_batched", key,
            lambda: self._build_tbptt_batched_step(*key))
        states = self._run_fused(fn, self._seeded_states(),
                                 self._stack_datasets(datas), K * n_seg)
        self.state_list = self._keep_non_recurrent(states)

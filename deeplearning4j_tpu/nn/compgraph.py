"""ComputationGraph — the DAG network.

Analog of the reference's nn/graph/ComputationGraph.java (3,062 LoC).
TPU-first translation of its design decisions:

- reference: topo order computed once (:340,1055), forward = walk topo
  order calling Vertex.doForward (:1291-1292), backward = reverse walk with
  explicit epsilon accumulation at fan-out vertices (:1480-1502).
- here: the same cached topo order drives a *pure function* of
  (params, inputs) built once and jitted; backward is jax.grad of that
  function, so fan-out accumulation is handled by autodiff and the whole
  step (forward + backward + updater) compiles to one XLA program.

Parameters are a list of per-layer-vertex dicts in topological order —
the same flattening convention as MultiLayerNetwork, so params()/
set_params() and the serializer work identically.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.common.dtypes import policy_from_name
from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.data.iterators import (
    DataSetIterator,
    ListDataSetIterator,
    MultiDataSetIterator,
)
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.graph import (
    ComputationGraphConfiguration,
    LayerVertex,
)
from deeplearning4j_tpu.nn.layers.registry import (
    LayerContext,
    forward_layer,
)
from deeplearning4j_tpu.nn.multilayer import (
    _OUTPUT_LAYER_TYPES,
    _is_recurrent,
    _l1_l2_penalty,
    _preout_of_output_layer,
    layer_scope,
)
from deeplearning4j_tpu.nn.netbase import NetworkBase
from deeplearning4j_tpu.ops.losses import (
    example_presence,
    loss_value,
    masked_example_mean,
    sparse_head_loss,
)
from deeplearning4j_tpu.train.evaluation import Evaluation
from deeplearning4j_tpu.train.updaters import updater_from_conf


def _head_in_blocks(lc) -> bool:
    """Does this output layer take its loss `head_rows_block` rows at a
    time (ops/losses.sparse_head_loss), so that the whole batch's logits
    never exist at once?"""
    return bool(getattr(lc, "head_rows_block", None))


# -- scan-over-identical-blocks ----------------------------------------------
#
# Deep nets built from repeated identical units (ResNet stage blocks) pay
# trace+compile cost proportional to depth: every unit is re-traced even
# though its program is the same. Detecting maximal runs of
# identically-configured, chain-connected units and compiling each run as
# ONE `lax.scan` over stacked per-unit params collapses that cost to one
# unit body per run — `compile_total{kind="graph_block"}` records k body
# traces unrolled vs 1 scanned. Opt-in via set_block_scan / DL4J_BLOCK_SCAN
# (forward numerics are unchanged; see the block-scan tests in
# tests/test_compgraph.py).

def _vertex_signature(v):
    """Structural identity of a vertex conf: (type, canonical-JSON config),
    or None when the vertex cannot participate in a scanned run."""
    from deeplearning4j_tpu.nn.conf.graph import (
        ElementWiseVertex,
        MergeVertex,
        ScaleVertex,
        ShiftVertex,
    )
    from deeplearning4j_tpu.nn.conf.serde import config_to_dict
    import json as _json

    if isinstance(v, LayerVertex):
        lc = v.layer
        if v.preprocessor is not None or lc.n_inputs() > 1:
            return None
        if _is_recurrent(lc) or isinstance(lc, _OUTPUT_LAYER_TYPES):
            return None
        body = lc
    elif isinstance(v, (ElementWiseVertex, MergeVertex, ScaleVertex,
                        ShiftVertex)):
        body = v
    else:
        return None
    try:
        return (type(v).__name__,
                _json.dumps(config_to_dict(body), sort_keys=True))
    except Exception:
        return None


def _detect_block_runs(conf, topo, pidx_map):
    """Find maximal runs of >=2 consecutive identical units in the topo
    order. A unit of period p starting at topo index s repeats at s+p,
    s+2p, ... when each repeated vertex has the same signature and the
    same *relative* input offsets, every offset d at local position q is
    internal (d <= q) or the previous unit's exit (d == q+1), no vertex
    but the run's exit is consumed outside the run, and each unit holds
    at least one layer. Returns run records consumed by _exec_block_run."""
    index = {n: i for i, n in enumerate(topo)}
    n = len(topo)
    sigs = [None] * n
    offsets = [None] * n
    for i, name in enumerate(topo):
        v = conf.vertices.get(name)
        if v is None:  # a graph input
            continue
        sigs[i] = _vertex_signature(v)
        offsets[i] = tuple(i - index[src] for src in conf.vertex_inputs[name])

    consumers = {}
    for name, ins in conf.vertex_inputs.items():
        for src in ins:
            consumers.setdefault(src, []).append(name)

    def unit_ok(s, p):
        """Template unit [s, s+p): signable, chain-connected."""
        for q in range(p):
            i = s + q
            if sigs[i] is None or offsets[i] is None:
                return False
            for d in offsets[i]:
                if not (1 <= d <= q + 1):
                    return False
        return any(
            isinstance(conf.vertices[topo[s + q]], LayerVertex)
            for q in range(p)
        )

    def repeats(s, p):
        k = 1
        while s + (k + 1) * p <= n:
            base = s + k * p
            if all(
                sigs[base + q] == sigs[s + q]
                and offsets[base + q] == offsets[s + q]
                for q in range(p)
            ):
                k += 1
            else:
                break
        return k

    def run_ok(s, p, k):
        lo, hi = s, s + p * k
        exit_name = topo[hi - 1]
        for i in range(lo, hi - 1):
            name = topo[i]
            if name in conf.outputs:
                return False
            for c in consumers.get(name, ()):
                if not (lo <= index[c] < hi):
                    return False
        return exit_name is not None

    runs = []
    i = len(conf.inputs)
    while i < n:
        found = None
        for p in range(1, (n - i) // 2 + 1):
            if not unit_ok(i, p):
                continue
            k = repeats(i, p)
            if k >= 2 and run_ok(i, p, k):
                found = (p, k)
                break  # smallest period = most units collapsed
        if found is None:
            i += 1
            continue
        p, k = found
        unit_names = topo[i:i + p]
        layer_slots = [
            q for q in range(p)
            if isinstance(conf.vertices[unit_names[q]], LayerVertex)
        ]
        pidx_rows = [
            [pidx_map[topo[i + u * p + q]] for q in layer_slots]
            for u in range(k)
        ]
        runs.append({
            "start": i,
            "period": p,
            "count": k,
            "entry": topo[i - 1],
            "exit": topo[i + p * k - 1],
            "unit_names": unit_names,
            "offsets": [offsets[i + q] for q in range(p)],
            "layer_slots": layer_slots,
            "pidx_rows": pidx_rows,
        })
        i += p * k
    return runs


def _as_multidataset(ds) -> MultiDataSet:
    if isinstance(ds, MultiDataSet):
        return ds
    if isinstance(ds, DataSet):
        out = MultiDataSet(
            [ds.features], [ds.labels],
            None if ds.features_mask is None else [ds.features_mask],
            None if ds.labels_mask is None else [ds.labels_mask],
        )
        # keep the wrapper's real-example count for listener accounting
        if hasattr(ds, "reported_examples"):
            out.reported_examples = ds.reported_examples
        return out
    raise TypeError(f"expected DataSet or MultiDataSet, got {type(ds)}")


class ComputationGraph(NetworkBase):
    """DAG network. API mirrors the reference: init, fit, output, score,
    evaluate, params/set_params."""

    def __init__(self, conf: ComputationGraphConfiguration):
        super().__init__()
        self.conf = conf
        self.net_conf = conf.net_conf
        self.policy = policy_from_name(self.net_conf.precision)
        self.updater_def = updater_from_conf(self.net_conf)
        self.topo: List[str] = conf.topological_order()
        self.layer_vertex_names: List[str] = [
            n for n in self.topo if isinstance(conf.vertices.get(n), LayerVertex)
        ]
        self._pidx: Dict[str, int] = {
            n: i for i, n in enumerate(self.layer_vertex_names)
        }
        self._layer_confs: List[L.LayerConf] = [
            conf.vertices[n].layer for n in self.layer_vertex_names
        ]
        self._output_fn = None
        self._block_scan = None  # None = DL4J_BLOCK_SCAN env decides
        self._block_runs_cache = None

    def _ordered_layer_confs(self):
        return self._layer_confs

    # -- scan-over-identical-blocks ------------------------------------------

    def set_block_scan(self, mode=True) -> "ComputationGraph":
        """Compile runs of identically-configured residual blocks as ONE
        scanned body with stacked params instead of tracing every block
        (True/"scan" on, False/"unroll" off, None = DL4J_BLOCK_SCAN env).
        Collapses `compile_total{kind="graph_block"}` and trace time on
        deep nets (ResNet-50 stage blocks); forward numerics unchanged.
        Note: feed_forward() then reports only each run's exit activation
        — per-block intermediates live inside the scan."""
        if mode not in (True, False, None, "scan", "unroll"):
            raise ValueError(
                f"set_block_scan: expected True/'scan', False/'unroll' or "
                f"None, got {mode!r}")
        self._block_scan = mode
        self._block_runs_cache = None
        self._reset_step_programs()
        return self

    def _block_scan_enabled(self) -> bool:
        mode = self._block_scan
        if mode is None:
            import os as _os

            mode = _os.environ.get("DL4J_BLOCK_SCAN", "0")
        return mode in (True, "1", "scan", "on")

    def _block_runs(self):
        """Detected identical-unit runs (cached; detection is pure conf
        analysis, so it is computed even with the scan off — the unrolled
        path uses it to count `graph_block` body traces honestly)."""
        if self._block_runs_cache is None:
            self._block_runs_cache = _detect_block_runs(
                self.conf, self.topo, self._pidx)
        return self._block_runs_cache

    # -- forward -------------------------------------------------------------

    def _forward(self, params, states, inputs: Sequence, *, training, rng,
                 input_masks: Optional[Sequence] = None, preout_outputs=False,
                 stateful=False):
        """Pure forward over the cached topo order. Returns
        (activations dict, new_states list). With preout_outputs, loss-head
        vertices also record their post-dropout input features under
        "<name>__features" (the center-loss term needs them). stateful
        seeds empty RNN state so recurrent layers return their carry
        (rnnTimeStep / TBPTT, reference: ComputationGraph rnn methods)."""
        conf = self.conf
        acts: Dict[str, jnp.ndarray] = dict(zip(conf.inputs, inputs))
        masks: Dict[str, jnp.ndarray] = {}
        if input_masks is not None:
            masks = {
                n: m for n, m in zip(conf.inputs, input_masks) if m is not None
            }
        # single-mask convenience: an rnn layer deeper in the graph uses the
        # sole input mask (the multi-input per-branch case needs explicit
        # LastTimeStep/mask vertices, as in the reference)
        sole_mask = next(iter(masks.values())) if len(masks) == 1 else None
        new_states: List[Optional[dict]] = [None] * len(self.layer_vertex_names)
        env = {"activations": acts, "input_masks": masks}
        scan_on = self._block_scan_enabled()
        run_by_start = {r["start"]: r for r in self._block_runs()}
        recompute_at = self._recompute_runs() if training else {}
        topo = self.topo
        pos = 0
        while pos < len(topo):
            name = topo[pos]
            if name in acts:
                pos += 1
                continue
            r = run_by_start.get(pos)
            if r is not None:
                x_entry = acts[r["entry"]]
                tracing = isinstance(x_entry, jax.core.Tracer)
                out = None
                if scan_on and self._run_shapes_ok(r, params, states):
                    out = self._exec_block_run(
                        r, params, states, x_entry,
                        training=training, rng=rng, sole_mask=sole_mask)
                if out is not None:
                    exit_act, st_updates = out
                    acts[r["exit"]] = exit_act
                    for pidx, ns in st_updates.items():
                        new_states[pidx] = ns
                    if tracing:
                        self._note_compile("graph_block", r["exit"])
                    pos = r["start"] + r["period"] * r["count"]
                    continue
                if tracing:
                    # unrolled: every unit's body is traced separately —
                    # count each so compile_total{kind="graph_block"}
                    # shows the collapse when the scan is on
                    for _ in range(r["count"]):
                        self._note_compile("graph_block", r["exit"])
            run = recompute_at.get(pos)
            if run is not None:
                self._exec_recompute_run(
                    run, acts, env, params, states, new_states,
                    training=training, rng=rng, sole_mask=sole_mask,
                    preout_outputs=preout_outputs, stateful=stateful)
                pos += len(run["names"])
                continue
            self._exec_vertex(name, acts, env, params, states, new_states,
                              training=training, rng=rng,
                              sole_mask=sole_mask,
                              preout_outputs=preout_outputs,
                              stateful=stateful)
            pos += 1
        return acts, new_states

    def _exec_vertex(self, name, acts, env, params, states, new_states, *,
                     training, rng, sole_mask, preout_outputs, stateful):
        """One vertex of the walk: reads its inputs from `acts`, writes its
        activation there and, for a layer, its new state into
        `new_states`."""
        conf = self.conf
        v = conf.vertices[name]
        with jax.named_scope(layer_scope(
                name, v.layer if isinstance(v, LayerVertex) else v)):
            xs = [acts[i] for i in conf.vertex_inputs[name]]
            if isinstance(v, LayerVertex):
                x = xs[0]
                timesteps = x.shape[1] if x.ndim == 3 else None
                if v.preprocessor is not None:
                    x = v.preprocessor(x, {"timesteps": timesteps})
                    if hasattr(x, "ndim") and x.ndim == 3:
                        timesteps = x.shape[1]
                pidx = self._pidx[name]
                lc = v.layer
                st = states[pidx]
                if stateful and _is_recurrent(lc) and st is None:
                    st = {}  # empty dict triggers zero-state seed + carry
                ctx = LayerContext(
                    training=training,
                    rng=(jax.random.fold_in(rng, pidx)
                         if rng is not None else None),
                    mask=(sole_mask if hasattr(x, "ndim")
                          and x.ndim == 3 else None),
                    timesteps=timesteps,
                    state=st,
                    compute_dtype=self.policy.compute_dtype,
                    extra_inputs=tuple(xs[1:]),
                )
                if (
                    preout_outputs
                    and name in conf.outputs
                    and isinstance(lc, _OUTPUT_LAYER_TYPES)
                ):
                    from deeplearning4j_tpu.nn.layers.core import (
                        apply_dropout,
                    )

                    x = apply_dropout(x, lc.dropout, ctx)
                    acts[name + "__features"] = x
                    # a head that takes its loss in blocks of rows never
                    # holds the whole batch's logits (`_loss` reads the
                    # features)
                    x = None if _head_in_blocks(lc) else \
                        _preout_of_output_layer(lc, params[pidx], x)
                    ns = None
                else:
                    x, ns = forward_layer(lc, params[pidx], x, ctx)
                new_states[pidx] = ns
                acts[name] = x
            else:
                acts[name] = v.forward(xs, env)

    # -- recomputation -------------------------------------------------------

    def _recompute_runs(self) -> Dict[int, dict]:
        """{topo position: run} of the configuration's `recompute` runs
        (cached; pure conf analysis). A run is consecutive vertices of the
        topological order; `inputs` are the activations it reads from
        outside, `exits` those of its own that are read outside it."""
        cached = getattr(self, "_recompute_cache", None)
        if cached is not None:
            return cached
        conf, out = self.conf, {}
        index = {n: i for i, n in enumerate(self.topo)}
        taken = set()
        for names in conf.recompute or ():
            names = list(names)
            unknown = [n for n in names if n not in conf.vertices]
            if not names or unknown:
                raise ValueError(f"recompute: {unknown or names} are not "
                                 "vertices of the graph")
            start = index[names[0]]
            if self.topo[start:start + len(names)] != names:
                raise ValueError(
                    f"recompute: {names} are not consecutive in the "
                    f"topological order (which has "
                    f"{self.topo[start:start + len(names)]} there)")
            if taken & set(names) or any(n in conf.outputs for n in names):
                raise ValueError(f"recompute: {names} overlap another run "
                                 "or hold an output vertex")
            taken |= set(names)
            inside = set(names)
            inputs = []
            for n in names:
                for src in conf.vertex_inputs[n]:
                    if src not in inside and src not in inputs:
                        inputs.append(src)
            exits = [n for n in names if any(
                n in ins and c not in inside
                for c, ins in conf.vertex_inputs.items())]
            out[start] = {"names": names, "inputs": inputs, "exits": exits}
        self._recompute_cache = out
        return out

    def _exec_recompute_run(self, run, acts, env, params, states, new_states,
                            **walk):
        """A `recompute` run under `jax.checkpoint`: the backward pass keeps
        the run's inputs and computes everything inside it again. The same
        vertices, scopes and arithmetic as the plain walk."""
        names, inputs, exits = run["names"], run["inputs"], run["exits"]
        slots = [self._pidx[n] for n in names if n in self._pidx]

        def body(p_run, s_run, in_acts):
            local = dict(zip(inputs, in_acts))
            local_env = dict(env, activations=local)
            params_of, states_of = dict(zip(slots, p_run)), \
                dict(zip(slots, s_run))
            ns: Dict[int, Optional[dict]] = {}
            for n in names:
                self._exec_vertex(n, local, local_env, params_of, states_of,
                                  ns, **walk)
            return [local[n] for n in exits], [ns[i] for i in slots]

        outs, ns = jax.checkpoint(body)(
            [params[i] for i in slots], [states[i] for i in slots],
            [acts[n] for n in inputs])
        acts.update(zip(exits, outs))
        for i, st in zip(slots, ns):
            new_states[i] = st

    def _run_shapes_ok(self, r, params, states) -> bool:
        """True when every unit's params/state trees share structure and
        leaf shapes — the precondition for stacking them (cached on the
        run record; shapes are fixed after init)."""
        cached = r.get("_shapes_ok")
        if cached is not None:
            return cached

        def sig(tree):
            leaves, treedef = jax.tree_util.tree_flatten(tree)
            return (str(treedef),
                    tuple((tuple(l.shape), str(l.dtype)) for l in leaves))

        ok = True
        rows = r["pidx_rows"]
        for j in range(len(r["layer_slots"])):
            base = (sig(params[rows[0][j]]), sig(states[rows[0][j]]))
            for row in rows[1:]:
                if (sig(params[row[j]]), sig(states[row[j]])) != base:
                    ok = False
        r["_shapes_ok"] = ok
        return ok

    def _exec_block_run(self, r, params, states, x, *, training, rng,
                        sole_mask):
        """Run one detected identical-unit run as a single `lax.scan`:
        per-unit params/states stacked in-graph (leading unit axis), the
        unit body replicating the per-vertex walk with run-local
        activations, the entry activation as carry. Per-layer rng keys
        fold in the REAL pidx (fed as scan xs), so dropout draws match
        the unrolled walk. Returns (exit activation, {pidx: new_state})
        or None when the unit is not shape-invariant (strided/shrinking
        units cannot be a scan carry) — caller falls back to unrolling."""
        conf = self.conf
        p, k = r["period"], r["count"]
        slots = r["layer_slots"]
        rows = r["pidx_rows"]
        unit_names = r["unit_names"]
        offsets = r["offsets"]
        slot_of = {q: j for j, q in enumerate(slots)}

        stack = lambda trees: jax.tree_util.tree_map(
            lambda *a: jnp.stack(a), *trees)
        sp = tuple(stack([params[row[j]] for row in rows])
                   for j in range(len(slots)))
        ss = tuple(stack([states[row[j]] for row in rows])
                   for j in range(len(slots)))
        pmat = jnp.asarray(rows, jnp.int32)  # [k, n_slots]

        def run_unit(carry, up, us, prow):
            local: Dict[int, jnp.ndarray] = {}
            new_sts = []
            for q, vname in enumerate(unit_names):
                v = conf.vertices[vname]
                srcs = [carry if d == q + 1 else local[q - d]
                        for d in offsets[q]]
                if isinstance(v, LayerVertex):
                    xq = srcs[0]
                    j = slot_of[q]
                    ctx = LayerContext(
                        training=training,
                        rng=(jax.random.fold_in(rng, prow[j])
                             if rng is not None else None),
                        mask=sole_mask if xq.ndim == 3 else None,
                        timesteps=xq.shape[1] if xq.ndim == 3 else None,
                        state=us[j],
                        compute_dtype=self.policy.compute_dtype,
                    )
                    with jax.named_scope(layer_scope(vname, v.layer)):
                        y, ns = forward_layer(v.layer, up[j], xq, ctx)
                    new_sts.append(ns)
                else:
                    with jax.named_scope(layer_scope(vname, v)):
                        y = v.forward(srcs, {})
                local[q] = y
            return local[p - 1], tuple(new_sts)

        # scan-carry contract: one abstract unit application must preserve
        # the entry activation's shape/dtype (a strided unit would not)
        try:
            probe = jax.eval_shape(
                lambda a: run_unit(
                    a,
                    tuple(params[i] for i in rows[0]),
                    tuple(states[i] for i in rows[0]),
                    pmat[0],
                )[0],
                x,
            )
        except Exception:
            return None
        if probe.shape != x.shape or probe.dtype != x.dtype:
            return None

        def body(carry, xs_scan):
            up, us, prow = xs_scan
            return run_unit(carry, up, us, prow)

        # one body for k units: the events carry the block's name (its
        # exit vertex, as compile_total{kind="graph_block"} has it) and,
        # under it, the first unit's layer scopes
        with jax.named_scope(f"block_{r['exit']}"):
            exit_act, ys = jax.lax.scan(body, x, (sp, ss, pmat))
        updates = {}
        for j in range(len(slots)):
            nsj = ys[j]
            if nsj is None:
                continue
            for u in range(k):
                updates[rows[u][j]] = jax.tree_util.tree_map(
                    lambda a, u=u: a[u], nsj)
        return exit_act, updates

    # -- loss ----------------------------------------------------------------

    def _loss(self, params, states, xs, ys, f_masks, l_masks, rng, training=True):
        conf = self.conf
        xs = [self.policy.cast_input(x) for x in xs]
        acts, new_states = self._forward(
            params, states, xs, training=training, rng=rng,
            input_masks=f_masks, preout_outputs=True,
        )
        with jax.named_scope("loss"):
            score = 0.0
            n_heads = 0
            for i, name in enumerate(conf.outputs):
                v = conf.vertices[name]
                if not (isinstance(v, LayerVertex)
                        and isinstance(v.layer, _OUTPUT_LAYER_TYPES)):
                    continue
                lc = v.layer
                lm = l_masks[i] if l_masks is not None else None
                if _head_in_blocks(lc):
                    with jax.named_scope(layer_scope(name, lc)):
                        per_ex = sparse_head_loss(
                            acts[name + "__features"],
                            params[self._pidx[name]], ys[i], lm,
                            rows_block=int(lc.head_rows_block),
                            compute_dtype=self.policy.compute_dtype)
                else:
                    per_ex = loss_value(
                        lc.loss, ys[i], self.policy.cast_output(acts[name]),
                        lc.activation, lm,
                    )
                score = score + masked_example_mean(per_ex, lm)
                if isinstance(lc, L.CenterLossOutputLayer):
                    # center loss head (reference: CenterLossOutputLayer.java):
                    # + lambda * mean(0.5||f - c_y||^2) on the head's input
                    # features, centers EMA-updated as non-trainable state
                    pidx = self._pidx[name]
                    feats = acts[name + "__features"]
                    centers = states[pidx]["centers"].astype(feats.dtype)
                    y32 = ys[i].astype(feats.dtype)
                    diff = feats - y32 @ centers
                    center_per_ex = 0.5 * jnp.sum(diff * diff, axis=-1)
                    present = example_presence(per_ex, lm)
                    score = score + lc.lambda_ * (
                        jnp.sum(center_per_ex * present)
                        / jnp.maximum(jnp.sum(present), 1.0))
                    if training:
                        f_sg = jax.lax.stop_gradient(feats)
                        yw = y32 * present[:, None]
                        counts = jnp.sum(yw, axis=0)[:, None]
                        means = (yw.T @ f_sg) / jnp.maximum(counts, 1.0)
                        updated = jnp.where(
                            counts > 0,
                            (1.0 - lc.alpha) * centers + lc.alpha * means,
                            centers,
                        )
                        new_states[pidx] = {
                            "centers": updated.astype(states[pidx]["centers"].dtype)
                        }
                n_heads += 1
            if n_heads == 0:
                raise ValueError(
                    "no output vertex is a loss head (OutputLayer/RnnOutputLayer/"
                    "LossLayer) — cannot compute a training loss"
                )
            return score + _l1_l2_penalty(self._layer_confs, params), \
                new_states

    # -- fit -----------------------------------------------------------------

    def fit(self, data, labels=None, *, epochs: int = 1, batch_size: int = 32,
            async_prefetch: bool = True, prefetch_buffer: int = 4,
            hang_timeout: float = None, resume_from: str = None,
            run_ledger=None):
        """Train. Accepts (features, labels) arrays, a DataSet/MultiDataSet,
        or a DataSetIterator/MultiDataSetIterator (reference:
        ComputationGraph.fit overloads :857-867). With async_prefetch the
        staged input pipeline (nn/netbase._stage_input_pipeline) feeds the
        loop; prefetch_buffer is the host stage's queue depth.
        `hang_timeout` (seconds) arms the hang watchdog: a stalled step
        raises utils.health.StepHangError with a flight-recorder dump
        path instead of blocking forever — pick it above the worst-case
        single phase (first-step trace+compile, longest legitimate data
        wait). `resume_from` names a checkpoint directory: the newest
        checkpoint loads into this net and the iterator fast-forwards to
        the saved mid-epoch position (empty directory = fresh start;
        `epochs` stays the TOTAL target)."""
        self._require_init()
        if isinstance(data, (DataSetIterator, MultiDataSetIterator)):
            iterator = data
        elif isinstance(data, MultiDataSet):
            iterator = _ListMultiIterator(data, batch_size)
        elif isinstance(data, DataSet):
            iterator = ListDataSetIterator(data, batch_size)
        else:
            iterator = ListDataSetIterator(
                DataSet(np.asarray(data), np.asarray(labels)), batch_size
            )
        return self._run_fit(iterator, epochs, async_prefetch,
                             prefetch_buffer, hang_timeout=hang_timeout,
                             resume_from=resume_from,
                             run_ledger=run_ledger)

    def _batch_data(self, ds):
        mds = _as_multidataset(ds)
        return (mds.features, mds.labels, mds.features_masks,
                mds.labels_masks)

    def _fit_dataset(self, ds):
        mds = _as_multidataset(ds)
        data = self._batch_data(mds)
        if self._is_tbptt(data):
            self._fit_tbptt(mds)
            return
        states, _ = self._fit_step(*data)
        self.state_list = states
        self._notify(getattr(mds, "reported_examples", None)
                     or mds.num_examples(), mds)

    # -- inference -----------------------------------------------------------

    def output(self, *inputs, input_masks: Optional[Sequence] = None):
        """Forward pass; returns one array for a single-output graph, else
        a list in set_outputs order (reference: ComputationGraph.output,
        incl. the output(INDArray[], masks) overloads — input_masks aligns
        with the graph's inputs and feeds mask-aware vertices such as
        LastTimeStepVertex)."""
        self._require_init()
        xs = [jnp.asarray(x) for x in inputs]
        masks = None
        if input_masks is not None:
            if len(input_masks) != len(self.conf.inputs):
                raise ValueError(
                    f"input_masks has {len(input_masks)} entries but the "
                    f"graph has {len(self.conf.inputs)} inputs "
                    f"({self.conf.inputs}); pass one mask (or None) per input"
                )
            masks = [
                None if m is None else jnp.asarray(m) for m in input_masks
            ]
        # shape-keyed jit cache + compile counter (same contract as
        # MultiLayerNetwork.output — see output_compile_count)
        key = (
            tuple((x.shape, str(x.dtype)) for x in xs),
            None if masks is None else tuple(
                None if m is None else (m.shape, str(m.dtype)) for m in masks
            ),
        )
        def make_fn():
            def fwd(params, states, xs, masks):
                xs = [self.policy.cast_input(x) for x in xs]
                acts, _ = self._forward(
                    params, states, xs, training=False, rng=None,
                    input_masks=masks,
                )
                return [
                    self.policy.cast_output(acts[n])
                    for n in self.conf.outputs
                ]

            return jax.jit(fwd)

        fn = self._cached_output_fn(key, make_fn)
        outs = fn(self.params_list, self.state_list, xs, masks)
        return outs[0] if len(outs) == 1 else outs

    def feed_forward(self, *inputs):
        """All vertex activations as a dict — debugging/inspection path."""
        self._require_init()
        acts, _ = self._forward(
            self.params_list, self.state_list,
            [jnp.asarray(x) for x in inputs], training=False, rng=None,
        )
        return acts

    def score(self, data, labels=None) -> float:
        self._require_init()
        if isinstance(data, (DataSet, MultiDataSet)):
            mds = _as_multidataset(data)
        else:
            mds = _as_multidataset(DataSet(np.asarray(data), np.asarray(labels)))
        s, _ = self._loss(
            self.params_list, self.state_list,
            [jnp.asarray(x) for x in mds.features],
            [jnp.asarray(y) for y in mds.labels],
            None if mds.features_masks is None else [
                None if m is None else jnp.asarray(m) for m in mds.features_masks
            ],
            None if mds.labels_masks is None else [
                None if m is None else jnp.asarray(m) for m in mds.labels_masks
            ],
            rng=None, training=False,
        )
        return float(s)

    def evaluate(self, data, labels=None, batch_size: int = 256,
                 output_index: int = 0) -> Evaluation:
        """Classification evaluation; multi-input graphs evaluate on all
        features, multi-output graphs on the head selected by
        output_index (reference: ComputationGraph.evaluate)."""
        ev = Evaluation()
        if isinstance(data, (DataSetIterator, MultiDataSetIterator)):
            batches = data
        elif isinstance(data, (DataSet, MultiDataSet)):
            batches = [data]
        else:
            batches = DataSet(np.asarray(data), np.asarray(labels)).split_batches(batch_size)
        for b in batches:
            mds = _as_multidataset(b)
            out = self.output(*mds.features, input_masks=mds.features_masks)
            if isinstance(out, list):
                out = out[output_index]
            lm = (
                None if mds.labels_masks is None
                else mds.labels_masks[output_index]
            )
            ev.eval_batch(mds.labels[output_index], out, lm)
        return ev

    # -- rnn streaming inference ---------------------------------------------

    def rnn_time_step(self, *inputs):
        """Stateful streaming inference over the graph (reference:
        ComputationGraph.rnnTimeStep). Each input: [batch, time, nIn] (or
        [batch, nIn] for a single step). Returns outputs in set_outputs
        order (single array for a single-output graph)."""
        self._require_init()
        xs = [jnp.asarray(x) for x in inputs]
        single = all(x.ndim == 2 for x in xs)
        if single:
            xs = [x[:, None, :] for x in xs]
        # only the recurrent carry is held between calls; non-recurrent
        # state (BN running stats) is always read fresh from state_list so
        # streaming matches output() even after an interleaved fit()
        carry = getattr(self, "_rnn_carry", None) or {}
        # a batch-size change is a NEW stream: drop the stale carry
        # (same contract as MultiLayerNetwork.rnn_time_step) instead of
        # leaking a previous caller's hidden state into this one
        bsz = xs[0].shape[0]
        if carry and any(v.shape[0] != bsz
                         for st in carry.values() for v in st.values()):
            carry = {}
            self._rnn_carry = None
        states = [
            carry.get(i, {}) if _is_recurrent(lc) else self.state_list[i]
            for i, lc in enumerate(self._layer_confs)
        ]
        acts, new_states = self._forward(
            self.params_list, states,
            [self.policy.cast_input(x) for x in xs],
            training=False, rng=None, stateful=True,
        )
        merged = self._merge_states(states, new_states)
        self._rnn_carry = {
            i: merged[i]
            for i, lc in enumerate(self._layer_confs) if _is_recurrent(lc)
        }
        outs = [self.policy.cast_output(acts[n]) for n in self.conf.outputs]
        if single:
            outs = [o[:, 0] if o.ndim == 3 else o for o in outs]
        return outs[0] if len(outs) == 1 else outs

    def rnn_clear_previous_state(self):
        self._rnn_carry = None

    def clear_rnn_state(self):
        """Alias of rnn_clear_previous_state (the MultiLayerNetwork
        streaming API carries the same name)."""
        self.rnn_clear_previous_state()

    def clone(self) -> "ComputationGraph":
        import copy

        other = ComputationGraph(copy.deepcopy(self.conf))
        if self.params_list is not None:
            other.init()
            other.params_list = jax.tree_util.tree_map(
                lambda a: a, self.params_list
            )
            other.state_list = [
                None if s is None else dict(s) for s in self.state_list
            ]
            other.upd_state = jax.tree_util.tree_map(lambda a: a, self.upd_state)
            other.iteration = self.iteration
            other.epoch = self.epoch
        return other


class _ListMultiIterator(MultiDataSetIterator):
    """Minibatches from one in-memory MultiDataSet."""

    def __init__(self, mds: MultiDataSet, batch: int):
        self.mds = mds
        self.batch = batch

    def __iter__(self):
        n = self.mds.num_examples()
        for i in range(0, n, self.batch):
            sl = slice(i, min(i + self.batch, n))

            def cut(arrs):
                return None if arrs is None else [
                    None if a is None else a[sl] for a in arrs
                ]

            yield MultiDataSet(
                [f[sl] for f in self.mds.features],
                [l[sl] for l in self.mds.labels],
                cut(self.mds.features_masks),
                cut(self.mds.labels_masks),
            )

    def batch_size(self):
        return self.batch

    def total_examples(self):
        return self.mds.num_examples()

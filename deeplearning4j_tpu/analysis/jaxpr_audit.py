"""Jaxpr program auditor — TPU hazards caught on an abstract trace.

One `jax.make_jaxpr` of a network's train-step loss (abstract inputs, no
compile, no device) and a walk over the program catches the hazard
classes that otherwise only show up as slow steps or OOMs on real
silicon:

  JX001  float64/complex128 values — TPUs emulate f64 at 10-100x cost
  JX002  widening float casts (bf16/f16 -> f32, f32 -> f64) — each one
         is a promotion point paying bandwidth for precision
  JX003  large constants folded into the program — baked into every
         executable and re-shipped per trace (pass them as arguments)
  JX004  host callbacks inside jit — a device->host round trip per step
  JX005  params with no cotangent path to the loss — dead weights that
         still cost memory, init time and optimizer state
  JX006  non-donated step buffers on a device backend — params + updater
         state held twice across the update (peak memory doubles)

Two entry points: `audit_fn` for any jittable callable (used by tests
and ad-hoc investigation), `audit_network` for a MultiLayerNetwork /
ComputationGraph (used by `net.doctor()` and `cli doctor`). The walk
recurses into sub-jaxprs (scan/while/cond bodies), so an LSTM's scanned
cell is audited too.
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.extend import core as jax_core

from deeplearning4j_tpu.analysis.findings import (
    ERROR,
    INFO,
    WARNING,
    Finding,
)

LARGE_CONST_BYTES = 1 << 20  # 1 MiB: bigger than any literal that belongs

_WIDE_FLOATS = ("float64", "complex128")
_FLOAT_WIDTH = {"bfloat16": 2, "float16": 2, "float32": 4, "float64": 8}
_CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                   "callback", "debug_print", "host_callback")


def _iter_jaxprs(jaxpr):
    """Yield a jaxpr and every sub-jaxpr reachable through eqn params
    (scan/while/cond/pjit bodies)."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for sub in _extract_jaxprs(v):
                yield from _iter_jaxprs(sub)


def _extract_jaxprs(v):
    if isinstance(v, jax_core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jax_core.Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for item in v:
            yield from _extract_jaxprs(item)


def _aval_dtype(var) -> Optional[str]:
    aval = getattr(var, "aval", None)
    dt = getattr(aval, "dtype", None)
    return None if dt is None else str(dt)


def _walk_eqns(closed: jax_core.ClosedJaxpr):
    for jaxpr in _iter_jaxprs(closed.jaxpr):
        yield from jaxpr.eqns


def audit_closed_jaxpr(
    closed: jax_core.ClosedJaxpr,
    *,
    large_const_bytes: int = LARGE_CONST_BYTES,
    what: str = "program",
) -> List[Finding]:
    """JX001-JX004 over an already-traced program."""
    findings: List[Finding] = []

    # JX001: any f64/c128 aval anywhere. Top-level invars/constvars are
    # counted once; inside sub-jaxprs only eqn OUTPUTS count (a
    # sub-jaxpr's invars alias values the enclosing level already
    # counted — tallying them again would inflate the diagnosis)
    f64_prims = {}
    for var in list(closed.jaxpr.invars) + list(closed.jaxpr.constvars):
        if _aval_dtype(var) in _WIDE_FLOATS:
            f64_prims["input/const"] = f64_prims.get("input/const", 0) + 1
    for eqn in _walk_eqns(closed):
        for var in eqn.outvars:
            if _aval_dtype(var) in _WIDE_FLOATS:
                key = eqn.primitive.name
                f64_prims[key] = f64_prims.get(key, 0) + 1
    if f64_prims:
        total = sum(f64_prims.values())
        findings.append(Finding(
            "JX001", ERROR, f"jaxpr:{what}",
            f"{total} float64/complex128 value(s) in the program "
            f"(by source: {dict(sorted(f64_prims.items()))}) — TPUs have "
            "no f64 units; this runs emulated",
            "keep x64 disabled, or cast the offending inputs/constants "
            "to f32 before the jit boundary"))

    # JX002: widening float casts (dedup by src->dst pair)
    widenings = {}
    for eqn in _walk_eqns(closed):
        if eqn.primitive.name != "convert_element_type":
            continue
        src = _aval_dtype(eqn.invars[0]) if eqn.invars else None
        dst = _aval_dtype(eqn.outvars[0]) if eqn.outvars else None
        if (src in _FLOAT_WIDTH and dst in _FLOAT_WIDTH
                and _FLOAT_WIDTH[dst] > _FLOAT_WIDTH[src]):
            key = (src, dst)
            widenings[key] = widenings.get(key, 0) + 1
    for (src, dst), n in sorted(widenings.items()):
        sev = WARNING if dst == "float64" else INFO
        findings.append(Finding(
            "JX002", sev, f"jaxpr:{what}",
            f"{n} widening cast(s) {src} -> {dst} in the program",
            "intentional at loss/accumulation boundaries; anywhere else "
            "it silently pays f32 bandwidth for bf16 math",
            name=f"JX002:jaxpr:{what}:{src}->{dst}"))

    # JX003: big constants folded into the graph
    for i, const in enumerate(closed.consts):
        nbytes = getattr(const, "nbytes", None)
        if nbytes is None:
            try:
                nbytes = np.asarray(const).nbytes
            except Exception:
                continue
        if nbytes >= large_const_bytes:
            shape = getattr(const, "shape", ())
            findings.append(Finding(
                "JX003", WARNING, f"jaxpr:{what}",
                f"constant #{i} ({nbytes / 2**20:.1f} MiB, shape {shape}) "
                "is folded into the program — it is re-traced into every "
                "shape variant and resident in every executable",
                "pass it as a function argument (or device_put it once) "
                "instead of closing over it",
                name=f"JX003:jaxpr:{what}:const{i}"))

    # JX004: host callbacks under jit
    callbacks = {}
    for eqn in _walk_eqns(closed):
        pname = eqn.primitive.name
        if pname in _CALLBACK_PRIMS or "callback" in pname:
            callbacks[pname] = callbacks.get(pname, 0) + 1
    for pname, n in sorted(callbacks.items()):
        findings.append(Finding(
            "JX004", WARNING, f"jaxpr:{what}",
            f"{n} host callback eqn(s) [{pname}] inside the program — "
            "each forces a device->host sync per step",
            "move host work outside jit, or gate debug callbacks off the "
            "hot path",
            name=f"JX004:jaxpr:{what}:{pname}"))

    return findings


def _live_invars(jaxpr, out_slice: Optional[int] = None):
    """Conservative liveness: which invars can reach the (first
    `out_slice`) outputs. One reverse pass suffices — eqns are in
    topological order. Sub-jaxpr-calling eqns are treated atomically
    (an invar consumed by a live scan counts as live), which can only
    under-report dead params, never false-positive them."""
    outs = jaxpr.outvars if out_slice is None else jaxpr.outvars[:out_slice]
    live = {v for v in outs if isinstance(v, jax_core.Var)}
    for eqn in reversed(jaxpr.eqns):
        if any(v in live for v in eqn.outvars):
            live.update(v for v in eqn.invars
                        if isinstance(v, jax_core.Var))
    return live


def _dead_arg_findings(closed, arg_leaf_labels: Sequence[str],
                       n_score_outputs: Optional[int],
                       what: str, code_target: str) -> List[Finding]:
    live = _live_invars(closed.jaxpr, n_score_outputs)
    findings = []
    for var, label in zip(closed.jaxpr.invars, arg_leaf_labels):
        if label is None:
            continue  # not a leaf we audit (states, data, rng)
        if var not in live:
            findings.append(Finding(
                "JX005", WARNING, label,
                f"{code_target} has no path to the loss — it is "
                "initialized, stored, and optimizer-tracked but can never "
                "receive a gradient",
                "remove the dead layer/vertex, or wire it into an output",
                name=f"JX005:{label}"))
    return findings


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "idx"):
            parts.append(f"[{p.idx}]")
        elif hasattr(p, "key"):
            parts.append(f".{p.key}")
        else:
            parts.append(str(p))
    return "".join(parts)


def audit_fn(fn, *example_args,
             large_const_bytes: int = LARGE_CONST_BYTES,
             what: str = "fn") -> List[Finding]:
    """Audit any jittable callable on abstract inputs (arrays or
    jax.ShapeDtypeStruct). Dead-input analysis runs against ALL outputs."""
    closed = jax.make_jaxpr(fn)(*example_args)
    findings = audit_closed_jaxpr(
        closed, large_const_bytes=large_const_bytes, what=what)
    leaves_with_path, _ = jax.tree_util.tree_flatten_with_path(example_args)
    labels = [f"{what}:arg{_path_str(path)}"
              for path, _ in leaves_with_path]
    findings.extend(_dead_arg_findings(
        closed, labels, None, what, "input"))
    return findings


def check_donation(donate_argnums: Tuple[int, ...],
                   backend: Optional[str] = None) -> List[Finding]:
    """JX006: on device backends the train step must donate its params
    and updater-state buffers (trainstep._make_step donates argnums 0 and
    2) or peak memory holds both the old and new copies."""
    backend = backend or jax.default_backend()
    if backend == "cpu":
        return []  # donation is a no-op on cpu; nothing to enforce
    missing = [i for i in (0, 2) if i not in tuple(donate_argnums)]
    if not missing:
        return []
    return [Finding(
        "JX006", WARNING, f"train_step:{backend}",
        f"train-step argnums {missing} (params/updater state) are not "
        f"donated on the {backend} backend — both old and new buffers "
        "are live across the update, doubling peak parameter memory",
        "jit the step with donate_argnums=(0, 2) as "
        "nn/trainstep._make_step does")]


# -- network-level audit ------------------------------------------------------


def _sds(shape, dtype=np.float32):
    return jax.ShapeDtypeStruct(tuple(int(s) for s in shape), dtype)


def _features_sds(it, batch: int, timesteps: int):
    from deeplearning4j_tpu.nn.conf.inputs import (
        ConvolutionalFlatInput,
        ConvolutionalInput,
        FeedForwardInput,
        RecurrentInput,
        TokenSequenceInput,
    )

    if isinstance(it, TokenSequenceInput):
        return _sds((batch, it.timesteps or timesteps), np.int32)
    if isinstance(it, ConvolutionalInput):
        return _sds((batch, it.height, it.width, it.channels))
    if isinstance(it, ConvolutionalFlatInput):
        return _sds((batch, it.arity()))
    if isinstance(it, RecurrentInput):
        return _sds((batch, it.timesteps or timesteps, it.size))
    if isinstance(it, FeedForwardInput):
        return _sds((batch, it.size))
    return None


def _labels_sds(out_type, batch: int, timesteps: int, head=None):
    """Abstract labels for an output of that type; `head` is the output
    layer's conf (a vertex that is no layer: None). A head whose loss takes
    integer labels (`sparse_mcxent`) gets int32 without the class axis."""
    from deeplearning4j_tpu.nn.conf.inputs import RecurrentInput

    if getattr(getattr(head, "inner", None) or head, "loss",
               None) == "sparse_mcxent" and out_type is not None:
        if isinstance(out_type, RecurrentInput):
            return _sds((batch, out_type.timesteps or timesteps), np.int32)
        return _sds((batch,), np.int32)
    if isinstance(out_type, RecurrentInput):
        return _sds((batch, out_type.timesteps or timesteps, out_type.size))
    if out_type is not None:
        return _sds((batch, out_type.arity()))
    return None


def _param_leaf_labels(params_list, layer_names,
                       skip_idx=()) -> List[Optional[str]]:
    """One label per flattened param leaf: '<layer>/<param name>'.
    Leaves of layers in `skip_idx` get label None — the dead-arg check
    skips unlabeled invars, which is how host-resident embedding tables
    (trained through the paramserver, not the device cotangent path)
    are exempted from JX005."""
    labels = []
    leaves_with_path, _ = jax.tree_util.tree_flatten_with_path(params_list)
    for path, _ in leaves_with_path:
        idx = next((p.idx for p in path if hasattr(p, "idx")), None)
        if idx is not None and idx in skip_idx:
            labels.append(None)
            continue
        key = next((p.key for p in path if hasattr(p, "key")), "?")
        layer = layer_names[idx] if idx is not None and \
            idx < len(layer_names) else f"layer[{idx}]"
        labels.append(f"param:{layer}/{key}")
    return labels


def audit_network(net, *, batch_size: int = 2, timesteps: int = 8,
                  large_const_bytes: int = LARGE_CONST_BYTES) -> List[Finding]:
    """Abstract-trace `net`'s training loss once and audit the program.

    Works for MultiLayerNetwork and ComputationGraph. Needs the conf's
    InputType(s) to shape an abstract batch; without them the audit is
    skipped with an INFO finding (shapeflow reports the same gap)."""
    from deeplearning4j_tpu.analysis import shapeflow
    from deeplearning4j_tpu.nn.conf.network import MultiLayerConfiguration

    net._require_init()
    conf = net.conf
    rng = jax.random.PRNGKey(0)
    skip = [Finding(
        "JX000", INFO, "network",
        "no InputType on the configuration — cannot shape an abstract "
        "batch, jaxpr audit skipped",
        "set an InputType (builder .set_input_type / .set_input_types)")]

    if isinstance(conf, MultiLayerConfiguration):
        x = _features_sds(conf.input_type, batch_size, timesteps)
        out_types = shapeflow.propagate_types(conf)
        y = _labels_sds(out_types[-1] if out_types else None,
                        batch_size, timesteps, conf.layers[-1])
        if x is None or y is None:
            return skip
        layer_names = [
            getattr(lc, "name", None) or f"layer[{i}]"
            for i, lc in enumerate(net._ordered_layer_confs())]

        def loss(params, states, x, y):
            return net._loss(params, states, x, y, None, None, rng,
                             training=True)[0]

        args = (net.params_list, net.state_list, x, y)
    else:
        if conf.input_types is None:
            return skip
        xs = tuple(_features_sds(t, batch_size, timesteps)
                   for t in conf.input_types)
        types = shapeflow.propagate_types(conf)
        ys = tuple(_labels_sds(types.get(name), batch_size, timesteps,
                               getattr(conf.vertices[name], "layer", None))
                   for name in conf.outputs)
        if any(v is None for v in xs) or any(v is None for v in ys):
            return skip
        layer_names = list(net.layer_vertex_names)

        def loss(params, states, xs, ys):
            return net._loss(params, states, xs, ys, None, None, rng,
                             training=True)[0]

        args = (net.params_list, net.state_list, xs, ys)

    closed = jax.make_jaxpr(loss)(*args)
    findings = audit_closed_jaxpr(
        closed, large_const_bytes=large_const_bytes, what="train_loss")

    # dead-weight analysis: which param leaves reach the score output
    # (`loss` returns ONLY the scalar score, so every program output is
    # score — liveness against all outputs IS the cotangent-path check)
    try:
        host_idx = frozenset(
            i for i, lc in enumerate(net._ordered_layer_confs())
            if getattr(lc, "host_resident", False))
    except Exception:
        host_idx = frozenset()
    param_labels = _param_leaf_labels(net.params_list, layer_names,
                                      skip_idx=host_idx)
    all_labels = param_labels + [None] * (
        len(closed.jaxpr.invars) - len(param_labels))
    findings.extend(_dead_arg_findings(
        closed, all_labels, None, "train_loss", "parameter"))

    # donation policy of the step this loss will be jitted into: audit
    # the value the net's step builders RECORDED (every jit site calls
    # trainstep._step_donate_argnums) — if no step was built yet, calling
    # the same helper records and returns what the first build will use
    donate = getattr(net, "_donate_argnums", None)
    if donate is None:
        donate = net._step_donate_argnums()
    findings.extend(check_donation(donate))
    return findings

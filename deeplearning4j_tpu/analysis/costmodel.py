"""Static device cost model over the train-step jaxpr — the static half
of the device performance/memory observability layer (utils/devprof.py
is the runtime half; each checks the other).

One `jax.make_jaxpr` of the FULL optimizer step (loss + backward +
updater — the same body every step jit uses, nn/trainstep's
`_make_step_body`) and a walk over the program produces, per primitive
family:

* **FLOPs** under HLO cost-analysis accounting: matmuls are 2·M·N·K,
  convolutions count only the *valid* (output, kernel-tap) pairs — SAME
  padding taps and dilation holes excluded, which is what makes
  backward-input convs (lhs_dilation = stride) come out right —
  elementwise ops are one FLOP per output element, reductions one per
  reduced element. `scan` bodies multiply by trip count (`flops`);
  a parallel accumulation counts loop bodies ONCE (`xla_flops_once`),
  matching XLA's own `Compiled.cost_analysis()` semantics so the two
  are directly comparable (the JX007 cross-check below).
* **bytes moved**: operand + result bytes per equation — the no-fusion
  upper bound on HBM traffic, the denominator of the roofline
  arithmetic-intensity classification.
* a **liveness-based activation peak**: one reverse pass computes each
  intermediate's last use; a forward pass then tracks the live-set byte
  watermark — the static analog of the `device_memory_bytes{kind=
  activations_est}` gauge utils/devprof.py publishes at runtime.

The model checks itself against XLA (`cross_check` → JX007 when the
divergence exceeds tolerance) and against the chip (`residency_findings`
→ JX008 when params + updater + data + activation peak exceed device
HBM). `utils/flops.py`'s hand-written per-layer estimator is demoted to
the fallback this model replaces (`flops.train_step_flops_for`).

Known accounting gaps, deliberate: `while` bodies count once (trip count
is not static); `cond` takes the most expensive branch; opaque custom
calls (pallas kernels) count zero — callers that need model FLOPs trace
with helpers disabled (flops.train_step_flops_for does), since model
FLOPs are implementation-independent.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.extend import core as jax_core

from deeplearning4j_tpu.analysis.findings import ERROR, Finding

# the MXU families — the "model FLOPs" numerator of the MFU accounting
# (elementwise/reduction work is bandwidth-, not FLOPs-bound on TPU, and
# excluding it keeps MFU comparable across frameworks)
MXU_FAMILIES = ("conv_general_dilated", "dot_general")

XLA_TOLERANCE = 0.10  # JX007 default: cost model vs cost_analysis()

_ELEMENTWISE = frozenset({
    "add", "sub", "mul", "div", "max", "min", "pow", "atan2", "rem",
    "neg", "abs", "sign", "exp", "exp2", "log", "log1p", "expm1", "tanh",
    "logistic", "sqrt", "rsqrt", "cbrt", "erf", "erfc", "erf_inv", "sin",
    "cos", "tan", "asin", "acos", "atan", "sinh", "cosh", "floor", "ceil",
    "round", "is_finite", "square", "integer_pow", "clamp", "select_n",
    "and", "or", "xor", "not", "eq", "ne", "lt", "le", "gt", "ge",
    "nextafter", "shift_left", "shift_right_logical",
    "shift_right_arithmetic",
})

# pure data movement: zero FLOPs, but bytes still count (that is the
# point — a transpose is free compute and real traffic)
_DATA_MOVEMENT = frozenset({
    "reshape", "transpose", "broadcast_in_dim", "slice", "concatenate",
    "pad", "rev", "squeeze", "gather", "dynamic_slice",
    "dynamic_update_slice", "convert_element_type", "bitcast_convert_type",
    "iota", "copy", "device_put", "stop_gradient", "split",
})


def _size(v) -> int:
    aval = getattr(v, "aval", None)
    if aval is None or not hasattr(aval, "shape"):
        return 0
    n = 1
    for s in aval.shape:
        n *= int(s)
    return n


def _nbytes(v) -> int:
    aval = getattr(v, "aval", None)
    if aval is None or not hasattr(aval, "dtype"):
        return 0
    return _size(v) * aval.dtype.itemsize


def _conv_valid_pairs(out_sz: int, k_sz: int, in_sz: int, stride: int,
                      pad_lo: int, w_dil: int, b_dil: int) -> int:
    """Valid (output position, kernel tap) pairs along ONE spatial dim:
    taps landing in padding or on base-dilation holes do no work, and
    HLO cost analysis does not count them. Separable across dims, so the
    multi-dim count is the product of the per-dim counts."""
    span = (in_sz - 1) * b_dil + 1
    n = 0
    for o in range(out_sz):
        base = o * stride - pad_lo
        for k in range(k_sz):
            pos = base + k * w_dil
            if 0 <= pos < span and pos % b_dil == 0:
                n += 1
    return n


def _conv_flops(eqn) -> float:
    dn = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    out = eqn.outvars[0].aval.shape
    batch_groups = eqn.params.get("batch_group_count", 1)
    strides = eqn.params["window_strides"]
    padding = eqn.params["padding"]
    ndims = len(strides)
    w_dil = eqn.params.get("rhs_dilation") or (1,) * ndims
    b_dil = eqn.params.get("lhs_dilation") or (1,) * ndims
    ls, rs, os_ = dn.lhs_spec, dn.rhs_spec, dn.out_spec
    batch = int(lhs[ls[0]])
    in_ch_per_group = int(rhs[rs[1]])
    out_ch = int(out[os_[1]])
    pairs = 1
    for i in range(ndims):
        pairs *= _conv_valid_pairs(
            int(out[os_[2 + i]]), int(rhs[rs[2 + i]]), int(lhs[ls[2 + i]]),
            strides[i], padding[i][0], w_dil[i], b_dil[i])
    return 2.0 * (batch // batch_groups) * out_ch * in_ch_per_group * pairs


def _same_pad_lo(in_sz: int, k_sz: int, stride: int) -> Tuple[int, int]:
    """(out_sz, pad_lo) of one spatial dim under XLA SAME padding:
    out = ceil(in/s), total pad = max((out-1)*s + k - in, 0), low half
    first (XLA puts the extra pad on the high side)."""
    out_sz = -(-in_sz // stride)
    pad_total = max((out_sz - 1) * stride + k_sz - in_sz, 0)
    return out_sz, pad_total // 2


def conv_instance_cost(*, kernel, stride, x_shape, n_out: int,
                       itemsize: int) -> dict:
    """FLOPs and minimal HBM bytes of ONE bias-free SAME NHWC conv
    instance, priced exactly like `_conv_flops` (HLO valid-pair
    accounting — taps landing in padding do no work). Bytes are the
    streaming floor: read x and w once, write y once; the fused stats
    epilogue adds nothing. This is the per-instance analogue of the
    per-family `CostModel.table()` rows, for kernel-routing decisions
    that must be made per shape rather than per program."""
    n, h, w, cin = (int(d) for d in x_shape)
    kh, kw = int(kernel[0]), int(kernel[1])
    sh, sw = int(stride[0]), int(stride[1])
    ho, ph = _same_pad_lo(h, kh, sh)
    wo, pw = _same_pad_lo(w, kw, sw)
    pairs = (_conv_valid_pairs(ho, kh, h, sh, ph, 1, 1)
             * _conv_valid_pairs(wo, kw, w, sw, pw, 1, 1))
    flops = 2.0 * n * n_out * cin * pairs
    bytes_ = itemsize * (n * h * w * cin + kh * kw * cin * n_out
                         + n * ho * wo * n_out)
    return {"flops": flops, "bytes": bytes_,
            "out_shape": (n, ho, wo, int(n_out))}


def bn_instance_cost(*, x_shape, itemsize: int, n_reads: int = 1,
                     n_writes: int = 1) -> dict:
    """FLOPs and bytes of one batch-norm pass over an NHWC activation:
    a handful of elementwise ops per element (priced at 4 FLOP/elem),
    `n_reads` full reads and `n_writes` full writes of the tensor.
    Per-channel vectors are noise and not counted."""
    numel = 1
    for d in x_shape:
        numel *= int(d)
    return {"flops": 4.0 * numel,
            "bytes": float(itemsize * numel * (n_reads + n_writes))}


def instance_roofline(flops: float, bytes_: float,
                      peak_flops: Optional[float] = None,
                      hbm_bandwidth: Optional[float] = None) -> dict:
    """Roofline verdict for a single op instance — the same ridge test
    `CostModel.table()` applies per family, exposed for per-shape kernel
    routing (`ops/pallas_conv_bn.conv_decision`). Off-TPU the v5e figures
    stand in: routing models the TPU the kernels target, not the host."""
    from deeplearning4j_tpu.utils import flops as _flops

    peak = peak_flops or _flops.peak_flops_per_chip()
    bw = hbm_bandwidth or _flops.hbm_bandwidth_per_chip()
    ridge = peak / bw
    intensity = flops / bytes_ if bytes_ else 0.0
    return {
        "flops": flops,
        "bytes": bytes_,
        "intensity": round(intensity, 3),
        "ridge_intensity": round(ridge, 3),
        "verdict": ("compute-bound" if intensity >= ridge
                    else "memory-bound"),
    }


def _eqn_flops(eqn) -> float:
    p = eqn.primitive.name
    if p == "dot_general":
        (contract_lhs, _), _ = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval.shape
        k = 1
        for d in contract_lhs:
            k *= int(lhs[d])
        return 2.0 * _size(eqn.outvars[0]) * k
    if p == "conv_general_dilated":
        return _conv_flops(eqn)
    if p in _ELEMENTWISE:
        return float(_size(eqn.outvars[0]))
    if p in _DATA_MOVEMENT:
        return 0.0
    if p.startswith("reduce_window"):
        return float(_size(eqn.invars[0]))
    if p.startswith("reduce_") or p in ("argmax", "argmin"):
        return float(max(
            sum(_size(v) for v in eqn.invars)
            - sum(_size(v) for v in eqn.outvars), 0))
    if p == "select_and_scatter_add":
        return float(_size(eqn.invars[0]) + _size(eqn.invars[1]))
    if p in ("cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp"):
        return float(_size(eqn.invars[0]))
    if p in ("scatter", "scatter_add", "scatter_mul", "scatter_min",
             "scatter_max"):
        return float(_size(eqn.invars[2]) if len(eqn.invars) > 2 else 0)
    if p == "sort":
        n = _size(eqn.invars[0])
        return float(n * max(1, int(np.log2(max(n, 2)))))
    return 0.0  # rng, custom calls, control flow shells


def _sub_jaxprs(eqn) -> List[jax_core.Jaxpr]:
    out = []
    for v in eqn.params.values():
        if isinstance(v, jax_core.ClosedJaxpr):
            out.append(v.jaxpr)
        elif isinstance(v, jax_core.Jaxpr):
            out.append(v)
        elif isinstance(v, (list, tuple)):
            for item in v:
                if isinstance(item, jax_core.ClosedJaxpr):
                    out.append(item.jaxpr)
                elif isinstance(item, jax_core.Jaxpr):
                    out.append(item)
    return out


@dataclasses.dataclass
class FamilyCost:
    """Aggregate cost of one primitive family across the program."""

    flops: float = 0.0        # full execution (scan bodies × trip count)
    flops_once: float = 0.0   # loop bodies once (cost_analysis semantics)
    bytes: float = 0.0        # operand+result bytes, full execution
    count: int = 0            # equations (static, not per-iteration)

    def to_dict(self) -> dict:
        return {"flops": self.flops, "bytes": self.bytes,
                "count": self.count}


def _accumulate(jaxpr, families: Dict[str, FamilyCost],
                scale: float, scale_once: float):
    for eqn in jaxpr.eqns:
        subs = _sub_jaxprs(eqn)
        p = eqn.primitive.name
        if subs:
            if p == "cond":
                # most expensive branch only — both accumulations
                best, best_f = None, -1.0
                for sj in subs:
                    probe: Dict[str, FamilyCost] = {}
                    _accumulate(sj, probe, scale, scale_once)
                    f = sum(fc.flops for fc in probe.values())
                    if f > best_f:
                        best, best_f = probe, f
                for name, fc in (best or {}).items():
                    dst = families.setdefault(name, FamilyCost())
                    dst.flops += fc.flops
                    dst.flops_once += fc.flops_once
                    dst.bytes += fc.bytes
                    dst.count += fc.count
                continue
            mult = scale
            if p == "scan":
                mult = scale * int(eqn.params.get("length", 1))
            # while: trip count unknown — body counts once in BOTH views
            for sj in subs:
                _accumulate(sj, families, mult, scale_once)
            continue
        f = _eqn_flops(eqn)
        b = (sum(_nbytes(v) for v in eqn.invars)
             + sum(_nbytes(v) for v in eqn.outvars))
        fc = families.setdefault(p, FamilyCost())
        fc.flops += f * scale
        fc.flops_once += f * scale_once
        fc.bytes += b * scale
        fc.count += 1


def _activation_peak(jaxpr) -> Tuple[int, Optional[dict]]:
    """Liveness watermark over top-level intermediates: each outvar goes
    live when produced, dies after its last consumer (program outputs
    live to the end). Invars (params/updater/data) are resident, not
    activations — counted separately by the caller. Sub-jaxpr-calling
    equations are atomic: a scan's stacked residuals are its outvars, so
    the big backward-saved tensors ARE seen; per-iteration temps inside
    the body are not (an under- never an over-estimate)."""
    last_use: Dict[jax_core.Var, int] = {}
    n = len(jaxpr.eqns)
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if isinstance(v, jax_core.Var):
                last_use[v] = i
    for v in jaxpr.outvars:
        if isinstance(v, jax_core.Var):
            last_use[v] = n
    produced = set()
    live_bytes = 0
    peak = 0
    largest: Optional[dict] = None
    dying: Dict[int, List[jax_core.Var]] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.outvars:
            if not isinstance(v, jax_core.Var) or v in produced:
                continue
            produced.add(v)
            nb = _nbytes(v)
            if nb:
                live_bytes += nb
                dying.setdefault(last_use.get(v, i), []).append(v)
                if largest is None or nb > largest["bytes"]:
                    aval = v.aval
                    largest = {"shape": tuple(int(s) for s in aval.shape),
                               "dtype": str(aval.dtype), "bytes": nb}
        peak = max(peak, live_bytes)
        for v in dying.pop(i, ()):
            live_bytes -= _nbytes(v)
    return peak, largest


@dataclasses.dataclass
class CostModel:
    """Per-family device cost of one traced program (usually one
    optimizer step), plus the static memory picture."""

    what: str
    families: Dict[str, FamilyCost]
    activation_peak_bytes: int
    largest_activation: Optional[dict]
    param_bytes: int = 0
    updater_bytes: int = 0
    data_bytes: int = 0
    const_bytes: int = 0
    # bytes (within param_bytes/updater_bytes) belonging to layers
    # declared `host_resident=True` (host-sharded embedding tables pulled
    # row-wise through the paramserver) — they never occupy device HBM,
    # so resident_bytes exempts them
    host_resident_param_bytes: int = 0
    host_resident_updater_bytes: int = 0
    # the traced on-device step also holds the table AND its cotangent
    # (the dense scatter-add gradient) live in the activation peak; the
    # pipeline keeps both host-side (rows pulled, row deltas pushed), so
    # that table-shaped share of the peak is exempt too (clamped to the
    # measured peak — an estimator, never negative)
    host_resident_activation_bytes: int = 0
    batch: Optional[int] = None
    # data-axis shard count of the net this step was traced from (1 for
    # single-device nets): the traced program is the GLOBAL step, so
    # every per-chip view divides batch-sharded quantities by this
    data_axis_shards: int = 1
    # the priced gradient-collective schedule (parallel/sharded
    # CollectivePlan.describe via the net's MeshPlan): wire bytes per
    # step at the configured grad dtype, bucket sizes, and the ring
    # all-reduce time estimate. None for single-device nets. Priced
    # SEPARATELY from the FLOP families — attaching it must never move
    # model_flops (JX007 guards that)
    collective: Optional[dict] = None

    @property
    def flops_total(self) -> float:
        return sum(fc.flops for fc in self.families.values())

    @property
    def xla_comparable_flops(self) -> float:
        """FLOPs with loop bodies counted ONCE — the number comparable
        to `Compiled.cost_analysis()['flops']` (XLA does not multiply a
        While body by its trip count)."""
        return sum(fc.flops_once for fc in self.families.values())

    @property
    def bytes_total(self) -> float:
        return sum(fc.bytes for fc in self.families.values())

    @property
    def model_flops(self) -> float:
        """MXU-family FLOPs only — the MFU numerator (GLOBAL: the whole
        traced step across all data shards)."""
        return sum(fc.flops for name, fc in self.families.items()
                   if name in MXU_FAMILIES)

    @property
    def model_flops_per_chip(self) -> float:
        """model_flops divided by the data-axis size — the per-chip MFU
        numerator. Using the global figure against one chip's peak would
        over-report multi-chip MFU data_axis_shards×."""
        return self.model_flops / max(1, self.data_axis_shards)

    @property
    def resident_bytes(self) -> int:
        """Static peak-memory estimate PER CHIP: everything that must be
        in one device's HBM at once during the step — params/updater/
        consts replicated (full size per chip), data and activations
        batch-sharded (divided by the data-axis size). Params held twice
        when not donated is deliberately NOT modeled — JX006 audits
        donation separately. Host-resident tables (sparse embedding
        weights served row-wise by the paramserver) are subtracted —
        they live in host RAM, not HBM."""
        n = max(1, self.data_axis_shards)
        device_param = self.param_bytes - self.host_resident_param_bytes
        device_upd = self.updater_bytes - self.host_resident_updater_bytes
        device_act = max(
            0, self.activation_peak_bytes - self.host_resident_activation_bytes)
        return (device_param + device_upd + self.const_bytes
                + (self.data_bytes + device_act) // n)

    def roofline(self, peak_flops: Optional[float] = None,
                 hbm_bandwidth: Optional[float] = None) -> dict:
        """Program-level roofline verdict: the step-time lower bound is
        max(compute, traffic) at the given peak; the MFU ceiling is what
        model FLOPs could at best achieve against that bound. Per-chip:
        a sharded step's work divides across the data axis before
        meeting one chip's peak."""
        from deeplearning4j_tpu.utils import flops as _flops

        peak = peak_flops or _flops.peak_flops_per_chip()
        bw = hbm_bandwidth or _flops.hbm_bandwidth_per_chip()
        n = max(1, self.data_axis_shards)
        t_compute = self.flops_total / n / peak
        t_memory = self.bytes_total / n / bw
        bound = max(t_compute, t_memory, 1e-30)
        out = {
            "peak_flops": peak,
            "hbm_bandwidth": bw,
            "ridge_intensity": peak / bw,
            "compute_seconds": t_compute,
            "memory_seconds": t_memory,
            "bound": "compute" if t_compute >= t_memory else "memory",
            "step_time_lower_bound_seconds": bound,
            "mfu_ceiling": self.model_flops_per_chip / (peak * bound),
        }
        if self.collective is not None:
            # the gradient all-reduce rides along unpriced in the bound:
            # the bucketed schedule exists to OVERLAP it with compute, so
            # the honest statement is "hidden if collective <= bound" —
            # reported, never silently added to the lower bound
            t_coll = self.collective.get("ring_estimate_seconds")
            out["collective_seconds"] = t_coll
            if t_coll is not None:
                out["collective_hidden_by_compute"] = bool(t_coll <= bound)
        return out

    def table(self, peak_flops: Optional[float] = None,
              hbm_bandwidth: Optional[float] = None) -> List[dict]:
        """Per-family rows, FLOPs-descending, each classified compute-
        vs memory-bound against the roofline ridge intensity."""
        from deeplearning4j_tpu.utils import flops as _flops

        peak = peak_flops or _flops.peak_flops_per_chip()
        bw = hbm_bandwidth or _flops.hbm_bandwidth_per_chip()
        ridge = peak / bw
        rows = []
        for name, fc in sorted(self.families.items(),
                               key=lambda kv: -kv[1].flops):
            intensity = fc.flops / fc.bytes if fc.bytes else 0.0
            rows.append({
                "family": name,
                "count": fc.count,
                "flops": fc.flops,
                "bytes": fc.bytes,
                "intensity": round(intensity, 3),
                "verdict": ("compute-bound" if intensity >= ridge
                            else "memory-bound"),
                "mxu": name in MXU_FAMILIES,
            })
        return rows

    def to_dict(self) -> dict:
        return {
            "what": self.what,
            "batch": self.batch,
            "flops_total": self.flops_total,
            "xla_comparable_flops": self.xla_comparable_flops,
            "bytes_total": self.bytes_total,
            "model_flops": self.model_flops,
            "activation_peak_bytes": self.activation_peak_bytes,
            "largest_activation": self.largest_activation,
            "param_bytes": self.param_bytes,
            "updater_bytes": self.updater_bytes,
            "host_resident_param_bytes": self.host_resident_param_bytes,
            "host_resident_updater_bytes": self.host_resident_updater_bytes,
            "host_resident_activation_bytes":
                self.host_resident_activation_bytes,
            "data_bytes": self.data_bytes,
            "const_bytes": self.const_bytes,
            "data_axis_shards": self.data_axis_shards,
            "model_flops_per_chip": self.model_flops_per_chip,
            "resident_bytes": self.resident_bytes,
            "collective": self.collective,
            "families": {k: v.to_dict() for k, v in self.families.items()},
        }


def cost_closed_jaxpr(closed: jax_core.ClosedJaxpr,
                      what: str = "program") -> CostModel:
    families: Dict[str, FamilyCost] = {}
    _accumulate(closed.jaxpr, families, 1.0, 1.0)
    peak, largest = _activation_peak(closed.jaxpr)
    const_bytes = sum(int(getattr(c, "nbytes", 0) or 0)
                      for c in closed.consts)
    return CostModel(what=what, families=families,
                     activation_peak_bytes=peak, largest_activation=largest,
                     const_bytes=const_bytes)


def cost_fn(fn: Callable, *args, what: str = "fn") -> CostModel:
    """Cost-model any jittable callable on abstract or concrete args."""
    return cost_closed_jaxpr(jax.make_jaxpr(fn)(*args), what=what)


# -- the train step of a network ---------------------------------------------


def _tree_bytes(tree) -> int:
    return sum(int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
               for leaf in jax.tree_util.tree_leaves(tree)
               if hasattr(leaf, "shape"))


def train_step_args(net, *, batch_size: int = 8, timesteps: int = 16):
    """(step_fn, args) of the FULL optimizer step — the same body every
    step jit compiles (nn/trainstep's `_make_step_body`: loss, backward,
    gradient normalization, updater, param update), with its signature
    `(params, states, upd_state, data, lr, t, rng)`, on an abstract batch
    shaped from the conf's InputTypes via shapeflow. Shared by the cost
    model and the XLA cross-check so both sides measure the same program.
    Raises ValueError when the conf has no InputType to shape a batch."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.analysis import shapeflow
    from deeplearning4j_tpu.analysis.jaxpr_audit import (
        _features_sds,
        _labels_sds,
    )
    from deeplearning4j_tpu.nn.conf.network import MultiLayerConfiguration

    net._require_init()
    conf = net.conf
    if isinstance(conf, MultiLayerConfiguration):
        x = _features_sds(conf.input_type, batch_size, timesteps)
        out_types = shapeflow.propagate_types(conf)
        y = _labels_sds(out_types[-1] if out_types else None,
                        batch_size, timesteps, conf.layers[-1])
        if x is None or y is None:
            raise ValueError(
                "no InputType on the configuration — cannot shape an "
                "abstract batch for the cost model")
    else:
        if conf.input_types is None:
            raise ValueError(
                "no InputTypes on the configuration — cannot shape an "
                "abstract batch for the cost model")
        x = tuple(_features_sds(t, batch_size, timesteps)
                  for t in conf.input_types)
        types = shapeflow.propagate_types(conf)
        y = tuple(_labels_sds(types.get(name), batch_size, timesteps,
                              getattr(conf.vertices[name], "layer", None))
                  for name in conf.outputs)
        if any(v is None for v in x) or any(v is None for v in y):
            raise ValueError(
                "could not shape abstract features/labels from the "
                "graph's InputTypes")
    step = net._make_step_body(net._std_loss_builder())
    args = (net.params_list, net.state_list, net.upd_state,
            (x, y, None, None), jnp.float32(0.1), jnp.float32(1.0),
            jax.random.PRNGKey(0))
    return step, args


def _host_resident_bytes(net) -> Tuple[int, int]:
    """(param, updater) bytes of layers tagged `host_resident=True` —
    host-sharded embedding tables served by the paramserver. Walks
    `_ordered_layer_confs()` (aligned with params_list / upd_state on
    both MLN and graph); a net without that surface is simply all
    device-resident."""
    try:
        confs = net._ordered_layer_confs()
        params = net.params_list
        upd = getattr(net, "upd_state", None) or [None] * len(params)
    except Exception:
        return 0, 0
    hp = hu = 0
    for i, conf in enumerate(confs):
        if not getattr(conf, "host_resident", False):
            continue
        if i < len(params):
            hp += _tree_bytes(params[i])
        if i < len(upd):
            hu += _tree_bytes(upd[i])
    return hp, hu


def _model_of_step(net, step, args, batch_size: int) -> CostModel:
    """Trace + static memory bookkeeping shared by train_step_cost and
    check_network (args[3] is the batch: feature/label structs (MLN) or
    tuples of them (graph))."""
    cm = cost_fn(step, *args, what=f"{type(net).__name__}:train_step")
    cm.batch = int(batch_size)
    cm.param_bytes = _tree_bytes(net.params_list)
    cm.updater_bytes = _tree_bytes(net.upd_state)
    cm.data_bytes = _tree_bytes(args[3])
    hp, hu = _host_resident_bytes(net)
    cm.host_resident_param_bytes = hp
    cm.host_resident_updater_bytes = hu
    # table + its cotangent ride the activation peak in the traced
    # device program; host-side they are paramserver traffic, not HBM
    cm.host_resident_activation_bytes = min(
        int(cm.activation_peak_bytes), 2 * hp)
    plan = getattr(net, "_mesh_plan", None)
    if plan is not None:
        cm.data_axis_shards = max(1, int(plan.n_data_shards))
        try:
            cm.collective = plan.collective_describe(net)
        except Exception:
            cm.collective = None  # pricing must never sink the model
    return cm


def train_step_cost(net, *, batch_size: int = 8,
                    timesteps: int = 16) -> CostModel:
    """Cost-model `net`'s full optimizer step at the given batch shape.
    One abstract trace — no compile, no device step, no mutation."""
    step, args = train_step_args(net, batch_size=batch_size,
                                 timesteps=timesteps)
    return _model_of_step(net, step, args, batch_size)


# -- cross-checks -------------------------------------------------------------


def xla_cost_analysis(fn: Callable, *args) -> Optional[dict]:
    """XLA's own post-optimization accounting of the same program:
    `{'flops': ..., 'bytes_accessed': ...}`, or None when the backend
    does not expose cost analysis (never raises — skip, don't fail)."""
    try:
        compiled = jax.jit(fn).lower(*args).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if not isinstance(ca, dict) or "flops" not in ca:
            return None
        flops = float(ca["flops"])
        if flops <= 0:
            # some backends report -1/0 when the figure is unavailable;
            # a non-positive denominator would make the JX007 check
            # vacuously green (or divide by zero) — treat as absent
            return None
        return {"flops": flops,
                "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
    except Exception:
        return None


def cross_check(cm: CostModel, xla_stats: Optional[dict],
                tolerance: float = XLA_TOLERANCE) -> List[Finding]:
    """JX007: the static model's loop-bodies-once FLOP total must agree
    with XLA's cost_analysis within `tolerance` — the self-check that
    keeps every MFU/roofline number built on this model falsifiable.
    No XLA stats available -> no finding (the check is skip-, not
    fail-silent: callers report `xla: unavailable`)."""
    if not xla_stats or not xla_stats.get("flops"):
        return []
    ours = cm.xla_comparable_flops
    theirs = xla_stats["flops"]
    rel = abs(ours - theirs) / theirs
    if rel <= tolerance:
        return []
    return [Finding(
        "JX007", ERROR, f"costmodel:{cm.what}",
        f"cost model diverges from XLA cost_analysis by {rel:.1%} "
        f"(model {ours:.4g} vs XLA {theirs:.4g} flops, tolerance "
        f"{tolerance:.0%}) — MFU/roofline numbers built on this model "
        "are not trustworthy for this program",
        "a primitive family is mis-accounted: compare per-family totals "
        "(`cli perf --json`) against the program and fix the rule",
        name=f"JX007:costmodel:{cm.what}")]


def residency_findings(cm: CostModel,
                       hbm_bytes: Optional[float] = None) -> List[Finding]:
    """JX008: static residency (params + updater + data + consts +
    activation liveness peak) exceeding device HBM — the step will
    RESOURCE_EXHAUSTED before it ever runs. Skipped when the chip's HBM
    size is unknown (CPU backends)."""
    if hbm_bytes is None:
        from deeplearning4j_tpu.utils import flops as _flops

        hbm_bytes = _flops.peak_hbm_bytes_per_chip()
    if not hbm_bytes:
        return []
    resident = cm.resident_bytes
    if resident <= hbm_bytes:
        return []
    exempt = cm.host_resident_param_bytes + cm.host_resident_updater_bytes
    exempt_note = (f"; {exempt / 2**30:.2f} GiB of host-resident tables "
                   "already exempted" if exempt else "")
    return [Finding(
        "JX008", ERROR, f"costmodel:{cm.what}",
        f"static peak memory estimate {resident / 2**30:.2f} GiB exceeds "
        f"device HBM {hbm_bytes / 2**30:.2f} GiB (activations "
        f"{cm.activation_peak_bytes / 2**30:.2f} GiB, params "
        f"{cm.param_bytes / 2**30:.2f} GiB, updater "
        f"{cm.updater_bytes / 2**30:.2f} GiB{exempt_note}) — the step "
        "will OOM before it runs",
        "shrink the batch, enable rematerialization, shard the model "
        "(parallel/ tensor/pipeline parallelism), or mark embedding "
        "tables host_resident and serve them via the paramserver",
        name=f"JX008:costmodel:{cm.what}")]


def check_network(net, *, batch_size: int = 8, timesteps: int = 16,
                  tolerance: float = XLA_TOLERANCE,
                  compile_xla: bool = False,
                  hbm_bytes: Optional[float] = None
                  ) -> Tuple[CostModel, Optional[dict], List[Finding]]:
    """The full static check: cost-model the train step, optionally
    compile it for the XLA cross-check (JX007 — expensive: a real
    compile), and check static residency against HBM (JX008). Returns
    (model, xla stats or None, findings)."""
    step, args = train_step_args(net, batch_size=batch_size,
                                 timesteps=timesteps)
    cm = _model_of_step(net, step, args, batch_size)
    xla_stats = xla_cost_analysis(step, *args) if compile_xla else None
    findings = cross_check(cm, xla_stats, tolerance=tolerance)
    findings += residency_findings(cm, hbm_bytes=hbm_bytes)
    return cm, xla_stats, findings

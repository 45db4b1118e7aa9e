"""FLOP accounting for MFU reporting — now a thin wrapper over the
jaxpr cost model (analysis/costmodel.py).

`train_step_flops_for(net, batch)` is the one entry point: it traces the
net's actual optimizer step and returns the MXU-family FLOPs the program
really runs (source `"costmodel"`). The hand-written per-layer estimator
below — 2·MACs forward × 3 for the step, the original MFU arithmetic —
is demoted to the fallback for nets the cost model cannot trace (no
InputType on the conf) and to the cheap lazy default the fit loop's
devprof sampling starts from; every surfaced number carries its
`flops_source` so the two accountings can never be silently conflated.
Elementwise/normalization work stays excluded from the MFU numerator in
BOTH accountings (bandwidth-, not FLOPs-bound on TPU; exclusion keeps
MFU comparable across frameworks).

Chip tables (peak matmul FLOP/s, HBM size, HBM bandwidth) live here too
— the denominators of MFU and the roofline ridge.
"""

from __future__ import annotations

import logging
from typing import Optional, Tuple

logger = logging.getLogger("deeplearning4j_tpu")

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.graph import (
    ComputationGraphConfiguration,
    LayerVertex,
)
from deeplearning4j_tpu.nn.conf.inputs import ConvolutionalInput, RecurrentInput


def _layer_forward_flops(conf, it) -> float:
    """Per-example forward FLOPs of one layer given its input type."""
    inner = conf.inner if isinstance(conf, L.FrozenLayer) else conf
    if isinstance(inner, L.ConvolutionLayer):
        out = inner.output_type(it)
        k = inner.kernel_size
        return 2.0 * k[0] * k[1] * inner.n_in * inner.n_out * out.height * out.width
    if isinstance(inner, L.Convolution1DLayer):
        out = inner.output_type(it)
        t = out.timesteps or (it.timesteps or 1)
        return 2.0 * inner.kernel_size * inner.n_in * inner.n_out * t
    if isinstance(inner, (L.LSTM, L.GravesLSTM, L.GravesBidirectionalLSTM)):
        t = it.timesteps or 1
        per_step = 2.0 * 4 * inner.n_out * (inner.n_in + inner.n_out)
        mult = 2 if isinstance(inner, L.GravesBidirectionalLSTM) else 1
        return per_step * t * mult
    if isinstance(inner, L.RnnOutputLayer):
        t = it.timesteps or 1
        return 2.0 * inner.n_in * inner.n_out * t
    if isinstance(inner, (L.DenseLayer, L.OutputLayer, L.CenterLossOutputLayer,
                          L.AutoEncoder)):
        return 2.0 * inner.n_in * inner.n_out
    if isinstance(inner, L.EmbeddingLayer):
        return 0.0  # gather, not matmul
    return 0.0


def graph_forward_flops(conf: ComputationGraphConfiguration) -> Optional[float]:
    """Per-example forward FLOPs of a ComputationGraph, via a shape-
    inference walk of the topo order. None if input_types are unset."""
    if conf.input_types is None:
        return None
    types = dict(zip(conf.inputs, conf.input_types))
    total = 0.0
    for name in conf.topological_order():
        if name in types:
            continue
        v = conf.vertices[name]
        its = [types.get(i) for i in conf.vertex_inputs[name]]
        if any(i is None for i in its):
            types[name] = None
            continue
        if isinstance(v, LayerVertex):
            it = its[0]
            if v.preprocessor is not None:
                it = v.preprocessor.output_type(it)
            total += _layer_forward_flops(v.layer, it)
            types[name] = v.layer.output_type(it)
        else:
            types[name] = v.output_type(its)
    return total


def mln_forward_flops(conf) -> Optional[float]:
    """Per-example forward FLOPs of a MultiLayerConfiguration."""
    if conf.input_type is None:
        return None
    it = conf.input_type
    total = 0.0
    for i, layer in enumerate(conf.layers):
        pp = conf.preprocessors.get(str(i))
        if pp is not None:
            it = pp.output_type(it)
        total += _layer_forward_flops(layer, it)
        it = layer.output_type(it)
    return total


def train_step_flops(forward_flops: float, batch: int) -> float:
    """Analytic model FLOPs of one optimizer step: 3× forward (fwd +
    grad wrt activations + grad wrt weights), times the batch."""
    return 3.0 * forward_flops * batch


def forward_flops(conf) -> Optional[float]:
    """Per-example analytic forward FLOPs of either conf flavor."""
    from deeplearning4j_tpu.nn.conf.graph import (
        ComputationGraphConfiguration,
    )

    if isinstance(conf, ComputationGraphConfiguration):
        return graph_forward_flops(conf)
    return mln_forward_flops(conf)


def _unbounded_recurrent(conf) -> bool:
    """Does this conf consume recurrent input with NO fixed timestep
    count? The per-layer walk then prices one timestep, and a
    "per-example" number derived from it would be ~seq_len× off."""
    its = getattr(conf, "input_types", None) \
        or (getattr(conf, "input_type", None),)
    return any(isinstance(it, RecurrentInput) and not it.timesteps
               for it in its if it is not None)


def analytic_step_flops_per_example(conf) -> Tuple[Optional[float], str]:
    """(per-example optimizer-step FLOPs, "analytic") — the lazy default
    devprof's live MFU gauges start from. Recurrent confs without a
    fixed timestep count return (None, "analytic"): the walk prices ONE
    timestep, and reporting that as per-example would publish an MFU
    ~seq_len× too small — no number beats a confidently wrong one
    (attach a cost model, or fix the InputType's timesteps)."""
    if _unbounded_recurrent(conf):
        return None, "analytic"
    fwd = forward_flops(conf)
    if fwd is None or fwd <= 0:
        return None, "analytic"
    return 3.0 * fwd, "analytic"


def train_step_flops_for(net, batch: int, *, timesteps: int = 16,
                         prefer_cost_model: bool = True
                         ) -> Tuple[Optional[float], str]:
    """Model FLOPs of one of `net`'s optimizer steps at `batch` —
    `(flops, source)` where source is `"costmodel"` (jaxpr trace of the
    real step, MXU families only) or `"analytic"` (the per-layer
    fallback). The trace runs with vendor helpers disabled: model FLOPs
    are implementation-independent, and opaque pallas custom calls
    would otherwise count zero."""
    if prefer_cost_model:
        try:
            from deeplearning4j_tpu.analysis.costmodel import (
                train_step_cost,
            )

            with _helpers_disabled():
                cm = train_step_cost(net, batch_size=batch,
                                     timesteps=timesteps)
            if cm.model_flops > 0:
                return cm.model_flops, "costmodel"
        except Exception:
            logger.warning(
                "cost-model FLOP trace failed; falling back to the "
                "analytic per-layer estimate", exc_info=True)
    fwd = forward_flops(net.conf)
    if fwd is None or fwd <= 0:
        return None, "analytic"
    if _unbounded_recurrent(net.conf):
        fwd *= timesteps  # the analytic walk priced ONE timestep
    return train_step_flops(fwd, batch), "analytic"


class _helpers_disabled:
    """Disable every registered vendor helper for the duration of a
    cost-model trace, restoring the caller's kill-switch state on exit
    (the same save/restore discipline as bench._run_ab)."""

    _OPS = ("conv2d", "batch_norm", "bn_backward", "lstm_sequence")

    def __enter__(self):
        from deeplearning4j_tpu.ops.helpers import (
            helper_enabled,
            set_helper_enabled,
        )

        self._set = set_helper_enabled
        self._saved = {op: helper_enabled(op) for op in self._OPS}
        for op in self._OPS:
            set_helper_enabled(op, False)
        return self

    def __exit__(self, *exc):
        for op, enabled in self._saved.items():
            if enabled is not None:
                self._set(op, enabled)
        return False


# jax's `device_kind` of each chip generation the tables below carry
# (the names of jax/_src/mesh_utils.py and pallas/mosaic/tpu_info.py).
# "TPU v5 lite" is the one this repo has run on; a TPU whose kind is not
# here is an error, never a default.
DEVICE_KINDS = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e",
    "TPU v5e": "v5e",
    "TPU v5": "v5p",
    "TPU v5p": "v5p",
    "TPU v6 lite": "v6e",
    "TPU v6e": "v6e",
}

# Off the TPU (the CPU hosts that run the cost model, `cli perf` and the
# kernel-coverage table) the roofline is a PLANNING model of the chip the
# kernels target: these are the v5e rows, named as what they are. There
# is no HBM off-TPU — a CPU host's RAM is not the ceiling JX008 is about.
PLANNING_CHIP = "v5e"

# bf16 peak matmul throughput per chip, for MFU (Google Cloud TPU docs).
TPU_PEAK_FLOPS = {
    "v5e": 197e12,
    "v4": 275e12,
    "v5p": 459e12,
    "v6e": 918e12,
}

# HBM capacity per chip — the JX008 residency ceiling.
TPU_HBM_BYTES = {
    "v5e": 16e9,
    "v4": 32e9,
    "v5p": 95e9,
    "v6e": 32e9,
}

# HBM bandwidth per chip — the roofline ridge denominator.
TPU_HBM_BANDWIDTH = {
    "v5e": 819e9,
    "v4": 1228e9,
    "v5p": 2765e9,
    "v6e": 1640e9,
}

# Aggregate inter-chip (ICI) bandwidth per chip — the denominator of the
# `train_step_collective_seconds{source="estimate"}` gradient-allreduce
# cost model (parallel/sharded.MeshPlan). Approximate public figures for
# all links of one chip combined; an estimate's denominator, clearly
# labeled as such wherever it surfaces.
TPU_ICI_BANDWIDTH = {
    "v5e": 200e9,
    "v4": 300e9,
    "v5p": 600e9,
    "v6e": 448e9,
}


def _chip_lookup(table: dict, env_var: str, off_tpu):
    """The table's row for the attached chip, matched on the device kind
    jax reports. `env_var` overrides; off the TPU the answer is `off_tpu`
    (a planning constant, see PLANNING_CHIP); a TPU kind that is not in
    DEVICE_KINDS raises."""
    import os

    env = os.environ.get(env_var)
    if env:
        return float(env)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return off_tpu
    gen = DEVICE_KINDS.get(dev.device_kind)
    if gen is None:
        raise ValueError(
            f"TPU device_kind {dev.device_kind!r} is not in "
            f"utils/flops.DEVICE_KINDS ({sorted(DEVICE_KINDS)}): add its "
            f"row to the peak tables, or set {env_var}")
    return table[gen]


def peak_flops_per_chip() -> float:
    """Peak bf16 FLOP/s of the attached chip (the v5e planning figure
    off-TPU)."""
    return _chip_lookup(TPU_PEAK_FLOPS, "BENCH_PEAK_FLOPS",
                        TPU_PEAK_FLOPS[PLANNING_CHIP])


def peak_hbm_bytes_per_chip() -> Optional[float]:
    """HBM capacity of the attached chip; None off-TPU (a CPU host's RAM
    is not the ceiling the JX008 check is about) unless BENCH_HBM_BYTES
    forces one."""
    return _chip_lookup(TPU_HBM_BYTES, "BENCH_HBM_BYTES", None)


def hbm_bandwidth_per_chip() -> float:
    """HBM bandwidth of the attached chip (roofline ridge); the v5e
    planning figure off-TPU — the roofline is a TPU-shaped model."""
    return _chip_lookup(TPU_HBM_BANDWIDTH, "BENCH_HBM_BANDWIDTH",
                        TPU_HBM_BANDWIDTH[PLANNING_CHIP])


def ici_bandwidth_per_chip() -> float:
    """Aggregate ICI bandwidth of the attached chip — the gradient
    all-reduce estimate's denominator; the v5e planning figure off-TPU
    (the estimate is a TPU-shaped cost model, labeled `estimate`)."""
    return _chip_lookup(TPU_ICI_BANDWIDTH, "BENCH_ICI_BANDWIDTH",
                        TPU_ICI_BANDWIDTH[PLANNING_CHIP])

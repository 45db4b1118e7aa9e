"""Step program: device time a step in the operations that hold the model's
FLOPs, forward and backward: the union of the device events whose scope path
ends in `conv_general_dilated` or `dot_general` (with whatever XLA fused into
them), from the scoped trace (benchmark/span_reduce.py). The time a share of
the MXU's peak will divide; PERF.md works that share out by hand."""

from benchmark import span_reduce


def read(facts, trace):
    return span_reduce.per_step_ms(facts, trace, "matmul_ns")

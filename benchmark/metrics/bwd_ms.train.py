"""Step program: device time a step in backward operations, from the scoped
trace (benchmark/span_reduce.py): the union of the device events whose scope
path holds `transpose(` (JAX's name for a layer's backward pass) or
`reduce_grads`, and not `update`. On the chip the optimizer's update of a
weight is fused into the convolution that makes its gradient, and is here."""

from benchmark import span_reduce


def read(facts, trace):
    return span_reduce.per_step_ms(facts, trace, "phase_ns", "bwd")

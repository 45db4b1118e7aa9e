"""Step program: device time a step in forward operations, from the scoped
trace (benchmark/span_reduce.py): the union of the device events whose scope
path holds neither `update` nor `transpose(`, over the whole step runs of the
traced slice."""

from benchmark import span_reduce


def read(facts, trace):
    return span_reduce.per_step_ms(facts, trace, "phase_ns", "fwd")

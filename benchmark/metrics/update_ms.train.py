"""Step program: device time a step in operations under the step's `update`
scope (gradient norm, masking, normalisation, updater, parameter add) that
XLA left as operations of their own, from the scoped trace
(benchmark/span_reduce.py)."""

from benchmark import span_reduce


def read(facts, trace):
    return span_reduce.per_step_ms(facts, trace, "phase_ns", "update")

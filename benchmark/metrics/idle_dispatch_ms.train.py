"""Fit-loop dispatch: device idle time a step that overlaps the fit thread's
`fit/dispatch` (inside the train-step call: the device ran dry while the host
was still launching), from the scoped trace and the program's step timeline
(benchmark/span_reduce.py)."""

from benchmark import span_reduce


def read(facts, trace):
    return span_reduce.per_step_ms(facts, trace, "idle_ns",
                                   "fit/dispatch")

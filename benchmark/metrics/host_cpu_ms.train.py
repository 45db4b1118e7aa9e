"""Fit-loop dispatch: CPU time of the fit thread a step, inside the dispatch
and inside the observers (`cpu_dispatch_ns + cpu_observe_ns` of the program's
step timeline), over the `fit/step` spans that lie inside the traced slice's
whole step runs. Host work, where `dispatch_ms.train` is mostly waiting."""

from benchmark import span_reduce


def read(facts, trace):
    return span_reduce.per_step_ms(facts, trace, "cpu_ns",
                                   over="fit_steps")

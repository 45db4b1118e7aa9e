"""Fit-loop dispatch: device idle time a step that overlaps the fit thread's
`fit/observe` (counters, flight recorder, devprof with its blocking read of
every 16th step's score, run ledger, sentinel), from the scoped trace and the
program's step timeline (benchmark/span_reduce.py)."""

from benchmark import span_reduce


def read(facts, trace):
    return span_reduce.per_step_ms(facts, trace, "idle_ns",
                                   "fit/observe")

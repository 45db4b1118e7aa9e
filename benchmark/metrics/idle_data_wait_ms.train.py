"""Input pipeline: device idle time a step that overlaps the fit thread's
`fit/data_wait` (from the end of one dispatch's observers to the start of the
next dispatch: the iterator, the transforms), from the scoped trace and the
program's step timeline (benchmark/span_reduce.py)."""

from benchmark import span_reduce


def read(facts, trace):
    return span_reduce.per_step_ms(facts, trace, "idle_ns",
                                   "fit/data_wait")

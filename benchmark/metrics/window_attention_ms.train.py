"""Window layers' attention: device time a step of the events under the
`window_attention` scope (scores, band mask, softmax and mix of the layers
that see a window; neither the projections nor the rotation), forward plus
backward with what the backward pass recomputes, from the scoped trace.
Nothing to read where no event carries the scope."""

from benchmark import scope_reduce


def read(facts, trace):
    return scope_reduce.ms_per_step(
        facts, trace, scope_reduce.of_component("window_attention"))

"""Attention layers: device time a step in the layers of kind
`groupedqueryattention`, forward plus backward with what the backward pass
recomputes, from the scoped trace."""

from benchmark import scope_reduce


def read(facts, trace):
    return scope_reduce.ms_per_step(
        facts, trace, scope_reduce.of_layer_kinds("groupedqueryattention"))

"""Gated dense MLPs: device time a step in the layers of kind `gatedmlp`
(a decoder's leading dense MLP and its shared experts as one MLP), forward
plus backward with what the backward pass recomputes, from the scoped trace.
Nothing to read where no event carries such a layer's scope."""

from benchmark import scope_reduce


def read(facts, trace):
    return scope_reduce.ms_per_step(
        facts, trace, scope_reduce.of_layer_kinds("gatedmlp"))

"""Mamba-2 mixers: device time a step in the layers of kind `mamba2`
(projections, conv, scan, gated norm), forward plus backward with what the
backward pass recomputes, from the scoped trace (benchmark/scope_reduce.py)."""

from benchmark import scope_reduce


def read(facts, trace):
    return scope_reduce.ms_per_step(
        facts, trace, scope_reduce.of_layer_kinds("mamba2"))

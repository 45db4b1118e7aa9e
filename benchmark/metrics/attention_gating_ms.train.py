"""What gated, QK-normed attention adds around the inner attention: device
time a step of the events under the `qk_norm` or `output_gate` scope inside
a grouped-query attention layer (`L<key>_groupedqueryattention`): the
per-head RMS norms of queries and keys, and the gate's projection, its
sigmoid and the multiply, forward plus backward with what the backward pass
recomputes, from the scoped trace. Nothing to read where no event carries
those scopes (a program without the two parts)."""

from benchmark import scope_reduce

_LAYER = scope_reduce.of_layer_kinds("groupedqueryattention")
_PARTS = (scope_reduce.of_component("qk_norm"),
          scope_reduce.of_component("output_gate"))


def match(scope: str) -> bool:
    return _LAYER(scope) and any(part(scope) for part in _PARTS)


def read(facts, trace):
    return scope_reduce.ms_per_step(facts, trace, match)

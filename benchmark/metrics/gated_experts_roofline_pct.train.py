"""Gated routed experts: the grouped products' share of their roofline. The
least time the chip could take for the operations and bytes
`benchmark/rooflines_decoder.gated_experts` counts from the cell's
configuration and traffic (three matrices an expert, at the uniform share),
over the device time a step of the events under the `experts` scope,
recomputation included. Nothing to read from a configuration whose experts
are not the gated ones, or where no event carries the scope."""

from benchmark import rooflines_decoder, scope_reduce


def read(facts, trace):
    cell = rooflines_decoder.cell_of_run(facts) if trace is not None else None
    if cell is None:
        return None
    try:
        work = rooflines_decoder.gated_experts(cell["config"],
                                               cell["traffic"])
    except KeyError:    # a configuration without such a layer
        return None
    return rooflines_decoder.share(
        work, scope_reduce.ms_per_step(
            facts, trace, scope_reduce.of_component("experts")),
        cell["peaks"])

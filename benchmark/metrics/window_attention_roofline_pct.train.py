"""Window layers' attention: the blocked products' share of their roofline.
The least time the chip could take for the operations and bytes
`benchmark/rooflines_decoder.window_attention` counts from the cell's
configuration and traffic (the band's pairs alone: a key block that is
multiplied and masked away counts for nothing), over the device time a step
of the events under the `window_attention` scope, recomputation included.
Nothing to read where no event carries the scope."""

from benchmark import rooflines_decoder, scope_reduce


def read(facts, trace):
    cell = rooflines_decoder.cell_of_run(facts) if trace is not None else None
    if cell is None:
        return None
    try:
        work = rooflines_decoder.window_attention(cell["config"],
                                                  cell["traffic"])
    except KeyError:    # a configuration without such a layer
        return None
    return rooflines_decoder.share(
        work, scope_reduce.ms_per_step(
            facts, trace, scope_reduce.of_component("window_attention")),
        cell["peaks"])

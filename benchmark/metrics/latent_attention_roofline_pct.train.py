"""Latent attention: the inner part's share of its roofline. The least time
the chip could take for the operations and bytes
`benchmark/rooflines_latent.latent_attention` counts from the cell's
configuration and traffic (the causal triangle's pairs at the unpadded 192
and 128 alone), over the device time a step of the events under the
`latent_attention` scope, recomputation included. Nothing to read from a
configuration without such layers, or where no event carries the scope."""

from benchmark import rooflines_latent, scope_reduce


def read(facts, trace):
    cell = rooflines_latent.cell_of_run(facts) if trace is not None else None
    if cell is None:
        return None
    try:
        work = rooflines_latent.latent_attention(cell["config"],
                                                 cell["traffic"])
    except KeyError:    # a configuration without such a layer
        return None
    return rooflines_latent.share(
        work, scope_reduce.ms_per_step(
            facts, trace, scope_reduce.of_component("latent_attention")),
        cell["peaks"])

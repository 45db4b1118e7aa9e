"""State-space scan: the recurrence's share of its roofline. The least time
the chip could take for the operations and bytes `benchmark/rooflines.ssd_scan`
counts from the cell's configuration and traffic (the larger of operations
over `bf16_flops_per_s` and bytes over `hbm_bytes_per_s` of peaks.json), over
the device time a step of the events under the `ssd_scan` scope, recomputation
included. Nothing to read where no event carries the scope."""

from benchmark import rooflines, scope_reduce


def read(facts, trace):
    cell = rooflines.cell_of_run(facts) if trace is not None else None
    if cell is None:
        return None
    try:
        work = rooflines.ssd_scan(cell["config"], cell["traffic"])
    except KeyError:    # a configuration without such a layer
        return None
    return rooflines.share(
        work, scope_reduce.ms_per_step(
            facts, trace, scope_reduce.of_component("ssd_scan")),
        cell["peaks"])

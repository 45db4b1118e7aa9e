"""Head and loss: device time a step in the output layer of kind `rnnoutput`,
whose scope holds the head's product and the loss taken on it in blocks of
rows, forward plus backward, from the scoped trace."""

from benchmark import scope_reduce


def read(facts, trace):
    return scope_reduce.ms_per_step(
        facts, trace, scope_reduce.of_layer_kinds("rnnoutput"))

"""Step program: device-busy time a step, from the trace: the union of the
device-op intervals over the runs of the step program in the traced
window (or the steps the host dispatched in it, where the trace names no
programs)."""


def read(facts, trace):
    if trace is None:
        return None
    steps = trace["main_module_runs"] or facts.get("trace_steps")
    if not steps:
        return None
    return trace["busy_s"] / steps * 1e3

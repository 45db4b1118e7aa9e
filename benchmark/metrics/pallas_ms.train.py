"""Kernels: device time a step in Pallas (Mosaic) custom calls, from the
trace. Nothing to read where no custom call ran."""


def read(facts, trace):
    if trace is None or not trace["custom_call_s"]:
        return None
    steps = trace["main_module_runs"] or facts.get("trace_steps")
    if not steps:
        return None
    return trace["custom_call_s"] / steps * 1e3

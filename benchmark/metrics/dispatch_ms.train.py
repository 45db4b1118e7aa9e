"""Fit-loop dispatch: host time in the train-step call, from the program's
`fit_dispatch_seconds` histogram, window only."""


def read(facts, trace):
    if not facts["steps"]:
        return None
    before, after = facts["registry_before"], facts["registry_after"]
    key = "fit_dispatch_seconds:sum"
    if key not in after:
        return None
    return (after[key] - before.get(key, 0.0)) / facts["steps"] * 1e3

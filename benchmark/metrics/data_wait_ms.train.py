"""Input pipeline: host time a step waited on the iterator, from the
program's `fit_data_wait_seconds` histogram, window only."""


def read(facts, trace):
    if not facts["steps"]:
        return None
    before, after = facts["registry_before"], facts["registry_after"]
    key = "fit_data_wait_seconds:sum"
    if key not in after:
        return None
    return (after[key] - before.get(key, 0.0)) / facts["steps"] * 1e3

"""Fit-loop dispatch: seconds the `fit()` calls before the window (the
checked steps' and the warm one) spent in their own entry and exit, outside
the epochs: `fit_phase_seconds{phase="setup"}` (auto mesh, staging the input
pipeline) plus `{phase="teardown"}` (the blocking read of the layers' books,
which drains the steps in flight, and the pipeline's close). Nothing to read
from a program that keeps no such family."""


def read(facts, trace):
    before = facts["registry_before"]
    setup = before.get('fit_phase_seconds{phase="setup"}:sum')
    teardown = before.get('fit_phase_seconds{phase="teardown"}:sum')
    if setup is None or teardown is None:
        return None
    return setup + teardown

"""Step program: the whole step's share of the chip's peak. Model FLOPs of
the examples completed in the window (benchmark/flops.py, from the
configuration's layer shapes) over window seconds, chips and the bf16 peak
of benchmark/peaks.json."""


def read(facts, trace):
    if not facts["examples"]:
        return None
    achieved = facts["flops_per_example"] * facts["examples"] \
        / facts["window_s"] / facts["chips"]
    return 100.0 * achieved / facts["peak_flops_per_s"]

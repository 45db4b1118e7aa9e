"""All examples of all optimizer steps completed in the window, over the
whole window (host clock, from just before `fit()` to the last step's score
and parameters being ready), over the chips."""


def read(facts, trace):
    return facts["examples"] / facts["window_s"] / facts["chips"]

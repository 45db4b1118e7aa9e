"""Process start to the start of the window: imports, the pool and the
weights from the seed, compile or cache load, the checked first steps and
the warm `fit()`."""


def read(facts, trace):
    return facts["setup_s"]

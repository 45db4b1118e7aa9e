"""Net construction: wall time of the net's `init()` before the window
(parameters, layer state, updater state, with the compiles or cache loads of
the initialisers' programs), from the program's `net_init_seconds` histogram
as the registry stood when the window opened. Nothing to read from a program
that keeps no such family."""


def read(facts, trace):
    return facts["registry_before"].get("net_init_seconds:sum")

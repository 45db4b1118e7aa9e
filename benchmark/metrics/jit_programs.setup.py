"""Step program: programs jax compiled or loaded from its persistent cache
before the window (the count of `jit_compile_seconds{phase="backend"}`: the
backend stage runs on a hit too). Nothing to read from a program that keeps
no such family."""


def read(facts, trace):
    return facts["registry_before"].get(
        'jit_compile_seconds{phase="backend"}:count')

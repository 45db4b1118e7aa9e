"""Step program: seconds the process spent before the window tracing,
lowering, compiling and loading programs from jax's persistent cache, summed
over the phases of the program's `jit_compile_seconds{phase}` histogram. The
phases hold self times (a cache load is inside its backend stage and is
booked once), so the sum is wall time on the compiling threads. Nothing to
read from a program that keeps no such family."""


def read(facts, trace):
    sums = [value for key, value in facts["registry_before"].items()
            if key.startswith("jit_compile_seconds{")
            and key.endswith(":sum")]
    return sum(sums) if sums else None

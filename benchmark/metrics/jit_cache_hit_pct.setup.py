"""Step program: of the programs asked of jax's persistent compilation cache
before the window, the share it held (`jit_cache_total{result}`): 100 on a
warm run, near 0 on a cold one, 0 where nothing was asked of a cache. Nothing
to read from a program that keeps no such family."""


def read(facts, trace):
    before = facts["registry_before"]
    hits = before.get('jit_cache_total{result="hit"}')
    misses = before.get('jit_cache_total{result="miss"}')
    if hits is None or misses is None:
        return None
    return 100.0 * hits / max(1.0, hits + misses)

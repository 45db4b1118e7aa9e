"""Sparse-expert layers: the fullest held expert's assignments over the mean of
the held ones (1 is even), the worst layer's, as the program's registry holds
it after the window (`experts_load_max_over_mean`, published where devprof
blocks and at the end of fit()). Nothing to read until the books were
published once, or from a program that keeps none."""


def read(facts, trace):
    return facts["registry_after"].get("experts_load_max_over_mean")

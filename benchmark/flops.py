"""Model FLOPs of one training example, from layer shapes alone.

The benchmark's own arithmetic, so that no PR to the program can move the
numerator of `step_mfu_pct.train`: 2 FLOPs for each multiply-accumulate of
every conv and dense layer in the forward pass, times 3 for forward and
backward (the backward pass makes two products of the forward's size for
each layer). Nothing recomputed is counted, and elementwise work, BN, pooling
and the updater are left out, as the usual model-FLOPs convention has it.
"""

from __future__ import annotations

from typing import Iterable, Mapping


def layer_macs(layer: Mapping) -> int:
    """Multiply-accumulates of one layer's forward pass on one example."""
    if layer["kind"] == "conv":
        return (layer["h_out"] * layer["w_out"] * layer["k"] * layer["k"]
                * layer["c_in"] * layer["c_out"])
    if layer["kind"] == "dense":
        return layer["n_in"] * layer["n_out"]
    raise ValueError(f"no FLOP rule for a layer of kind {layer['kind']!r}")


def forward_flops_per_example(layers: Iterable[Mapping]) -> int:
    return 2 * sum(layer_macs(layer) for layer in layers)


def train_flops_per_example(layers: Iterable[Mapping]) -> int:
    return 3 * forward_flops_per_example(layers)

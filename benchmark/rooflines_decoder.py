"""Operations and bytes of the window layers' attention and of the gated
routed experts, for their shares of the roofline: beside `rooflines.py`
(whose counts read `hybrid_override_pattern` and two-matrix experts), from
the configuration and the traffic file alone, the same whatever implements
the scope. A training step is the forward pass and a backward pass of twice
its size, operands in the configuration's compute type, as there.
"""

from __future__ import annotations

from typing import Dict

from benchmark.rooflines import _BYTES, _PASSES, _tokens, cell_of_run, share

__all__ = ["band_pairs", "window_attention", "gated_experts", "cell_of_run",
           "share"]


def band_pairs(positions: int, window: int) -> int:
    """(query, key) pairs of one causal head under a window that counts
    the query's own position: `min(p + 1, window)` keys for query `p`."""
    w = min(int(window), int(positions))
    return w * (w + 1) // 2 + (positions - w) * w


def window_attention(config: dict, traffic: dict) -> Dict[str, float]:
    """The two products (scores, mix) of every window layer, a step on one
    chip: `n_heads head_dim` multiply-accumulates a product for each pair
    of the band; `q`, `k`, `v` read and the output written once a pass."""
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, t = config["head_dim"], int(traffic["seq_len"])
    wide = _BYTES[config["precision"]]
    layers = sum(1 for w in config["sliding_window_layout"] if w)
    macs = 2 * heads * hd * band_pairs(t, config["sliding_window_size"])
    moved = t * (2 * heads + 2 * kv) * hd * wide
    n = int(traffic["batch_per_chip"]) * layers * _PASSES
    return {"flops": 2.0 * macs * n, "bytes": float(moved * n)}


def gated_experts(config: dict, traffic: dict) -> Dict[str, float]:
    """The routed experts' three products (gate, up, down) in every layer,
    a step on one chip, at the uniform share: a token meets
    `experts_per_token * held / router_width` of this chip's experts. The
    held experts' three matrices are read once a pass; an assignment's
    input row is read, its two hidden rows written and read, its output
    row written."""
    d, f = config["hidden_size"], config["moe_ffn_hidden_size"]
    held = config["moe_num_primary_experts"]
    wide = _BYTES[config["precision"]]
    rows = _tokens(traffic) * config["moe_num_active_primary_experts"] \
        * held / config.get("router_width", held)
    macs = rows * 3 * d * f
    moved = held * 3 * d * f * wide + rows * (2 * d + 4 * f) * wide
    n = config["num_hidden_layers"] * _PASSES
    return {"flops": 2.0 * macs * n, "bytes": float(moved * n)}

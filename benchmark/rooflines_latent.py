"""Operations and bytes of the latent-attention layers' inner part (scores,
mask, softmax, mix), for its share of the roofline: beside `rooflines.py`
and `rooflines_decoder.py`, from the configuration and the traffic file
alone. The unpadded mathematics, whatever implements the scope: a kernel
that pads the 192-wide queries and keys to 256, or multiplies tiles above
the diagonal and masks them away, reads as a lost share, and no reading can
pass 100%. A training step is the forward pass and a backward pass of twice
its size, operands in the configuration's compute type, as there.
"""

from __future__ import annotations

from typing import Dict

from benchmark.rooflines import _BYTES, _PASSES, cell_of_run, share

__all__ = ["latent_attention", "cell_of_run", "share"]


def latent_attention(config: dict, traffic: dict) -> Dict[str, float]:
    """The two products of every latent-attention layer, a step on one
    chip: a causal head's `T (T + 1) / 2` (query, key) pairs multiply
    `qk_nope_head_dim + qk_rope_head_dim` for the score and `v_head_dim`
    for the mix; `q` (every head's own), `k_nope` and `v` (every head's
    own), `k_rope` (one head's, once) read and the output written once a
    pass."""
    heads, t = config["num_attention_heads"], int(traffic["seq_len"])
    nope, rot, vd = config["qk_nope_head_dim"], config["qk_rope_head_dim"], \
        config["v_head_dim"]
    wide = _BYTES[config["precision"]]
    macs = heads * (nope + rot + vd) * (t * (t + 1) // 2)
    moved = t * (heads * (nope + rot) + heads * nope + heads * vd + rot
                 + heads * vd) * wide
    n = int(traffic["batch_per_chip"]) * config["num_hidden_layers"] * _PASSES
    return {"flops": 2.0 * macs * n, "bytes": float(moved * n)}

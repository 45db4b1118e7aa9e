"""The smallthinker family's decoder as a ComputationGraph: layers of

    l = x W_router                          the router reads the layer's input
    x <- x + attention(RMSNorm(x))          window or full, rotary or none
    x <- x + experts(RMSNorm(x); l)         softmax over the chosen, gated

a token embedding before them, a final RMSNorm and an untied, bias-free head
after them; next-token cross-entropy on integer labels. Layer `i` is a
window layer (`sliding_window_size` keys, its own position among them) where
`sliding_window_layout[i]` is 1 and rotates queries and keys by position
where `rope_layout[i]` is 1; the published pattern is a full layer without a
positional term, then three window layers with one.

`smallthinker_conf` takes the keys of the family's published `config.json`
under their own names. The chip's share of a deployment is said with
`moe_num_primary_experts` (the experts held here) beside `router_width` (the
router's published width) and `experts_held`, and with `vocab_size` (the
rows of the vocabulary held here).
"""

from __future__ import annotations

from typing import Optional, Sequence

from deeplearning4j_tpu.nn.conf import (
    ElementWiseVertex,
    EmbeddingSequenceLayer,
    ExpertRouterLayer,
    GroupedQueryAttentionLayer,
    InputType,
    NeuralNetConfiguration,
    RMSNorm,
    RnnOutputLayer,
    SparseExpertsLayer,
    Updater,
)


def smallthinker_conf(
    num_hidden_layers: int = 4,
    sliding_window_layout: Sequence[int] = (0, 1, 1, 1),
    rope_layout: Sequence[int] = (0, 1, 1, 1),
    hidden_size: int = 2560,
    vocab_size: int = 18992,
    # attention
    num_attention_heads: int = 28,
    num_key_value_heads: int = 4,
    head_dim: int = 128,
    sliding_window_size: int = 4096,
    rope_theta: float = 1.5e6,
    # experts
    moe_num_primary_experts: int = 8,
    router_width: Optional[int] = None,
    experts_held: Optional[Sequence[int]] = None,
    moe_num_active_primary_experts: int = 6,
    moe_ffn_hidden_size: int = 768,
    moe_primary_router_apply_softmax: bool = True,
    norm_topk_prob: bool = True,
    capacity_factor: Optional[float] = None,
    rms_norm_eps: float = 1e-6,
    # training
    seq_len: Optional[int] = None,
    recompute: bool = True,
    head_rows_block: Optional[int] = 1,
    seed: int = 123,
    learning_rate: float = 1e-4,
    beta1: float = 0.9,
    beta2: float = 0.95,
    epsilon: float = 1e-8,
    initializer_range: float = 0.02,
    precision: str = "f32",
):
    """The decoder as a ComputationGraphConfiguration. Vertices: `embed`,
    then for layer `i` `b<i>_router` (on the layer's input), `b<i>_attn_norm`,
    `b<i>_attn`, `b<i>_attn_add`, `b<i>_ffn_norm`, `b<i>_experts` (on the
    norm and the router), `b<i>_ffn_add`, then `final_norm` and `head`.
    With `recompute` each of a layer's two sub-blocks (norm, mixer, add)
    runs under `jax.checkpoint`; the router's logits are kept.
    `head_rows_block` rows of the batch at a time go through the head and
    its loss."""
    windows, ropes = list(sliding_window_layout), list(rope_layout)
    if len(windows) != int(num_hidden_layers) or \
            len(ropes) != int(num_hidden_layers):
        raise ValueError(
            f"sliding_window_layout {windows} and rope_layout {ropes} do not "
            f"say what each of the {num_hidden_layers} layers is")
    if not (moe_primary_router_apply_softmax and norm_topk_prob):
        raise ValueError(
            "the family's router is a softmax normalised over the chosen "
            "experts (moe_primary_router_apply_softmax, norm_topk_prob)")
    router_width = int(router_width or moe_num_primary_experts)
    held = list(experts_held) if experts_held is not None \
        else list(range(int(moe_num_primary_experts)))
    if len(held) != int(moe_num_primary_experts):
        raise ValueError(f"experts_held {held} are not the "
                         f"{moe_num_primary_experts} experts held here")
    gb = (
        NeuralNetConfiguration.builder()
        .seed(seed)
        .updater(Updater.ADAM)
        .learning_rate(learning_rate)
        .adam_mean_decay(beta1)
        .adam_var_decay(beta2)
        .epsilon(epsilon)
        .activation("identity")
        .weight_init("distribution")
        # no "type" key: the serde reads one as a config tag, and normal is
        # the distribution's default
        .dist({"mean": 0.0, "std": initializer_range})
        .precision(precision)
        .graph_builder()
        .add_inputs("tokens")
        .set_input_types(InputType.token_sequence(vocab_size, seq_len))
    )
    gb.add_layer("embed", EmbeddingSequenceLayer(n_in=vocab_size,
                                                 n_out=hidden_size), "tokens")
    extra = {} if capacity_factor is None else {
        "capacity_factor": float(capacity_factor)}
    prev = "embed"
    for i, (window, rotary) in enumerate(zip(windows, ropes)):
        b = f"b{i}_"
        gb.add_layer(b + "router", ExpertRouterLayer(n_out=router_width),
                     prev)
        gb.add_layer(b + "attn_norm", RMSNorm(eps=rms_norm_eps), prev)
        gb.add_layer(b + "attn", GroupedQueryAttentionLayer(
            n_out=hidden_size, n_heads=num_attention_heads,
            n_kv_heads=num_key_value_heads, head_dim=head_dim, causal=True,
            window=int(sliding_window_size) if window else None,
            rope_theta=float(rope_theta) if rotary else None),
            b + "attn_norm")
        gb.add_vertex(b + "attn_add", ElementWiseVertex(op="add"), prev,
                      b + "attn")
        gb.add_layer(b + "ffn_norm", RMSNorm(eps=rms_norm_eps),
                     b + "attn_add")
        gb.add_layer(b + "experts", SparseExpertsLayer(
            n_out=hidden_size, router_width=router_width, experts_held=held,
            experts_per_token=moe_num_active_primary_experts,
            width=moe_ffn_hidden_size, activation="relu", gated=True,
            score="softmax", router_input=True, **extra),
            b + "ffn_norm", b + "router")
        gb.add_vertex(b + "ffn_add", ElementWiseVertex(op="add"),
                      b + "attn_add", b + "experts")
        if recompute:
            gb.recompute(b + "attn_norm", b + "attn", b + "attn_add")
            gb.recompute(b + "ffn_norm", b + "experts", b + "ffn_add")
        prev = b + "ffn_add"
    gb.add_layer("final_norm", RMSNorm(eps=rms_norm_eps), prev)
    gb.add_layer("head", RnnOutputLayer(
        n_out=vocab_size, activation="softmax", loss="sparse_mcxent",
        has_bias=False, head_rows_block=head_rows_block), "final_norm")
    gb.set_outputs("head")
    return gb.build()


def tiny_smallthinker_conf(precision: str = "f32",
                           seq_len: Optional[int] = 24, **kw):
    """The family at a size for CPU tests: one period (a full layer without
    positions, three window layers with), hidden 64, 4 query heads on 2
    key-value heads, a window of 8 (shorter than the sequence), 16 routed
    experts of which 8 are held."""
    sizes = dict(
        num_hidden_layers=4, sliding_window_layout=(0, 1, 1, 1),
        rope_layout=(0, 1, 1, 1), hidden_size=64, vocab_size=128,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        sliding_window_size=8, rope_theta=1.5e6,
        moe_num_primary_experts=8, router_width=16,
        experts_held=list(range(8)), moe_num_active_primary_experts=3,
        moe_ffn_hidden_size=48, seq_len=seq_len, precision=precision)
    sizes.update(kw)
    return smallthinker_conf(**sizes)

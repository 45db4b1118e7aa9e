"""The DeepSeek-V3 family's decoder (`model_type: deepseek_v3`; e.g.
kanana-2-30b-a3b) as a ComputationGraph: layers of

    x <- x + latent_attention(RMSNorm(x))     low-rank keys and values, a
                                              rotary slice of every head
    u  = RMSNorm(x)
    x <- x + Wd(act(Wg u) * (Wu u))           the first `first_k_dense_replace`
                                              layers: a dense gated MLP
    x <- x + experts(u) + shared(u)           the others: sigmoid-scored
                                              gated experts chosen on score +
                                              bias, and the shared experts as
                                              one gated MLP of their summed
                                              width

a token embedding before them, a final RMSNorm and an untied, bias-free head
after them; next-token cross-entropy on integer labels.

`deepseek_v3_conf` takes the keys of the family's published `config.json`
under their own names. The chip's share of a deployment is said with
`n_routed_experts` (the experts held here) beside `router_width` (the
router's published width) and `experts_held`, and with `vocab_size` (the
rows of the vocabulary held here). What every chip of a deployment computes
alike (attention, the dense MLP, the shared experts) is a vertex of its own
beside the expert vertex, which is the one told what it holds.

Not built, and refused rather than ignored: a query bottleneck
(`q_lora_rank`), group-limited selection (`n_group` or `topk_group` other
than 1), scaled rotary positions (`rope_scaling`), the half-split rotary
pairing (`rope_interleave` false), an expert layer on some of the later
layers only (`moe_layer_freq` other than 1). The update of the
selection bias from the load books (the family's auxiliary-loss-free
balancing) is outside the gradient and is not built: the bias is a
parameter that no optimizer step moves.
"""

from __future__ import annotations

from typing import Optional, Sequence

from deeplearning4j_tpu.nn.conf import (
    ElementWiseVertex,
    EmbeddingSequenceLayer,
    GatedMLPLayer,
    InputType,
    LatentAttentionLayer,
    NeuralNetConfiguration,
    RMSNorm,
    RnnOutputLayer,
    SparseExpertsLayer,
    Updater,
)


def deepseek_v3_conf(
    num_hidden_layers: int = 5,
    first_k_dense_replace: int = 1,
    moe_layer_freq: int = 1,
    hidden_size: int = 2048,
    vocab_size: int = 16032,
    hidden_act: str = "silu",
    intermediate_size: int = 6144,
    # attention
    num_attention_heads: int = 32,
    q_lora_rank: Optional[int] = None,
    kv_lora_rank: int = 512,
    qk_nope_head_dim: int = 128,
    qk_rope_head_dim: int = 64,
    v_head_dim: int = 128,
    rope_theta: float = 1e6,
    rope_interleave: bool = True,
    rope_scaling: Optional[dict] = None,
    # experts
    n_routed_experts: int = 16,
    router_width: Optional[int] = None,
    experts_held: Optional[Sequence[int]] = None,
    num_experts_per_tok: int = 6,
    n_shared_experts: int = 2,
    moe_intermediate_size: int = 768,
    routed_scaling_factor: float = 2.448,
    norm_topk_prob: bool = True,
    scoring_func: str = "sigmoid",
    topk_method: str = "noaux_tc",
    n_group: int = 1,
    topk_group: int = 1,
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
    then for layer `i` `b<i>_attn_norm`, `b<i>_attn`, `b<i>_attn_add`,
    `b<i>_ffn_norm`, `b<i>_mlp` (a dense layer) or `b<i>_experts` and
    `b<i>_shared` (an expert layer), `b<i>_ffn_add`, then `final_norm` and
    `head`. With `recompute` each of a layer's two sub-blocks (norm, mixer,
    add) runs under `jax.checkpoint`. `head_rows_block` rows of the batch at
    a time go through the head and its loss."""
    if q_lora_rank is not None:
        raise ValueError(f"q_lora_rank {q_lora_rank}: a query bottleneck is "
                         "not built (the queries are one projection)")
    if int(n_group) != 1 or int(topk_group) != 1:
        raise ValueError(f"n_group {n_group}, topk_group {topk_group}: "
                         "group-limited selection is not built")
    if not rope_interleave:
        raise ValueError("rope_interleave false: the latent layer rotates "
                         "adjacent pairs, the half-split pairing is not built")
    if rope_scaling is not None:
        raise ValueError("rope_scaling: scaled rotary positions (and their "
                         "mscale on the scores) are not built")
    if int(moe_layer_freq) != 1:
        raise ValueError(f"moe_layer_freq {moe_layer_freq}: every layer "
                         "past the leading dense ones is an expert layer")
    if scoring_func != "sigmoid" or topk_method != "noaux_tc" \
            or not norm_topk_prob:
        raise ValueError(
            "the family's router scores with a sigmoid, chooses on score + "
            "bias (noaux_tc) and normalises over the chosen "
            f"(norm_topk_prob); got {scoring_func!r}, {topk_method!r}, "
            f"{norm_topk_prob}")
    n_layers, n_dense = int(num_hidden_layers), int(first_k_dense_replace)
    if not 0 <= n_dense <= n_layers:
        raise ValueError(f"first_k_dense_replace {n_dense} of "
                         f"{n_layers} layers")
    router_width = int(router_width or n_routed_experts)
    held = list(experts_held) if experts_held is not None \
        else list(range(int(n_routed_experts)))
    if len(held) != int(n_routed_experts):
        raise ValueError(f"experts_held {held} are not the "
                         f"{n_routed_experts} experts held here")
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
    for i in range(n_layers):
        b = f"b{i}_"
        gb.add_layer(b + "attn_norm", RMSNorm(eps=rms_norm_eps), prev)
        gb.add_layer(b + "attn", LatentAttentionLayer(
            n_out=hidden_size, n_heads=num_attention_heads,
            qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            kv_lora_rank=kv_lora_rank, rope_theta=float(rope_theta),
            eps=rms_norm_eps),
            b + "attn_norm")
        gb.add_vertex(b + "attn_add", ElementWiseVertex(op="add"), prev,
                      b + "attn")
        gb.add_layer(b + "ffn_norm", RMSNorm(eps=rms_norm_eps),
                     b + "attn_add")
        if i < n_dense:
            mixers = [b + "mlp"]
            gb.add_layer(b + "mlp", GatedMLPLayer(
                n_out=hidden_size, width=intermediate_size,
                activation=hidden_act), b + "ffn_norm")
        else:
            mixers = [b + "experts"]
            gb.add_layer(b + "experts", SparseExpertsLayer(
                n_out=hidden_size, router_width=router_width,
                experts_held=held, experts_per_token=num_experts_per_tok,
                width=moe_intermediate_size, activation=hidden_act,
                gated=True, score="sigmoid", select_bias=True,
                scaling=routed_scaling_factor, **extra), b + "ffn_norm")
            if n_shared_experts:
                mixers.append(b + "shared")
                gb.add_layer(b + "shared", GatedMLPLayer(
                    n_out=hidden_size,
                    width=int(n_shared_experts) * int(moe_intermediate_size),
                    activation=hidden_act), b + "ffn_norm")
        gb.add_vertex(b + "ffn_add", ElementWiseVertex(op="add"),
                      b + "attn_add", *mixers)
        if recompute:
            gb.recompute(b + "attn_norm", b + "attn", b + "attn_add")
            gb.recompute(b + "ffn_norm", *mixers, b + "ffn_add")
        prev = b + "ffn_add"
    gb.add_layer("final_norm", RMSNorm(eps=rms_norm_eps), prev)
    gb.add_layer("head", RnnOutputLayer(
        n_out=vocab_size, activation="softmax", loss="sparse_mcxent",
        has_bias=False, head_rows_block=head_rows_block), "final_norm")
    gb.set_outputs("head")
    return gb.build()


def tiny_deepseek_v3_conf(precision: str = "f32",
                          seq_len: Optional[int] = 24, **kw):
    """The family at a size for CPU tests: one dense layer and two expert
    layers, hidden 64, 4 heads of 16 + 8 (queries and keys) and 16
    (values), a latent of 32, 16 routed experts of which 8 are held, 3 a
    token, 2 shared."""
    sizes = dict(
        num_hidden_layers=3, first_k_dense_replace=1, hidden_size=64,
        vocab_size=128, intermediate_size=96, num_attention_heads=4,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_theta=1e6, n_routed_experts=8, router_width=16,
        experts_held=list(range(8)), num_experts_per_tok=3,
        n_shared_experts=2, moe_intermediate_size=48,
        routed_scaling_factor=2.448, seq_len=seq_len, precision=precision)
    sizes.update(kw)
    return deepseek_v3_conf(**sizes)

"""The AFMoE family's decoder (`model_type: afmoe`; e.g. Trinity-Mini) as a
ComputationGraph: the token embedding times `sqrt(hidden_size)`
(`mup_enabled`), then layers of

    h = RMSNorm(x)
    x <- x + RMSNorm(attention(h))        query and key heads RMS-normed over
                                          head_dim, window layers rotated,
                                          the output times sigmoid(h Wg)
    u = RMSNorm(x)
    x <- x + RMSNorm(Wd(act(Wg u) * (Wu u)))           the first
                                          `num_dense_layers`: a dense MLP
    x <- x + RMSNorm(experts(u) + shared(u))           the others: sigmoid-
                                          scored gated experts chosen on
                                          score + bias, and the shared expert

a final RMSNorm and an untied, bias-free head after them; next-token
cross-entropy on integer labels. Each sub-layer is normed before and after
(the sandwich), the post-norm before the residual add. `layer_types[i]` says
what layer `i`'s queries see: `sliding_attention` the `sliding_window` keys
that end at their own position, with rotary positions; `full_attention` all
that came before, with no positional term.

`afmoe_conf` takes the keys of the family's published `config.json` under
their own names. The chip's share of a deployment is said with
`num_experts` (the experts held here) beside `router_width` (the router's
published width) and `experts_held`, and with `vocab_size` (the rows of the
vocabulary held here). What every chip of a deployment computes alike
(attention, the dense MLP, the shared expert) is a vertex of its own beside
the expert vertex, which is the one told what it holds.

Not built, and refused rather than ignored: group-limited selection
(`n_group`, `topk_group`, `num_expert_groups` or `num_limited_groups` other
than 1), another score than the sigmoid (`score_func`), weights that are not
normalised over the chosen (`route_norm` false), a head tied to the
embedding (`tie_word_embeddings`), scaled rotary positions (`rope_scaling`)
and any other kind of layer in `layer_types`. The update of the selection
bias from the load books (`load_balance_coeff`) is outside the gradient and
is not built: the bias is a parameter that no optimizer step moves.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

from deeplearning4j_tpu.nn.conf import (
    ElementWiseVertex,
    EmbeddingSequenceLayer,
    GatedMLPLayer,
    GroupedQueryAttentionLayer,
    InputType,
    NeuralNetConfiguration,
    RMSNorm,
    RnnOutputLayer,
    ScaleVertex,
    SparseExpertsLayer,
    Updater,
)

LAYER_TYPES = ("sliding_attention", "full_attention")


def afmoe_conf(
    num_hidden_layers: int = 5,
    layer_types: Sequence[str] = ("sliding_attention",) * 3
    + ("full_attention", "sliding_attention"),
    num_dense_layers: int = 1,
    hidden_size: int = 2048,
    vocab_size: int = 25024,
    hidden_act: str = "silu",
    intermediate_size: int = 6144,
    mup_enabled: bool = True,
    tie_word_embeddings: bool = False,
    # attention
    num_attention_heads: int = 32,
    num_key_value_heads: int = 4,
    head_dim: int = 128,
    sliding_window: int = 2048,
    rope_theta: float = 1e4,
    rope_scaling: Optional[dict] = None,
    # experts
    num_experts: int = 16,
    router_width: Optional[int] = None,
    experts_held: Optional[Sequence[int]] = None,
    num_experts_per_tok: int = 8,
    num_shared_experts: int = 1,
    moe_intermediate_size: int = 1024,
    score_func: str = "sigmoid",
    route_norm: bool = True,
    route_scale: float = 2.826,
    n_group: int = 1,
    topk_group: int = 1,
    num_expert_groups: int = 1,
    num_limited_groups: int = 1,
    capacity_factor: Optional[float] = None,
    rms_norm_eps: float = 1e-5,
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
    `embed_scale` (with `mup_enabled`), then for layer `i` `b<i>_attn_norm`,
    `b<i>_attn`, `b<i>_attn_post_norm`, `b<i>_attn_add`, `b<i>_ffn_norm`,
    `b<i>_mlp` (a dense layer) or `b<i>_experts`, `b<i>_shared` and their
    sum `b<i>_ffn_sum` (an expert layer), `b<i>_ffn_post_norm`,
    `b<i>_ffn_add`, then `final_norm` and `head`. With `recompute` each of
    a layer's two sub-blocks (norm, mixer, post-norm, add) runs under
    `jax.checkpoint`. `head_rows_block` rows of the batch at a time go
    through the head and its loss."""
    groups = {"n_group": n_group, "topk_group": topk_group,
              "num_expert_groups": num_expert_groups,
              "num_limited_groups": num_limited_groups}
    for key, value in groups.items():
        if int(value) != 1:
            raise ValueError(f"{key} {value}: group-limited selection is not "
                             "built")
    if score_func != "sigmoid":
        raise ValueError(f"score_func {score_func!r}: the family's router "
                         "scores with a sigmoid")
    if not route_norm:
        raise ValueError("route_norm false: the chosen experts' weights are "
                         "normalised over the chosen")
    if tie_word_embeddings:
        raise ValueError("tie_word_embeddings: a head tied to the embedding "
                         "is not built")
    if rope_scaling is not None:
        raise ValueError("rope_scaling: scaled rotary positions are not "
                         "built")
    types, n_layers = list(layer_types), int(num_hidden_layers)
    if len(types) != n_layers:
        raise ValueError(f"layer_types {types} do not say what each of the "
                         f"{n_layers} layers is")
    unknown = sorted(set(types) - set(LAYER_TYPES))
    if unknown:
        raise ValueError(f"layer_types {unknown}: only {LAYER_TYPES} are "
                         "built")
    n_dense = int(num_dense_layers)
    if not 0 <= n_dense <= n_layers:
        raise ValueError(f"num_dense_layers {n_dense} of {n_layers} layers")
    router_width = int(router_width or num_experts)
    held = list(experts_held) if experts_held is not None \
        else list(range(int(num_experts)))
    if len(held) != int(num_experts):
        raise ValueError(f"experts_held {held} are not the {num_experts} "
                         "experts held here")
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
    prev = "embed"
    if mup_enabled:
        gb.add_vertex("embed_scale",
                      ScaleVertex(scale=math.sqrt(hidden_size)), prev)
        prev = "embed_scale"
    extra = {} if capacity_factor is None else {
        "capacity_factor": float(capacity_factor)}
    norm = lambda: RMSNorm(eps=rms_norm_eps)
    for i, kind in enumerate(types):
        b = f"b{i}_"
        window = kind == "sliding_attention"
        gb.add_layer(b + "attn_norm", norm(), prev)
        gb.add_layer(b + "attn", GroupedQueryAttentionLayer(
            n_out=hidden_size, n_heads=num_attention_heads,
            n_kv_heads=num_key_value_heads, head_dim=head_dim, causal=True,
            window=int(sliding_window) if window else None,
            rope_theta=float(rope_theta) if window else None,
            qk_norm=True, output_gate=True, eps=rms_norm_eps),
            b + "attn_norm")
        gb.add_layer(b + "attn_post_norm", norm(), b + "attn")
        gb.add_vertex(b + "attn_add", ElementWiseVertex(op="add"), prev,
                      b + "attn_post_norm")
        gb.add_layer(b + "ffn_norm", norm(), b + "attn_add")
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
                scaling=route_scale, **extra), b + "ffn_norm")
            if num_shared_experts:
                gb.add_layer(b + "shared", GatedMLPLayer(
                    n_out=hidden_size,
                    width=int(num_shared_experts) * int(moe_intermediate_size),
                    activation=hidden_act), b + "ffn_norm")
                # the post-norm reads the sum of the two parts
                gb.add_vertex(b + "ffn_sum", ElementWiseVertex(op="add"),
                              b + "experts", b + "shared")
                mixers += [b + "shared", b + "ffn_sum"]
        gb.add_layer(b + "ffn_post_norm", norm(), mixers[-1])
        gb.add_vertex(b + "ffn_add", ElementWiseVertex(op="add"),
                      b + "attn_add", b + "ffn_post_norm")
        if recompute:
            gb.recompute(b + "attn_norm", b + "attn", b + "attn_post_norm",
                         b + "attn_add")
            gb.recompute(b + "ffn_norm", *mixers, b + "ffn_post_norm",
                         b + "ffn_add")
        prev = b + "ffn_add"
    gb.add_layer("final_norm", norm(), prev)
    gb.add_layer("head", RnnOutputLayer(
        n_out=vocab_size, activation="softmax", loss="sparse_mcxent",
        has_bias=False, head_rows_block=head_rows_block), "final_norm")
    gb.set_outputs("head")
    return gb.build()


def tiny_afmoe_conf(precision: str = "f32", seq_len: Optional[int] = 24,
                    **kw):
    """The family at a size for CPU tests: one dense window layer, then
    four expert layers (window, full, window, window), hidden 64, 4 query
    heads on 2 key-value heads of 16, a window of 8 (shorter than the
    sequence), 16 routed experts of which 8 are held, 3 a token, 1
    shared."""
    s, f = "sliding_attention", "full_attention"
    sizes = dict(
        num_hidden_layers=5, layer_types=(s, s, f, s, s), num_dense_layers=1,
        hidden_size=64, vocab_size=128, intermediate_size=96,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        sliding_window=8, rope_theta=1e4, num_experts=8, router_width=16,
        experts_held=list(range(8)), num_experts_per_tok=3,
        num_shared_experts=1, moe_intermediate_size=48, route_scale=2.826,
        seq_len=seq_len, precision=precision)
    sizes.update(kw)
    return afmoe_conf(**sizes)

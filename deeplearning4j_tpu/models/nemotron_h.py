"""The nemotron_h family's hybrid decoder as a ComputationGraph: blocks of
`x <- x + mixer(RMSNorm(x))` whose mixer is a Mamba-2 layer (`M`), a
sparse-expert feed-forward (`E`) or grouped-query causal attention (`*`), as
the pattern string says; a token embedding before them, a final RMSNorm and
an untied, bias-free head after them; next-token cross-entropy on integer
labels.

`nemotron_h_conf` takes the keys of the family's published `config.json`
under their own names. The chip's share of a deployment is said with
`n_routed_experts` (the experts held here) beside `router_width` (the
router's published width) and `experts_held`, and with `vocab_size` (the
rows of the vocabulary held here). No positional embedding is applied: the
family has none.
"""

from __future__ import annotations

from typing import Optional, Sequence

from deeplearning4j_tpu.nn.conf import (
    ElementWiseVertex,
    EmbeddingSequenceLayer,
    GroupedQueryAttentionLayer,
    InputType,
    Mamba2Layer,
    NeuralNetConfiguration,
    RMSNorm,
    RnnOutputLayer,
    SparseExpertsLayer,
    Updater,
)

BLOCK_KINDS = "ME*"   # Mamba-2 mixer, sparse experts, attention


def nemotron_h_conf(
    hybrid_override_pattern: str = "MEMEM*EME",
    hidden_size: int = 2688,
    vocab_size: int = 16384,
    # Mamba-2 mixer
    mamba_num_heads: int = 64,
    mamba_head_dim: int = 64,
    ssm_state_size: int = 128,
    n_groups: int = 8,
    conv_kernel: int = 4,
    chunk_size: int = 128,
    time_step_min: float = 1e-3,
    time_step_max: float = 1e-1,
    time_step_floor: float = 1e-4,
    # attention
    num_attention_heads: int = 32,
    num_key_value_heads: int = 2,
    head_dim: int = 128,
    # experts
    n_routed_experts: int = 8,
    router_width: Optional[int] = None,
    experts_held: Optional[Sequence[int]] = None,
    num_experts_per_tok: int = 6,
    moe_intermediate_size: int = 1856,
    moe_shared_expert_intermediate_size: int = 3712,
    routed_scaling_factor: float = 2.5,
    mlp_hidden_act: str = "relu2",
    layer_norm_epsilon: float = 1e-5,
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
    then for block `i` `b<i>_norm`, `b<i>_mixer`, `b<i>_add`, then
    `final_norm` and `head`. With `recompute` every block runs under
    `jax.checkpoint`; `head_rows_block` rows of the batch at a time go
    through the head and its loss."""
    unknown = set(hybrid_override_pattern) - set(BLOCK_KINDS)
    if unknown or not hybrid_override_pattern:
        raise ValueError(f"hybrid_override_pattern {hybrid_override_pattern!r}"
                         f": blocks are of {sorted(BLOCK_KINDS)}")
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
    prev = "embed"
    for i, kind in enumerate(hybrid_override_pattern):
        norm, mixer, add = f"b{i}_norm", f"b{i}_mixer", f"b{i}_add"
        gb.add_layer(norm, RMSNorm(eps=layer_norm_epsilon), prev)
        if kind == "M":
            layer = Mamba2Layer(
                n_out=hidden_size, n_heads=mamba_num_heads,
                head_dim=mamba_head_dim, state_size=ssm_state_size,
                n_groups=n_groups, conv_kernel=conv_kernel,
                chunk_size=chunk_size, norm_eps=layer_norm_epsilon,
                time_step_min=time_step_min, time_step_max=time_step_max,
                time_step_floor=time_step_floor)
        elif kind == "*":
            layer = GroupedQueryAttentionLayer(
                n_out=hidden_size, n_heads=num_attention_heads,
                n_kv_heads=num_key_value_heads, head_dim=head_dim,
                causal=True)
        else:
            layer = SparseExpertsLayer(
                n_out=hidden_size, router_width=router_width,
                experts_held=held, experts_per_token=num_experts_per_tok,
                width=moe_intermediate_size,
                shared_width=moe_shared_expert_intermediate_size,
                scaling=routed_scaling_factor, activation=mlp_hidden_act)
        gb.add_layer(mixer, layer, norm)
        gb.add_vertex(add, ElementWiseVertex(op="add"), prev, mixer)
        if recompute:
            gb.recompute(norm, mixer, add)
        prev = add
    gb.add_layer("final_norm", RMSNorm(eps=layer_norm_epsilon), prev)
    gb.add_layer("head", RnnOutputLayer(
        n_out=vocab_size, activation="softmax", loss="sparse_mcxent",
        has_bias=False, head_rows_block=head_rows_block), "final_norm")
    gb.set_outputs("head")
    return gb.build()


def tiny_nemotron_h_conf(precision: str = "f32", seq_len: Optional[int] = 32,
                         **kw):
    """The family at a size for CPU tests: hidden 64, 2 key-value heads, 16
    routed experts of which 8 are held, state 16, chunk 8, one block of
    each kind and a second mixer."""
    sizes = dict(
        hybrid_override_pattern="ME*M", hidden_size=64, vocab_size=128,
        mamba_num_heads=8, mamba_head_dim=16, ssm_state_size=16, n_groups=2,
        conv_kernel=4, chunk_size=8, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, n_routed_experts=8,
        router_width=16, experts_held=list(range(8)), num_experts_per_tok=3,
        moe_intermediate_size=48, moe_shared_expert_intermediate_size=96,
        seq_len=seq_len, precision=precision)
    sizes.update(kw)
    return nemotron_h_conf(**sizes)

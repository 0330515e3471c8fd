"""trinity-mini [moe]: afmoe, 32L d_model=2048 32H (GQA kv=4, head_dim 128),
2 leading dense layers (SwiGLU 6144), then 30 MoE layers of 128 sigmoid-
routed experts (width 1024, top-8, route_scale 2.826) plus one shared
expert; 3 sliding-window (2048) layers with RoPE then 1 full-attention
layer without position embedding, repeating; a sigmoid gate on the
attention output, q/k norms, and an RMS norm on each of the attention and
MLP outputs; vocab 200192, untied.
[hf:arcee-ai/Trinity-Mini config.json; the gate, q/k norms, output norms,
NoPE full layers and selection bias follow transformers' modeling_afmoe.py]

The stated deployment is expert parallel over 16 chips: each MoE layer's
128 experts are split 8 a chip, and everything else is replicated.  This
chip holds experts 0-7 of every MoE layer (``held_experts``); the router
keeps its 128 outputs and the top-8 choice.
"""

from repro.configs.base import AttentionConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="trinity-mini",
    family="moe",
    num_layers=32,
    d_model=2048,
    d_ff=6144,                      # the two leading dense layers' MLP
    vocab_size=200_192,
    attention=AttentionConfig(
        num_heads=32,
        num_kv_heads=4,
        head_dim=128,
        sliding_window=2048,
        local_global_ratio=3,       # layer_types: 3 sliding, 1 full
        rope_theta=10_000.0,
        qk_norm=True,
        output_gate=True,
        nope_global=True,
    ),
    moe=MoEConfig(
        num_experts=128,
        top_k=8,
        score_func="sigmoid",
        route_scale=2.826,
        selection_bias=True,
        shared_experts=1,
        expert_ff=1024,
        first_dense=2,
        held_experts=8,
        first_held=0,
        load_balance_loss=1e-3,     # load_balance_coeff
    ),
    max_seq_len=131_072,
    norm_eps=1e-5,
    tie_embeddings=False,
    act_fn="silu",
    post_norms=True,
)

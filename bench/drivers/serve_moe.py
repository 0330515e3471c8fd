"""Serve driver for an MoE decoder at one chip's expert share (afmoe):
``drivers/serve.py``'s run, with this file's ``model_config`` for the
configuration's afmoe keys.

``serve.py`` is loaded as a module of its own (nothing of it is edited
or shared) and its ``model_config`` is replaced by :func:`model_config`.
The program's MoE fields are checked before any weight is made, so a
program without the expert share refuses the configuration at once.
"""

from __future__ import annotations

import dataclasses

import harness as H

serve = H.load_module(H.BENCH / "drivers" / "serve.py")

# what the program has to offer for the configuration's afmoe keys
NEEDS = {"MoEConfig": ("score_func", "route_scale", "selection_bias",
                       "shared_experts", "expert_ff", "first_dense",
                       "held_experts", "first_held"),
         "AttentionConfig": ("qk_norm", "output_gate", "nope_global"),
         "ModelConfig": ("post_norms",)}


def model_config(c: dict):
    """The program's ``ModelConfig`` for an afmoe configuration file,
    refusing a file that states what the program cannot run, and a
    program that lacks the expert share's fields."""
    from repro.configs import base
    for cls, names in NEEDS.items():
        have = {f.name for f in dataclasses.fields(getattr(base, cls))}
        missing = [n for n in names if n not in have]
        if missing:
            raise H.BenchError(f"the program's {cls} has no {missing}: it "
                               f"cannot run {c['model_type']} at an "
                               f"expert share")
    d, h = c["hidden_size"], c["num_attention_heads"]
    pattern = ["sliding_attention"] * (c["global_attn_every_n_layers"] - 1) \
        + ["full_attention"]
    n = c["num_hidden_layers"]
    fixed = {"model_type": "afmoe", "hidden_act": "silu",
             "tie_word_embeddings": False, "mup_enabled": True,
             "score_func": "sigmoid", "route_norm": True, "n_group": 1,
             "topk_group": 1, "rope_scaling": None,
             "layer_types": (pattern * n)[:n]}
    for k, v in fixed.items():
        if c[k] != v:
            raise H.BenchError(f"the program runs {k} = {v!r}; the "
                               f"configuration states {c[k]!r}")
    dep = c["deployment"]
    e = c["num_experts"]
    if not 0 <= dep["first_held"] <= dep["router_experts"] - e:
        raise H.BenchError(f"experts [{dep['first_held']}, "
                           f"{dep['first_held'] + e}) are not among the "
                           f"router's {dep['router_experts']}")
    return base.ModelConfig(
        name=c["name"], family="moe", num_layers=n, d_model=d,
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        attention=base.AttentionConfig(
            num_heads=h, num_kv_heads=c["num_key_value_heads"],
            head_dim=c["head_dim"], sliding_window=c["sliding_window"],
            local_global_ratio=c["global_attn_every_n_layers"] - 1,
            rope_theta=c["rope_theta"], qk_norm=True, output_gate=True,
            nope_global=True),
        moe=base.MoEConfig(
            num_experts=dep["router_experts"],
            top_k=c["num_experts_per_tok"], score_func="sigmoid",
            route_scale=c["route_scale"], selection_bias=True,
            shared_experts=c["num_shared_experts"],
            expert_ff=c["moe_intermediate_size"],
            first_dense=c["num_dense_layers"], held_experts=e,
            first_held=dep["first_held"]),
        max_seq_len=c["max_position_embeddings"], norm_eps=c["rms_norm_eps"],
        tie_embeddings=False, act_fn="silu", gated_mlp=True,
        post_norms=True, dtype=c["torch_dtype"],
        param_dtype=c["torch_dtype"])


serve.model_config = model_config


def run(ctx) -> dict:
    return serve.run(ctx)


__all__ = ["run", "model_config"]

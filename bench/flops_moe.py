"""Operations of an MoE decoder at one chip's expert share
(afmoe: a dense prefix, then MoE layers with a shared expert; sliding and
full attention mixed), from the configuration's own shapes: the
yardstick's arithmetic, independent of the program."""

from __future__ import annotations


def _dims(c: dict):
    d, h = c["hidden_size"], c["num_attention_heads"]
    return d, h, c["num_key_value_heads"], c.get("head_dim") or d // h


def token_flops(c: dict, ctx: int) -> float:
    """Model FLOPs of one token at context ``ctx`` (the positions it
    attends to, itself included) on this chip: 2 per multiply-add of
    every weight it runs, plus 4 per head dimension per attended position
    (scores and values), the context capped at ``sliding_window`` on the
    sliding layers.  The routed experts count at their expected share
    here: each of the token's top-k experts is held on this chip with
    probability held / router_experts."""
    d, h, kvh, hd = _dims(c)
    layers, nd = c["num_hidden_layers"], c["num_dense_layers"]
    attn = d * hd * (h + 2 * kvh) + h * hd * d + d * h * hd   # + the gate
    f = c["moe_intermediate_size"]
    routed = c["num_experts_per_tok"] * c["num_experts"] \
        / c["deployment"]["router_experts"]
    moe = d * c["deployment"]["router_experts"] \
        + 3 * d * f * (c["num_shared_experts"] + routed)
    weights = layers * attn + nd * 3 * d * c["intermediate_size"] \
        + (layers - nd) * moe + d * c["vocab_size"]
    window = c["sliding_window"]
    seen = sum(min(ctx, window) if kind == "sliding_attention" else ctx
               for kind in c["layer_types"])
    return 2.0 * weights + 4.0 * h * hd * seen


__all__ = ["token_flops"]

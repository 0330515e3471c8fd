"""Operations a decoder LM needs per token, from the configuration's own
shapes (the yardstick's arithmetic, independent of the program)."""

from __future__ import annotations


def decoder_token_flops(c: dict, ctx: int) -> float:
    """Model FLOPs of one token at context ``ctx`` (the number of positions
    it attends to, itself included) through a dense GQA decoder with a
    gated MLP and an output head: 2 per multiply-add of every weight, plus
    4 per head dimension per attended position (scores and values)."""
    d = c["hidden_size"]
    h, kvh = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    f, v, layers = c["intermediate_size"], c["vocab_size"], \
        c["num_hidden_layers"]
    per_layer = d * hd * (h + 2 * kvh) + h * hd * d + 3 * d * f
    return 2.0 * (layers * per_layer + d * v) + 4.0 * layers * h * hd * ctx


__all__ = ["decoder_token_flops"]

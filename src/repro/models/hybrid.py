"""Hymba-style hybrid LM: every block runs attention heads and a mamba
SSM in parallel on the same (normed) input, combining the two branch
outputs (each RMS-normed) by averaging.  Sliding-window attention on all
but the first / middle / last layers; the SSM state plus windowed KV is
what makes the 500k decode cell feasible.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.layers.attention import (attention_init, attend, attend_naive,
                                    output_project, qkv_project)
from repro.layers.common import constrain, dtype_of, rmsnorm, rmsnorm_init, stacked_init
from repro.layers.embedding import embed, embedding_init, logits as logits_fn
from repro.layers.kvcache import (kv_cache_init, kv_update, kv_update_slots,
                                  slot_validity)
from repro.layers.mamba import mamba, mamba_init, mamba_state_init
from repro.layers.mlp import mlp, mlp_init
from repro.models.losses import ce_metrics, chunked_ce_loss
from repro.models.transformer import layer_flags


def hybrid_init(rng, cfg: ModelConfig) -> dict:
    a = cfg.attention
    r = jax.random.split(rng, 3)

    def one_layer(lr):
        ks = jax.random.split(lr, 3)
        return {
            "norm1": rmsnorm_init(cfg.d_model),
            "norm2": rmsnorm_init(cfg.d_model),
            "attn": attention_init(ks[0], cfg.d_model, a.num_heads,
                                   a.num_kv_heads, cfg.head_dim),
            "attn_norm": rmsnorm_init(cfg.d_model),
            "mamba": mamba_init(ks[1], cfg.d_model, cfg.ssm),
            "mamba_norm": rmsnorm_init(cfg.d_model),
            "mlp": mlp_init(ks[2], cfg.d_model, cfg.d_ff, gated=cfg.gated_mlp),
        }

    return {
        "embed": embedding_init(r[0], cfg.vocab_size, cfg.d_model,
                                tied=cfg.tie_embeddings),
        "layers": stacked_init(r[1], cfg.num_layers, one_layer),
        "final_norm": rmsnorm_init(cfg.d_model),
    }


def _block(lp, x, *, cfg, dp, positions, window, theta, mode,
           cache=None, cache_pos=None, impl="flash", q_block=512,
           kv_block=1024):
    a = cfg.attention
    h = rmsnorm(lp["norm1"], x, cfg.norm_eps)

    # --- attention branch ---
    q, k, v = qkv_project(lp["attn"], h, num_kv_heads=a.num_kv_heads,
                          positions=positions, theta=theta, qk_norm=False,
                          eps=cfg.norm_eps, dp=dp)
    new_cache = dict(cache) if cache is not None else None
    if mode == "decode":
        ck, cv = kv_update(cache["k"], cache["v"], k, v, cache_pos)
        s_max = ck.shape[1]
        k_pos = jnp.arange(s_max, dtype=jnp.int32)
        o = attend(q, ck, cv, q_pos=positions, k_pos=k_pos, causal=True,
                   window=window, k_valid=k_pos <= cache_pos,
                   impl="flash", q_block=1, kv_block=kv_block)
        new_cache["k"], new_cache["v"] = ck, cv
    elif mode == "decode_slots":
        # fixed-shape slot decode (serve/engine.py): q len 1 per slot,
        # per-slot write positions ``cache_pos`` (B,).  Same batched-mask
        # naive attend as the transformer's decode_slots — exact and tiny
        # at q=1.  The mamba branch below is already per-row recurrent, so
        # only the attention mask changes between the scalar-position
        # decode (the batch-1 reference loop, tests/conftest.py) and slot
        # decode.
        ck, cv = kv_update_slots(cache["k"], cache["v"], k, v, cache_pos)
        s_max = ck.shape[1]
        k_pos = jnp.arange(s_max, dtype=jnp.int32)
        valid = slot_validity(s_max, cache_pos)               # (B, S_max)
        w = jnp.asarray(window)
        valid &= jnp.where(w > 0,
                           cache_pos[:, None] - k_pos[None, :] < w, True)
        o = attend_naive(q, ck, cv, valid[:, None, :])
        new_cache["k"], new_cache["v"] = ck, cv
    else:
        if cache is not None:  # prefill
            new_cache["k"], new_cache["v"] = kv_update(cache["k"], cache["v"],
                                                       k, v, 0)
        o = attend(q, k, v, q_pos=positions, k_pos=positions,
                   causal=True, window=window, impl=impl,
                   q_block=q_block, kv_block=kv_block)
    attn_out = output_project(lp["attn"], o, dp=dp)

    # --- mamba branch (parallel, same input) ---
    st = {"conv": cache["conv"], "h": cache["h"]} if cache is not None else None
    m_out, m_state = mamba(lp["mamba"], h, cfg.ssm, state=st, dp=dp)
    if new_cache is not None:
        new_cache["conv"], new_cache["h"] = m_state["conv"], m_state["h"]

    x = x + 0.5 * (rmsnorm(lp["attn_norm"], attn_out, cfg.norm_eps)
                   + rmsnorm(lp["mamba_norm"], m_out, cfg.norm_eps))

    h = rmsnorm(lp["norm2"], x, cfg.norm_eps)
    x = x + mlp(lp["mlp"], h, act=cfg.act_fn, dp=dp)
    x = constrain(dp, x, ("batch", "seq_resid", "embed"), tag="layer/out")
    return x, new_cache


def hybrid_apply(params, cfg: ModelConfig, batch: dict, *, dp=None,
                 cache=None, train=False, remat="none", impl="flash",
                 q_block=512, kv_block=1024):
    dtype = dtype_of(cfg.dtype)
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = embed(params["embed"], tokens, dtype, dp=dp)
    positions = jnp.arange(s, dtype=jnp.int32)
    window_arr, theta_arr = layer_flags(cfg)
    mode = "prefill" if cache is not None else "train"

    def body(carry, xs):
        x = carry
        if cache is not None:
            lp, w, th, c = xs
        else:
            lp, w, th = xs
            c = None
        x, c = _block(lp, x, cfg=cfg, dp=dp, positions=positions, window=w,
                      theta=th, mode=mode, cache=c, impl=impl,
                      q_block=q_block, kv_block=kv_block)
        return x, c

    if remat in ("full", "dots"):
        pol = (None if remat == "full"
               else jax.checkpoint_policies.checkpoint_dots)
        body = jax.checkpoint(body, policy=pol, prevent_cse=False)

    xs = (params["layers"], jnp.asarray(window_arr), jnp.asarray(theta_arr))
    if cache is not None:
        xs = xs + (cache,)
    x, new_cache = jax.lax.scan(body, x, xs)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, jnp.zeros((), jnp.float32), new_cache, 0


def hybrid_loss(params, cfg, batch, *, dp=None, rng=None, remat="none",
                impl="flash"):
    x, aux, _, _ = hybrid_apply(params, cfg, batch, dp=dp, train=True,
                                remat=remat, impl=impl)
    table = params["embed"].get("head", params["embed"]["tok"])
    loss, correct, count = chunked_ce_loss(x, table, batch["labels"], dp=dp)
    m = ce_metrics(loss, correct, count, aux)
    return m["loss"], m


def hybrid_init_cache(cfg: ModelConfig, batch: int, max_len: int):
    a = cfg.attention
    kv = kv_cache_init(cfg.num_layers, batch, max_len, a.num_kv_heads,
                       cfg.head_dim, dtype=dtype_of(cfg.dtype))
    st = mamba_state_init(batch, cfg.d_model, cfg.ssm, dtype_of(cfg.dtype))
    L = cfg.num_layers
    return {
        "k": kv["k"], "v": kv["v"],
        "conv": jnp.broadcast_to(st["conv"][None], (L,) + st["conv"].shape).astype(jnp.float32),
        "h": jnp.broadcast_to(st["h"][None], (L,) + st["h"].shape),
    }


def hybrid_prefill(params, cfg, batch, cache, *, dp=None, impl="flash",
                   last_pos=None):
    """Fill attention cache + mamba state with the prompt.

    ``last_pos`` (B,) selects which hidden position feeds the logits.
    Unlike the transformer, right padding is NOT harmless here — padding
    tokens advance the mamba recurrence — so the serve engine prefills
    recurrent families at exact prompt length (``Model.recurrent``)."""
    x, _aux, cache, _ = hybrid_apply(params, cfg, batch, dp=dp, cache=cache,
                                     impl=impl)
    if last_pos is None:
        last = x[:, -1:, :]
    else:
        idx = jnp.asarray(last_pos, jnp.int32)
        last = x[jnp.arange(x.shape[0]), idx][:, None, :]
    return logits_fn(params["embed"], last, dp=dp), cache


def hybrid_decode_step(params, cfg, token, cache, pos, *, dp=None,
                       kv_block=1024):
    dtype = dtype_of(cfg.dtype)
    b = token.shape[0]
    x = embed(params["embed"], token, dtype, dp=dp)
    positions = jnp.full((1,), pos, jnp.int32)
    window_arr, theta_arr = layer_flags(cfg)

    def body(x, xs):
        lp, w, th, c = xs
        x, c = _block(lp, x, cfg=cfg, dp=dp, positions=positions, window=w,
                      theta=th, mode="decode", cache=c, cache_pos=pos,
                      kv_block=kv_block)
        return x, c

    xs = (params["layers"], jnp.asarray(window_arr), jnp.asarray(theta_arr),
          cache)
    x, new_cache = jax.lax.scan(body, x, xs)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return logits_fn(params["embed"], x, dp=dp), new_cache


def hybrid_decode_step_slots(params, cfg, token, cache, pos, *, dp=None,
                             kv_block=1024):
    """Fixed-shape slot decode: advance every slot one token at its own
    position ``pos`` (B,).  The attention branch masks per slot; the mamba
    branch is per-row recurrent state and needs no masking — a freed
    slot's state evolves harmlessly until ``state_slot_insert`` replaces
    the whole row."""
    dtype = dtype_of(cfg.dtype)
    x = embed(params["embed"], token, dtype, dp=dp)
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[:, None]                              # (B, 1)
    window_arr, theta_arr = layer_flags(cfg)

    def body(x, xs):
        lp, w, th, c = xs
        x, c = _block(lp, x, cfg=cfg, dp=dp, positions=positions, window=w,
                      theta=th, mode="decode_slots", cache=c, cache_pos=pos,
                      kv_block=kv_block)
        return x, c

    xs = (params["layers"], jnp.asarray(window_arr), jnp.asarray(theta_arr),
          cache)
    x, new_cache = jax.lax.scan(body, x, xs)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return logits_fn(params["embed"], x, dp=dp), new_cache


__all__ = ["hybrid_init", "hybrid_apply", "hybrid_loss", "hybrid_init_cache",
           "hybrid_prefill", "hybrid_decode_step", "hybrid_decode_step_slots"]

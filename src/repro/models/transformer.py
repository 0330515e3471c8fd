"""Decoder-only transformer LM covering the dense, MoE and VLM families
(gemma3-4b/1b, granite-34b/3-2b, llava-next-34b, arctic-480b, grok-1-314b,
trinity-mini).

The layer stack is a single ``lax.scan`` over stacked per-layer params;
per-layer heterogeneity (gemma3's 5:1 local:global pattern, per-layer RoPE
theta, afmoe's RoPE-less global layers) rides along as scanned flag
arrays, so the traced HLO contains ONE layer body regardless of depth —
which is what keeps 88-layer granite compilable at 512-way SPMD.  An MoE
model with leading dense layers (``MoEConfig.first_dense``) has two
bodies, each one scan: the dense prefix (``params["dense_layers"]``),
then the MoE layers (``params["layers"]``).

All communication edges are issued through the CoRD dataplane (``dp``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.paged_attention import paged_decode_attention
from repro.layers.attention import (
    attend,
    attend_naive,
    attention_init,
    output_gate,
    output_project,
    qkv_project,
)
from repro.layers.common import constrain, dense_init, dtype_of, rmsnorm, rmsnorm_init, stacked_init
from repro.layers.embedding import embed, embedding_init
from repro.layers.kvcache import (
    kv_cache_init,
    kv_update,
    kv_update_slots,
    slot_validity,
)
from repro.layers.mlp import mlp, mlp_init
from repro.layers.moe import expert_share, moe, moe_init
from repro.models.losses import ce_metrics, chunked_ce_loss

BIG_WINDOW = 0  # window value meaning "no window" in make_mask


# ---------------------------------------------------------------------------
# per-layer flags (local/global pattern, per-layer rope theta)
# ---------------------------------------------------------------------------

def layer_flags(cfg: ModelConfig) -> tuple[np.ndarray, np.ndarray]:
    a = cfg.attention
    L = cfg.num_layers
    if a.local_global_ratio > 0 and a.sliding_window > 0:
        # pattern: r local layers then 1 global, repeating (gemma3)
        r = a.local_global_ratio
        is_global = np.array([(i % (r + 1)) == r for i in range(L)])
    elif cfg.family == "hybrid" and a.sliding_window > 0:
        # hymba: first / middle / last layers are global
        is_global = np.zeros(L, bool)
        is_global[[0, L // 2, L - 1]] = True
    elif a.sliding_window > 0:
        is_global = np.zeros(L, bool)
    else:
        is_global = np.ones(L, bool)
    theta_g = a.rope_theta_global or a.rope_theta
    theta = np.where(is_global, theta_g, a.rope_theta).astype(np.float32)
    window = np.where(is_global, 0, a.sliding_window).astype(np.int32)
    return window, theta


def _per_layer(cfg: ModelConfig, *extra) -> tuple:
    """The scanned per-layer arrays: window, theta, then ``extra`` (a
    layer index, caches), then, where global layers take no position
    embedding, the RoPE flag — appended last so that a model without it
    scans exactly what it always did."""
    window, theta = layer_flags(cfg)
    xs = (jnp.asarray(window), jnp.asarray(theta), *extra)
    if cfg.attention.nope_global:
        xs += (jnp.asarray(window > 0),)
    return xs


def _scan_layers(body, carry, params, cfg: ModelConfig, xs: tuple,
                 wrap=None):
    """``lax.scan`` of ``body(carry, (lp, *xs_i))`` over the stack (through
    ``wrap``, e.g. a remat policy, where given), ``xs`` the per-layer
    arrays over all layers.  ``body`` returns ``(carry, (y, rows))``;
    returns ``(carry, ys, rows)``, the ys of all layers joined in layer
    order, ``rows`` those of the MoE layers."""
    f = wrap(body) if wrap else body
    nd = cfg.moe.first_dense if cfg.family == "moe" else 0
    if not nd:
        carry, (ys, rows) = jax.lax.scan(f, carry, (params["layers"], *xs))
        return carry, ys, rows
    carry, (ys0, _) = jax.lax.scan(f, carry, (params["dense_layers"],
                                              *(a[:nd] for a in xs)))
    carry, (ys1, rows) = jax.lax.scan(f, carry, (params["layers"],
                                                 *(a[nd:] for a in xs)))
    ys = jax.tree.map(lambda p, q: jnp.concatenate([p, q]), ys0, ys1)
    return carry, ys, rows


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def transformer_init(rng, cfg: ModelConfig) -> dict:
    a = cfg.attention
    r = jax.random.split(rng, 4)

    nd = cfg.moe.first_dense if cfg.family == "moe" else 0

    def one_layer(lr, dense=False):
        ks = jax.random.split(lr, 2)
        p = {
            "norm1": rmsnorm_init(cfg.d_model),
            "norm2": rmsnorm_init(cfg.d_model),
            "attn": attention_init(ks[0], cfg.d_model, a.num_heads,
                                   a.num_kv_heads, cfg.head_dim,
                                   qk_norm=a.qk_norm,
                                   output_gate=a.output_gate),
        }
        if cfg.post_norms:
            p["post_attn_norm"] = rmsnorm_init(cfg.d_model)
            p["post_mlp_norm"] = rmsnorm_init(cfg.d_model)
        if cfg.family == "moe" and not dense:
            p["moe"] = moe_init(ks[1], cfg.d_model, cfg.d_ff, cfg.moe,
                                gated=cfg.gated_mlp)
        else:
            p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff,
                                gated=cfg.gated_mlp)
        return p

    params = {
        "embed": embedding_init(r[0], cfg.vocab_size, cfg.d_model,
                                tied=cfg.tie_embeddings),
        "layers": stacked_init(r[1], cfg.num_layers - nd, one_layer),
        "final_norm": rmsnorm_init(cfg.d_model),
    }
    if nd:
        params["dense_layers"] = stacked_init(r[3], nd,
                                              partial(one_layer, dense=True))
    if cfg.family == "vlm":
        params["vision_proj"] = dense_init(r[2], cfg.frontend_dim, cfg.d_model)
    return params


# ---------------------------------------------------------------------------
# layer body (shared by train / prefill / decode)
# ---------------------------------------------------------------------------

def _layer(lp, x, *, cfg, dp, positions, window, theta, mode,
           cache_k=None, cache_v=None, cache_pos=None, tables=None,
           layer=None, kv_len=None, train=False, impl="flash", q_block=512,
           kv_block=1024, rope=None):
    """One layer.  Returns ``(x, aux, new_ck, new_cv, rows)``: ``rows``
    (held,) int32 counts the rows each held expert computed where the
    layer runs through its expert share (``expert_share``), else None."""
    a = cfg.attention
    h = rmsnorm(lp["norm1"], x, cfg.norm_eps)
    q, k, v = qkv_project(lp["attn"], h, num_kv_heads=a.num_kv_heads,
                          positions=positions, theta=theta,
                          qk_norm=a.qk_norm, eps=cfg.norm_eps, dp=dp,
                          rope=rope)
    aux = jnp.zeros((), jnp.float32)
    if mode == "train":
        o = attend(q, k, v, q_pos=positions, k_pos=positions,
                   causal=True, window=window, logit_cap=a.logit_softcap,
                   impl=impl, q_block=q_block, kv_block=kv_block)
        new_ck = new_cv = None
    elif mode == "prefill":
        cache_k, cache_v = kv_update(cache_k, cache_v, k, v, 0)
        o = attend(q, k, v, q_pos=positions, k_pos=positions,
                   causal=True, window=window, logit_cap=a.logit_softcap,
                   impl=impl, q_block=q_block, kv_block=kv_block)
        new_ck, new_cv = cache_k, cache_v
    elif mode == "decode_slots":
        # fixed-shape slot decode: q len 1 per slot, per-slot write
        # positions (B,). The (B, 1, S_max) mask is tiny at q=1, so the
        # batched-mask naive path is exact and memory-safe here (the
        # make_mask hoisting hazard only bites the flash scans).
        cache_k, cache_v = kv_update_slots(cache_k, cache_v, k, v, cache_pos)
        s_max = cache_k.shape[1]
        k_pos = jnp.arange(s_max, dtype=jnp.int32)
        ck = constrain(dp, cache_k,
                       ("batch", "kv_seq", "kv_heads", "cache_head_dim"),
                       tag="attn/cache_k")
        cv = constrain(dp, cache_v,
                       ("batch", "kv_seq", "kv_heads", "cache_head_dim"),
                       tag="attn/cache_v")
        valid = slot_validity(s_max, cache_pos)               # (B, S_max)
        w = jnp.asarray(window)
        valid &= jnp.where(w > 0,
                           cache_pos[:, None] - k_pos[None, :] < w, True)
        o = attend_naive(q, ck, cv, valid[:, None, :],
                         logit_cap=a.logit_softcap)
        new_ck, new_cv = cache_k, cache_v
    elif mode == "decode_paged":
        # paged slot decode: cache_k / cache_v are the whole block pool
        # (layers, n_blocks + 1, bs, KVH·hd).  This layer's slice crosses
        # the dataplane as its cache edge, and the kernel reads each
        # slot's blocks through its table from the edge's output — or,
        # where the pipeline has no stages (bypass: the edge moves
        # nothing), from the pool in place.  The new token is attended
        # from k / v and returned for the caller to write into the pool.
        pool_axes = (None, None, ("kv_heads", "cache_head_dim"))
        ck = constrain(dp, cache_k[layer], pool_axes, tag="attn/cache_k")
        cv = constrain(dp, cache_v[layer], pool_axes, tag="attn/cache_v")
        if dp is not None and dp.pipeline.stages:
            cache_k, cache_v, layer = ck[None], cv[None], 0
        o = paged_decode_attention(q[:, 0], cache_k, cache_v, tables,
                                   cache_pos, k[:, 0], v[:, 0], layer=layer,
                                   window=window,
                                   logit_cap=a.logit_softcap)[:, None]
        new_ck, new_cv = k[:, 0], v[:, 0]
    elif mode == "chunk":
        # chunked prefill: q len C written into the cache at a *traced*
        # offset (cache_pos), attending to everything filled so far.  The
        # cache constrain is the same mediation edge decode pays, so every
        # chunk is accounted through the fused pipeline like a decode tick.
        cache_k, cache_v = kv_update(cache_k, cache_v, k, v, cache_pos)
        s_max = cache_k.shape[1]
        k_pos = jnp.arange(s_max, dtype=jnp.int32)
        k_valid = k_pos < cache_pos + q.shape[1]
        ck = constrain(dp, cache_k,
                       ("batch", "kv_seq", "kv_heads", "cache_head_dim"),
                       tag="attn/cache_k")
        cv = constrain(dp, cache_v,
                       ("batch", "kv_seq", "kv_heads", "cache_head_dim"),
                       tag="attn/cache_v")
        o = attend(q, ck, cv, q_pos=positions, k_pos=k_pos, causal=True,
                   window=window, logit_cap=a.logit_softcap, k_valid=k_valid,
                   impl="flash", q_block=q_block, kv_block=kv_block)
        new_ck, new_cv = cache_k, cache_v
    else:  # decode: q len 1 against the cache
        cache_k, cache_v = kv_update(cache_k, cache_v, k, v, cache_pos)
        s_max = cache_k.shape[1]
        k_pos = jnp.arange(s_max, dtype=jnp.int32)
        k_valid = k_pos <= cache_pos
        ck = constrain(dp, cache_k,
                       ("batch", "kv_seq", "kv_heads", "cache_head_dim"),
                       tag="attn/cache_k")
        cv = constrain(dp, cache_v,
                       ("batch", "kv_seq", "kv_heads", "cache_head_dim"),
                       tag="attn/cache_v")
        o = attend(q, ck, cv, q_pos=positions, k_pos=k_pos, causal=True,
                   window=window, logit_cap=a.logit_softcap, k_valid=k_valid,
                   impl="flash", q_block=1, kv_block=kv_block)
        new_ck, new_cv = cache_k, cache_v
    if "wgate" in lp["attn"]:
        o = output_gate(lp["attn"], h, o)
    o = output_project(lp["attn"], o, dp=dp)
    if "post_attn_norm" in lp:
        o = rmsnorm(lp["post_attn_norm"], o, cfg.norm_eps)
    x = x + o

    h = rmsnorm(lp["norm2"], x, cfg.norm_eps)
    rows = None
    if "moe" not in lp:
        f = mlp(lp["mlp"], h, act=cfg.act_fn, dp=dp)
    elif cfg.moe.held_experts and not train:
        f, rows = expert_share(lp["moe"], h, cfg.moe, act=cfg.act_fn, dp=dp)
    else:
        f, aux = moe(lp["moe"], h, cfg.moe, act=cfg.act_fn, train=train,
                     dp=dp)
    if "post_mlp_norm" in lp:
        f = rmsnorm(lp["post_mlp_norm"], f, cfg.norm_eps)
    x = x + f
    x = constrain(dp, x, ("batch", "seq_resid", "embed"), tag="layer/out")
    return x, aux, new_ck, new_cv, rows


# ---------------------------------------------------------------------------
# full-sequence apply (train / prefill)
# ---------------------------------------------------------------------------

def transformer_apply(params, cfg: ModelConfig, batch: dict, *, dp=None,
                      cache=None, train=False, remat="none", impl="flash",
                      q_block=512, kv_block=1024):
    """Returns (final_hiddens, aux_loss, new_cache, prefix_len)."""
    dtype = dtype_of(cfg.dtype)
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = embed(params["embed"], tokens, dtype, dp=dp)
    prefix = 0
    if cfg.family == "vlm" and "patches" in batch:
        pe = jnp.einsum("bpf,fd->bpd", batch["patches"].astype(dtype),
                        params["vision_proj"].astype(dtype))
        pe = constrain(dp, pe, ("batch", "seq", "embed"), tag="vision/proj")
        x = jnp.concatenate([pe, x], axis=1)
        prefix = pe.shape[1]
        s = s + prefix
    positions = jnp.arange(s, dtype=jnp.int32)

    mode = "prefill" if cache is not None else "train"

    def body(carry, xs):
        x, aux = carry
        if cache is not None:
            lp, w, th, ck, cv, *rope = xs
        else:
            lp, w, th, *rope = xs
            ck = cv = None
        x, a, ck, cv, rows = _layer(lp, x, cfg=cfg, dp=dp,
                                    positions=positions, window=w, theta=th,
                                    mode=mode, cache_k=ck, cache_v=cv,
                                    train=train, impl=impl, q_block=q_block,
                                    kv_block=kv_block,
                                    rope=rope[0] if rope else None)
        out = (ck, cv) if cache is not None else None
        return (x, aux + a), (out, rows)

    wrap = None
    if remat == "full":
        wrap = partial(jax.checkpoint, prevent_cse=False)
    elif remat == "dots":
        wrap = partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.checkpoint_dots,
                       prevent_cse=False)

    xs = _per_layer(cfg, *((cache["k"], cache["v"]) if cache is not None
                           else ()))
    (x, aux), caches, _ = _scan_layers(body, (x, jnp.zeros((), jnp.float32)),
                                       params, cfg, xs, wrap=wrap)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    new_cache = None
    if cache is not None:
        new_cache = {"k": caches[0], "v": caches[1]}
    return x, aux, new_cache, prefix


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def transformer_loss(params, cfg: ModelConfig, batch: dict, *, dp=None,
                     rng=None, remat="none", impl="flash"):
    x, aux, _, prefix = transformer_apply(params, cfg, batch, dp=dp,
                                          train=True, remat=remat, impl=impl)
    if prefix:
        x = x[:, prefix:]
    table = params["embed"].get("head", params["embed"]["tok"])
    loss, correct, count = chunked_ce_loss(x, table, batch["labels"], dp=dp)
    m = ce_metrics(loss, correct, count, aux)
    return m["loss"], m


def transformer_init_cache(cfg: ModelConfig, batch: int, max_len: int):
    a = cfg.attention
    return kv_cache_init(cfg.num_layers, batch, max_len, a.num_kv_heads,
                         cfg.head_dim, dtype=dtype_of(cfg.dtype))


def transformer_prefill(params, cfg: ModelConfig, batch: dict, cache, *,
                        dp=None, impl="flash", last_pos=None):
    """Fill the cache with the prompt; returns (last_hidden_logits, cache).

    ``last_pos`` (B,) int32 selects the per-request position whose hidden
    state feeds the logits — the last *real* prompt token when prompts are
    right-padded to a bucket capacity.  Right padding sits causally after
    every real token, so bucketing never perturbs the returned logits.
    Default (None) keeps the legacy behaviour: logits at the final
    sequence position."""
    # caches sized >= prompt length; positions start at 0
    x, _aux, cache, prefix = transformer_apply(params, cfg, batch, dp=dp,
                                               cache=cache, impl=impl)
    from repro.layers.embedding import logits as logits_fn
    if last_pos is None:
        last = x[:, -1:, :]
    else:
        idx = jnp.asarray(last_pos, jnp.int32) + prefix
        last = x[jnp.arange(x.shape[0]), idx][:, None, :]
    return logits_fn(params["embed"], last, dp=dp), cache


def transformer_prefill_chunk(params, cfg: ModelConfig, batch: dict, cache,
                              offset, *, dp=None, last_pos=None,
                              kv_block=1024):
    """One prefill *chunk*: write ``batch["tokens"]`` (B, C) into the cache
    at traced position ``offset`` and attend causally to everything filled
    so far.  Returns (logits, cache) like :func:`transformer_prefill`;
    the logits only matter on the chunk containing ``last_pos`` (the last
    real prompt token) — earlier chunks' logits are discarded by the
    caller.

    ``offset`` is a traced scalar, so ONE jitted chunk step serves every
    chunk of every prompt of a given chunk length — the chunked analogue
    of the fixed-shape slot decode.  Token-only batches (no vision
    prefix); the engine falls back to whole prefill otherwise."""
    dtype = dtype_of(cfg.dtype)
    tokens = batch["tokens"]
    b, c = tokens.shape
    offset = jnp.asarray(offset, jnp.int32)
    x = embed(params["embed"], tokens, dtype, dp=dp)
    positions = offset + jnp.arange(c, dtype=jnp.int32)

    def body(x, xs):
        lp, w, th, ck, cv, *rope = xs
        x, _aux, ck, cv, rows = _layer(lp, x, cfg=cfg, dp=dp,
                                       positions=positions, window=w,
                                       theta=th, mode="chunk", cache_k=ck,
                                       cache_v=cv, cache_pos=offset,
                                       kv_block=kv_block,
                                       rope=rope[0] if rope else None)
        return x, ((ck, cv), rows)

    xs = _per_layer(cfg, cache["k"], cache["v"])
    x, caches, _ = _scan_layers(body, x, params, cfg, xs)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    from repro.layers.embedding import logits as logits_fn
    if last_pos is None:
        last = x[:, -1:, :]
    else:
        idx = jnp.clip(jnp.asarray(last_pos, jnp.int32) - offset, 0, c - 1)
        last = x[jnp.arange(b), idx][:, None, :]
    return logits_fn(params["embed"], last, dp=dp), {"k": caches[0],
                                                     "v": caches[1]}


def transformer_decode_step(params, cfg: ModelConfig, token, cache, pos, *,
                            dp=None, kv_block=1024):
    """One decode step. token: (B,1) int32; pos: scalar int32 (current
    write position = number of tokens already in cache)."""
    dtype = dtype_of(cfg.dtype)
    b = token.shape[0]
    x = embed(params["embed"], token, dtype, dp=dp)
    positions = jnp.full((1,), pos, jnp.int32)

    def body(x, xs):
        lp, w, th, ck, cv, *rope = xs
        x, _aux, ck, cv, rows = _layer(lp, x, cfg=cfg, dp=dp,
                                       positions=positions, window=w,
                                       theta=th, mode="decode", cache_k=ck,
                                       cache_v=cv, cache_pos=pos,
                                       kv_block=kv_block,
                                       rope=rope[0] if rope else None)
        return x, ((ck, cv), rows)

    xs = _per_layer(cfg, cache["k"], cache["v"])
    x, caches, _ = _scan_layers(body, x, params, cfg, xs)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    from repro.layers.embedding import logits as logits_fn
    return logits_fn(params["embed"], x, dp=dp), {"k": caches[0], "v": caches[1]}


def transformer_decode_step_slots(params, cfg: ModelConfig, token, cache,
                                  pos, *, dp=None):
    """One fixed-shape decode step over persistent slots.

    token: (B, 1) int32 — each slot's last sampled token; pos: (B,) int32
    per-slot write position.  Every shape is a function of the engine's
    slot geometry (max_batch, max_cache_len), never of the request mix, so
    this traces and compiles exactly once per engine.  Free slots still
    compute — their writes land at their stale position and are replaced
    on slot refill; the per-slot validity mask keeps stale cache entries
    unreachable."""
    dtype = dtype_of(cfg.dtype)
    pos = jnp.asarray(pos, jnp.int32)
    x = embed(params["embed"], token, dtype, dp=dp)
    positions = pos[:, None]                       # (B, 1) per-slot RoPE

    def body(x, xs):
        lp, w, th, ck, cv, *rope = xs
        x, _aux, ck, cv, rows = _layer(lp, x, cfg=cfg, dp=dp,
                                       positions=positions, window=w,
                                       theta=th, mode="decode_slots",
                                       cache_k=ck, cache_v=cv, cache_pos=pos,
                                       rope=rope[0] if rope else None)
        return x, ((ck, cv), rows)

    xs = _per_layer(cfg, cache["k"], cache["v"])
    x, caches, _ = _scan_layers(body, x, params, cfg, xs)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    from repro.layers.embedding import logits as logits_fn
    return logits_fn(params["embed"], x, dp=dp), {"k": caches[0], "v": caches[1]}


def transformer_decode_step_paged(params, cfg: ModelConfig, token, pool,
                                  tables, pos, *, dp=None):
    """One fixed-shape decode step over persistent slots whose caches live
    in a shared block pool.

    token: (B, 1) int32; pool: {"k", "v"} of (layers, n_blocks + 1, bs,
    KVH·hd) (``kv_pool_init``); tables: (B, T) int32 block ids; pos: (B,)
    int32, each slot's write position (the pool holds ``[0, pos)``).  Each
    layer attends each slot's live blocks straight from the pool
    (kernels/paged_attention) plus its new token, so nothing of the size
    of the whole table is built.  Returns ``(logits, new, rows)`` with
    ``new`` the appended token's {"k", "v"} of (layers, B, KVH, hd), which
    the caller writes into the pool (``kv_pool_scatter_token``), and
    ``rows`` the (MoE layers, held experts) int32 rows each held expert
    computed where the MoE layers run through their expert share, else
    None.  Shapes depend only on the slot and pool geometry: one compile
    per engine."""
    dtype = dtype_of(cfg.dtype)
    pos = jnp.asarray(pos, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    x = embed(params["embed"], token, dtype, dp=dp)
    positions = pos[:, None]                       # (B, 1) per-slot RoPE

    def body(x, xs):
        lp, w, th, layer, *rope = xs
        x, _aux, nk, nv, rows = _layer(lp, x, cfg=cfg, dp=dp,
                                       positions=positions, window=w,
                                       theta=th, mode="decode_paged",
                                       cache_k=pool["k"], cache_v=pool["v"],
                                       cache_pos=pos, tables=tables,
                                       layer=layer,
                                       rope=rope[0] if rope else None)
        return x, ((nk, nv), rows)

    xs = _per_layer(cfg, jnp.arange(cfg.num_layers, dtype=jnp.int32))
    x, new, rows = _scan_layers(body, x, params, cfg, xs)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    from repro.layers.embedding import logits as logits_fn
    return (logits_fn(params["embed"], x, dp=dp), {"k": new[0], "v": new[1]},
            rows)


__all__ = [
    "transformer_init", "transformer_apply", "transformer_loss",
    "transformer_init_cache", "transformer_prefill",
    "transformer_prefill_chunk", "transformer_decode_step",
    "transformer_decode_step_slots", "transformer_decode_step_paged",
    "layer_flags",
]

"""Unified model interface + input specs for every (arch × shape) cell.

``build_model(cfg)`` returns a :class:`Model` whose methods have identical
signatures across families, so the launcher / dry-run / serving engine are
architecture-agnostic.

``input_specs(cfg, shape)`` returns ``jax.ShapeDtypeStruct`` stand-ins for
every model input of that shape cell (weak-type-correct, shardable, no
device allocation) — the dry-run contract.  Modality frontends are stubs:
VLM cells get precomputed patch embeddings, audio cells get precomputed
mel frames.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import encdec, hybrid, transformer, xlstm_model


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable
    loss: Callable
    apply: Callable
    init_cache: Callable
    prefill: Callable
    decode_step: Callable
    # Fixed-shape decode over persistent slots (per-slot positions): the
    # serving engine's decode step on the stripe layout, which every
    # family's cache supports.
    decode_step_slots: Callable
    # Fixed-shape decode over slots whose caches live in a shared block
    # pool, read in place through per-slot block tables.  None for
    # families whose cache cannot be paged; the engine then refuses
    # block_size > 0.
    decode_step_paged: Callable | None = None
    # Chunked prefill: write one (B, C) chunk at a traced offset.  None for
    # families without it; the engine prefills whole prompts when absent.
    prefill_chunk: Callable | None = None
    # True when the decode cache holds recurrent state that every token —
    # real or padding — advances (mamba/xLSTM).  The serving engine then
    # prefills at exact prompt length instead of bucketed capacity: right
    # padding is causally inert for attention but corrupts a recurrence.
    recurrent: bool = False


def build_model(cfg: ModelConfig) -> Model:
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        m = transformer
        return Model(
            cfg=cfg,
            init=lambda rng: m.transformer_init(rng, cfg),
            loss=lambda params, batch, **kw: m.transformer_loss(
                params, cfg, batch, **kw),
            apply=lambda params, batch, **kw: m.transformer_apply(
                params, cfg, batch, **kw),
            init_cache=lambda batch, max_len: m.transformer_init_cache(
                cfg, batch, max_len),
            prefill=lambda params, batch, cache, **kw: m.transformer_prefill(
                params, cfg, batch, cache, **kw),
            decode_step=lambda params, token, cache, pos, **kw:
                m.transformer_decode_step(params, cfg, token, cache, pos, **kw),
            decode_step_slots=lambda params, token, cache, pos, **kw:
                m.transformer_decode_step_slots(params, cfg, token, cache,
                                                pos, **kw),
            decode_step_paged=lambda params, token, pool, tables, pos, **kw:
                m.transformer_decode_step_paged(params, cfg, token, pool,
                                                tables, pos, **kw),
            prefill_chunk=lambda params, batch, cache, offset, **kw:
                m.transformer_prefill_chunk(params, cfg, batch, cache,
                                            offset, **kw),
        )
    if fam == "hybrid":
        m = hybrid
        return Model(
            cfg=cfg,
            init=lambda rng: m.hybrid_init(rng, cfg),
            loss=lambda params, batch, **kw: m.hybrid_loss(params, cfg, batch, **kw),
            apply=lambda params, batch, **kw: m.hybrid_apply(params, cfg, batch, **kw),
            init_cache=lambda batch, max_len: m.hybrid_init_cache(cfg, batch, max_len),
            prefill=lambda params, batch, cache, **kw: m.hybrid_prefill(
                params, cfg, batch, cache, **kw),
            decode_step=lambda params, token, cache, pos, **kw:
                m.hybrid_decode_step(params, cfg, token, cache, pos, **kw),
            decode_step_slots=lambda params, token, cache, pos, **kw:
                m.hybrid_decode_step_slots(params, cfg, token, cache, pos,
                                           **kw),
            recurrent=True,
        )
    if fam == "ssm":
        m = xlstm_model
        return Model(
            cfg=cfg,
            init=lambda rng: m.xlstm_init(rng, cfg),
            loss=lambda params, batch, **kw: m.xlstm_loss(params, cfg, batch, **kw),
            apply=lambda params, batch, **kw: m.xlstm_apply(params, cfg, batch, **kw),
            init_cache=lambda batch, max_len=0: m.xlstm_init_cache(cfg, batch, max_len),
            prefill=lambda params, batch, cache, **kw: m.xlstm_prefill(
                params, cfg, batch, cache, **kw),
            decode_step=lambda params, token, cache, pos, **kw:
                m.xlstm_decode_step(params, cfg, token, cache, pos, **kw),
            decode_step_slots=lambda params, token, cache, pos, **kw:
                m.xlstm_decode_step_slots(params, cfg, token, cache, pos,
                                          **kw),
            recurrent=True,
        )
    if fam == "encdec":
        m = encdec
        return Model(
            cfg=cfg,
            init=lambda rng: m.encdec_init(rng, cfg),
            loss=lambda params, batch, **kw: m.encdec_loss(params, cfg, batch, **kw),
            apply=lambda params, batch, **kw: m.encdec_apply(params, cfg, batch, **kw),
            init_cache=lambda batch, max_len: m.encdec_init_cache(cfg, batch, max_len),
            prefill=lambda params, batch, cache, **kw: m.encdec_prefill(
                params, cfg, batch, cache, **kw),
            decode_step=lambda params, token, cache, pos, **kw:
                m.encdec_decode_step(params, cfg, token, cache, pos, **kw),
            decode_step_slots=lambda params, token, cache, pos, **kw:
                m.encdec_decode_step_slots(params, cfg, token, cache, pos,
                                           **kw),
        )
    raise ValueError(f"unknown family {fam!r}")


# ---------------------------------------------------------------------------
# input specs (dry-run contract)
# ---------------------------------------------------------------------------

def _text_len(cfg: ModelConfig, seq_len: int) -> int:
    """VLM cells budget the patch prefix inside the cell's seq_len."""
    if cfg.family == "vlm" and cfg.num_patches:
        return max(seq_len - cfg.num_patches, 16)
    return seq_len


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict[str, Any]:
    """ShapeDtypeStruct stand-ins for the step function of this cell."""
    b = shape.global_batch
    i32 = jnp.int32

    if shape.kind in ("train", "prefill"):
        s = _text_len(cfg, shape.seq_len)
        specs = {
            "tokens": jax.ShapeDtypeStruct((b, s), i32),
            "labels": jax.ShapeDtypeStruct((b, s), i32),
        }
        if cfg.family == "vlm":
            specs["patches"] = jax.ShapeDtypeStruct(
                (b, cfg.num_patches, cfg.frontend_dim), jnp.float32)
        if cfg.family == "encdec":
            specs["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.encoder_max_len, cfg.frontend_dim), jnp.float32)
        if shape.kind == "prefill":
            specs.pop("labels")
        return specs

    # decode: one new token against a cache of seq_len
    return {"token": jax.ShapeDtypeStruct((b, 1), i32)}


def cache_specs(cfg: ModelConfig, shape: ShapeConfig):
    """Abstract cache pytree for decode cells (no allocation)."""
    model = build_model(cfg)
    return jax.eval_shape(lambda: model.init_cache(shape.global_batch,
                                                   shape.seq_len))


def make_batch(cfg: ModelConfig, shape: ShapeConfig, rng,
               vocab_cap: int | None = None):
    """Concrete random batch matching input_specs (smoke tests, examples)."""
    specs = input_specs(cfg, shape)
    out = {}
    v = vocab_cap or cfg.vocab_size
    for name, sd in specs.items():
        rng, k = jax.random.split(rng)
        if sd.dtype == jnp.int32:
            out[name] = jax.random.randint(k, sd.shape, 0, v, jnp.int32)
        else:
            out[name] = jax.random.normal(k, sd.shape, sd.dtype)
    return out


__all__ = ["Model", "build_model", "input_specs", "cache_specs", "make_batch"]

"""xLSTM LM (xlstm-350m): a stack of mLSTM and sLSTM blocks following the
configured block pattern (e.g. "mmms" = 3 mLSTM : 1 sLSTM), scanned over
*pattern units* so the traced program contains one unit regardless of
depth.  Decode is pure recurrent state — O(1) memory per token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.layers.common import constrain, dtype_of, rmsnorm, rmsnorm_init, stacked_init
from repro.layers.embedding import embed, embedding_init, logits as logits_fn
from repro.layers.xlstm import (
    mlstm, mlstm_init, mlstm_state_init,
    slstm, slstm_init, slstm_state_init,
)
from repro.models.losses import ce_metrics, chunked_ce_loss


def _pattern(cfg: ModelConfig) -> str:
    pat = cfg.ssm.block_pattern
    L = cfg.num_layers
    if L % len(pat):
        # cycle the pattern and cut: fall back to unit = full depth
        pat = (pat * L)[:L]
    return pat


def xlstm_init(rng, cfg: ModelConfig) -> dict:
    pat = _pattern(cfg)
    reps = cfg.num_layers // len(pat)
    r = jax.random.split(rng, 2 + len(pat))

    unit = {}
    for j, kind in enumerate(pat):
        def one(lr, kind=kind):
            ks = jax.random.split(lr, 2)
            blk = {"norm": rmsnorm_init(cfg.d_model)}
            if kind == "m":
                blk["core"] = mlstm_init(ks[0], cfg.d_model, cfg.ssm)
            else:
                blk["core"] = slstm_init(ks[0], cfg.d_model, cfg.ssm)
            return blk
        unit[f"blk{j}"] = stacked_init(r[2 + j], reps, one)

    return {
        "embed": embedding_init(r[0], cfg.vocab_size, cfg.d_model,
                                tied=cfg.tie_embeddings),
        "units": unit,
        "final_norm": rmsnorm_init(cfg.d_model),
    }


def _unit_states(cfg: ModelConfig, batch: int) -> dict:
    pat = _pattern(cfg)
    reps = cfg.num_layers // len(pat)

    def stack(st):
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (reps,) + a.shape), st)

    states = {}
    for j, kind in enumerate(pat):
        if kind == "m":
            states[f"blk{j}"] = stack(mlstm_state_init(batch, cfg.d_model,
                                                       cfg.ssm))
        else:
            states[f"blk{j}"] = stack(slstm_state_init(batch, cfg.d_model,
                                                       cfg.ssm))
    return states


def xlstm_apply(params, cfg: ModelConfig, batch: dict, *, dp=None,
                cache=None, train=False, remat="none", chunk: int = 128):
    dtype = dtype_of(cfg.dtype)
    tokens = batch["tokens"]
    pat = _pattern(cfg)
    x = embed(params["embed"], tokens, dtype, dp=dp)

    def body(x, xs):
        new_states = {}
        for j, kind in enumerate(pat):
            blk = xs[f"blk{j}"]
            st = xs.get(f"st{j}")
            h = rmsnorm(blk["norm"], x, cfg.norm_eps)
            if kind == "m":
                out, ns = mlstm(blk["core"], h, cfg.ssm, state=st, dp=dp,
                                chunk=chunk)
            else:
                out, ns = slstm(blk["core"], h, cfg.ssm, state=st, dp=dp)
            x = x + out
            new_states[f"blk{j}"] = ns
        from repro.layers.common import constrain
        x = constrain(dp, x, ("batch", "seq_resid", "embed"), tag="layer/out")
        return x, new_states if cache is not None else None

    if remat in ("full", "dots"):
        pol = (None if remat == "full"
               else jax.checkpoint_policies.checkpoint_dots)
        body = jax.checkpoint(body, policy=pol, prevent_cse=False)

    xs = dict(params["units"])
    if cache is not None:
        for j in range(len(pat)):
            xs[f"st{j}"] = cache[f"blk{j}"]
    x, new_cache = jax.lax.scan(body, x, xs)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, jnp.zeros((), jnp.float32), new_cache, 0


def xlstm_loss(params, cfg, batch, *, dp=None, rng=None, remat="none",
               impl="flash"):
    x, aux, _, _ = xlstm_apply(params, cfg, batch, dp=dp, train=True,
                               remat=remat)
    table = params["embed"].get("head", params["embed"]["tok"])
    loss, correct, count = chunked_ce_loss(x, table, batch["labels"], dp=dp)
    m = ce_metrics(loss, correct, count, aux)
    return m["loss"], m


def xlstm_init_cache(cfg: ModelConfig, batch: int, max_len: int = 0):
    return _unit_states(cfg, batch)


def xlstm_prefill(params, cfg, batch, cache, *, dp=None, impl="flash",
                  last_pos=None):
    """Run the prompt through the recurrence, returning (logits, states).

    ``last_pos`` (B,) selects the hidden position feeding the logits.
    Padding is NOT inert for a recurrence (every token, real or pad,
    advances the mLSTM/sLSTM memories), so the serve engine prefills this
    family at exact prompt length (``Model.recurrent``)."""
    x, _aux, cache, _ = xlstm_apply(params, cfg, batch, dp=dp, cache=cache)
    if last_pos is None:
        last = x[:, -1:, :]
    else:
        idx = jnp.asarray(last_pos, jnp.int32)
        last = x[jnp.arange(x.shape[0]), idx][:, None, :]
    return logits_fn(params["embed"], last, dp=dp), cache


def xlstm_decode_step(params, cfg, token, cache, pos, *, dp=None, **_):
    dtype = dtype_of(cfg.dtype)
    x = embed(params["embed"], token, dtype, dp=dp)
    pat = _pattern(cfg)

    def body(x, xs):
        new_states = {}
        for j, kind in enumerate(pat):
            blk = xs[f"blk{j}"]
            st = xs[f"st{j}"]
            h = rmsnorm(blk["norm"], x, cfg.norm_eps)
            if kind == "m":
                out, ns = mlstm(blk["core"], h, cfg.ssm, state=st, chunk=1,
                                dp=dp)
            else:
                out, ns = slstm(blk["core"], h, cfg.ssm, state=st, dp=dp)
            x = x + out
            new_states[f"blk{j}"] = ns
        return x, new_states

    xs = dict(params["units"])
    for j in range(len(pat)):
        xs[f"st{j}"] = cache[f"blk{j}"]
    x, new_cache = jax.lax.scan(body, x, xs)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return logits_fn(params["embed"], x, dp=dp), new_cache


def xlstm_decode_step_slots(params, cfg, token, cache, pos, *, dp=None, **_):
    """Fixed-shape slot decode for the pure-recurrent family.

    Decode here is position-free — the recurrence carries all sequence
    context in the (reps, B, ...) unit states, and every batch row
    advances independently — so the per-slot ``pos`` vector the engine
    feeds is simply unused and ``xlstm_decode_step`` (the step the batch-1
    reference decode runs, tests/conftest.py) IS the slot decode step.  A
    freed slot's state keeps evolving on stale tokens until
    ``state_slot_insert`` overwrites the whole row at the next insert."""
    del pos
    return xlstm_decode_step(params, cfg, token, cache, 0, dp=dp)


__all__ = ["xlstm_init", "xlstm_apply", "xlstm_loss", "xlstm_init_cache",
           "xlstm_prefill", "xlstm_decode_step", "xlstm_decode_step_slots"]

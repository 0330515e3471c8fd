"""Grouped-query attention with causal / sliding-window / bidirectional
masking, RoPE, qk-norm, logit softcap, and a memory-bounded blocked
("flash"-style) XLA path.

Layouts: activations (B, S, H, D); KV (B, S, KVH, D); GQA groups the H
query heads into KVH groups of size G = H // KVH.

The blocked path (``impl="flash"``) is an online-softmax scan over KV
chunks, with queries processed in blocks — this is what keeps the 32k
prefill and 4k train cells inside per-device HBM at 256-way SPMD.  The
TPU production path swaps in the Pallas kernel (repro.kernels.flash_attention);
both are validated against ``impl="naive"``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.layers.common import constrain, dense_init, rmsnorm, softcap as _softcap

NEG_INF = -2.0**30   # large-negative for masking (safe in bf16 after cast)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def attention_init(rng, d_model: int, num_heads: int, num_kv_heads: int,
                   head_dim: int, qk_norm: bool = False,
                   output_gate: bool = False) -> dict:
    r = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(r[0], d_model, num_heads, head_dim),
        "wk": dense_init(r[1], d_model, num_kv_heads, head_dim),
        "wv": dense_init(r[2], d_model, num_kv_heads, head_dim),
        "wo": dense_init(r[3], num_heads * head_dim, d_model,
                         scale=1.0 / math.sqrt(num_heads * head_dim)),
    }
    if qk_norm:
        p["q_norm"] = {"scale": jnp.zeros((head_dim,), jnp.float32)}
        p["k_norm"] = {"scale": jnp.zeros((head_dim,), jnp.float32)}
    if output_gate:
        p["wgate"] = dense_init(jax.random.fold_in(rng, 4), d_model,
                                num_heads, head_dim)
    return p


def qkv_project(params: dict, x: jax.Array, *, num_kv_heads: int,
                positions: jax.Array, theta, qk_norm: bool,
                eps: float, dp=None, kv_input: jax.Array | None = None,
                rope=None):
    """Project to q, k, v (with RoPE + optional qk-norm applied).

    ``kv_input`` (cross-attention) routes k/v projections off a different
    sequence (encoder output); positions then only rotate q.  ``rope``, a
    (traced) per-layer flag, keeps q and k unrotated where it is false:
    a layer without position embedding inside a scanned stack."""
    xkv = x if kv_input is None else kv_input
    q = jnp.einsum("bsd,dhe->bshe", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhe->bshe", xkv, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhe->bshe", xkv, params["wv"].astype(x.dtype))
    if qk_norm:
        q = rmsnorm(params["q_norm"], q, eps)
        k = rmsnorm(params["k_norm"], k, eps)
    if theta is not None:
        from repro.layers.rope import apply_rope
        qr = apply_rope(q, positions, theta)
        kr = apply_rope(k, positions, theta) if kv_input is None else k
        if rope is None:
            q, k = qr, kr
        else:
            q, k = jnp.where(rope, qr, q), jnp.where(rope, kr, k)
    q = constrain(dp, q, ("batch", "seq", "heads", "head_dim"), tag="attn/q")
    k = constrain(dp, k, ("batch", "seq", "kv_heads", "head_dim"), tag="attn/k")
    v = constrain(dp, v, ("batch", "seq", "kv_heads", "head_dim"), tag="attn/v")
    return q, k, v


def output_gate(params: dict, x: jax.Array, o: jax.Array) -> jax.Array:
    """afmoe's gated attention: ``o * sigmoid(x Wgate)``, x the layer's
    normed input (B, S, D), o the attention output (B, S, H, hd)."""
    g = jnp.einsum("bsd,dhe->bshe", x, params["wgate"].astype(x.dtype))
    return o * jax.nn.sigmoid(g).astype(o.dtype)


def output_project(params: dict, o: jax.Array, dp=None) -> jax.Array:
    b, s, h, d = o.shape
    out = jnp.einsum("bsf,fd->bsd", o.reshape(b, s, h * d),
                     params["wo"].astype(o.dtype))
    return constrain(dp, out, ("batch", "seq", "embed"), tag="attn/out")


# ---------------------------------------------------------------------------
# masking
# ---------------------------------------------------------------------------

def make_mask(q_pos: jax.Array, k_pos: jax.Array, *, causal: bool,
              window, k_valid: jax.Array | None = None) -> jax.Array:
    """Boolean mask (Sq, Sk) from 1-D position vectors. ``window`` may be a
    traced scalar; 0 means "no window" (global layers).

    Positions are deliberately batch-free: a batched mask here gets hoisted
    out of the flash scans by XLA as a (nq, nk, B, qb, kb) monster buffer
    (measured: ~10 GiB/device at 4k×256 — see EXPERIMENTS.md §Perf)."""
    qp = q_pos[:, None]
    kp = k_pos[None, :]
    mask = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        mask &= kp <= qp
    if window is not None:
        w = jnp.asarray(window)
        mask &= jnp.where(w > 0, qp - kp < w, True)
    if k_valid is not None:
        mask &= k_valid[..., None, :]
    return mask


# ---------------------------------------------------------------------------
# reference (naive) attention — the oracle
# ---------------------------------------------------------------------------

def attend_naive(q, k, v, mask, *, logit_cap: float = 0.0,
                 scale: float | None = None) -> jax.Array:
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(b, sq, kvh, g, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    logits = _softcap(logits, logit_cap)
    if mask.ndim == 2:        # (Sq, Sk) from 1-D positions
        mask = mask[None, None, None]
    elif mask.ndim == 3:      # (B, Sq, Sk)
        mask = mask[:, None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return o.reshape(b, sq, h, d)


# ---------------------------------------------------------------------------
# blocked flash-style attention (pure XLA, memory-bounded)
#
# Forward: online-softmax scan over KV blocks (queries in blocks).
# Backward: custom VJP recomputing per-block probabilities from the saved
# (q, k, v, o, lse) — the real flash-attention algorithm, so the residual
# footprint is O(B·S·H·d) instead of O(B·H·S·S/blocks) saved probabilities.
# ---------------------------------------------------------------------------

def _float0_like(x):
    import numpy as _np
    return _np.zeros(x.shape, jax.dtypes.float0)


def attend_flash(q, k, v, *, q_pos, k_pos, causal: bool, window,
                 logit_cap: float = 0.0, k_valid=None,
                 q_block: int = 512, kv_block: int = 1024,
                 scale: float | None = None) -> jax.Array:
    """Flash attention with memory-bounded forward AND backward."""
    if window is None:
        window = jnp.zeros((), jnp.int32)        # 0 = no window
    if k_valid is None:
        k_valid = jnp.ones(k.shape[1], bool)
    q_pos = q_pos[0] if q_pos.ndim == 2 else jnp.broadcast_to(q_pos, (q.shape[1],))
    k_pos = jnp.broadcast_to(k_pos, (k.shape[1],))
    k_valid = jnp.broadcast_to(k_valid, (k.shape[1],))
    return _flash(q, k, v, q_pos, k_pos, jnp.asarray(window), k_valid,
                  causal, float(logit_cap), int(q_block), int(kv_block),
                  scale or 1.0 / math.sqrt(q.shape[-1]))


@partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11))
def _flash(q, k, v, q_pos, k_pos, window, k_valid, causal, logit_cap,
           q_block, kv_block, scale):
    o, _ = _flash_fwd_impl(q, k, v, q_pos, k_pos, window, k_valid, causal,
                           logit_cap, q_block, kv_block, scale)
    return o


def _blocking(sq, skv, q_block, kv_block):
    qb = min(q_block, sq)
    while sq % qb:
        qb -= 1
    kb = min(kv_block, skv)
    while skv % kb:
        kb -= 1
    return qb, kb


def _block_logits(qi, kj, qpos_i, kpos_j, kval_j, *, causal, window,
                  logit_cap, scale):
    """Masked, (soft-capped) scaled logits for one (q, kv) block pair.
    qi: (b,qb,kvh,g,d); kj: (b,kb,kvh,d) → (b,kvh,g,qb,kb)."""
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qi, kj,
                        preferred_element_type=jnp.float32) * scale
    logits = _softcap(logits, logit_cap)
    mask = make_mask(qpos_i, kpos_j, causal=causal, window=window,
                     k_valid=kval_j)                          # (qb, kb)
    return jnp.where(mask[None, None, None], logits, NEG_INF), mask


def _flash_fwd_impl(q, k, v, q_pos, k_pos, window, k_valid, causal,
                    logit_cap, q_block, kv_block, scale):
    """Returns (o, lse). lse: (B,KVH,G,Sq) log-sum-exp of scaled logits."""
    b, sq, h, d = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    qb, kb = _blocking(sq, skv, q_block, kv_block)
    nq, nk = sq // qb, skv // kb

    qg = q.reshape(b, nq, qb, kvh, g, d)
    q_pos_b = q_pos.reshape(nq, qb)
    kc = k.reshape(b, nk, kb, kvh, d)
    vc = v.reshape(b, nk, kb, kvh, d)
    k_pos_b = k_pos.reshape(nk, kb)
    kv_valid_b = k_valid.reshape(nk, kb)

    def q_step(_, q_args):
        qi, qpos_i = q_args                       # (b, qb, kvh, g, d), (qb,)

        def kv_step(carry, kv_args):
            m, l, acc = carry
            kj, vj, kpos_j, kval_j = kv_args
            logits, _ = _block_logits(qi, kj, qpos_i, kpos_j, kval_j,
                                      causal=causal, window=window,
                                      logit_cap=logit_cap, scale=scale)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vj.dtype), vj)
            acc_new = acc * corr[..., None].astype(acc.dtype) + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kvh, g, qb), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, qb), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, qb, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kc.swapaxes(0, 1), vc.swapaxes(0, 1), k_pos_b, kv_valid_b))
        o = acc / jnp.maximum(l[..., None], 1e-30)            # (b,kvh,g,qb,d)
        o = o.transpose(0, 3, 1, 2, 4).reshape(b, qb, h, d)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))              # (b,kvh,g,qb)
        return None, (o.astype(q.dtype), lse)

    _, (outs, lses) = jax.lax.scan(q_step, None,
                                   (qg.swapaxes(0, 1), q_pos_b))
    o = outs.swapaxes(0, 1).reshape(b, sq, h, d)
    lse = lses.transpose(1, 2, 3, 0, 4).reshape(b, kvh, g, sq)
    return o, lse


def _flash_fwd(q, k, v, q_pos, k_pos, window, k_valid, causal, logit_cap,
               q_block, kv_block, scale):
    o, lse = _flash_fwd_impl(q, k, v, q_pos, k_pos, window, k_valid, causal,
                             logit_cap, q_block, kv_block, scale)
    return o, (q, k, v, o, lse, q_pos, k_pos, window, k_valid)


def _flash_bwd(causal, logit_cap, q_block, kv_block, scale, res, do):
    q, k, v, o, lse, q_pos, k_pos, window, k_valid = res
    b, sq, h, d = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    qb, kb = _blocking(sq, skv, q_block, kv_block)
    nq, nk = sq // qb, skv // kb

    # delta = rowsum(do * o)  (B,KVH,G,Sq)
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
    delta = delta.reshape(b, sq, kvh, g).transpose(0, 2, 3, 1)

    qg = q.reshape(b, nq, qb, kvh, g, d).swapaxes(0, 1)
    dog = do.reshape(b, nq, qb, kvh, g, d).swapaxes(0, 1)
    lse_b = lse.reshape(b, kvh, g, nq, qb).transpose(3, 0, 1, 2, 4)
    delta_b = delta.reshape(b, kvh, g, nq, qb).transpose(3, 0, 1, 2, 4)
    qpos_b = q_pos.reshape(nq, qb)
    kc = k.reshape(b, nk, kb, kvh, d).swapaxes(0, 1)
    vc = v.reshape(b, nk, kb, kvh, d).swapaxes(0, 1)
    kpos_b = k_pos.reshape(nk, kb)
    kval_b = k_valid.reshape(nk, kb)

    def kv_step(carry, kv_args):
        dk, dv = carry
        kj, vj, kpos_j, kval_j = kv_args

        def q_step(carry2, q_args):
            dkj, dvj = carry2
            qi, doi, lse_i, delta_i, qpos_i = q_args
            logits, _ = _block_logits(qi, kj, qpos_i, kpos_j, kval_j,
                                      causal=causal, window=window,
                                      logit_cap=logit_cap, scale=scale)
            p = jnp.exp(logits - lse_i[..., None])            # (b,h,g,qb,kb)
            dp = jnp.einsum("bqhgd,bkhd->bhgqk", doi.astype(jnp.float32),
                            vj.astype(jnp.float32))
            ds = p * (dp - delta_i[..., None])
            if logit_cap > 0:   # softcap derivative: 1 - tanh(raw/cap)^2
                ds = ds * (1.0 - jnp.square(jnp.tanh(
                    jnp.einsum("bqhgd,bkhd->bhgqk", qi, kj,
                               preferred_element_type=jnp.float32)
                    * scale / logit_cap)))
            dq_i = jnp.einsum("bhgqk,bkhd->bqhgd", ds, kj.astype(jnp.float32)) * scale
            dkj = dkj + jnp.einsum("bhgqk,bqhgd->bkhd", ds,
                                   qi.astype(jnp.float32)) * scale
            dvj = dvj + jnp.einsum("bhgqk,bqhgd->bkhd", p,
                                   doi.astype(jnp.float32))
            return (dkj, dvj), dq_i

        dk0 = jnp.zeros((b, kb, kvh, d), jnp.float32)
        dv0 = jnp.zeros((b, kb, kvh, d), jnp.float32)
        (dkj, dvj), dq_blocks = jax.lax.scan(
            q_step, (dk0, dv0), (qg, dog, lse_b, delta_b, qpos_b))
        return (dk, dv), (dkj, dvj, dq_blocks)

    # iterate kv blocks in the outer scan, accumulating dq across them
    def kv_step2(dq_acc, kv_args):
        (_, _), (dkj, dvj, dq_blocks) = kv_step((None, None), kv_args)
        return dq_acc + dq_blocks, (dkj, dvj)

    dq0 = jnp.zeros((nq, b, qb, kvh, g, d), jnp.float32)
    dq_acc, (dk_blocks, dv_blocks) = jax.lax.scan(
        kv_step2, dq0, (kc, vc, kpos_b, kval_b))

    dq = dq_acc.swapaxes(0, 1).reshape(b, sq, h, d).astype(q.dtype)
    dk = dk_blocks.swapaxes(0, 1).reshape(b, skv, kvh, d).astype(k.dtype)
    dv = dv_blocks.swapaxes(0, 1).reshape(b, skv, kvh, d).astype(v.dtype)
    zero = _float0_like
    return (dq, dk, dv, zero(q_pos), zero(k_pos), zero(window),
            zero(k_valid))


_flash.defvjp(_flash_fwd, _flash_bwd)


def attend(q, k, v, *, q_pos, k_pos, causal: bool = True, window=None,
           logit_cap: float = 0.0, k_valid=None, impl: str = "flash",
           q_block: int = 512, kv_block: int = 1024) -> jax.Array:
    if impl == "naive":
        qp = q_pos[0] if q_pos.ndim == 2 else q_pos
        mask = make_mask(qp, k_pos, causal=causal, window=window,
                         k_valid=k_valid)
        return attend_naive(q, k, v, mask, logit_cap=logit_cap)
    if impl == "pallas":
        from repro.kernels.flash_attention.ops import flash_attention
        return flash_attention(q, k, v, q_pos=q_pos, k_pos=k_pos,
                               causal=causal, window=window,
                               logit_cap=logit_cap)
    return attend_flash(q, k, v, q_pos=q_pos, k_pos=k_pos, causal=causal,
                        window=window, logit_cap=logit_cap, k_valid=k_valid,
                        q_block=q_block, kv_block=kv_block)


__all__ = [
    "attention_init", "qkv_project", "output_gate", "output_project",
    "make_mask",
    "attend", "attend_naive", "attend_flash", "NEG_INF",
]

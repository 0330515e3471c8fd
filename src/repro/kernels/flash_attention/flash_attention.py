"""Pallas TPU flash-attention kernel (forward).

TPU-native adaptation: the GPU flash algorithm's
shared-memory tiling becomes explicit VMEM BlockSpecs; the online-softmax
state (m, l, acc) lives in VMEM scratch that persists across the
innermost ("arbitrary") KV-block grid dimension; MXU-aligned block shapes
(multiples of 128 on the contracting/lane dims).

Grid: (batch, q_heads, num_q_blocks, num_kv_blocks) — the first three are
parallel, the last sequential.  GQA is handled in the k/v index maps
(kv head = q head // group).  Sliding window and cache-valid length arrive
as dynamic scalars (per-layer values under a scan), so one compiled kernel
serves local and global layers; fully-masked KV blocks are skipped via
``pl.when``.

Validated in interpret mode against ``ref.py`` (pure jnp oracle); the
backward pass routes through the XLA flash custom-VJP
(repro.layers.attention) — residuals (o, lse) match.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0**30


def _flash_kernel(scal_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *,
                  causal: bool, logit_cap: float, scale: float,
                  q_block: int, kv_block: int):
    """One (b, h, qi, kj) grid step.

    scal_ref: (2,) int32 [window, kv_valid_len] (scalar block).
    q_ref: (1, 1, qb, d); k_ref/v_ref: (1, 1, kb, d); o_ref: (1, 1, qb, d).
    Scratch: acc (qb, d) f32; m/l (qb, 128) f32 (scalars on lane 0).
    """
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    window = scal_ref[0]
    valid_len = scal_ref[1]

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * q_block
    k_start = kj * kv_block

    # Block-level skip: past the valid length, above the causal diagonal,
    # or entirely left of the sliding window.
    run = k_start < valid_len
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + q_block - 1)
    run = jnp.logical_and(
        run,
        jnp.where(window > 0,
                  k_start + kv_block - 1 > q_start - window, True))

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)           # (qb, d)
        k = k_ref[0, 0].astype(jnp.float32)           # (kb, d)
        v = v_ref[0, 0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (qb, kb)
        if logit_cap > 0:
            logits = logit_cap * jnp.tanh(logits / logit_cap)

        qpos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (q_block, kv_block), 0)
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (q_block, kv_block), 1)
        mask = kpos < valid_len
        if causal:
            mask &= kpos <= qpos
        mask &= jnp.where(window > 0, qpos - kpos < window, True)
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev = m_ref[:, 0]                          # (qb,)
        m_new = jnp.maximum(m_prev, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[:, None])          # (qb, kb)
        p = jnp.where(mask, p, 0.0)  # fully-masked rows stay 0, not uniform
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_ref[:, 0] * corr + p.sum(axis=-1)
        m_ref[:, 0] = m_new
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv

    @pl.when(kj == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "logit_cap", "q_block", "kv_block",
                     "interpret"))
def flash_attention_fwd(q, k, v, scalars, *, causal: bool = True,
                        logit_cap: float = 0.0, q_block: int = 256,
                        kv_block: int = 512, interpret: bool = False):
    """q: (B, H, Sq, D); k/v: (B, KVH, Skv, D); scalars: (2,) int32
    [window (0 = none), valid_len]. Returns o: (B, H, Sq, D)."""
    b, h, sq, d = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    g = h // kvh
    qb = min(q_block, sq)
    while sq % qb:
        qb -= 1
    kb = min(kv_block, skv)
    while skv % kb:
        kb -= 1
    nq, nk = sq // qb, skv // kb
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _flash_kernel, causal=causal, logit_cap=float(logit_cap),
        scale=scale, q_block=qb, kv_block=kb)

    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((2,), lambda b, h, i, j: (0,)),
            pl.BlockSpec((1, 1, qb, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, kb, d), lambda b, h, i, j: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, kb, d), lambda b, h, i, j: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qb, d), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qb, d), jnp.float32),
            pltpu.VMEM((qb, 128), jnp.float32),
            pltpu.VMEM((qb, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(scalars, q, k, v)


__all__ = ["flash_attention_fwd", "NEG_INF"]

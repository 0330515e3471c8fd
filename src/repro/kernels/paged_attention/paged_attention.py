"""Pallas TPU paged decode attention: one query token per slot against the
slot's own blocks of a shared KV block pool, read through its block table.

The pool is the lane-dense block pool of layers/kvcache.py, ``(layers,
n_blocks + 1, block_size, KVH·hd)``, read at one ``layer`` in place (a
single layer's slice is a pool of one layer); block 0 is the null block.
A slot at position ``pos`` holds positions ``[0, pos)`` in the pool and
brings its current token's k and v separately, so the kernel reads
exactly the live blocks of each slot, ``ceil(pos / block_size)`` of them
(fewer under a sliding window), and never the rest of the table.

Grid: one program per slot.  The block table, positions, window and layer
are scalar-prefetched into SMEM.  Each program walks its live blocks
``pages`` at a time in a loop whose trip count is the slot's own: the
block DMAs of step *i+1* (one per block, HBM → a VMEM slot) are issued
before step *i* computes, over two VMEM slots.  Dead blocks of the last
step are neither copied nor attended.

Maths (the ``decode_slots`` path of ``models/transformer.py``, online):
f32 scores scaled by ``1/sqrt(hd)``, the optional ``logit_cap`` softcap,
positions outside ``[pos - window + 1, pos]`` masked (window 0 = none),
and an online softmax whose running max, sum and accumulator start from
the current token's own score and value — so every row, an inactive
slot's included, has a finite output.  GQA: each query row is laid over
its KV head's lanes (``q_bd``, zero elsewhere), so one matmul scores all
heads against the lane-dense block and the output keeps each row's own
head's lanes.

``interpret=True`` (automatic off-TPU, as in ``kernels/flash_attention``)
runs the body, DMAs and semaphores included, in the Pallas interpreter.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0**30            # masked score (as layers/attention.py)

# positions scored per loop step: enough blocks that a step's DMAs
# outlast its fixed cost, few enough that two slots of K and V stay small
# in VMEM (bf16, KVH·hd = 512: 2 MiB in all)
STEP_POSITIONS = 512


def _is_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _kernel(tables_ref, pos_ref, scal_ref, q_ref, kn_ref, vn_ref, k_hbm,
            v_hbm, o_ref, kbuf, vbuf, sems, m_ref, l_ref, acc_ref, *,
            pages: int, bs: int, t_len: int, scale: float,
            logit_cap: float):
    """One slot.  ``q_ref``: (1, H, D) block-diagonal queries; ``kn_ref`` /
    ``vn_ref``: (1, 1, D) the current token's k / v; ``k_hbm`` /
    ``v_hbm``: (layers, n_blocks + 1, bs, D) in HBM; ``scal_ref``:
    [window, layer]; ``o_ref``: (1, H, D).
    Scratch: ``kbuf`` / ``vbuf`` (2, pages, bs, D); ``sems`` DMA (2, 2)
    by (k/v, slot); ``m_ref`` / ``l_ref`` (H, 128) f32 (every lane holds
    the row's value); ``acc_ref`` (H, D) f32."""
    b = pl.program_id(0)
    h, d = acc_ref.shape
    pos = pos_ref[b]
    window = scal_ref[0]
    layer = scal_ref[1]
    lo = jnp.where(window > 0, jnp.maximum(pos - window + 1, 0), 0)
    first = lo // bs
    last = jnp.minimum((pos + bs - 1) // bs, t_len)      # exclusive
    n_steps = (jnp.maximum(last - first, 0) + pages - 1) // pages

    @pl.when(b == 0)
    def _zero():
        # a dead block of a last step keeps what its VMEM slot held: make
        # that finite once, so a zero weight times it stays zero
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    def live(step):                           # live blocks of a step
        return jnp.clip(last - first - step * pages, 0, pages)

    def dma(slot, j, blk):
        return (pltpu.make_async_copy(k_hbm.at[layer, blk],
                                      kbuf.at[slot, j], sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, blk],
                                      vbuf.at[slot, j], sems.at[1, slot]))

    def start(step, slot):
        def one(j, carry):
            blk = tables_ref[b * t_len + first + step * pages + j]
            for c in dma(slot, j, blk):
                c.start()
            return carry
        jax.lax.fori_loop(0, live(step), one, 0)

    def wait(step, slot):
        def one(j, carry):
            for c in dma(slot, j, 0):         # a wait needs only the sizes
                c.wait()
            return carry
        jax.lax.fori_loop(0, live(step), one, 0)

    q = q_ref[0]                                          # (H, D)

    def cap(s):
        return logit_cap * jnp.tanh(s / logit_cap) if logit_cap > 0 else s

    # the current token opens the online softmax: weight 1 on its value
    s_self = cap(jnp.sum(q.astype(jnp.float32)
                         * kn_ref[0].astype(jnp.float32),
                         axis=-1, keepdims=True) * scale)   # (H, 1)
    m_ref[...] = jnp.broadcast_to(s_self, m_ref.shape)
    l_ref[...] = jnp.ones_like(l_ref)
    acc_ref[...] = jnp.broadcast_to(vn_ref[0].astype(jnp.float32), (h, d))

    @pl.when(n_steps > 0)
    def _prefetch_first():
        start(0, 0)

    def step(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_steps)
        def _prefetch_next():
            start(i + 1, 1 - slot)

        wait(i, slot)
        k = kbuf[slot].reshape(pages * bs, d)
        v = vbuf[slot].reshape(pages * bs, d)
        s = cap(jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale)  # (H, pages·bs)
        kpos = (first + i * pages) * bs + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where((kpos >= lo) & (kpos < pos), s, NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                            # masked → 0
        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :1] * corr + p.sum(axis=1, keepdims=True), l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, n_steps, step, 0)
    o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("logit_cap", "interpret"))
def _paged_fwd(q, k_pool, v_pool, tables, pos, k_new, v_new, scalars, *,
               logit_cap: float, interpret: bool):
    b, h, hd = q.shape
    _, _, bs, d = k_pool.shape
    kvh = d // hd
    t_len = tables.shape[1]
    pages = max(1, min(t_len, STEP_POSITIONS // bs))
    own = jnp.arange(h) // (h // kvh)                     # each row's KV head
    lay = (own[:, None] == jnp.arange(kvh)[None, :]).astype(q.dtype)
    q_bd = (q[:, :, None, :] * lay[None, :, :, None]).reshape(b, h, d)
    kernel = functools.partial(_kernel, pages=pages, bs=bs, t_len=t_len,
                               scale=1.0 / math.sqrt(hd), logit_cap=logit_cap)
    row = pl.BlockSpec((1, 1, d), lambda i, *_: (i, 0, 0))
    heads = pl.BlockSpec((1, h, d), lambda i, *_: (i, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    o = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[heads, row, row, hbm, hbm],
            out_specs=heads,
            scratch_shapes=[
                pltpu.VMEM((2, pages, bs, d), k_pool.dtype),
                pltpu.VMEM((2, pages, bs, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((h, 128), jnp.float32),
                pltpu.VMEM((h, 128), jnp.float32),
                pltpu.VMEM((h, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        # sequential: the first program clears the VMEM slots for all
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tables.reshape(-1), pos, scalars, q_bd,
      k_new.reshape(b, 1, d).astype(k_pool.dtype),
      v_new.reshape(b, 1, d).astype(v_pool.dtype),
      k_pool, v_pool)
    return o.reshape(b, h, kvh, hd)[:, jnp.arange(h), own]


def paged_decode_attention(q, k_pool, v_pool, tables, pos, k_new, v_new, *,
                           layer=0, window=0, logit_cap: float = 0.0,
                           interpret: bool | None = None):
    """Decode attention of one token per slot over its pool blocks.

    q: (B, H, hd); k_pool / v_pool: (layers, n_blocks + 1, bs, KVH·hd)
    pools (layers/kvcache.py), read at ``layer`` (may be traced); tables:
    (B, T) int32 block ids; pos: (B,) int32, the position of each slot's
    current token (the pool holds ``[0, pos)``);
    k_new / v_new: (B, KVH, hd), the current token's k and v; window:
    scalar (0 = none, may be traced).  Returns (B, H, hd) in q's dtype."""
    if interpret is None:
        interpret = not _is_tpu()
    return _paged_fwd(q, k_pool, v_pool, jnp.asarray(tables, jnp.int32),
                      jnp.asarray(pos, jnp.int32), k_new, v_new,
                      jnp.stack([jnp.asarray(window, jnp.int32).reshape(()),
                                 jnp.asarray(layer, jnp.int32).reshape(())]),
                      logit_cap=float(logit_cap), interpret=bool(interpret))


__all__ = ["paged_decode_attention", "STEP_POSITIONS"]

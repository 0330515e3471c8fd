"""KV / recurrent-state caches for serving.

Caches are pytrees with a leading layer axis so the decode step can
``lax.scan`` over layers, slicing one layer's cache in and the updated
slice out.  Sharding is issued through the dataplane by the serve step
(kv_seq → data/model axes depending on the shape cell, see
parallel/sharding.py): :func:`kv_cache_constrain` routes the cache's
sharding edges through the mediation pipeline like any other dataplane
traffic, so cache placement is visible to (and accountable by) the same
policies that see the collectives.

Slot-aware helpers (persistent-slot continuous batching, serve/engine.py):
the engine preallocates ONE ``(layers, max_batch, max_cache_len, ...)``
cache whose batch rows are long-lived *slots*.  A request is prefilled
alone (batch 1, prompt-length-bucketed), its cache written into a free
slot with :func:`kv_slot_insert`, and the fixed-shape decode step advances
every slot at its own position (:func:`kv_update_slots`) behind a per-slot
validity mask (:func:`slot_validity`).  Entries beyond a slot's position
are never attended, so stale bytes from a previous resident (or prefill
padding) are harmless — each position is rewritten by the current resident
before it first becomes valid.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# logical axis names of a (layers, batch, kv_seq, kv_heads, head_dim) cache
KV_CACHE_AXES = (None, "batch", "kv_seq", "kv_heads", "head_dim")


def kv_cache_init(layers: int, batch: int, max_len: int, kv_heads: int,
                  head_dim: int, dtype=jnp.bfloat16) -> dict:
    return {
        "k": jnp.zeros((layers, batch, max_len, kv_heads, head_dim), dtype),
        "v": jnp.zeros((layers, batch, max_len, kv_heads, head_dim), dtype),
    }


def kv_update(cache_k: jax.Array, cache_v: jax.Array, k: jax.Array,
              v: jax.Array, pos) -> tuple[jax.Array, jax.Array]:
    """Insert (B, s, KVH, hd) new keys/values at position ``pos`` into a
    single layer's (B, S_max, KVH, hd) cache."""
    pos = jnp.asarray(pos, jnp.int32)
    ck = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype),
                                      (0, pos, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype),
                                      (0, pos, 0, 0))
    return ck, cv


def kv_update_slots(cache_k: jax.Array, cache_v: jax.Array, k: jax.Array,
                    v: jax.Array, pos) -> tuple[jax.Array, jax.Array]:
    """Per-slot cache write: insert (B, s, KVH, hd) new keys/values into a
    (B, S_max, KVH, hd) cache at *per-slot* positions ``pos`` (B,) — the
    continuous-batching analogue of :func:`kv_update`, where every batch
    row is a slot advancing independently."""
    pos = jnp.asarray(pos, jnp.int32)

    def one(ck, cv, kk, vv, p):
        return (jax.lax.dynamic_update_slice(ck, kk.astype(ck.dtype),
                                             (p, 0, 0)),
                jax.lax.dynamic_update_slice(cv, vv.astype(cv.dtype),
                                             (p, 0, 0)))

    return jax.vmap(one)(cache_k, cache_v, k, v, pos)


def kv_slot_insert(cache: dict, prefilled: dict, slot) -> dict:
    """Write one prefilled request's cache (leading batch dim 1) into slot
    ``slot`` of a persistent slot cache.

    ``slot`` may be a traced scalar, so one jitted insert serves every
    slot.  Positions beyond the prefill capacity keep whatever the slot
    held before; the per-slot validity mask makes them unreachable until
    the new resident overwrites them token by token."""
    slot = jnp.asarray(slot, jnp.int32)
    zero = jnp.zeros((), jnp.int32)

    def ins(dst, src):
        if not (hasattr(dst, "ndim") and dst.ndim == 5):
            return dst
        start = (zero, slot, zero, zero, zero)
        return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype), start)

    return {name: ins(dst, prefilled[name]) for name, dst in cache.items()}


def state_slot_insert(cache, prefilled, slot, *, batch_axis: int = 1):
    """Family-agnostic slot insert: write one prefilled request's decode
    state (batch dim 1 at ``batch_axis``) into row ``slot`` of every array
    leaf of a persistent slot cache.

    This is the :func:`kv_slot_insert` analogue for recurrent and hybrid
    caches: mamba's ``(L, B, W-1, d_inner)`` conv tail and ``(L, B,
    d_inner, N)`` SSM state, xLSTM's per-unit ``(reps, B, ...)`` matrix/
    scalar memories, and encdec's rank-5 cross-attention cache all carry
    batch on axis 1, so one tree-map of ``dynamic_update_slice`` covers
    every family.  KV stripe leaves whose source is shorter than the
    stripe (prefill capacity < kv_cache_len) are written only over their
    leading positions, exactly like :func:`kv_slot_insert` — the tail
    stays masked by per-slot validity until the resident reaches it.

    ``batch_axis=0`` serves the layer-local states (before the model
    stacks a layer axis in front): see ``mamba_state_slot_insert`` /
    ``xlstm_state_slot_insert`` in layers/mamba.py / layers/xlstm.py.
    """
    slot = jnp.asarray(slot, jnp.int32)
    zero = jnp.zeros((), jnp.int32)

    def ins(dst, src):
        start = tuple(slot if d == batch_axis else zero
                      for d in range(dst.ndim))
        return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype), start)

    return jax.tree.map(ins, cache, prefilled)


def slot_vectors_init(slots: int) -> dict:
    """Per-slot bookkeeping vectors: next write position, active flag and
    tenant index (−1 = free) — the host-mirrored slot state of the
    continuous-batching engine.  Host-side numpy by design: the engine
    mutates them in place between decode steps and feeds the position
    vector to the fixed-shape decode step each tick."""
    import numpy as np
    return {
        "pos": np.zeros((slots,), np.int32),
        "active": np.zeros((slots,), bool),
        "tenant": np.full((slots,), -1, np.int32),
    }


def slot_validity(max_len: int, pos) -> jax.Array:
    """(B, max_len) mask of cache entries visible to each slot decoding at
    per-slot position ``pos`` (inclusive: the entry written at ``pos``
    this step is attended)."""
    return (jnp.arange(max_len, dtype=jnp.int32)[None, :]
            <= jnp.asarray(pos, jnp.int32)[:, None])


def cache_positions(max_len: int) -> jax.Array:
    return jnp.arange(max_len, dtype=jnp.int32)


def cache_validity(max_len: int, filled_len) -> jax.Array:
    """Boolean (max_len,) mask of filled cache slots."""
    return jnp.arange(max_len, dtype=jnp.int32) < filled_len


# ---------------------------------------------------------------------------
# Paged KV block pool (vLLM-style, docs/serving.md)
# ---------------------------------------------------------------------------
#
# The paged layout replaces the per-slot stripe with ONE shared pool of
# fixed-size blocks: ``(layers, n_blocks + 1, block_size, kv_heads *
# head_dim)`` per k/v leaf, plus a host-side ``(max_batch, tables_len)``
# int32 block table mapping each slot's logical block index to a physical
# pool block.  The heads are folded into one lane-dense minor dimension so
# that a block is contiguous on the device: a (…, kv_heads, head_dim)
# minor pair narrower than a lane row makes TPU layouts put the block
# index minor-most instead, and every block read or token write then
# relayouts the whole pool.  Physical block 0 is reserved as a shared
# *null* block: free slots and unallocated table tail entries point at
# it, so reads stay total functions of the table.  Decode reads each
# slot's live blocks in place (kernels/paged_attention) and writes its
# one new token with :func:`kv_pool_scatter_token`; :func:`kv_pool_gather`
# builds the dense view of every table only as the reference.  Prefill
# writes to padded block ids are routed to the out-of-bounds index
# ``n_blocks + 1`` and dropped (`mode="drop"`), and an inactive slot's
# decode write puts back what is there, so block 0 is never corrupted.

def kv_pool_init(layers: int, n_blocks: int, block_size: int, kv_heads: int,
                 head_dim: int, dtype=jnp.bfloat16) -> dict:
    """Block pool with ``n_blocks`` usable blocks (physical ids 1..n_blocks;
    id 0 is the shared null block)."""
    shape = (layers, n_blocks + 1, block_size, kv_heads * head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def kv_pool_gather(pool: dict, tables, block_size: int) -> dict:
    """Reference: materialise a dense (layers, B, T*block_size, KVH*hd)
    cache from the pool by per-slot block table (B, T) — reshaped to
    ``(…, KVH, hd)`` it is the view the stripe layout's fixed-shape decode
    step reads.  Rows mapped to the null block read zeros; validity
    masking keeps them unattended."""
    tables = jnp.asarray(tables, jnp.int32)

    def one(buf):
        ll, _, bs, d = buf.shape
        b, t = tables.shape
        g = buf[:, tables]                     # (L, B, T, bs, KVH*hd)
        return g.reshape(ll, b, t * bs, d)

    return {name: one(buf) for name, buf in pool.items()}


def kv_pool_scatter_token(pool: dict, tokens: dict, tables, pos, active,
                          block_size: int) -> dict:
    """Write the ONE token each active slot appended this decode tick.

    ``tokens`` holds each leaf's (L, B, KVH, hd) or (L, B, KVH*hd) new
    keys / values; slot
    b's lands in pool block ``tables[b, pos[b] // block_size]`` at offset
    ``pos[b] % block_size``; an inactive slot writes back what is there.
    One in-place update per layer and slot: a scatter over the (block,
    offset) dims would have TPU layouts move them major and relayout the
    whole pool."""
    tables = jnp.asarray(tables, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    active = jnp.asarray(active, bool)
    rows = jnp.arange(tables.shape[0], dtype=jnp.int32)
    blk = tables[rows, pos // block_size]      # (B,) physical ids
    off = pos % block_size
    zero = jnp.zeros((), jnp.int32)

    def one(buf, tok):
        ll, _, _, d = buf.shape
        tok = tok.reshape(ll, rows.shape[0], 1, 1, 1, d).astype(buf.dtype)

        def layer(l, buf):
            for i in range(rows.shape[0]):
                at = (l, blk[i], off[i], zero)
                old = jax.lax.dynamic_slice(buf, at, (1, 1, 1, d))
                new = jax.lax.dynamic_index_in_dim(tok[:, i], l, 0, False)
                buf = jax.lax.dynamic_update_slice(
                    buf, jnp.where(active[i], new, old), at)
            return buf

        return jax.lax.fori_loop(0, ll, layer, buf)

    return {name: one(buf, tokens[name]) for name, buf in pool.items()}


def kv_pool_insert(pool: dict, prefilled: dict, block_ids,
                   block_size: int) -> dict:
    """Insert one prefilled request's cache (leading batch dim 1, capacity
    ``cap``) into the pool blocks ``block_ids`` (static-length int32 array,
    ceil(cap / block_size) entries; pad unused entries with the OOB index
    so they drop)."""
    block_ids = jnp.asarray(block_ids, jnp.int32)

    def one(buf, src):
        ll, _, bs, d = buf.shape
        src = src[:, 0].reshape(ll, -1, d)     # (L, cap, KVH*hd)
        cap = src.shape[1]
        pad = (-cap) % bs
        if pad:
            src = jnp.pad(src, ((0, 0), (0, pad), (0, 0)))
        chunks = src.reshape(ll, -1, bs, d)    # (L, nblk, bs, KVH*hd)
        return buf.at[:, block_ids].set(chunks.astype(buf.dtype),
                                        mode="drop")

    return {name: one(buf, prefilled[name]) for name, buf in pool.items()}


def kv_pool_scatter_chunk(pool: dict, cache: dict, table_row, offset,
                          chunk: int, block_size: int) -> dict:
    """Scatter one prefill chunk (written into a dense batch-1 ``cache`` at
    traced ``offset``) into the pool.  ``offset`` and ``chunk`` are multiples
    of ``block_size`` (ServeConfig validation), so the chunk covers whole
    blocks: ids come from ``table_row[offset//bs : offset//bs + chunk//bs]``
    via a traced dynamic slice."""
    table_row = jnp.asarray(table_row, jnp.int32)
    offset = jnp.asarray(offset, jnp.int32)
    nblk = chunk // block_size

    def one(buf, dense):
        ll, _, bs, d = buf.shape
        _, _, _, kvh, hd = dense.shape
        piece = jax.lax.dynamic_slice(
            dense, (0, 0, offset, 0, 0),
            (ll, 1, chunk, kvh, hd))[:, 0]          # (L, chunk, KVH, hd)
        chunks = piece.reshape(ll, nblk, bs, d)
        ids = jax.lax.dynamic_slice(table_row, (offset // bs,), (nblk,))
        return buf.at[:, ids].set(chunks.astype(buf.dtype), mode="drop")

    return {name: one(buf, cache[name]) for name, buf in pool.items()}


class BlockAllocator:
    """Host-side free-list over the pool's usable physical blocks
    (ids 1..n_blocks; 0 is the null block).  All-or-nothing ``alloc``;
    double-free raises — table bugs corrupt *other tenants'* caches, so
    they must fail loudly."""

    def __init__(self, n_blocks: int):
        if n_blocks < 1:
            raise ValueError(f"need n_blocks >= 1, got {n_blocks}")
        self.n_blocks = n_blocks
        self._free = list(range(n_blocks, 0, -1))   # pop() yields 1, 2, ...
        self._held: set[int] = set()

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def alloc(self, k: int) -> list[int] | None:
        """Claim ``k`` blocks, or None (and no change) if fewer are free."""
        if k < 0:
            raise ValueError(f"need k >= 0, got {k}")
        if k > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(k)]
        self._held.update(ids)
        return ids

    def free(self, ids) -> None:
        for i in ids:
            if i not in self._held:
                raise ValueError(f"double free / foreign block id {i}")
            self._held.discard(i)
            self._free.append(int(i))


def kv_cache_constrain(dp, cache, *, tag: str = "kvcache",
                       qos: str = "kvcache", tenant: str | None = None):
    """Issue the KV cache's sharding edges through the dataplane.

    Applies to {"k","v"}-style caches of rank-5 leaves (other recurrent
    cache layouts pass through untouched).  A no-op without a dataplane."""
    if dp is None or not isinstance(cache, dict):
        return cache
    return {k: (dp.constrain(v, KV_CACHE_AXES, tag=f"{tag}/{k}", qos=qos,
                             tenant=tenant)
                if hasattr(v, "ndim") and v.ndim == 5 else v)
            for k, v in cache.items()}


__all__ = ["kv_cache_init", "kv_update", "kv_update_slots", "kv_slot_insert",
           "state_slot_insert",
           "slot_vectors_init", "slot_validity", "cache_positions",
           "cache_validity", "kv_cache_constrain", "KV_CACHE_AXES",
           "kv_pool_init", "kv_pool_gather", "kv_pool_scatter_token",
           "kv_pool_insert", "kv_pool_scatter_chunk", "BlockAllocator"]

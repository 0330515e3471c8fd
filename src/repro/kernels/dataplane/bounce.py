"""Pallas TPU dataplane kernels: the mediation data-movement primitives.

The mediation pipeline's ``staged-copy`` stage and fused delay chain were
XLA-level emulations (``core/techniques.py``): real data movement and
real serial work, but shaped by what XLA happens to emit.  This module
implements the same primitives as explicit Pallas TPU kernels, so
measured-mode mediation cost is a *hardware measurement* — the DMA
engine moves the payload through a VMEM bounce buffer, and the delay is
a serial scalar chain executing on the core between the copy-in and the
copy-out, exactly where the emulated user→kernel crossing sits.

One kernel body serves both entry points:

* :func:`bounce_copy` — the zero-copy-removed bounce-buffer copy.  The
  payload is chunked; chunk DMAs HBM→VMEM are **double-buffered** over
  two scratch slots so the copy-in of chunk *i+1* overlaps the copy-out
  of chunk *i* (the overlapped copy-in/copy-out slots of a real bounce
  buffer).  Extra ``copies`` bounce the chunk VMEM→VMEM through a third
  slot — one round trip per extra pass, matching ``staged_copy``'s
  pass count.
* :func:`mediated_cost` — the fused-mediation cost kernel: the same
  copy path plus a calibrated serial delay burned *inside the kernel*
  between a chunk's copy-in and copy-out, with the cost it ran (iters
  burned, copy passes) summed into a fixed-size SMEM output.  One
  launch covers a fused pipeline side's delay chain + staged copies.

Both are **bit-identical** to the emulations they replace: the payload
is only ever moved, never computed on — availability is delayed by
routing the chunk's first tile through a vector select on the delay
token (the same ``tie`` trick as ``core/techniques.py``, in-kernel).

Layout, as Mosaic requires it: the flat payload is viewed as
``(n_chunks, rows, lanes)`` — lane-dense 128-wide rows whenever the
chunk allows it — so every DMA moves one whole chunk between a leading
index of the HBM view and a leading index of the ``(3, rows, lanes)``
VMEM scratch; no DMA slices a tiled dimension.  A payload that is not a
whole number of chunks is zero-padded outside the kernel and the
padding sliced back off, so no DMA is ragged.  On TPU a chunk must be a
whole number of ``(sublanes, 128)`` tiles of its dtype (``_tile_elems``).

``interpret=True`` (selected automatically off-TPU, pattern per
``kernels/flash_attention``) runs the kernel body — including the DMAs
and semaphores — in the Pallas interpreter for validation on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Default chunk size through the VMEM bounce buffer, in elements.  At
# 4 B/elem this is a 32 KiB chunk — small enough that three slots fit
# comfortably in VMEM, large enough to amortize DMA issue overhead, and
# a whole number of native tiles for every 1/2/4-byte dtype.
DEFAULT_CHUNK_ELEMS = 8192

# Columns of the SMEM cost-counter output.
COST_ITERS = 0    # delay iterations burned
COST_COPIES = 1   # bounce passes made through VMEM
NUM_COST_COLS = 2

_LANES = 128


def _is_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _tile_elems(dtype) -> int:
    """Elements in one native ``(sublanes, 128)`` VMEM tile of ``dtype``:
    8 sublanes of 32-bit words, packed 2 or 4 per word for narrower
    types."""
    return 8 * (4 // jnp.dtype(dtype).itemsize) * _LANES


def _n_chunks(n: int, chunk_elems: int) -> tuple[int, int]:
    """``(chunk, n_chunks)``: the accounting split of an ``n``-element
    payload that the kernel runs and :func:`kernel_cost_totals` mirrors."""
    chunk = max(1, min(chunk_elems, n))
    return chunk, -(-n // chunk)


def _geometry(n: int, chunk_elems: int, dtype, interpret: bool):
    """``(n_chunks, rows, lanes)`` of the chunked view of an ``n``-element
    payload: a single-chunk payload is rounded up to the tile, a
    multi-chunk one is padded to whole chunks."""
    chunk, n_chunks = _n_chunks(n, chunk_elems)
    tile = _tile_elems(dtype)
    if n_chunks == 1:
        chunk = -(-n // tile) * tile
    elif chunk % tile and not interpret:
        raise ValueError(
            f"chunk_elems={chunk_elems} is not a whole number of "
            f"{jnp.dtype(dtype).name} tiles ({tile} elements) — Mosaic "
            f"cannot DMA a partial tile")
    lanes = _LANES if chunk % _LANES == 0 else chunk
    return n_chunks, chunk // lanes, lanes


def _burn(iters: int, seed):
    """The serial dependent fma chain from ``techniques.delay_scalar``,
    executed on the scalar core inside the kernel."""
    return jax.lax.fori_loop(0, iters,
                             lambda j, v: v * 1.0000001 + 1e-9, seed)


def _tie_slot(scratch, slot, tok):
    """Route the slot's first tile through a select on the delay token —
    the in-kernel mirror of ``techniques.tie``: O(1), bit-identical, and
    the copy-out cannot be reordered before the burn.  A select, never
    arithmetic on the payload, so NaN and -0.0 survive."""
    rows = min(_tile_elems(scratch.dtype) // _LANES, scratch.shape[1])
    head = scratch[slot, :rows]
    scratch[slot, :rows] = jnp.where(tok == tok, head, jnp.zeros_like(head))


def _bounce_kernel(x_hbm, o_hbm, ctr_ref, *, n_chunks: int, copies: int,
                   iters_per_chunk: int):
    """Double-buffered bounce-buffer copy with in-kernel cost accounting.

    ``x_hbm``/``o_hbm`` are ``(n_chunks, rows, lanes)`` HBM views; scratch
    slots 0/1 double-buffer the chunk DMAs and slot 2 is the extra-pass
    bounce target.  ``ctr_ref`` is the ``(NUM_COST_COLS,)`` SMEM total."""

    def body(scratch, in_sem, out_sem, pass_sem):
        def dma_in(slot, i):
            return pltpu.make_async_copy(x_hbm.at[i], scratch.at[slot],
                                         in_sem.at[slot])

        def extra_passes(slot):
            # each extra copy is one full round trip through the bounce
            # slot: VMEM slot -> slot 2 -> slot, two real data movements
            # per pass, like the roll/roll-back pair in staged_copy.
            for _ in range(copies - 1):
                for src, dst in ((slot, 2), (2, slot)):
                    d = pltpu.make_async_copy(scratch.at[src],
                                              scratch.at[dst], pass_sem)
                    d.start()
                    d.wait()

        dma_in(0, 0).start()

        def loop(i, burned):
            slot = i % 2

            @pl.when(i + 1 < n_chunks)
            def _prefetch():
                dma_in((i + 1) % 2, i + 1).start()

            dma_in(slot, i).wait()
            extra_passes(slot)
            tok = _burn(iters_per_chunk, jnp.float32(1.0))
            _tie_slot(scratch, slot, tok)
            out = pltpu.make_async_copy(scratch.at[slot], o_hbm.at[i],
                                        out_sem.at[slot])
            out.start()
            out.wait()
            return burned + iters_per_chunk * (tok == tok).astype(jnp.int32)

        burned = jax.lax.fori_loop(0, n_chunks, loop, jnp.int32(0))
        ctr_ref[COST_ITERS] = burned
        ctr_ref[COST_COPIES] = jnp.int32(copies * n_chunks)

    pl.run_scoped(
        body,
        scratch=pltpu.VMEM((3,) + x_hbm.shape[1:], x_hbm.dtype),
        in_sem=pltpu.SemaphoreType.DMA((2,)),
        out_sem=pltpu.SemaphoreType.DMA((2,)),
        pass_sem=pltpu.SemaphoreType.DMA(()),
    )


@functools.partial(
    jax.jit,
    static_argnames=("copies", "delay_iters", "chunk_elems", "interpret"))
def _bounce_fwd(flat, *, copies: int, delay_iters: int, chunk_elems: int,
                interpret: bool):
    """Launch the bounce kernel over a flat payload.  Returns
    ``(out, totals)`` with totals ``(NUM_COST_COLS,)`` int32 from SMEM."""
    n = flat.shape[0]
    n_chunks, rows, lanes = _geometry(n, chunk_elems, flat.dtype, interpret)
    pad = n_chunks * rows * lanes - n
    view = jnp.pad(flat, (0, pad)) if pad else flat
    # total delay split evenly across chunks, rounded up: the kernel
    # burns at least the requested iterations (counters report actuals).
    iters_per_chunk = -(-delay_iters // n_chunks) if delay_iters > 0 else 0
    kernel = functools.partial(_bounce_kernel, n_chunks=n_chunks,
                               copies=copies,
                               iters_per_chunk=iters_per_chunk)
    out, totals = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pltpu.SMEM)),
        out_shape=(jax.ShapeDtypeStruct((n_chunks, rows, lanes), flat.dtype),
                   jax.ShapeDtypeStruct((NUM_COST_COLS,), jnp.int32)),
        interpret=interpret,
    )(view.reshape(n_chunks, rows, lanes))
    out = out.reshape(-1)
    return (out[:n] if pad else out), totals


def _launch(x, *, copies: int, delay_iters: int, chunk_elems: int,
            interpret: bool | None):
    if interpret is None:
        interpret = not _is_tpu()
    out, totals = _bounce_fwd(x.reshape(-1), copies=int(copies),
                              delay_iters=int(delay_iters),
                              chunk_elems=int(chunk_elems),
                              interpret=bool(interpret))
    return out.reshape(x.shape), totals


def bounce_copy(x: jax.Array, copies: int = 1, *,
                chunk_elems: int = DEFAULT_CHUNK_ELEMS,
                interpret: bool | None = None) -> jax.Array:
    """``copies`` real bounce-buffer passes of ``x`` through VMEM.

    Drop-in for ``techniques.staged_copy``: bit-identical output, but
    the copies are explicit double-buffered DMA transfers instead of an
    XLA roll/barrier emulation.  ``copies <= 0`` is the identity."""
    if copies <= 0 or x.size == 0:
        return x
    out, _ = _launch(x, copies=copies, delay_iters=0,
                     chunk_elems=chunk_elems, interpret=interpret)
    return out


def kernel_cost_totals(nelems: int, delay_iters: int, copies: int = 0,
                       chunk_elems: int = DEFAULT_CHUNK_ELEMS
                       ) -> tuple[int, int]:
    """Static ``(total_iters, total_copy_passes)`` the cost kernel's SMEM
    counters sum to for a payload of ``nelems`` elements — the exact
    chunk split of :func:`mediated_cost` (even per-chunk delay split,
    rounded up; ``copies`` passes per chunk), mirrored host-side.

    The fused mediation pipeline uses this to bump the tenant
    ``kernel_iters``/``kernel_copies`` counters identically whether the
    cost ran as the Pallas kernel or the XLA emulation, keeping reports
    bit-identical across backends (tests/test_dataplane_kernels.py)."""
    if (delay_iters <= 0 and copies <= 0) or nelems <= 0:
        return 0, 0
    _, n_chunks = _n_chunks(nelems, chunk_elems)
    iters_per_chunk = -(-delay_iters // n_chunks) if delay_iters > 0 else 0
    return iters_per_chunk * n_chunks, copies * n_chunks


def mediated_cost(x: jax.Array, delay_iters: int, copies: int = 0, *,
                  chunk_elems: int = DEFAULT_CHUNK_ELEMS,
                  interpret: bool | None = None):
    """One kernel launch covering a fused mediation side's cost: burn
    ``delay_iters`` of serial work in-kernel and make ``copies`` bounce
    passes, returning ``(out, counters)``.

    ``out`` is bit-identical to ``x`` (``delay_chain`` tie semantics:
    availability is delayed, values never touched).  ``counters`` is the
    ``(n_chunks, 2)`` int32 per-chunk view of the kernel's SMEM totals:
    every chunk burns and copies the same amount by construction, so
    row *i* is the totals over ``n_chunks`` — column ``COST_ITERS`` sums
    to at least ``delay_iters`` (even split, rounded up), column
    ``COST_COPIES`` is the pass count per chunk.  The kernel's output
    stays fixed-size however large the payload."""
    if (delay_iters <= 0 and copies <= 0) or x.size == 0:
        return x, jnp.zeros((1, NUM_COST_COLS), jnp.int32)
    out, totals = _launch(x, copies=copies, delay_iters=delay_iters,
                          chunk_elems=chunk_elems, interpret=interpret)
    _, n_chunks = _n_chunks(x.size, chunk_elems)
    return out, jnp.broadcast_to(totals // n_chunks,
                                 (n_chunks, NUM_COST_COLS))


__all__ = ["bounce_copy", "mediated_cost", "kernel_cost_totals",
           "DEFAULT_CHUNK_ELEMS",
           "COST_ITERS", "COST_COPIES", "NUM_COST_COLS"]

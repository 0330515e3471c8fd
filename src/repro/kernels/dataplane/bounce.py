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

Layout, as Mosaic requires it.  A payload whose trailing dims merge
into a lane-aligned width (a multiple of 128), with leading dims left
over, is taken in its own layout as a 2-D ``(rows, width)`` view — a
bitcast of a row-major tiled array, so XLA relays nothing out around the
call.  Its DMA blocks are the smallest tile-aligned run of rows holding
``chunk_elems`` elements (``_block_rows``); rows past the last whole
block are written into the kernel's output in place after it (a DMA
cannot end at a partial tile), and a payload smaller than one block is
a single whole-array block.  Every
other payload (1-D verbs messages and collective buffers among them)
takes the flat path: viewed as ``(n_chunks, rows, lanes)`` — lane-dense
128-wide rows whenever the chunk allows it — so every DMA moves one
whole chunk between leading indices of the HBM view and of the
``(3, rows, lanes)`` VMEM scratch, no DMA slicing a tiled dimension; a
payload that is not a whole number of chunks is zero-padded outside the
kernel and the padding sliced back off.  On TPU a flat chunk must be a
whole number of ``(sublanes, 128)`` tiles of its dtype (``_tile_elems``).

The emulated cost does not depend on the view: the delay and the copy
passes are accounted in ``chunk_elems``-element chunks
(:func:`kernel_cost_totals`), and the kernel burns that total spread
over whatever blocks it moves.

``interpret=True`` (selected automatically off-TPU, pattern per
``kernels/flash_attention``) runs the kernel body — including the DMAs
and semaphores — in the Pallas interpreter for validation on CPU.
"""

from __future__ import annotations

import functools
import math

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

# Largest row-view DMA block, in bytes: its three scratch slots stay
# well inside the default scoped VMEM.  A wider row takes the flat path.
_MAX_BLOCK_BYTES = 1 << 20


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
    """``(n_chunks, rows, lanes)`` of the flat chunked view of an
    ``n``-element payload: a single-chunk payload is rounded up to the
    tile, a multi-chunk one is padded to whole chunks."""
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


def _block_rows(width: int, chunk_elems: int, dtype) -> int:
    """Rows of a row-view DMA block: the smallest whole number of tile
    rows (sublanes) whose ``width``-wide rows hold ``chunk_elems``."""
    sub = _tile_elems(dtype) // _LANES
    return -(-chunk_elems // (width * sub)) * sub


def _row_view(shape, dtype, chunk_elems: int) -> tuple[int, int] | None:
    """``(rows, width)`` view of a payload in its own layout, or ``None``
    for the flat path: the fewest trailing dims whose product is a
    multiple of 128 lanes become ``width``, the leading dims ``rows``.
    A payload with no such split (a 1-D one among them), or whose DMA
    block would not fit the VMEM scratch, has no row view."""
    width = 1
    for k in range(len(shape) - 1, 0, -1):
        width *= shape[k]
        if width % _LANES == 0:
            block = _block_rows(width, chunk_elems, dtype) * width
            if block * jnp.dtype(dtype).itemsize > _MAX_BLOCK_BYTES:
                return None
            return math.prod(shape[:k]), width
    return None


def _burn(iters, seed):
    """The serial dependent fma chain from ``techniques.delay_scalar``,
    executed on the scalar core inside the kernel."""
    return jax.lax.fori_loop(0, iters,
                             lambda j, v: v * 1.0000001 + 1e-9, seed)


def _tie(buf, tok):
    """Route the buffer's first tile through a select on the delay token
    — the in-kernel mirror of ``techniques.tie``: O(1), bit-identical,
    and the copy-out cannot be reordered before the burn.  A select,
    never arithmetic on the payload, so NaN and -0.0 survive."""
    rows = min(_tile_elems(buf.dtype) // _LANES, buf.shape[0])
    lanes = min(_LANES, buf.shape[1])
    head = buf[:rows, :lanes]
    buf[:rows, :lanes] = jnp.where(tok == tok, head, jnp.zeros_like(head))


def _bounce_kernel(x_hbm, o_hbm, ctr_ref, *, block_rows: int | None,
                   n_blocks: int, copies: int, total_iters: int,
                   total_copies: int):
    """Double-buffered bounce-buffer copy with in-kernel cost accounting.

    ``x_hbm``/``o_hbm`` are the flat ``(n_chunks, rows, lanes)`` HBM
    views (``block_rows`` None: block *i* is leading index *i*) or the
    ``(rows, width)`` row views (block *i* is rows ``[i·block_rows,
    (i+1)·block_rows)``, or the whole array where one block holds it).
    Scratch slots 0/1 double-buffer the block DMAs and slot 2 is the
    extra-pass bounce target; a whole-array block gets three separate
    buffers, since Mosaic cannot slice a stacked slot whose rows are a
    partial tile.  ``total_iters`` burns spread over the blocks, the
    first ``total_iters % n_blocks`` one iteration longer.  ``ctr_ref``
    is the ``(NUM_COST_COLS,)`` SMEM total: the iterations burned and
    ``total_copies``, the passes in accounting chunks."""
    base, longer = divmod(total_iters, n_blocks)
    whole = block_rows == x_hbm.shape[0]

    def block(ref, i):
        if block_rows is None:
            return ref.at[i]
        if whole:
            return ref
        return ref.at[pl.ds(pl.multiple_of(i * block_rows, block_rows),
                            block_rows)]

    def body(scratch, in_sem, out_sem, pass_sem):
        def buf(slot):
            return scratch[slot] if whole else scratch.at[slot]

        def dma_in(slot, i):
            return pltpu.make_async_copy(block(x_hbm, i), buf(slot),
                                         in_sem.at[slot])

        def extra_passes(slot):
            # each extra copy is one full round trip through the bounce
            # slot: VMEM slot -> slot 2 -> slot, two real data movements
            # per pass, like the roll/roll-back pair in staged_copy.
            for _ in range(copies - 1):
                for src, dst in ((slot, 2), (2, slot)):
                    d = pltpu.make_async_copy(buf(src), buf(dst), pass_sem)
                    d.start()
                    d.wait()

        dma_in(0, 0).start()

        def step(i, burned):
            slot = i % 2
            if n_blocks > 1:
                @pl.when(i + 1 < n_blocks)
                def _prefetch():
                    dma_in((i + 1) % 2, i + 1).start()

            dma_in(slot, i).wait()
            extra_passes(slot)
            iters = base + (i < longer).astype(jnp.int32) if longer else base
            tok = _burn(iters, jnp.float32(1.0))
            _tie(buf(slot), tok)
            out = pltpu.make_async_copy(buf(slot), block(o_hbm, i),
                                        out_sem.at[slot])
            out.start()
            out.wait()
            return burned + iters * (tok == tok).astype(jnp.int32)

        burned = (step(0, jnp.int32(0)) if n_blocks == 1 else
                  jax.lax.fori_loop(0, n_blocks, step, jnp.int32(0)))
        ctr_ref[COST_ITERS] = burned
        ctr_ref[COST_COPIES] = jnp.int32(total_copies)

    if whole:
        scratch = (pltpu.VMEM(x_hbm.shape, x_hbm.dtype),) * 3
    else:
        slot = x_hbm.shape[1:] if block_rows is None else (
            (block_rows,) + x_hbm.shape[1:])
        scratch = pltpu.VMEM((3,) + slot, x_hbm.dtype)
    pl.run_scoped(
        body,
        scratch=scratch,
        in_sem=pltpu.SemaphoreType.DMA((2,)),
        out_sem=pltpu.SemaphoreType.DMA((2,)),
        pass_sem=pltpu.SemaphoreType.DMA(()),
    )


@functools.partial(
    jax.jit,
    static_argnames=("copies", "delay_iters", "chunk_elems", "interpret"))
def _bounce_fwd(view, *, copies: int, delay_iters: int, chunk_elems: int,
                interpret: bool):
    """Launch the bounce kernel over a flat (1-D) or row (2-D) view of a
    payload.  Returns ``(out, totals)``: ``out`` in the view's shape,
    totals ``(NUM_COST_COLS,)`` int32 from SMEM."""
    _, n_chunks = _n_chunks(view.size, chunk_elems)
    # the total delay split evenly across accounting chunks, rounded up:
    # the kernel burns at least the requested iterations (counters
    # report actuals), whatever blocks it moves them in.
    iters_per_chunk = -(-delay_iters // n_chunks) if delay_iters > 0 else 0
    if view.ndim == 1:
        n = view.shape[0]
        _, rows, lanes = _geometry(n, chunk_elems, view.dtype, interpret)
        pad = n_chunks * rows * lanes - n
        x = (jnp.pad(view, (0, pad)) if pad else view).reshape(
            n_chunks, rows, lanes)
        block_rows, n_blocks = None, n_chunks
    else:
        x, rows = view, view.shape[0]
        block_rows = _block_rows(view.shape[1], chunk_elems, view.dtype)
        if rows <= block_rows:
            block_rows = rows
        n_blocks = rows // block_rows
    kernel = functools.partial(_bounce_kernel, block_rows=block_rows,
                               n_blocks=n_blocks, copies=copies,
                               total_iters=iters_per_chunk * n_chunks,
                               total_copies=copies * n_chunks)
    out, totals = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pltpu.SMEM)),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((NUM_COST_COLS,), jnp.int32)),
        interpret=interpret,
    )(x)
    if view.ndim == 1:
        out = out.reshape(-1)
        return (out[:n] if pad else out), totals
    # the rows past the last whole block: a tiled dim cannot be sliced
    # by a DMA at a partial tile, so they are written into the kernel's
    # output in place, after it
    done = n_blocks * block_rows
    if done < rows:
        out = jax.lax.dynamic_update_slice(out, view[done:], (done, 0))
    return out, totals


def _launch(x, *, copies: int, delay_iters: int, chunk_elems: int,
            interpret: bool | None):
    if interpret is None:
        interpret = not _is_tpu()
    view = _row_view(x.shape, x.dtype, chunk_elems)
    out, totals = _bounce_fwd(x.reshape(view or (-1,)), copies=int(copies),
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

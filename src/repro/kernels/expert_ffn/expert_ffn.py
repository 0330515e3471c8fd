"""Pallas TPU grouped gated FFN over the experts a chip holds: the expert
half of an expert-parallel MoE layer at inference.

Rows come sorted by expert, each expert's group starting on a whole tile
of ``tm`` rows (``group_starts``), so every tile of rows belongs to one
expert.  The grid walks the tiles in order; the tile's expert and its
row tile are scalar-prefetched into SMEM, and the block specs read the
expert's three weight matrices and the tile of rows through them.  The
pipeline fetches a block only when its index changes, so an expert's
weights are read once a call however many tiles it has, and an expert
with no rows has no tile and is never read.  Tiles past the last used
one repeat its indices (nothing is fetched) and compute nothing: their
output rows are left as they are and must not be read.

Maths per row (the gated MLP of ``layers/mlp.py``, ``act`` named as in
``jax.nn``): ``(act(x Wg) * (x Wi)) Wo``, the two products accumulated
in float32, their gated product rounded to the rows' dtype before
``Wo``.

``interpret=True`` (automatic off-TPU) runs the body in the Pallas
interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _is_tpu() -> bool:
    return jax.default_backend() == "tpu"


def tile_rows(max_rows: int) -> int:
    """Rows per tile for a call of at most ``max_rows`` routed rows: one
    bf16 sublane tile (16) for decode-sized calls, so a held expert's few
    rows are not padded far; 128 for prefill-sized ones, so the grid stays
    short and each expert's matmuls fill the MXU."""
    return 16 if max_rows <= 512 else 128


def num_tiles(max_rows: int, experts: int, tm: int) -> int:
    """Tiles that any split of ``max_rows`` rows over ``experts`` groups,
    each padded to whole tiles, can need."""
    return -(-max_rows // tm) + experts


def group_starts(rows, tm: int):
    """First row of each expert's group when groups of ``rows`` (E,) rows
    are each padded to whole tiles of ``tm``: (E,) int32."""
    padded = -(-rows // tm) * tm
    return jnp.cumsum(padded) - padded


def _kernel(tile_exp, tile_row, used, x_ref, wg_ref, wi_ref, wo_ref, o_ref,
            *, act: str):
    @pl.when(pl.program_id(0) < used[0])
    def _compute():
        x = x_ref[...]                                     # (tm, D)
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wi_ref[0], preferred_element_type=jnp.float32)
        h = (getattr(jax.nn, act)(g) * u).astype(x.dtype)  # (tm, F)
        o_ref[...] = jnp.dot(h, wo_ref[0], preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "act", "interpret"))
def _expert_ffn_fwd(x, rows, wg, wi, wo, *, tm: int, act: str,
                    interpret: bool):
    m, d = x.shape
    e, _, f = wg.shape
    nt = m // tm
    tiles = -(-rows // tm)                                 # (E,)
    ends = jnp.cumsum(tiles)
    used = ends[-1]
    t = jnp.minimum(jnp.arange(nt, dtype=jnp.int32), jnp.maximum(used - 1, 0))
    tile_exp = jnp.minimum(jnp.sum(ends[None, :] <= t[:, None], axis=1),
                           e - 1).astype(jnp.int32)
    # a padded group's tiles are consecutive, from its start's tile
    tile_row = t.astype(jnp.int32)
    wspec = pl.BlockSpec((1, d, f), lambda i, te, tr, u: (te[i], 0, 0))
    ospec = pl.BlockSpec((1, f, d), lambda i, te, tr, u: (te[i], 0, 0))
    rspec = pl.BlockSpec((tm, d), lambda i, te, tr, u: (tr[i], 0))
    wbytes = 3 * d * f * wg.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_kernel, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nt,),
            in_specs=[rspec, wspec, wspec, ospec],
            out_specs=rspec),
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        # sequential: a repeated output index keeps its block, unwritten
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * wbytes + 4 * tm * (d + f) * 4 + (8 << 20)),
        interpret=interpret,
    )(tile_exp, tile_row, used.reshape(1).astype(jnp.int32), x, wg, wi, wo)


def expert_ffn(x, rows, wg, wi, wo, *, tm: int, act: str = "silu",
               interpret: bool | None = None):
    """Gated FFN of each held expert over its own rows.

    x: (M, D) rows sorted by expert, expert e's ``rows[e]`` rows starting
    at ``group_starts(rows, tm)[e]``, M a multiple of ``tm`` of at least
    ``num_tiles`` tiles; rows: (E,) int32; wg / wi: (E, D, F); wo: (E, F,
    D).  Returns (M, D) in x's dtype: each group's rows through its
    expert.  Rows outside the groups hold nothing to be read."""
    if interpret is None:
        interpret = not _is_tpu()
    return _expert_ffn_fwd(x, jnp.asarray(rows, jnp.int32), wg.astype(x.dtype),
                           wi.astype(x.dtype), wo.astype(x.dtype), tm=int(tm),
                           act=act, interpret=bool(interpret))


def expert_ffn_ref(x, rows, wg, wi, wo, *, tm: int, act: str = "silu"):
    """Reference: every row through every expert as a plain grouped
    einsum, each group's rows then taken from its own expert."""
    m = x.shape[0]
    g = jnp.einsum("md,edf->emf", x, wg.astype(x.dtype),
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("md,edf->emf", x, wi.astype(x.dtype),
                   preferred_element_type=jnp.float32)
    h = (getattr(jax.nn, act)(g) * u).astype(x.dtype)
    y = jnp.einsum("emf,efd->emd", h, wo.astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    start = group_starts(jnp.asarray(rows, jnp.int32), tm)
    r = jnp.arange(m)
    owner = (r[None, :] >= start[:, None]) & (r[None, :] < start[:, None]
                                               + jnp.asarray(rows)[:, None])
    return jnp.einsum("em,emd->md", owner.astype(y.dtype), y), owner.any(0)


__all__ = ["expert_ffn", "expert_ffn_ref", "group_starts", "num_tiles",
           "tile_rows"]

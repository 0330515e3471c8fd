"""Pallas TPU kernel for the mamba selective-state-space scan.

    h_t = exp(dt_t ⊗ A) ⊙ h_{t-1} + (dt_t ⊙ x_t) ⊗ B_t
    y_t = (h_t · C_t)

TPU adaptation: the recurrence state h (channels × N)
lives in VMEM scratch and persists across the innermost chunk grid
dimension; channels are blocked to keep the (db, N) state VREG/VMEM
friendly; the discretization exp(dt·A) is computed in-kernel (never
materializing the (B, S, d_inner, N) dA tensor in HBM — that tensor is
what makes the XLA path memory-bound).

Grid: (batch, channel_blocks, chunks) — chunks sequential, rest parallel.
State-neutral padding: dt = 0 ⇒ dA = 1, dBx = 0 (h unchanged), so ragged
sequence lengths pad cleanly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(dt_ref, x_ref, a_ref, b_ref, c_ref, h0_ref,
                y_ref, hf_ref, h_scr, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    a = a_ref[...].astype(jnp.float32)                 # (db, N)

    def step(t, h):
        dt_t = dt_ref[0, t, :].astype(jnp.float32)     # (db,)
        x_t = x_ref[0, t, :].astype(jnp.float32)       # (db,)
        b_t = b_ref[0, t, :].astype(jnp.float32)       # (N,)
        c_t = c_ref[0, t, :].astype(jnp.float32)       # (N,)
        dA = jnp.exp(dt_t[:, None] * a)                # (db, N)
        h = dA * h + (dt_t * x_t)[:, None] * b_t[None, :]
        y_ref[0, t, :] = jnp.sum(h * c_t[None, :], axis=1).astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h

    @pl.when(ci == nc - 1)
    def _finalize():
        hf_ref[0] = h_scr[...].astype(hf_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "channel_block", "interpret"))
def ssm_scan_fwd(dt, x, a, b, c, h0, *, chunk: int = 128,
                 channel_block: int = 256, interpret: bool = False):
    """dt/x: (B, S, di); a: (di, N); b/c: (B, S, N); h0: (B, di, N).

    Returns (y: (B, S, di), h_final: (B, di, N))."""
    bsz, s, di = dt.shape
    n = a.shape[1]
    ck = min(chunk, s)
    while s % ck:
        ck -= 1
    db = min(channel_block, di)
    while di % db:
        db -= 1
    nc, nd = s // ck, di // db

    return pl.pallas_call(
        functools.partial(_ssm_kernel, chunk=ck),
        grid=(bsz, nd, nc),
        in_specs=[
            pl.BlockSpec((1, ck, db), lambda b_, j, c_: (b_, c_, j)),   # dt
            pl.BlockSpec((1, ck, db), lambda b_, j, c_: (b_, c_, j)),   # x
            pl.BlockSpec((db, n), lambda b_, j, c_: (j, 0)),            # A
            pl.BlockSpec((1, ck, n), lambda b_, j, c_: (b_, c_, 0)),    # B
            pl.BlockSpec((1, ck, n), lambda b_, j, c_: (b_, c_, 0)),    # C
            pl.BlockSpec((1, db, n), lambda b_, j, c_: (b_, j, 0)),     # h0
        ],
        out_specs=[
            pl.BlockSpec((1, ck, db), lambda b_, j, c_: (b_, c_, j)),   # y
            pl.BlockSpec((1, db, n), lambda b_, j, c_: (b_, j, 0)),     # hf
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, di), dt.dtype),
            jax.ShapeDtypeStruct((bsz, di, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((db, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(dt, x, a, b, c, h0)


__all__ = ["ssm_scan_fwd"]

"""Paged decode attention: the Pallas kernel that reads each slot's blocks
from the KV pool through its block table (interpret mode here), the
model's paged decode step built on it, and the paged engine's decode
program, which holds no array of the whole table's positions."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_model_config
from repro.configs.base import ServeConfig
from repro.kernels.paged_attention import paged_decode_attention
from repro.layers.attention import attend_naive
from repro.layers.kvcache import (
    kv_pool_gather,
    kv_pool_scatter_token,
    kv_update_slots,
    slot_validity,
)
from repro.models import build_model
from repro.serve import Engine, Request


def _case(bs, t_len, h, kvh, window, logit_cap, dtype):
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    name = (f"bs{bs}-group{h // kvh}-" + ("window" if window else
            "softcap" if logit_cap else "plain") + f"-{jnp.dtype(dtype)}")
    return pytest.param(bs, t_len, h, kvh, window, logit_cap, dtype, tol,
                        id=name)


# block 4 over a table of 8: one loop step per slot; block 64 over 20:
# three steps of STEP_POSITIONS // 64 = 8 blocks, the last one partial.
# GQA groups of 1 and 4; windows within a block and across blocks.
CASES = [
    _case(4, 8, 2, 2, 0, 0.0, jnp.float32),
    _case(4, 8, 8, 2, 6, 0.0, jnp.float32),
    _case(64, 20, 8, 2, 70, 0.0, jnp.float32),
    _case(64, 20, 2, 2, 0, 1.0, jnp.float32),
    _case(4, 8, 8, 2, 0, 1.0, jnp.bfloat16),
    _case(64, 20, 2, 2, 70, 0.0, jnp.bfloat16),
]


def _slots(bs, t_len, rng):
    """Four slots: one ending mid-block, one on a block boundary, an
    inactive one (position 0, all-null table) and a long one; live blocks
    are distinct pool ids in random order, tails point at the null block."""
    cap = t_len * bs - 1
    pos = np.asarray([bs * 3 + 1, bs * 2, 0, cap], np.int32)
    n_blocks = int(sum(-(-p // bs) for p in pos)) + 3
    ids = rng.permutation(np.arange(1, n_blocks + 1))
    tables = np.zeros((len(pos), t_len), np.int32)
    at = 0
    for i, p in enumerate(pos):
        live = -(-int(p) // bs)
        tables[i, :live] = ids[at:at + live]
        at += live
    return pos, tables, n_blocks


@pytest.mark.parametrize("bs,t_len,h,kvh,window,logit_cap,dtype,tol", CASES)
def test_kernel_matches_gather_then_naive(bs, t_len, h, kvh, window,
                                          logit_cap, dtype, tol):
    hd = 16
    rng = np.random.default_rng(bs * 1000 + h + window)
    pos, tables, n_blocks = _slots(bs, t_len, rng)
    b = len(pos)
    ks = jax.random.split(jax.random.PRNGKey(int(bs + h + window)), 5)
    shape = (1, n_blocks + 1, bs, kvh * hd)
    pool = {"k": jax.random.normal(ks[0], shape, dtype).at[:, 0].set(0),
            "v": jax.random.normal(ks[1], shape, dtype).at[:, 0].set(0)}
    q = jax.random.normal(ks[2], (b, h, hd), dtype)
    k_new = jax.random.normal(ks[3], (b, kvh, hd), dtype)
    v_new = jax.random.normal(ks[4], (b, kvh, hd), dtype)

    got = paged_decode_attention(q, pool["k"], pool["v"], tables, pos,
                                 k_new, v_new, window=window,
                                 logit_cap=logit_cap)

    @jax.jit
    def reference(pool, q, k_new, v_new):
        s_max = t_len * bs
        dense = {n: c[0].reshape(b, s_max, kvh, hd) for n, c in
                 kv_pool_gather(pool, tables, bs).items()}
        ck, cv = kv_update_slots(dense["k"], dense["v"], k_new[:, None],
                                 v_new[:, None], pos)
        valid = slot_validity(s_max, pos)
        if window:
            valid &= pos[:, None] - np.arange(s_max)[None, :] < window
        return attend_naive(q[:, None], ck, cv, valid[:, None, :],
                            logit_cap=logit_cap)[:, 0]

    want = reference(pool, q, k_new, v_new)
    assert got.shape == want.shape and got.dtype == dtype
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# the model's paged decode step, and the engine's decode program
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_model_config("gemma3-1b", smoke=True)   # windowed layers, GQA
    model = build_model(cfg)
    return cfg, model, jax.jit(model.init)(jax.random.PRNGKey(0))


def test_paged_step_matches_gather_dense_scatter(smoke_model):
    """One paged decode step gives the logits and the pool that the
    reference gives: gather every table, the dense slot decode, scatter
    each active slot's token back."""
    cfg, model, params = smoke_model
    L, bs, t_len = cfg.num_layers, 8, 6
    rng = np.random.default_rng(3)
    pos, tables, n_blocks = _slots(bs, t_len, rng)
    active = pos > 0
    kvh, hd = cfg.attention.num_kv_heads, cfg.head_dim
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    shape = (L, n_blocks + 1, bs, kvh * hd)
    pool = {"k": jax.random.normal(ks[0], shape).at[:, 0].set(0),
            "v": jax.random.normal(ks[1], shape).at[:, 0].set(0)}
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (len(pos), 1)),
                      jnp.int32)

    @jax.jit
    def paged(params, tok, pool):
        logits, new, _ = model.decode_step_paged(params, tok, pool, tables,
                                                 pos)
        return logits, kv_pool_scatter_token(pool, new, tables, pos, active,
                                             bs)

    @jax.jit
    def reference(params, tok, pool):
        dense = {n: c.reshape(L, len(pos), t_len * bs, kvh, hd)
                 for n, c in kv_pool_gather(pool, tables, bs).items()}
        logits, dense = model.decode_step_slots(params, tok, dense, pos)
        rows = np.arange(len(pos))
        return logits, kv_pool_scatter_token(
            pool, {n: c[:, rows, pos] for n, c in dense.items()}, tables,
            pos, active, bs)

    logits, got = paged(params, tok, pool)
    want_logits, want = reference(params, tok, pool)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits),
                               rtol=1e-5, atol=1e-5)
    for n in ("k", "v"):
        np.testing.assert_allclose(np.asarray(got[n]), np.asarray(want[n]),
                                   rtol=1e-6, atol=1e-6)


def _tensor_dims(text):
    """Every tensor shape in a StableHLO module, as tuples of dims."""
    return {tuple(int(x) for x in m.split("x")[:-1])
            for m in re.findall(r"tensor<((?:\d+x)+)[a-z]", text)}


def test_paged_decode_program_holds_no_table_wide_cache(smoke_model):
    """The engine's paged decode compiles once, and no array in it holds
    ``max_batch × tables_len × block_size`` positions — the whole-pool
    gather's dense view (as ``(B, T·bs)`` or ``(B, T, bs)``)."""
    cfg, model, params = smoke_model
    B, bs = 3, 8
    eng = Engine(model, params, cfg,
                 ServeConfig(max_batch=B, max_new_tokens=4, kv_cache_len=64,
                             block_size=bs), eos_id=-1)
    done = eng.run([Request(rid=i, prompt=np.arange(5 + 9 * i,
                                                    dtype=np.int32) % 50,
                            max_new_tokens=4) for i in range(4)])
    assert all(len(r.out_tokens) == 4 for r in done)
    assert eng.decode_compile_count() == 1

    T = eng._tables_len
    layers, kvh, hd, dt = eng._pool_geom
    pool = {n: jax.ShapeDtypeStruct((layers, eng._n_usable + 1, bs,
                                     kvh * hd), dt) for n in "kv"}
    i32 = jnp.int32
    text = eng._step_pool.lower(
        params, jax.ShapeDtypeStruct((B, 1), i32), pool,
        jax.ShapeDtypeStruct((B, T), i32), jax.ShapeDtypeStruct((B,), i32),
        jax.ShapeDtypeStruct((B,), bool)).as_text()
    dims = _tensor_dims(text)
    assert dims, "no tensor shapes found in the lowered program"

    def holds(d, run):
        return any(d[i:i + len(run)] == run for i in range(len(d)))

    wide = [d for d in dims if holds(d, (B, T * bs)) or holds(d, (B, T, bs))]
    assert not wide, f"table-wide arrays in serve_decode: {wide}"
    # the reference path does hold one, so the check can see it
    ref = jax.jit(lambda p: kv_pool_gather(p, jnp.zeros((B, T), i32), bs)
                  ).lower(pool).as_text()
    assert any(holds(d, (B, T * bs)) for d in _tensor_dims(ref))

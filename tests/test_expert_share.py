"""The expert layer at one chip's share (layers/moe.py ``expert_share``)
and its grouped kernel (kernels/expert_ffn), on the CPU: the kernel
against a plain grouped einsum, empty groups included; the held parts of
disjoint shares, plus the shared expert once, against the uncut layer;
and the share-aware training dispatch against the same uncut layer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.kernels.expert_ffn import (
    expert_ffn,
    expert_ffn_ref,
    group_starts,
    num_tiles,
    tile_rows,
)
from repro.layers.mlp import mlp
from repro.layers.moe import expert_share, moe, moe_init

RNG = jax.random.PRNGKey(0)


@pytest.mark.parametrize("rows", [
    pytest.param([3, 0, 17, 5], id="mixed"),
    pytest.param([0, 0, 0, 0], id="all-empty"),
    pytest.param([16, 16, 0, 1], id="whole-tiles"),
    pytest.param([0, 0, 0, 40], id="one-expert"),
])
@pytest.mark.parametrize("dtype,tol", [
    pytest.param(jnp.float32, 1e-5, id="f32"),
    # the gated product is rounded to bf16 before Wo on both sides, the
    # products accumulate in f32 in different orders: 2 bf16 ulps
    pytest.param(jnp.bfloat16, 1.6e-2, id="bf16"),
])
def test_kernel_matches_grouped_einsum(rows, dtype, tol):
    e, d, f = 4, 128, 256
    rows = np.asarray(rows, np.int32)
    tm = tile_rows(int(rows.sum()))
    m = num_tiles(int(rows.sum()), e, tm) * tm
    ks = jax.random.split(RNG, 4)
    x = jax.random.normal(ks[0], (m, d)).astype(dtype)
    wg = (jax.random.normal(ks[1], (e, d, f)) / np.sqrt(d)).astype(dtype)
    wi = (jax.random.normal(ks[2], (e, d, f)) / np.sqrt(d)).astype(dtype)
    wo = (jax.random.normal(ks[3], (e, f, d)) / np.sqrt(f)).astype(dtype)
    got = np.asarray(expert_ffn(x, rows, wg, wi, wo, tm=tm), np.float32)
    want, valid = expert_ffn_ref(x, rows, wg, wi, wo, tm=tm)
    valid = np.asarray(valid)
    assert valid.sum() == rows.sum()
    np.testing.assert_allclose(got[valid],
                               np.asarray(want, np.float32)[valid],
                               rtol=tol, atol=tol)


def test_group_starts_are_whole_tiles():
    starts = np.asarray(group_starts(jnp.asarray([3, 0, 17, 16]), 16))
    assert starts.tolist() == [0, 16, 16, 48]
    assert num_tiles(36, 4, 16) == 3 + 4
    assert tile_rows(128) == 16 and tile_rows(4096) == 128


CFG = MoEConfig(num_experts=16, top_k=4, score_func="sigmoid",
                route_scale=2.826, selection_bias=True, shared_experts=1,
                expert_ff=32)
D = 64


def _uncut():
    p = moe_init(RNG, D, 0, CFG)
    # a selection bias large enough to move some tokens' choices
    p["bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(7), (16,))
    return p


def _share(p, i, n=4):
    """Share ``i`` of ``16 / n``: experts [n·i, n·i + n), without the
    shared expert (every chip computes that alike)."""
    cfg = dataclasses.replace(CFG, held_experts=n, first_held=n * i)
    q = {k: p[k] for k in ("router", "bias")}
    q.update({k: p[k][n * i:n * i + n] for k in ("wi", "wg", "wo")})
    return q, cfg


def _plain_layer(p, x):
    """The uncut layer written out: sigmoid scores, top-k of the biased
    scores, normalised and scaled gates, every expert densely."""
    xf = x.reshape(-1, D)
    s = jax.nn.sigmoid(xf @ p["router"])
    _, top = jax.lax.top_k(s + p["bias"], CFG.top_k)
    g = jnp.take_along_axis(s, top, -1)
    g = g / g.sum(-1, keepdims=True) * CFG.route_scale
    out = jnp.zeros_like(xf)
    for e in range(CFG.num_experts):
        ge = jnp.sum(jnp.where(top == e, g, 0.0), -1)
        h = jax.nn.silu(xf @ p["wg"][e]) * (xf @ p["wi"][e])
        out += ge[:, None] * (h @ p["wo"][e])
    return out.reshape(x.shape), top


def test_disjoint_shares_add_up_to_the_uncut_layer():
    p = _uncut()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, D))
    want, top = _plain_layer(p, x)
    want = want + mlp(p["shared"], x)
    total, rows = 0.0, []
    for i in range(4):
        q, cfg = _share(p, i)
        part, r = expert_share(q, x, cfg)
        total = total + part
        rows.append(np.asarray(r))
    shared = mlp(p["shared"], x)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # each held expert computed exactly the rows routed to it
    counts = np.bincount(np.asarray(top).ravel(), minlength=16)
    assert np.concatenate(rows).tolist() == counts.tolist()
    # the bias moved some choices: the unbiased top-k differs somewhere
    s = jax.nn.sigmoid(x.reshape(-1, D) @ p["router"])
    assert (np.sort(np.asarray(jax.lax.top_k(s, 4)[1]), 1)
            != np.sort(np.asarray(top), 1)).any()


def test_share_output_is_its_own_tokens_alone():
    """A token's output does not depend on which tokens share the call:
    batch-composition invariance, as the serving engine needs."""
    q, cfg = _share(_uncut(), 1)
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 8, D))
    whole, _ = expert_share(q, x, cfg)
    for b in range(3):
        one, _ = expert_share(q, x[b:b + 1, 2:5], cfg)
        np.testing.assert_array_equal(np.asarray(one),
                                      np.asarray(whole[b:b + 1, 2:5]))


def test_training_dispatch_at_a_share_matches():
    """The capacity dispatch of ``moe`` (training), at a capacity no
    token overflows, computes the same held part as the inference path."""
    p = _uncut()
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, D))
    q, cfg = _share(p, 2)
    cfg = dataclasses.replace(cfg, capacity_factor=16.0)
    got, aux = moe(q, x, cfg, group_size=16, train=True)
    want, _ = expert_share(q, x, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    assert np.isfinite(float(aux))

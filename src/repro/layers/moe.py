"""Mixture-of-Experts layer: top-k routing with **block-wise capacity**
dispatch (GShard/MaxText-style "dropping" MoE), EP-shardable under GSPMD.

Tokens are grouped into blocks of ``group_size``; each block dispatches to
all experts with a per-block capacity C = ceil(group_size·k·cf / E).  The
dispatch/combine tensors are (G, n, E, C) with E·C ≈ group_size·k·cf —
their footprint is **independent of the expert count**, which is what
keeps arctic-480b (128 experts) inside HBM at 256-way SPMD.

Capacity dropping applies at **training only**.  Inference (``train=False``)
is dropless (C = n·k over a small block), because serving exactness —
continuous batching ≡ the batch-1 greedy reference decode at temperature
0 (tests/conftest.py), bit-exact slot preempt/resume — requires
per-token outputs that are invariant to batch composition, and capacity
races between co-resident tokens break that.

Sharding (via the dataplane): blocks G → data axis, experts E → model
axis.  The G↔E resharding between dispatch and expert compute is the EP
all-to-all, materialized by GSPMD from the constraints this module issues.

Arctic-style ``dense_residual``: a dense MLP runs in parallel and its
output is added to the expert output.  afmoe-style ``shared_experts``:
one gated MLP every token runs, added the same way.

**Expert share** (``MoEConfig.held_experts``, expert parallelism): the
layer holds only experts ``[first_held, first_held + held)``.  The router
scores all ``num_experts`` and picks each token's top-k among all of
them; the layer computes the part of the result its own experts give,
and assignments to experts held elsewhere add nothing here (on a
deployment their part arrives over the EP exchange, which one chip does
not have).  At inference (:func:`expert_share`) only the (token, held
expert) pairs are computed: they are counted by expert, laid out sorted
by expert with each group on whole row tiles, run through the grouped
kernel (kernels/expert_ffn), and combined with their gates.  Each row's
output depends on its own token alone, so outputs stay invariant to
batch composition.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.kernels.expert_ffn import (
    expert_ffn,
    group_starts,
    num_tiles,
    tile_rows,
)
from repro.layers.common import act_fn, constrain, dense_init
from repro.layers.mlp import mlp, mlp_init


def moe_init(rng, d_model: int, d_ff: int, cfg: MoEConfig,
             gated: bool = True) -> dict:
    """Router over all ``num_experts``; expert weights (E, D, F) / (E, F,
    D) for the ``cfg.held`` experts this chip holds, of width
    ``cfg.expert_ff or d_ff``."""
    r = jax.random.split(rng, 5)
    e, f = cfg.held, cfg.expert_ff or d_ff
    p = {
        "router": dense_init(r[0], d_model, cfg.num_experts, scale=1e-2),
        "wi": dense_init(r[1], d_model, e, f).transpose(1, 0, 2),  # (E,D,F)
        "wo": dense_init(r[2], f, e, d_model).transpose(1, 0, 2),  # (E,F,D)
    }
    if gated:
        p["wg"] = dense_init(r[3], d_model, e, f).transpose(1, 0, 2)
    if cfg.dense_residual:
        p["dense"] = mlp_init(r[4], d_model, cfg.dense_residual_ff, gated)
    if cfg.selection_bias:
        p["bias"] = jnp.zeros((cfg.num_experts,), jnp.float32)
    if cfg.shared_experts:
        p["shared"] = mlp_init(jax.random.fold_in(rng, 5), d_model,
                               cfg.shared_experts * f, gated)
    return p


def _capacity(group: int, cfg: MoEConfig) -> int:
    c = int(group * cfg.top_k * cfg.capacity_factor / max(cfg.num_experts, 1))
    return max(c, 1)


def route(params: dict, x2d: jax.Array, cfg: MoEConfig, *,
          train: bool, rng=None):
    """Router: top-k gates + aux losses. x2d: (T, D) flat tokens.  ``idx``
    (T, k) names experts of all ``num_experts``."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    if train and cfg.router_jitter > 0 and rng is not None:
        logits += cfg.router_jitter * jax.random.normal(rng, logits.shape)
    if cfg.score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choose = scores + params["bias"] if "bias" in params else scores
        _, idx = jax.lax.top_k(choose, cfg.top_k)             # (T,k)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20) \
            * cfg.route_scale
        probs = scores / scores.sum(-1, keepdims=True)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, cfg.top_k)          # (T,k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # aux losses (Switch-style)
    me = probs.mean(axis=0)                                    # (E,)
    ce = jnp.zeros_like(me).at[idx[:, 0]].add(1.0) / idx.shape[0]
    lb_loss = cfg.num_experts * jnp.sum(me * ce) * cfg.load_balance_loss
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * cfg.router_z_loss
    return gates, idx, lb_loss + z_loss


def moe(params: dict, x: jax.Array, cfg: MoEConfig, *, act: str = "silu",
        group_size: int = 512, train: bool = False, rng=None, dp=None):
    """Apply the MoE layer. x: (B, S, D). Returns (out, aux_loss)."""
    b, s, d = x.shape
    tokens = b * s
    # Inference is dropless: serving correctness (continuous ≡ batch-1
    # reference decode at temp 0, slot-exact preempt/resume) needs
    # per-token outputs that do not depend on which other rows share the
    # batch, and capacity dropping is exactly such a coupling (the block cumsum races tokens
    # for expert queue slots).  With C = n·k no token can ever drop, and
    # co-token contributions enter every einsum as exact zeros, so each
    # token's output is invariant to grouping and batch composition.
    # Training keeps the fixed-capacity dispatch (EP all-to-all friendly,
    # bounded footprint); the smaller eval group bounds the dropless
    # (G,n,E,C≈n·k) dispatch tensor.
    g_sz = min(group_size if train else min(group_size, 64), tokens)
    while tokens % g_sz:
        g_sz -= 1
    g = tokens // g_sz
    e = cfg.held
    c = _capacity(g_sz, cfg) if train else g_sz * cfg.top_k

    xf = x.reshape(tokens, d)
    gates, idx, aux = route(params, xf, cfg, train=train, rng=rng)

    # block-local positions in each held expert's queue (an assignment to
    # an expert held elsewhere is an all-zero one-hot row: it takes no
    # queue slot and adds nothing)
    onehot = jax.nn.one_hot(idx.reshape(g, g_sz, cfg.top_k) - cfg.first_held,
                            e, dtype=jnp.int32)                # (G,n,k,E)
    flat = onehot.reshape(g, g_sz * cfg.top_k, e)
    pos = jnp.cumsum(flat, axis=1) - 1                         # (G,n*k,E)
    pos = pos.reshape(g, g_sz, cfg.top_k, e)
    keep = (pos < c) & (onehot > 0)
    slot = jax.nn.one_hot(jnp.where(keep, pos, -1), c,
                          dtype=x.dtype)                       # (G,n,k,E,C)
    dispatch = slot.sum(2)                                     # (G,n,E,C)
    gmat = (gates.reshape(g, g_sz, cfg.top_k, 1, 1) * slot).sum(2)

    xg = xf.reshape(g, g_sz, d)
    xg = constrain(dp, xg, ("exp_groups", None, "embed"), tag="moe/tokens")
    # dispatch/combine edges carry the "moe-dispatch" QoS class: the EP
    # all-to-alls are latency-critical (every token waits on them), so the
    # chunk scheduler can prioritize them over bulk traffic.
    dispatch = constrain(dp, dispatch, ("exp_groups", None, "experts", None),
                         tag="moe/dispatch", qos="moe-dispatch")
    # EP all-to-all edge: (G blocks on data) -> (E experts on model)
    ein = jnp.einsum("gnec,gnd->gecd", dispatch, xg)
    ein = constrain(dp, ein, ("exp_groups", "experts", None, "embed"),
                    tag="moe/expert_in", qos="moe-dispatch")

    h = jnp.einsum("gecd,edf->gecf", ein, params["wi"].astype(x.dtype))
    if "wg" in params:
        gate = jnp.einsum("gecd,edf->gecf", ein, params["wg"].astype(x.dtype))
        h = act_fn(act)(gate) * h
    else:
        h = act_fn(act)(h)
    h = constrain(dp, h, ("exp_groups", "experts", None, "expert_mlp"),
                  tag="moe/hidden")
    eo = jnp.einsum("gecf,efd->gecd", h, params["wo"].astype(x.dtype))
    eo = constrain(dp, eo, ("exp_groups", "experts", None, "embed"),
                   tag="moe/expert_out")

    # combine: EP all-to-all back (E on model) -> (G on data)
    out = jnp.einsum("gnec,gecd->gnd", gmat.astype(x.dtype), eo)
    out = out.reshape(b, s, d)
    out = constrain(dp, out, ("batch", "seq", "embed"), tag="moe/out",
                    qos="moe-dispatch")

    return _add_dense(params, x, out, act=act, dp=dp), aux


def _add_dense(params: dict, x, out, *, act: str, dp):
    """What every token runs besides its routed experts: arctic's dense
    residual MLP, afmoe's shared experts."""
    if "dense" in params:
        out = out + mlp(params["dense"], x, act=act, dp=dp,
                        tag="moe/dense_residual")
    if "shared" in params:
        out = out + mlp(params["shared"], x, act=act, dp=dp,
                        tag="moe/shared")
    return out


def expert_share(params: dict, x: jax.Array, cfg: MoEConfig, *,
                 act: str = "silu", dp=None):
    """Inference through the held experts alone (module docstring).

    x: (B, S, D).  Returns ``(out, rows)``: out (B, S, D) is the held
    experts' part of the routed result plus what every token runs; rows
    (held,) int32 counts the rows each held expert computed."""
    b, s, d = x.shape
    t, k, e = b * s, cfg.top_k, cfg.held
    xf = x.reshape(t, d)
    gates, idx, _ = route(params, xf, cfg, train=False)
    local = idx.reshape(-1) - cfg.first_held                   # (T·k,)
    held = (local >= 0) & (local < e)
    onehot = jax.nn.one_hot(jnp.where(held, local, -1), e,
                            dtype=jnp.int32)                   # (T·k, E)
    rows = onehot.sum(0)
    # counting sort: each pair's rank in its expert's group, and its row
    # in the tile-padded, expert-sorted layout
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1)
    max_rows = t * min(k, e)
    tm = tile_rows(max_rows)
    m = num_tiles(max_rows, e, tm) * tm
    dest = jnp.where(held, group_starts(rows, tm)[jnp.clip(local, 0, e - 1)]
                     + rank, m)
    xs = jnp.zeros((m, d), x.dtype).at[dest].set(
        jnp.repeat(xf, k, axis=0), mode="drop")
    ys = expert_ffn(xs, rows, params["wg"], params["wi"], params["wo"],
                    tm=tm, act=act)
    # rows of ys outside the groups hold nothing: select, never multiply
    y = jnp.where(held[:, None],
                  ys[jnp.minimum(dest, m - 1)].astype(jnp.float32)
                  * gates.reshape(-1, 1), 0.0)
    out = y.reshape(t, k, d).sum(1).astype(x.dtype).reshape(b, s, d)
    out = constrain(dp, out, ("batch", "seq", "embed"), tag="moe/out",
                    qos="moe-dispatch")
    return _add_dense(params, x, out, act=act, dp=dp), rows


__all__ = ["moe_init", "moe", "route", "expert_share"]

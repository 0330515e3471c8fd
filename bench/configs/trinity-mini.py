"""Plain reference of Trinity-Mini (afmoe) as its cells run it, at one
chip's expert share, and the seeded weights that the program and the
reference are both given.

The layer, from the configuration file's keys and transformers'
``modeling_afmoe.py`` (``x`` the residual stream, ``w*`` norm weights,
GQA heads grouped as ``repeat_kv`` groups them):

    x  = E[tokens] * sqrt(hidden_size)                     (mup_enabled)
    a  = rmsnorm(x) * w_in                                 (eps rms_norm_eps)
    q, k, v = a Wq, a Wk, a Wv;  q, k = rmsnorm(q) * w_q, rmsnorm(k) * w_k
                                                           (per head)
    q, k = rope(q), rope(k)   on sliding_attention layers only
                              (rope_theta, rotate-half)
    o  = softmax(q k^T / sqrt(head_dim) + causal
                 [+ the last sliding_window positions on sliding layers]) v
    o  = o * sigmoid(a Wgate)
    x += rmsnorm(o Wo) * w_post_attn
    h  = rmsnorm(x) * w_pre_mlp
    dense layers (the first num_dense_layers):  m = (silu(h Wg) * h Wi) Wd
    MoE layers:  s = sigmoid(h Wr)                   (all of the router's
                                                      router_experts outputs)
                 top = top-k of (s + b)              (b: selection bias)
                 g = s[top] / sum(s[top]) * route_scale
                 m = shared(h) + sum over the held experts e in top of
                     g_e * (silu(h Wg_e) * h Wi_e) Wd_e
    x += rmsnorm(m) * w_post_mlp
    logits = (rmsnorm(x) * wf) Whead^T                    (untied head)

The expert share: this chip holds experts ``[first_held, first_held +
num_experts)`` of every MoE layer.  The reference computes the same
share: it runs every held expert densely over every token and weighs each
by its gate where the token chose it, and zero where it did not; experts
held elsewhere add nothing, as in the program.

It imports nothing of the program and computes in float32 at
``highest`` matmul precision, layer by layer, one sequence at a time,
attention a block of queries at a time.  ``compute`` rounds weights and
activations to a narrower type where the served model rounds to
bfloat16: the control.

Weight layout (the program's parameter tree, which only the weights'
keys and shapes depend on): norm weights are stored as ``w - 1``; the
first ``num_dense_layers`` layers are ``dense_layers``, the MoE layers
``layers``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# queries per attention block of the reference
Q_BLOCK = 512


def _dims(c: dict):
    d, h = c["hidden_size"], c["num_attention_heads"]
    return (d, h, c["num_key_value_heads"], c.get("head_dim") or d // h,
            c["vocab_size"], c["num_hidden_layers"], c["num_dense_layers"])


def _layer_specs(c: dict, n: int, moe: bool) -> dict:
    d, h, kvh, hd, v, _, _ = _dims(c)
    out = {
        ("norm1", "scale"): ((n, d), 0.0),
        ("norm2", "scale"): ((n, d), 0.0),
        ("post_attn_norm", "scale"): ((n, d), 0.0),
        ("post_mlp_norm", "scale"): ((n, d), 0.0),
        ("attn", "wq"): ((n, d, h, hd), 1 / math.sqrt(d)),
        ("attn", "wk"): ((n, d, kvh, hd), 1 / math.sqrt(d)),
        ("attn", "wv"): ((n, d, kvh, hd), 1 / math.sqrt(d)),
        ("attn", "wo"): ((n, h * hd, d), 1 / math.sqrt(h * hd)),
        ("attn", "q_norm", "scale"): ((n, hd), 0.0),
        ("attn", "k_norm", "scale"): ((n, hd), 0.0),
        ("attn", "wgate"): ((n, d, h, hd), 1 / math.sqrt(d)),
    }
    if not moe:
        f = c["intermediate_size"]
        out.update({
            ("mlp", "wi"): ((n, d, f), 1 / math.sqrt(d)),
            ("mlp", "wg"): ((n, d, f), 1 / math.sqrt(d)),
            ("mlp", "wo"): ((n, f, d), 1 / math.sqrt(f)),
        })
        return out
    e, f = c["num_experts"], c["moe_intermediate_size"]
    fs = f * c["num_shared_experts"]
    r = c["deployment"]["router_experts"]
    out.update({
        ("moe", "router"): ((n, d, r), 1 / math.sqrt(d)),
        ("moe", "bias"): ((n, r), -1.0),
        ("moe", "wi"): ((n, e, d, f), 1 / math.sqrt(d)),
        ("moe", "wg"): ((n, e, d, f), 1 / math.sqrt(d)),
        ("moe", "wo"): ((n, e, f, d), 1 / math.sqrt(f)),
        ("moe", "shared", "wi"): ((n, d, fs), 1 / math.sqrt(d)),
        ("moe", "shared", "wg"): ((n, d, fs), 1 / math.sqrt(d)),
        ("moe", "shared", "wo"): ((n, fs, d), 1 / math.sqrt(fs)),
    })
    return out


def weight_specs(c: dict) -> dict:
    """``{path: (shape, std)}`` of every leaf; std 0 marks a norm weight
    (stored as ``w - 1``, drawn with std ``norm_std`` around 1), std -1
    the selection bias (std ``bias_std``).  The embedding is drawn
    ``embed_gain / sqrt(hidden_size)`` wide, so that scaled by
    sqrt(hidden_size) it has RMS ``embed_gain``."""
    d, h, kvh, hd, v, n, nd = _dims(c)
    out = {
        ("embed", "tok"): ((v, d), c["weights"]["embed_gain"] / math.sqrt(d)),
        ("embed", "head"): ((v, d), 1 / math.sqrt(d)),
        ("final_norm", "scale"): ((d,), 0.0),
    }
    for group, k, moe in (("dense_layers", nd, False),
                          ("layers", n - nd, True)):
        for path, spec in _layer_specs(c, k, moe).items():
            out[(group, *path)] = spec
    return out


def init_weights(c: dict, words: list[int], device, dtype=jnp.bfloat16):
    """Every weight, drawn on ``device`` in one jitted call from the seed
    words, held in ``dtype`` (the type the cells serve them in)."""
    specs = weight_specs(c)
    w = c["weights"]

    def make(key):
        keys = jax.random.split(key, len(specs))
        tree: dict = {}
        for k, (path, (shape, std)) in zip(keys, specs.items()):
            scale = {0.0: w["norm_std"], -1.0: w["bias_std"]}.get(std, std)
            x = jax.random.normal(k, shape, jnp.float32) * scale
            node = tree
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = x.astype(dtype)
        return tree

    key = jax.random.fold_in(jax.random.key(words[0]), words[1])
    out = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(make, out_shardings=out)(key)


# ---------------------------------------------------------------------------
# the forward
# ---------------------------------------------------------------------------

def _rnd(x, compute):
    return x if compute == jnp.float32 else \
        x.astype(compute).astype(jnp.float32)


def _rmsnorm(x, w_minus_1, eps, compute):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return _rnd(x * (1.0 + w_minus_1.astype(jnp.float32)), compute)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv            # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, window, compute):
    """Causal attention, a block of ``Q_BLOCK`` queries at a time; keys
    more than ``window`` positions back are masked where window > 0."""
    t, h, hd = q.shape
    kpos = jnp.arange(t)
    nb = -(-t // Q_BLOCK)
    qb = jnp.pad(q, ((0, nb * Q_BLOCK - t), (0, 0), (0, 0))).reshape(
        nb, Q_BLOCK, h, hd)

    def block(args):
        qi, i = args
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qhe,khe->hqk", qi, k) / math.sqrt(hd)
        ok = kpos[None, :] <= qpos[:, None]
        if window:
            ok &= qpos[:, None] - kpos[None, :] < window
        s = jnp.where(ok[None], s, -jnp.inf)
        p = _rnd(jax.nn.softmax(s, axis=-1), compute)
        return jnp.einsum("hqk,khe->qhe", p, v)

    o = jax.lax.map(block, (qb, jnp.arange(nb)))
    return o.reshape(nb * Q_BLOCK, h, hd)[:t]


def _ffn(h, wg, wi, wo, compute):
    gate = _rnd(h @ wg, compute)
    up = _rnd(h @ wi, compute)
    return _rnd(_rnd(jax.nn.silu(gate) * up, compute) @ wo, compute)


@partial(jax.jit, static_argnames=("c_items", "sliding", "moe", "compute"))
def _layer(lp, x, c_items, sliding, moe, compute):
    c = dict(c_items)
    d, h, kvh, hd, v, n, nd = _dims(c)
    eps = c["rms_norm_eps"]
    w = jax.tree.map(lambda a: _rnd(a.astype(jnp.float32), compute), lp)
    t = x.shape[0]
    pos = jnp.arange(t, dtype=jnp.int32)
    a = _rmsnorm(x, lp["norm1"]["scale"], eps, compute)
    q = jnp.einsum("td,dhe->the", a, w["attn"]["wq"])
    k = jnp.einsum("td,dhe->the", a, w["attn"]["wk"])
    vv = _rnd(jnp.einsum("td,dhe->the", a, w["attn"]["wv"]), compute)
    q = _rmsnorm(_rnd(q, compute), lp["attn"]["q_norm"]["scale"], eps,
                 compute)
    k = _rmsnorm(_rnd(k, compute), lp["attn"]["k_norm"]["scale"], eps,
                 compute)
    if sliding:
        q = _rnd(_rope(q, pos, c["rope_theta"]), compute)
        k = _rnd(_rope(k, pos, c["rope_theta"]), compute)
    g = h // kvh
    o = _attend(q, jnp.repeat(k, g, axis=1), jnp.repeat(vv, g, axis=1),
                c["sliding_window"] if sliding else 0, compute)
    o = _rnd(o, compute)
    gate = jax.nn.sigmoid(_rnd(jnp.einsum("td,dhe->the", a,
                                          w["attn"]["wgate"]), compute))
    o = _rnd(o * gate, compute).reshape(t, h * hd)
    o = _rnd(o @ w["attn"]["wo"], compute)
    x = _rnd(x + _rmsnorm(o, lp["post_attn_norm"]["scale"], eps, compute),
             compute)
    hh = _rmsnorm(x, lp["norm2"]["scale"], eps, compute)
    if not moe:
        m = _ffn(hh, w["mlp"]["wg"], w["mlp"]["wi"], w["mlp"]["wo"], compute)
    else:
        p = w["moe"]
        s = jax.nn.sigmoid(hh @ p["router"])                # (T, R)
        _, top = jax.lax.top_k(s + p["bias"], c["num_experts_per_tok"])
        gs = jnp.take_along_axis(s, top, axis=-1)
        gs = gs / (gs.sum(-1, keepdims=True) + 1e-20) * c["route_scale"]
        m = _ffn(hh, p["shared"]["wg"], p["shared"]["wi"],
                 p["shared"]["wo"], compute)
        for e in range(c["num_experts"]):
            ge = jnp.sum(jnp.where(top == c["first_held"] + e, gs, 0.0),
                         axis=-1)
            m = m + ge[:, None] * _ffn(hh, p["wg"][e], p["wi"][e],
                                       p["wo"][e], compute)
        m = _rnd(m, compute)
    return _rnd(x + _rmsnorm(m, lp["post_mlp_norm"]["scale"], eps, compute),
                compute)


@partial(jax.jit, static_argnames=("c_items", "compute"))
def _embed(table, tokens, c_items, compute):
    c = dict(c_items)
    e = _rnd(table.astype(jnp.float32)[tokens], compute)
    return _rnd(e * math.sqrt(c["hidden_size"]), compute)


@partial(jax.jit, static_argnames=("c_items", "compute"))
def _head(table, final, x, at, c_items, compute):
    c = dict(c_items)
    x = _rmsnorm(x[at], final, c["rms_norm_eps"], compute)
    return x @ _rnd(table.astype(jnp.float32), compute).T


_MODEL_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
               "head_dim", "intermediate_size", "moe_intermediate_size",
               "vocab_size", "num_hidden_layers", "num_dense_layers",
               "num_experts", "num_experts_per_tok", "num_shared_experts",
               "route_scale", "rms_norm_eps", "rope_theta", "sliding_window")


def _items(c: dict) -> tuple:
    """The numbers the jitted pieces read, hashable: the model's keys and
    the held experts' first index."""
    return tuple((k, c[k]) for k in _MODEL_KEYS if k in c) + \
        (("first_held", c["deployment"]["first_held"]),)


def logits_at(weights, c: dict, tokens: np.ndarray, at: np.ndarray,
              compute=jnp.float32, pad_to: int = 512) -> np.ndarray:
    """Logits ``(len(at), vocab)`` at positions ``at`` of one sequence
    ``tokens``.  The sequence is right-padded to the power of two at or
    above its length, ``pad_to`` at least (causal: padding never reaches
    an earlier position), so a run's sequences share one or two lengths
    and each layer kind compiles once for each."""
    items = _items(c)
    t = len(tokens)
    tp = max(pad_to, 1 << (t - 1).bit_length())
    toks = np.zeros(tp, np.int32)
    toks[:t] = tokens
    n, nd = c["num_hidden_layers"], c["num_dense_layers"]
    with jax.default_matmul_precision("highest"):
        x = _embed(weights["embed"]["tok"], jnp.asarray(toks), items,
                   compute)
        for i in range(n):
            group, j = ("dense_layers", i) if i < nd else ("layers", i - nd)
            lp = jax.tree.map(lambda a: a[j], weights[group])
            x = _layer(lp, x, items,
                       c["layer_types"][i] == "sliding_attention", i >= nd,
                       compute)
        out = _head(weights["embed"]["head"],
                    weights["final_norm"]["scale"], x,
                    jnp.asarray(at, jnp.int32), items, compute)
    return np.asarray(out)


__all__ = ["weight_specs", "init_weights", "logits_at"]

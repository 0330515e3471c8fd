"""Plain reference of granite-3.0-2b as its cells run it, and the seeded
weights that the program and the reference are both given.

The layer, from the configuration file's keys (``x`` the residual
stream, ``w1``/``w2`` the norm weights, GQA heads grouped as the
published ``repeat_kv`` groups them):

    x  = E[tokens] * embedding_multiplier
    h  = rmsnorm(x) * w1                                 (eps rms_norm_eps)
    q, k, v = h Wq, h Wk, h Wv;  q, k = rope(q), rope(k) (rope_theta,
                                                          rotate-half)
    x += residual_multiplier * (softmax(q k^T * attention_multiplier
                                        + causal) v) Wo
    h  = rmsnorm(x) * w2
    x += residual_multiplier * (silu(h Wg) * (h Wi)) Wd
    logits = (rmsnorm(x) * wf) E^T / logits_scaling     (tied embeddings)

It imports nothing of the program and computes in float32 at
``highest`` matmul precision, layer by layer, one sequence at a time.
``compute`` rounds weights and activations to a narrower type where the
served model rounds to bfloat16: the control.

Weight layout (the program's parameter tree, which only the weights'
keys and shapes depend on): norm weights are stored as ``w - 1``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _dims(c: dict):
    d, h = c["hidden_size"], c["num_attention_heads"]
    return (d, h, c["num_key_value_heads"], c.get("head_dim") or d // h,
            c["intermediate_size"], c["vocab_size"], c["num_hidden_layers"])


def weight_specs(c: dict) -> dict:
    """``{path: (shape, std)}`` of every leaf; std 0 marks a norm weight
    (stored as ``w - 1``, drawn with std ``norm_std`` around 1).  The two
    output projections are drawn ``out_gain`` times wider than fan-in and
    the embedding ``embed_gain`` times as wide, so the residual stream is
    carried by what the layers add and not by the token's own embedding
    (see the configuration's ``weights``)."""
    d, h, kvh, hd, f, v, n = _dims(c)
    g = c["weights"]["out_gain"]
    return {
        ("embed", "tok"): ((v, d), c["weights"]["embed_gain"] / math.sqrt(d)),
        ("final_norm", "scale"): ((d,), 0.0),
        ("layers", "norm1", "scale"): ((n, d), 0.0),
        ("layers", "norm2", "scale"): ((n, d), 0.0),
        ("layers", "attn", "wq"): ((n, d, h, hd), 1 / math.sqrt(d)),
        ("layers", "attn", "wk"): ((n, d, kvh, hd), 1 / math.sqrt(d)),
        ("layers", "attn", "wv"): ((n, d, kvh, hd), 1 / math.sqrt(d)),
        ("layers", "attn", "wo"): ((n, h * hd, d), g / math.sqrt(h * hd)),
        ("layers", "mlp", "wi"): ((n, d, f), 1 / math.sqrt(d)),
        ("layers", "mlp", "wg"): ((n, d, f), 1 / math.sqrt(d)),
        ("layers", "mlp", "wo"): ((n, f, d), g / math.sqrt(f)),
    }


def init_weights(c: dict, words: list[int], device, dtype=jnp.bfloat16):
    """Every weight, drawn on ``device`` in one jitted call from the seed
    words, held in ``dtype`` (the type the cells serve them in)."""
    specs = weight_specs(c)

    def make(key):
        keys = jax.random.split(key, len(specs))
        tree: dict = {}
        for k, (path, (shape, std)) in zip(keys, specs.items()):
            x = jax.random.normal(k, shape, jnp.float32)
            x = x * (std if std else c["weights"]["norm_std"])
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


@partial(jax.jit, static_argnames=("c_items", "compute"))
def _layer(lp, x, c_items, compute):
    c = dict(c_items)
    d, h, kvh, hd, f, v, n = _dims(c)
    w = jax.tree.map(lambda a: _rnd(a.astype(jnp.float32), compute), lp)
    t = x.shape[0]
    pos = jnp.arange(t, dtype=jnp.int32)
    a = _rmsnorm(x, lp["norm1"]["scale"], c["rms_norm_eps"], compute)
    q = _rnd(_rope(jnp.einsum("td,dhe->the", a, w["attn"]["wq"]), pos,
                   c["rope_theta"]), compute)
    k = _rnd(_rope(jnp.einsum("td,dhe->the", a, w["attn"]["wk"]), pos,
                   c["rope_theta"]), compute)
    vv = _rnd(jnp.einsum("td,dhe->the", a, w["attn"]["wv"]), compute)
    g = h // kvh
    k = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(vv, g, axis=1)
    s = jnp.einsum("qhe,khe->hqk", q, k) * c["attention_multiplier"]
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = _rnd(jax.nn.softmax(s, axis=-1), compute)
    o = _rnd(jnp.einsum("hqk,khe->qhe", p, vv).reshape(t, h * hd), compute)
    x = _rnd(x + c["residual_multiplier"]
             * _rnd(o @ w["attn"]["wo"], compute), compute)
    a = _rmsnorm(x, lp["norm2"]["scale"], c["rms_norm_eps"], compute)
    gate = _rnd(a @ w["mlp"]["wg"], compute)
    up = _rnd(a @ w["mlp"]["wi"], compute)
    m = _rnd(jax.nn.silu(gate) * up, compute)
    return _rnd(x + c["residual_multiplier"]
                * _rnd(m @ w["mlp"]["wo"], compute), compute)


@partial(jax.jit, static_argnames=("c_items", "compute"))
def _embed(table, tokens, c_items, compute):
    c = dict(c_items)
    e = _rnd(table.astype(jnp.float32), compute)[tokens]
    return _rnd(e * c["embedding_multiplier"], compute)


@partial(jax.jit, static_argnames=("c_items", "compute"))
def _head(table, final, x, at, c_items, compute):
    c = dict(c_items)
    x = _rmsnorm(x[at], final, c["rms_norm_eps"], compute)
    e = _rnd(table.astype(jnp.float32), compute)
    return (x @ e.T) / c["logits_scaling"]


_MODEL_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
               "head_dim", "intermediate_size", "vocab_size",
               "num_hidden_layers", "rms_norm_eps", "rope_theta",
               "attention_multiplier", "residual_multiplier",
               "embedding_multiplier", "logits_scaling")


def logits_at(weights, c: dict, tokens: np.ndarray, at: np.ndarray,
              compute=jnp.float32, pad_to: int = 512) -> np.ndarray:
    """Logits ``(len(at), vocab)`` at positions ``at`` of one sequence
    ``tokens``.  The sequence is right-padded to a multiple of ``pad_to``
    (causal: padding never reaches an earlier position), so few lengths
    compile."""
    items = tuple((k, c[k]) for k in _MODEL_KEYS if k in c)
    t = len(tokens)
    tp = -(-t // pad_to) * pad_to
    toks = np.zeros(tp, np.int32)
    toks[:t] = tokens
    n = c["num_hidden_layers"]
    with jax.default_matmul_precision("highest"):
        x = _embed(weights["embed"]["tok"], jnp.asarray(toks), items,
                   compute)
        for i in range(n):
            lp = jax.tree.map(lambda a: a[i], weights["layers"])
            x = _layer(lp, x, items, compute)
        out = _head(weights["embed"]["tok"], weights["final_norm"]["scale"],
                    x, jnp.asarray(at, jnp.int32), items, compute)
    return np.asarray(out)


__all__ = ["weight_specs", "init_weights", "logits_at"]

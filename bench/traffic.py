"""The one traffic generator.  A mix is a data file (``traffic/<name>.json``)
of parameters; its ``kind`` says which of two shapes of work it describes:

* ``requests``: a closed backlog of generation requests.  Prompt and
  output lengths are lognormal (``median``, ``sigma``, clipped to at
  most ``max``).  Every ``block`` consecutive requests hold the same
  multiset of lengths, the block's ``(i + 0.5) / block`` quantiles, in
  the order and pairing that the mix's ``order_seed`` fixes: every seed
  gets the same work in the same order.  Tenants take turns; prompt
  tokens are uniform over the vocabulary and follow the run's seed.
* ``messages``: ``sets`` distinct sets of messages of ``message_bytes``
  random bytes per sending endpoint, cycled call by call.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def lognormal_quantiles(spec: dict, n: int) -> list[int]:
    """The ``(i + 0.5) / n`` quantiles of a clipped lognormal length."""
    nd = NormalDist()
    mu = math.log(spec["median"])
    out = []
    for i in range(n):
        v = math.exp(mu + spec["sigma"] * nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(v), 1), spec["max"])))
    return out


def requests(mix: dict, words: list[int], vocab: int) -> list[dict]:
    """The backlog: ``mix["backlog"]`` requests as dicts with ``prompt``
    (int32 array), ``max_new_tokens`` and ``tenant``."""
    if mix["kind"] != "requests":
        raise ValueError(f"mix kind {mix['kind']!r} is not 'requests'")
    block = mix["block"]
    plens = lognormal_quantiles(mix["prompt"], block)
    olens = lognormal_quantiles(mix["output"], block)
    tenants = mix["tenants"]
    rng = np.random.default_rng(words)
    order = np.random.default_rng(mix["order_seed"])
    out = []
    for b in range(-(-mix["backlog"] // block)):
        pp, po = order.permutation(block), order.permutation(block)
        for j in range(block):
            i = b * block + j
            out.append({"prompt": rng.integers(0, vocab, plens[pp[j]],
                                               dtype=np.int32),
                        "max_new_tokens": olens[po[j]],
                        "tenant": tenants[i % len(tenants)]})
    return out[:mix["backlog"]]


def messages(mix: dict, words: list[int], senders: int,
             per_call: int) -> np.ndarray:
    """``(sets, senders, per_call, message_bytes)`` uint8 payloads."""
    if mix["kind"] != "messages":
        raise ValueError(f"mix kind {mix['kind']!r} is not 'messages'")
    rng = np.random.default_rng(words)
    return rng.integers(0, 256, (mix["sets"], senders, per_call,
                                 mix["message_bytes"]), dtype=np.uint8)


__all__ = ["lognormal_quantiles", "requests", "messages"]

"""Pallas TPU paged decode attention (``paged_attention.py``): each slot's
query against its own blocks of the KV block pool, read through its
block table.  Its reference is ``layers/kvcache.py``'s ``kv_pool_gather``
followed by ``layers/attention.py``'s ``attend_naive``
(tests/test_paged_attention.py)."""

from repro.kernels.paged_attention.paged_attention import (
    STEP_POSITIONS,
    paged_decode_attention,
)

__all__ = ["paged_decode_attention", "STEP_POSITIONS"]

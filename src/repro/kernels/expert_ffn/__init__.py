"""Pallas TPU grouped gated FFN over the experts a chip holds
(``expert_ffn.py``): rows sorted by expert, each expert's weights read
once a call.  Its reference is ``expert_ffn_ref``, a plain grouped einsum
(tests/test_expert_share.py)."""

from repro.kernels.expert_ffn.expert_ffn import (
    expert_ffn,
    expert_ffn_ref,
    group_starts,
    num_tiles,
    tile_rows,
)

__all__ = ["expert_ffn", "expert_ffn_ref", "group_starts", "num_tiles",
           "tile_rows"]

from repro.kernels.block_sparse_attention.ops import (attention_tile_work,
                                                      block_sparse_attention)
from repro.kernels.block_sparse_attention.ref import (
    block_sparse_attention_ref)
from repro.kernels.block_sparse_attention.tiles import (
    AttentionTiles, choose_attention_tiles)

__all__ = ["AttentionTiles", "attention_tile_work", "block_sparse_attention",
           "block_sparse_attention_ref", "choose_attention_tiles"]

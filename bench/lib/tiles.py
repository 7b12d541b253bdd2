"""Operations and bytes of one (query block, key block) tile of the
block-sparse attention kernels, from the kernels' own code
(``repro/kernels/block_sparse_attention``), per head, and the attention
roofline that the system's live-tile count makes possible.

Each live tile of a bq x bk block pair at head size hd does, per head
(2 FLOPs a multiply-add):

  forward   q k^T and p v                          4 bq bk hd
  dq        q k^T again, dO v^T, dS k              6 bq bk hd
  dk/dv     q k^T again, dO v^T, p^T dO, dS^T q    8 bq bk hd

and reads at least the blocks that change along its grid's inner axis:
the forward and dq sweeps a key and a value block (2 bk hd), the dk/dv
sweep a query and an output-gradient block and two row columns, the
log-sum-exp and delta (2 bq hd + 2 bq).  Blocks held across the inner
sweep and the outputs are left out, so the bytes are a floor.

With ``remat="block"`` the forward kernel runs twice a step (the forward
and its recomputation in the backward); the backward kernels once each.
The system counts each layer's live tiles once per step
(``stats["attn_tiles"]``, summed by ``ElasticEngine.attn_tiles_total``).
"""
from __future__ import annotations

# block size of the kernels where no mask is given (``layers._kernel_mask``:
# min(kv_block, 128)); with a mask the kernels take the mask's block
DENSE_BLOCK = 128
# kernel -> (FLOPs / (bq bk hd), runs a step under remat="block")
KERNELS = {"forward": (4, 2), "dq": (6, 1), "dkv": (8, 1)}


def tile(kernel: str, block: int, head_dim: int, itemsize: int = 4):
    """(FLOPs, bytes) of one live tile of ``kernel`` for one head."""
    flops = KERNELS[kernel][0] * block * block * head_dim
    if kernel == "dkv":
        nbytes = itemsize * 2 * block * head_dim + 4 * 2 * block
    else:
        nbytes = itemsize * 2 * block * head_dim
    return float(flops), float(nbytes)


def block_of(cell) -> int:
    """The kernels' block size in a cell: the sparse mask's, else dense."""
    return cell.sparse["block"] if cell.sparse is not None else DENSE_BLOCK


def least_seconds(config: dict, tiles: int, block: int, peak: dict,
                  itemsize: int = 4) -> float:
    """Least device time of the attention kernels over ``tiles`` live
    tiles (the system's count: once per layer, sequence and step): every
    kernel run of every head of every tile at the larger of its FLOPs over
    the bf16 peak and its bytes over HBM bandwidth."""
    heads, hd = config["num_attention_heads"], config["head_dim"]
    t = 0.0
    for kernel, (_, runs) in KERNELS.items():
        flops, nbytes = tile(kernel, block, hd, itemsize)
        t += runs * max(flops / peak["bf16_flops_per_s"],
                        nbytes / peak["hbm_bytes_per_s"])
    return t * heads * tiles

"""Block-pruned matmul backward — built from the same Pallas kernel.

The backward of a block-pruned matmul is itself a block-pruned matmul with
the mask moved between the output slots ("m", "n") and the reduction slot
("k"):

  mask over N:  out = (x @ w) ⊙ m_N
      dx = (g ⊙ m_N) @ wᵀ   — m in the REDUCTION slot of a [M,N]@[N,K] GEMM
      dw = xᵀ @ (g ⊙ m_N)   — m stays in the output-column slot
  mask over K:  out = (x ⊙ m_K) @ w
      dx = (g @ wᵀ) ⊙ m_K   — m moves to the output-column slot
      dw = m_K ⊙ (xᵀ @ g)   — m in the output-row slot

Every transposed operand is read as stored, through the kernel's dot
dimension numbers (``x_t``, ``w_t``): no transpose is materialised.  All
four products run through ``pruned_matmul_p`` with tiles chosen for their
own shapes — pruned blocks skip the MXU tiles in the backward exactly as in
the forward, which is where the paper's per-layer backward compute
reduction (§2.2/§4.2.2) comes from.
"""
from __future__ import annotations

import numpy as np

from repro.kernels.pruned_matmul.pruned_matmul import (choose_tiles,
                                                       pruned_matmul_p)


def pruned_matmul_bwd_p(x, w, block_mask, g, *, mask_axis: str = "n",
                        mask_block: int = 128, interpret: bool = False):
    """dx, dw for out = pruned_matmul_p(x, w, mask).  x: [M, K]; w: [K, N];
    g: [M, N]; all dims pre-padded (ops.py)."""
    dx = pruned_matmul_p(g, w, block_mask, w_t=True,
                         mask_axis="k" if mask_axis == "n" else "n",
                         mask_block=mask_block, interpret=interpret)
    dw = pruned_matmul_p(x, g, block_mask, x_t=True,
                         mask_axis="n" if mask_axis == "n" else "m",
                         mask_block=mask_block, interpret=interpret)
    return dx.astype(x.dtype), dw.astype(w.dtype)


def _grid_steps(M, K, N, mask_axis, mask_block, itemsize):
    t = choose_tiles(M, K, N, mask_axis, mask_block, itemsize)
    return (M // t.bm) * (K // t.bk) * (N // t.bn)


def matmul_tile_work(M: int, K: int, N: int, block_mask, *,
                     mask_axis: str = "n", itemsize: int = 4):
    """MXU tile-work accounting mirroring the kernels' grids and pl.when
    gating, at the tiles ``choose_tiles`` gives each product (M, K, N as
    ops.py pads them).  A pruned block kills its share of every tile it
    gates (the whole tile, or its slice of a tile over several blocks);
    each product is gated by the same mask, so each keeps the same
    fraction.  Backward = dx product + dw product (pruned_matmul_bwd_p)."""
    mask = np.asarray(block_mask)
    keep = float((mask > 0).mean())
    block = (N if mask_axis == "n" else K) // mask.shape[0]
    fwd_total = _grid_steps(M, K, N, mask_axis, block, itemsize)
    if mask_axis == "n":
        bwd = (_grid_steps(M, N, K, "k", block, itemsize)
               + _grid_steps(K, M, N, "n", block, itemsize))
    else:
        bwd = (_grid_steps(M, N, K, "n", block, itemsize)
               + _grid_steps(K, M, N, "m", block, itemsize))
    return {
        "fwd_active": fwd_total * keep, "fwd_total": fwd_total,
        "bwd_active": bwd * keep, "bwd_total": bwd,
    }

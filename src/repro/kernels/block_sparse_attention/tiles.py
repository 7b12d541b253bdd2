"""Tiles of the attention kernels where no block mask fixes them.

A block mask fixes the kernels' tile to its own block.  Without one the
tile is free, and ``choose_attention_tiles`` takes it from the call's
shape: among (block_q, block_k) in ``CANDIDATES`` on each axis whose
blocks fit ``VMEM_BUDGET``, the one of least modelled time
(``attention_cost``) over the forward and both backward sweeps.

The model follows the kernels' grids.  Every grid step pays a fixed
cost, live or dead (a dead step is one ``tile_active`` rejects: above
the causal diagonal).  A dead step still fetches the blocks that change
along the inner axis; a live one also does its tile's work, taken as
proportional to the tile's area, padded rows and columns included (the
softmax's elementwise work and the matmuls both grow with it).  The
pipeline overlaps a step's fetch with its work.  So small tiles pay in
steps and large ones in the padding and the causal diagonal's dead
half.  The two constants solve the model for the attention kernels'
device time a step on a TPU v5e at 8192 tokens, hd 64, f32: 3.56 s at
128² tiles with no mask, 0.653 s at 512² tiles under a hash mask with 85
of 256 tiles live.
"""
from __future__ import annotations

import functools
import itertools
from typing import NamedTuple

CANDIDATES = (128, 256, 512, 1024)
# the 16 MiB of scoped VMEM a kernel gets by default, with room: inside
# the model's stacked layer scan XLA holds a kernel to that limit whatever
# vmem_limit_bytes it asks for (kernels/pruned_matmul)
VMEM_BUDGET = 15 << 20
# the backward's f32 [bq, bk] temporaries: scores, probabilities, dP, dS
TEMPORARIES = 4
LANES = 128
HBM_BYTES_PER_S = 819e9
STEP_S = 0.29e-6
ELEM_S = 7.0e-12          # a live tile's work, per element
KERNELS = ("forward", "dq", "dkv")


class AttentionTiles(NamedTuple):
    block_q: int
    block_k: int


def _lanes(width: int) -> int:
    """A row's width in VMEM and HBM: whole 128-lane vregs."""
    return -(-width // LANES) * LANES


def attention_grid(sq: int, sk: int, block_q: int, block_k: int,
                   causal: bool) -> tuple[int, int]:
    """(grid steps, live tiles) of one kernel sweep for one head: the
    tiles ``tile_active`` passes with no mask, as the kernels gate them."""
    nqb, nkb = -(-sq // block_q), -(-sk // block_k)
    if not causal:
        return nqb * nkb, nqb * nkb
    live = sum(min(nkb, (qi * block_q + block_q - 1) // block_k + 1)
               for qi in range(nqb))
    return nqb * nkb, live


def _inner_bytes(kernel: str, block_q: int, block_k: int, head_dim: int,
                 itemsize: int) -> int:
    """Bytes a grid step fetches along its kernel's inner axis: the key
    and value blocks (forward, dq), or the query, output-gradient,
    log-sum-exp and delta blocks (dk/dv)."""
    row = _lanes(head_dim) * itemsize
    if kernel == "dkv":
        return block_q * (2 * row + 2 * LANES * 4)
    return 2 * block_k * row


def attention_vmem(block_q: int, block_k: int, head_dim: int,
                   itemsize: int) -> int:
    """VMEM the most demanding kernel holds: its blocks twice (the
    pipeline's double buffers), its accumulators, and the backward's
    f32 [bq, bk] temporaries.  Rows take whole vregs; an [rows, 1]
    column takes 128 lanes."""
    row, row32, col = (_lanes(head_dim) * itemsize, _lanes(head_dim) * 4,
                       LANES * 4)
    q_side = block_q * (2 * row + 2 * col)        # q, dO, lse, delta
    k_side = 2 * block_k * row                    # k, v
    blocks = {"forward": block_q * (2 * row + col) + k_side,
              "dq": q_side + k_side + block_q * row,
              "dkv": q_side + k_side + 2 * block_k * row}
    scratch = {"forward": block_q * (row32 + 2 * col),
               "dq": block_q * row32, "dkv": 2 * block_k * row32}
    temps = TEMPORARIES * block_q * block_k * 4
    return max(2 * blocks[k] + scratch[k] for k in blocks) + temps


def attention_cost(sq: int, sk: int, head_dim: int, causal: bool,
                   itemsize: int, tiles: AttentionTiles) -> float:
    """Modelled seconds of the forward, dq and dk/dv sweeps for one head:
    a fixed cost a grid step; a dead step's fetch; a live step's fetch or
    its tile's work, whichever is longer (the pipeline overlaps them)."""
    bq, bk = tiles
    steps, live = attention_grid(sq, sk, bq, bk, causal)
    total = 0.0
    for kernel in KERNELS:
        fetch = _inner_bytes(kernel, bq, bk, head_dim,
                             itemsize) / HBM_BYTES_PER_S
        total += (steps * STEP_S + (steps - live) * fetch
                  + live * max(fetch, bq * bk * ELEM_S))
    return total


@functools.lru_cache(maxsize=256)
def choose_attention_tiles(sq: int, sk: int, head_dim: int, causal: bool,
                           itemsize: int = 4) -> AttentionTiles:
    """The kernels' (block_q, block_k) for an unmasked call over ``sq``
    queries and ``sk`` keys: of the candidates within ``VMEM_BUDGET``, the
    one of least ``attention_cost`` (to the picosecond, so that tilings
    the model prices alike tie), then the smaller tile, then the taller."""
    best = None
    for bq, bk in itertools.product(CANDIDATES, repeat=2):
        t = AttentionTiles(bq, bk)
        if attention_vmem(bq, bk, head_dim, itemsize) > VMEM_BUDGET:
            continue
        key = (round(attention_cost(sq, sk, head_dim, causal, itemsize, t),
                     12), bq * bk, -bq)
        if best is None or key < best[0]:
            best = (key, t)
    return best[1]

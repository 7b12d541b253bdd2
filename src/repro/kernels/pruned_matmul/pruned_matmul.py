"""Block-structured pruned matmul — Pallas TPU kernel.

TPU adaptation of Sputnik-style sparse matmul (paper §4.2.2): unstructured
CSR cannot accelerate the MXU's dense 128×128 tiles, so pruning removes
feature *blocks* (the mask block, a multiple of 128 on the chip) and the
kernel skips dead blocks with pl.when — zero MXU work for pruned tiles,
which is where the paper's per-layer compute reduction (p_i^(k)·c_i, §2.2)
physically comes from on TPU.

Three mask positions:
  * mask over N (output-column blocks): pruned output columns are zeros —
    the FFN up-projection x@W1;
  * mask over K (reduction blocks): pruned rows skip accumulation — the
    down-projection h@W2 (h's pruned columns are dead anyway);
  * mask over M (output-row blocks): pruned output rows are zeros — the
    weight gradient of a mask-over-K product (backward.py).

Tiles are chosen per call from the operand shapes and the mask block
(``choose_tiles``), not fixed: the tile on the masked axis divides the mask
block, so the kernel reads the mask at ``tile // (mask_block // tile)``, or
spans a whole number of blocks, each gated by its own entry; either way
skipping stays exact at the mask's granularity.  An unmasked axis takes a
multiple of 128 that divides it or, up to ``FULL_AXIS`` wide, its whole
extent (so a 960-wide axis needs no padding).  Among those the chooser
takes the tiling of least modelled time whose blocks fit the scoped VMEM,
with the grid order that re-streams the operands least.  Either operand
may be read transposed (``x_t``, ``w_t``) through the dot's dimension
numbers, so the backward needs no materialised transpose.

The mask rides as scalar prefetch (SMEM), as in paged_attention.
"""
from __future__ import annotations

import functools
import itertools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

FULL_AXIS = 1024          # an unmasked axis up to this wide may be one tile
MIN_SPLIT = 256           # a longer one is padded to a multiple of this
# double-buffered blocks + accumulator, under the 16 MiB of scoped VMEM a
# kernel gets by default: where XLA fuses the call into its consumer (the
# dynamic-update-slice of a stacked gradient), it holds the kernel to that
# limit whatever vmem_limit_bytes the kernel asks for
VMEM_BUDGET = 15 << 20
MAX_RESTREAM = 8          # HBM reads of one operand per call, at most
ORDERS = ("mnk", "nmk")
# the chip the cost is modelled on (TPU v5e): float32 operands at the
# dot's default precision (one bfloat16 pass) as this kernel reaches them
# at large tiles, HBM bandwidth, and the fixed cost of one grid step
MXU_FLOPS_PER_S = 1.15e14
HBM_BYTES_PER_S = 819e9
STEP_S = 0.35e-6


class Tiles(NamedTuple):
    """A tiling of one product: block sizes and grid order, outer to
    inner; the reduction axis k is always innermost."""
    bm: int
    bk: int
    bn: int
    order: str


class Cost(NamedTuple):
    """What a tiling costs: modelled seconds, HBM bytes moved, VMEM bytes
    held, grid steps, and the most times one operand is read."""
    seconds: float
    hbm: int
    vmem: int
    steps: int
    restream: int


def padded_extent(extent: int) -> int:
    """What an unmasked axis is padded to: unchanged when it may be one
    tile, else the next multiple of ``MIN_SPLIT``."""
    if extent <= FULL_AXIS:
        return extent
    return -(-extent // MIN_SPLIT) * MIN_SPLIT


def _axis_tiles(extent: int, mask_block: Optional[int]):
    if mask_block is not None:
        assert extent % mask_block == 0, (extent, mask_block)
        ts = [t for t in range(128, extent + 1, 128) if extent % t == 0
              and (mask_block % t == 0 or t % mask_block == 0)]
        return ts or [mask_block]
    assert extent <= FULL_AXIS or extent % MIN_SPLIT == 0, (
        "unpadded axis", extent)
    ts = [t for t in range(128, min(extent, FULL_AXIS) + 1, 128)
          if extent % t == 0]
    return ts + [extent] if extent <= FULL_AXIS and extent not in ts else ts


def restream(order: str, extents: dict, deps: str) -> int:
    """How many times an operand indexed by the axes ``deps`` is read from
    HBM over one call: its block is fetched again whenever its index
    changes, i.e. once per step of every axis out to the innermost one it
    depends on, so each axis before that one which it does not depend on
    multiplies its traffic."""
    live = [a for a in order if extents[a] > 1]
    inner = [i for i, a in enumerate(live) if a in deps]
    if not inner:
        return 1
    r = 1
    for a in live[:inner[-1]]:
        if a not in deps:
            r *= extents[a]
    return r


def tile_cost(M: int, K: int, N: int, t: Tiles, itemsize: int) -> Cost:
    """The cost model of a tiling.  Time is the larger of the MXU work and
    the HBM traffic (each operand counted as often as the grid order
    re-streams it), plus the bytes the pipeline cannot hide (the first
    blocks in, the last one out), plus a fixed cost per grid step.  VMEM
    holds every block twice, and an f32 accumulator unless the output is
    f32 (which accumulates in place)."""
    n = {"m": M // t.bm, "k": K // t.bk, "n": N // t.bn}
    rx = restream(t.order, n, "mk")
    rw = restream(t.order, n, "kn")
    hbm = itemsize * (M * K * rx + K * N * rw + M * N)
    blocks = itemsize * (t.bm * t.bk + t.bk * t.bn + t.bm * t.bn)
    acc = 0 if itemsize == 4 else 4 * t.bm * t.bn
    steps = n["m"] * n["k"] * n["n"]
    seconds = (max(2.0 * M * K * N / MXU_FLOPS_PER_S,
                   hbm / HBM_BYTES_PER_S)
               + blocks / HBM_BYTES_PER_S + steps * STEP_S)
    return Cost(seconds, hbm, 2 * blocks + acc, steps, max(rx, rw))


@functools.lru_cache(maxsize=256)
def choose_tiles(M: int, K: int, N: int, mask_axis: str, mask_block: int,
                 itemsize: int = 4) -> Tiles:
    """Tiles for ``[M,K] @ [K,N]`` with a block mask over ``mask_axis``
    (blocks of ``mask_block``): among the legal tilings whose blocks fit
    ``VMEM_BUDGET``, and that read no operand more than ``MAX_RESTREAM``
    times where any can, the one of least modelled time (``tile_cost``),
    then of least traffic and VMEM."""
    ext = {"m": M, "k": K, "n": N}
    cands = {a: _axis_tiles(ext[a], mask_block if a == mask_axis else None)
             for a in "mkn"}
    best = None
    for bm, bk, bn, order in itertools.product(
            cands["m"], cands["k"], cands["n"], ORDERS):
        t = Tiles(bm, bk, bn, order)
        c = tile_cost(M, K, N, t, itemsize)
        if c.vmem > VMEM_BUDGET:
            continue
        key = (c.restream > MAX_RESTREAM, c.seconds, c.hbm, c.vmem)
        if best is None or key < best[0]:
            best = (key, t)
    assert best is not None, ("no tiling fits VMEM", M, K, N, mask_block)
    return best[1]


def _at(axes: str, axis: str, part):
    """Index of a block whose axes are ``axes``: ``part`` on ``axis``."""
    return tuple(part if a == axis else slice(None) for a in axes)


def _kernel(mask_ref, x_ref, w_ref, o_ref, *scratch, order: str,
            mask_axis: str, per: int, sub: int, width: int, nk: int,
            x_t: bool, w_t: bool):
    """One (row-tile, col-tile, k-tile) cell; k innermost accumulates, in
    the output block itself when it is f32.  The flat block mask sits in
    SMEM (scalar prefetch).  A tile within one mask block reads the mask
    at its block; a tile over ``sub`` mask blocks adds each block's slice
    of the product (``width`` wide) under that block's own entry.  A
    dead block adds nothing, so dead output columns or rows stay the
    zeros they were set to."""
    acc_ref = scratch[0] if scratch else o_ref
    pid = {a: pl.program_id(p) for p, a in enumerate(order)}
    k = pid["k"]
    xa, wa = ("km" if x_t else "mk"), ("nk" if w_t else "kn")
    tile = pid[mask_axis]

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for s in range(sub):
        part = slice(None) if sub == 1 else pl.ds(s * width, width)
        live = mask_ref[tile // per if sub == 1 else tile * sub + s] > 0

        @pl.when(live)
        def _compute(part=part):
            acc_ref[_at("mn", mask_axis, part)] += jax.lax.dot_general(
                x_ref[_at(xa, mask_axis, part)].astype(jnp.float32),
                w_ref[_at(wa, mask_axis, part)].astype(jnp.float32),
                (((0 if x_t else 1,), (1 if w_t else 0,)), ((), ())),
                preferred_element_type=jnp.float32)

    if scratch:
        @pl.when(k == nk - 1)
        def _finish():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def pruned_matmul_p(x, w, block_mask, *, mask_axis: str = "n",
                    mask_block: int = 128, x_t: bool = False,
                    w_t: bool = False, interpret: bool = False,
                    _tiles: Optional[Tiles] = None):
    """out[M, N] = x·w with a 0/1 block mask.

    x is [M, K], or [K, M] read transposed (``x_t``); w is [K, N], or
    [N, K] read transposed (``w_t``).  ``block_mask`` has one entry per
    ``mask_block`` elements of the masked axis ('m', 'n' or 'k').  Tiles
    are ``choose_tiles``' (``_tiles`` overrides them, for tests); every
    axis must be a multiple of its tile (ops.py pads)."""
    M, K = x.shape[::-1] if x_t else x.shape
    N = w.shape[0] if w_t else w.shape[1]
    assert (w.shape[1] if w_t else w.shape[0]) == K, (x.shape, w.shape)
    tiles = _tiles or choose_tiles(M, K, N, mask_axis, mask_block,
                                   jnp.dtype(x.dtype).itemsize)
    bm, bk, bn, order = tiles
    b = {"m": bm, "k": bk, "n": bn}
    ext = {"m": M, "k": K, "n": N}
    assert order in ORDERS, order
    assert all(ext[a] % b[a] == 0 for a in "mkn"), (ext, b)
    bt = b[mask_axis]
    assert mask_block % bt == 0 or bt % mask_block == 0, (mask_block, bt)
    assert block_mask.shape == (ext[mask_axis] // mask_block,), (
        block_mask.shape, ext[mask_axis], mask_block)

    def spec(axes):
        def index(*ids):
            pid = dict(zip(order, ids[:3]))
            return tuple(pid[a] for a in axes)
        return pl.BlockSpec(tuple(b[a] for a in axes), index)

    f32_out = jnp.dtype(x.dtype) == jnp.float32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=tuple(ext[a] // b[a] for a in order),
        in_specs=[spec("km" if x_t else "mk"), spec("nk" if w_t else "kn")],
        out_specs=spec("mn"),
        scratch_shapes=[] if f32_out else [pltpu.VMEM((bm, bn),
                                                      jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, order=order, mask_axis=mask_axis,
                          per=max(1, mask_block // bt),
                          sub=max(1, bt // mask_block),
                          width=min(bt, mask_block), nk=K // bk,
                          x_t=x_t, w_t=w_t),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
    )(block_mask.astype(jnp.int32), x, w)

"""Block-structured pruned matmul — Pallas TPU kernel.

TPU adaptation of Sputnik-style sparse matmul (paper §4.2.2): unstructured
CSR cannot accelerate the MXU's dense 128×128 tiles, so pruning removes
feature *blocks* (width = MXU tile) and the kernel skips dead blocks with
pl.when — zero DMA, zero MXU work for pruned tiles, which is where the
paper's per-layer compute reduction (p_i^(k)·c_i, §2.2) physically comes
from on TPU.

Two mask positions:
  * mask over N (output-feature blocks): pruned output columns are zeros —
    used for the FFN up-projection x@W1;
  * mask over K (reduction blocks): pruned rows skip accumulation — used for
    the down-projection h@W2 (h's pruned columns are dead anyway).

The mask rides as scalar prefetch (SMEM), as in paged_attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(mask_ref, x_ref, w_ref, o_ref, acc_ref, *, nkb: int,
            mask_axis: str):
    """One (row-tile, col-tile, k-tile) cell; k innermost accumulates.  The
    flat block mask sits in SMEM (scalar prefetch) and is read at the
    column tile (mask over N) or the reduction tile (mask over K)."""
    j = pl.program_id(1)
    ki = pl.program_id(2)
    live = mask_ref[j if mask_axis == "n" else ki] > 0

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _compute():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == nkb - 1)
    def _finish():
        acc = acc_ref[...]
        if mask_axis == "n":
            acc = jnp.where(live, acc, 0.0)
        o_ref[...] = acc.astype(o_ref.dtype)


def pruned_matmul_p(x, w, block_mask, *, mask_axis: str = "n",
                    bm: int = 128, bn: int = 128, bk: int = 128,
                    interpret: bool = False):
    """x: [M, K] @ w: [K, N] with a 0/1 block mask.

    mask_axis='n': block_mask [N // bn]; pruned output-column blocks skipped.
    mask_axis='k': block_mask [K // bk]; pruned reduction blocks skipped.
    Shapes must be multiples of the block sizes (ops.py pads)."""
    M, K = x.shape
    _, N = w.shape
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (M, K, N)
    nkb = K // bk
    n_mask = N // bn if mask_axis == "n" else nkb
    assert block_mask.shape == (n_mask,), block_mask.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M // bm, N // bn, nkb),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k_, m: (i, k_)),
            pl.BlockSpec((bk, bn), lambda i, j, k_, m: (k_, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k_, m: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, nkb=nkb, mask_axis=mask_axis),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
    )(block_mask.astype(jnp.int32), x, w)

"""Pallas grouped/ragged expert matmul (MoE sort -> matmul -> unsort path).

Tokens are pre-sorted by (batch row, physical expert group) into a
``[G, cap, K]`` buffer (G = b * E groups, each zero-padded to ``cap`` rows);
``counts[g]`` is the number of live rows in group g.  The grid tiles
(group, row-tile, n-tile, k-tile) and a row tile whose first row is past the
group's count is **skipped entirely** (``pl.when`` on the count scalar —
data-dependent, no recompile when routing changes), so an empty expert costs
zero MXU tile work and a cold expert costs work proportional to its load,
not to the capacity bound — unlike the dense GShard capacity einsum which
pays full ``cap`` rows per expert unconditionally.

Group g uses weight ``w[g % E]``: groups are batch-major (g = bi * E + e)
so every batch row's expert-e tokens hit the same expert weights.

The counts ride as scalar prefetch (an int32 vector in SMEM, as in
paged_attention and pruned_matmul), so the gating scalar is read without a
VMEM block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scratch(shape):
    return pltpu.VMEM(shape, jnp.float32)


def _gm_kernel(c_ref, x_ref, w_ref, o_ref, acc_ref, *, nkb, bm):
    """One (group, row-tile, n-tile, k-tile) cell; k innermost accumulates."""
    g = pl.program_id(0)
    i = pl.program_id(1)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # live-row tile test: rows are packed front-of-group, so a tile whose
    # first row index reaches the count holds no live rows at all
    @pl.when(i * bm < c_ref[g])
    def _compute():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nkb - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def grouped_matmul_p(x, w, counts, *, gpb: int, bm: int, bn: int, bk: int,
                     interpret: bool = False):
    """x: [G*cap, K] row-sorted groups (cap = gpb*bm rows each, dead rows
    zero), w: [E, K, N] with G % E == 0, counts: [G] (any numeric dtype;
    read as int32).  Returns [G*cap, N].  K/N must be block multiples (pad
    outside)."""
    M, K = x.shape
    E, _, N = w.shape
    G = M // (gpb * bm)
    assert M == G * gpb * bm and G % E == 0, (M, G, gpb, bm, E)
    assert K % bk == 0 and N % bn == 0, (K, bk, N, bn)
    nkb = K // bk
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, gpb, N // bn, nkb),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda g, i, j, k, c: (g * gpb + i, k)),
            pl.BlockSpec((1, bk, bn), lambda g, i, j, k, c: (g % E, k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn),
                               lambda g, i, j, k, c: (g * gpb + i, j)),
        scratch_shapes=[_scratch((bm, bn))],
    )
    return pl.pallas_call(
        functools.partial(_gm_kernel, nkb=nkb, bm=bm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
    )(counts.astype(jnp.int32), x, w)


def _gm_dw_kernel(c_ref, x_ref, g_ref, o_ref, acc_ref, *, nrb, bm, gpb,
                  num_experts):
    """dw[e] = sum over batch groups of x_{b,e}^T @ g_{b,e}; the row-chunk
    axis r (innermost) walks every (batch, row-tile) pair of expert e."""
    e = pl.program_id(0)
    r = pl.program_id(3)

    @pl.when(r == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((r % gpb) * bm < c_ref[(r // gpb) * num_experts + e])
    def _compute():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(r == nrb - 1)
    def _finish():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def grouped_matmul_dw_p(x, g, counts, *, num_experts: int, gpb: int,
                        bm: int, bn: int, bk: int, interpret: bool = False):
    """x: [G*cap, K], g: [G*cap, N] (dead rows zero in both), counts: [G].
    Returns dw [E, K, N] summing each expert's groups across batch rows —
    the same ragged tile skipping as the forward, transposed."""
    M, K = x.shape
    _, N = g.shape
    E = num_experts
    G = M // (gpb * bm)
    assert G % E == 0, (G, E)
    nrb = (G // E) * gpb
    row = lambda e, r: ((r // gpb) * E + e) * gpb + (r % gpb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(E, K // bk, N // bn, nrb),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda e, kk, j, r, c: (row(e, r), kk)),
            pl.BlockSpec((bm, bn), lambda e, kk, j, r, c: (row(e, r), j)),
        ],
        out_specs=pl.BlockSpec((1, bk, bn),
                               lambda e, kk, j, r, c: (e, kk, j)),
        scratch_shapes=[_scratch((bk, bn))],
    )
    return pl.pallas_call(
        functools.partial(_gm_dw_kernel, nrb=nrb, bm=bm, gpb=gpb,
                          num_experts=E),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((E, K, N), jnp.float32),
        interpret=interpret,
    )(counts.astype(jnp.int32), x, g)

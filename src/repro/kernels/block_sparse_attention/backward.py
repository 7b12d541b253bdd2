"""Block-sparse FlashAttention backward — Pallas TPU kernels.

Recompute-from-lse flash backward (no stored probability matrices): each
tile rebuilds p = exp(q·kᵀ·scale − lse) from the forward's log-sum-exp and
applies the standard dq/dk/dv recurrences.  Both kernels reuse the forward's
block mask, so dead (q-block × kv-block) tiles skip the MXU work in the
backward exactly as in the forward — per-layer backward compute shrinks
proportionally with mask density (paper §2.2 / §4.2.4).

Two sweeps:
  * dq kernel:  grid (BH, q_blocks, kv_blocks), kv innermost — dq[qi] sums
    over the active kv blocks of row qi;
  * dk/dv kernel: grid (BH, kv_blocks, q_blocks), q innermost — dk/dv[ki]
    sum over the active q blocks of column ki.

``delta`` = rowsum(dout ⊙ out) is a cheap elementwise reduction computed in
plain jnp by the vjp wrapper (ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.block_sparse_attention.block_sparse_attention import (
    NEG_INF, mask_index, tile_active, tile_scores)


def _tile_p_ds(q, k, v, do, lse, delta, *, qi, ki, sm_scale, causal,
               block_q, block_k, kv_len, sk_pad):
    """Shared per-tile recompute: returns (p, ds) [bq, bk] in float32.
    ``lse`` and ``delta`` are [bq, 1] columns."""
    s = tile_scores(q, k, qi, ki, sm_scale=sm_scale, causal=causal,
                    block_q=block_q, block_k=block_k, kv_len=kv_len,
                    sk_pad=sk_pad)                          # [bq, bk]
    p = jnp.exp(s - lse)
    # masked entries: s=NEG_INF ⇒ p→0 when lse is finite; fully-masked rows
    # have lse≈NEG_INF (sentinel) which would make p spuriously 1 — zero them
    p = jnp.where(lse <= NEG_INF / 4, 0.0, p)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                 # [bq, bk]
    ds = p * (dp - delta) * sm_scale
    return p, ds


def _dq_kernel(mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, acc_ref, *, nqb: int, nkb: int, sm_scale: float,
               causal: bool, block_q: int, block_k: int, kv_len: int):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    sk_pad = nkb * block_k

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    active = tile_active(mask_ref[mask_index(b, qi, ki, nqb=nqb, nkb=nkb)],
                         qi, ki, causal=causal,
                         block_q=block_q, block_k=block_k, kv_len=kv_len,
                         sk_pad=sk_pad)

    @pl.when(active)
    def _compute():
        k = k_ref[0].astype(jnp.float32)
        _, ds = _tile_p_ds(
            q_ref[0].astype(jnp.float32), k, v_ref[0].astype(jnp.float32),
            do_ref[0].astype(jnp.float32), lse_ref[0], delta_ref[0],
            qi=qi, ki=ki, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, kv_len=kv_len, sk_pad=sk_pad)
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nkb - 1)
    def _finish():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, nqb: int, nkb: int,
                sm_scale: float, causal: bool, block_q: int, block_k: int,
                kv_len: int):
    b = pl.program_id(0)
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    sk_pad = nkb * block_k

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    active = tile_active(mask_ref[mask_index(b, qi, ki, nqb=nqb, nkb=nkb)],
                         qi, ki, causal=causal,
                         block_q=block_q, block_k=block_k, kv_len=kv_len,
                         sk_pad=sk_pad)

    @pl.when(active)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        p, ds = _tile_p_ds(
            q, k_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
            do, lse_ref[0], delta_ref[0],
            qi=qi, ki=ki, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, kv_len=kv_len, sk_pad=sk_pad)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bk, d]
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bk, d]

    @pl.when(qi == nqb - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def block_sparse_attention_bwd_p(q, k, v, block_mask, dout, lse, delta, *,
                                 causal: bool = True, block_q: int = 128,
                                 block_k: int = 128,
                                 sm_scale: float | None = None,
                                 kv_len: int | None = None,
                                 interpret: bool = False):
    """Flash backward over pre-padded flat inputs.

    q, dout: [BH, sq, d]; k, v: [BH, sk, d]; block_mask: [BH, nqb, nkb];
    lse, delta: [BH, sq, 1] float32.  Returns (dq, dk, dv) in the input
    dtypes.
    """
    BH, sq, d = q.shape
    sk = k.shape[1]
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk)
    nqb, nkb = sq // block_q, sk // block_k
    assert block_mask.shape == (BH, nqb, nkb), block_mask.shape
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    if kv_len is None:
        kv_len = sk
    flat_mask = block_mask.reshape(-1)
    statics = dict(nqb=nqb, nkb=nkb, sm_scale=sm_scale, causal=causal,
                   block_q=block_q, block_k=block_k, kv_len=kv_len)

    q_spec_q = pl.BlockSpec((1, block_q, d), lambda b, qi, ki, m: (b, qi, 0))
    k_spec_q = pl.BlockSpec((1, block_k, d), lambda b, qi, ki, m: (b, ki, 0))
    row_spec_q = pl.BlockSpec((1, block_q, 1),
                              lambda b, qi, ki, m: (b, qi, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **statics),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, nqb, nkb),
            in_specs=[q_spec_q, k_spec_q, k_spec_q, q_spec_q, row_spec_q,
                      row_spec_q],
            out_specs=q_spec_q,
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((BH, sq, d), q.dtype),
        interpret=interpret,
    )(flat_mask, q, k, v, dout, lse, delta)

    # kv sweep: grid order (BH, kv_blocks, q_blocks), q innermost
    q_spec_k = pl.BlockSpec((1, block_q, d), lambda b, ki, qi, m: (b, qi, 0))
    k_spec_k = pl.BlockSpec((1, block_k, d), lambda b, ki, qi, m: (b, ki, 0))
    row_spec_k = pl.BlockSpec((1, block_q, 1),
                              lambda b, ki, qi, m: (b, qi, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **statics),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, nkb, nqb),
            in_specs=[q_spec_k, k_spec_k, k_spec_k, q_spec_k, row_spec_k,
                      row_spec_k],
            out_specs=[k_spec_k, k_spec_k],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((BH, sk, d), k.dtype),
            jax.ShapeDtypeStruct((BH, sk, d), v.dtype),
        ],
        interpret=interpret,
    )(flat_mask, q, k, v, dout, lse, delta)
    return dq, dk, dv

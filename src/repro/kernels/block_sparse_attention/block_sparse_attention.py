"""Hash-based block-sparse FlashAttention — Pallas TPU kernel (forward).

TPU adaptation of the paper's dynamic sparse flash attention (§4.2.4): the
hash-derived block mask gates whole (q-block × kv-block) tiles; masked tiles
skip the MXU work via pl.when (the grid slot still iterates, but no DMA
compute is issued — on TPU the saved time is the tile's matmul+softmax).

Tiling: grid = (batch·heads, q_blocks, kv_blocks), kv innermost so the
online-softmax accumulator lives in VMEM scratch across the kv sweep.
Block shapes default to (128, 128) — MXU-aligned; a mask fixes them to its
block, and where there is none ``tiles.choose_attention_tiles`` takes them
from the call's shape (models/layers.py).  The block mask rides as
scalar prefetch (a flat int32 vector in SMEM, like paged_attention's page
table), and per-row statistics are ``[rows, 1]`` columns, so every VMEM
block has a shape the TPU lowering accepts.

The forward emits the per-row log-sum-exp alongside the output so the
backward kernels (backward.py) can recompute probabilities tile-by-tile
from (q, k, lse) instead of storing them — the standard flash backward.
``kv_len`` (static) masks key columns beyond the unpadded sequence length,
so ops.py can zero-pad kv to a block multiple without attending garbage in
the non-causal / non-square case.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def tile_active(mask_val, qi, ki, *, causal: bool, block_q: int,
                block_k: int, kv_len: int, sk_pad: int):
    """The pl.when tile-gating predicate SHARED by the forward and both
    backward sweeps (backward.py) — these must stay in lockstep, or a tile
    skipped in one direction gets computed in the other and gradients
    silently diverge."""
    active = mask_val > 0
    if causal:
        # whole block above the diagonal band is dead regardless of the mask
        active = jnp.logical_and(
            active, ki * block_k <= qi * block_q + (block_q - 1))
    if kv_len < sk_pad:
        # kv padding exists: blocks fully beyond kv_len are dead
        active = jnp.logical_and(active, ki * block_k < kv_len)
    return active


def tile_scores(q, k, qi, ki, *, sm_scale: float, causal: bool,
                block_q: int, block_k: int, kv_len: int, sk_pad: int):
    """Masked score tile [bq, bk] in fp32 — shared by forward and backward
    (token-level causal + exact padded-kv column masking)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    cols = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    if causal:
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        s = jnp.where(rows >= cols, s, NEG_INF)
    if kv_len < sk_pad:
        # padded kv tail: mask token columns exactly (only the last block
        # has cols >= kv_len; elementwise where is cheap)
        s = jnp.where(cols < kv_len, s, NEG_INF)
    return s


def mask_index(b, qi, ki, *, nqb: int, nkb: int):
    """Position of tile (b, qi, ki) in the flat ``[BH * nqb * nkb]`` mask."""
    return (b * nqb + qi) * nkb + ki


def _kernel(mask_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
            l_ref, *, nqb: int, nkb: int, sm_scale: float, causal: bool,
            block_q: int, block_k: int, kv_len: int):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    sk_pad = nkb * block_k

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    active = tile_active(mask_ref[mask_index(b, qi, ki, nqb=nqb, nkb=nkb)],
                         qi, ki, causal=causal,
                         block_q=block_q, block_k=block_k, kv_len=kv_len,
                         sk_pad=sk_pad)

    @pl.when(active)
    def _compute():
        q = q_ref[0].astype(jnp.float32)           # [bq, d]
        k = k_ref[0].astype(jnp.float32)           # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        s = tile_scores(q, k, qi, ki, sm_scale=sm_scale, causal=causal,
                        block_q=block_q, block_k=block_k, kv_len=kv_len,
                        sk_pad=sk_pad)             # [bq, bk]
        m_prev = m_ref[...]                        # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        # a row with NO live entry so far has m_new == NEG_INF, making
        # p = exp(0) = 1 for its all-masked columns (e.g. block_q > block_k
        # tiles entirely above the diagonal band) — zero it so l stays 0
        p = jnp.where(m_new <= NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * corr
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ki == nkb - 1)
    def _finish():
        l = l_ref[...]
        out = acc_ref[...] / jnp.maximum(l, 1e-30)
        # fully-masked rows (l == 0) emit zeros
        out = jnp.where(l > 0, out, 0.0)
        o_ref[0] = out.astype(o_ref.dtype)
        # lse of fully-masked rows stays ~NEG_INF: the backward zeroes their
        # probabilities off that sentinel (zero, not NaN, gradients)
        lse_ref[0] = m_ref[...] + jnp.log(jnp.maximum(l, 1e-30))


def block_sparse_attention_p(q, k, v, block_mask, *, causal: bool = True,
                             block_q: int = 128, block_k: int = 128,
                             sm_scale: float | None = None,
                             kv_len: int | None = None,
                             interpret: bool = False):
    """q: [BH, sq, d]; k, v: [BH, sk, d]; block_mask: [BH, nqb, nkb] int32.

    Shapes must be pre-padded to block multiples (ops.py handles that);
    ``kv_len`` is the unpadded key length (defaults to sk = no padding).
    Returns (out [BH, sq, d], lse [BH, sq, 1] float32)."""
    BH, sq, d = q.shape
    sk = k.shape[1]
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk)
    nqb, nkb = sq // block_q, sk // block_k
    assert block_mask.shape == (BH, nqb, nkb), block_mask.shape
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    if kv_len is None:
        kv_len = sk

    kernel = functools.partial(
        _kernel, nqb=nqb, nkb=nkb, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, kv_len=kv_len)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, nqb, nkb),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki, m: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki, m: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki, m: (b, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki, m: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, qi, ki, m: (b, qi, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BH, sq, d), q.dtype),
            jax.ShapeDtypeStruct((BH, sq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(block_mask.reshape(-1), q, k, v)

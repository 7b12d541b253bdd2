"""Primitive layers shared by all architectures.

Everything is a pure function of (params, inputs).  Attention defaults to a
scan-based online-softmax implementation ("xla flash") so 32k+ contexts never
materialise the full score matrix — this is also the pure-jnp oracle that the
Pallas kernels are validated against.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import kernels

NEG_INF = -1e30
KERNEL_IMPLS = ("reference", "scan", "pallas")


def expand_ff_mask(ff_mask: jax.Array, dim: int) -> jax.Array:
    """Block-level [n_blocks] -> feature-level [dim] pruning mask (no-op if
    already expanded).  Single home for the expansion rule — swiglu,
    gelu_mlp and blocks.py all share it."""
    if ff_mask.shape[0] != dim:
        ff_mask = jnp.repeat(ff_mask, dim // ff_mask.shape[0])
    return ff_mask


def pin_batch(x: jax.Array) -> jax.Array:
    """Constrain dim 0 (batch) to shard over the DP mesh axes.

    XLA's auto propagation inside the pipeline's remat+scan bodies sometimes
    replicates large activations (its involuntary-full-rematerialization
    fallback); pinning the batch dim of block-internal tensors keeps the
    per-tick working set 1/dp-sized.  No-op outside a mesh context or when
    the batch dim is not divisible."""
    am = jax.sharding.get_abstract_mesh()
    if not am.axis_names:
        return x
    daxes = tuple(a for a in am.axis_names
                  if a != "model" and am.shape[a] > 1)
    if not daxes:
        return x
    dp = 1
    for a in daxes:
        dp *= am.shape[a]
    if x.ndim < 1 or x.shape[0] % dp or x.shape[0] < dp:
        return x
    spec = jax.sharding.PartitionSpec(
        daxes if len(daxes) > 1 else daxes[0], *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(am, spec))


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(dtype)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., seq, heads, head_dim]; positions: [..., seq]."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)                       # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., seq, hd/2]
    angles = angles[..., None, :]                              # broadcast heads
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def swiglu(x: jax.Array, wi: jax.Array, wg: jax.Array, wo: jax.Array,
           ff_mask: Optional[jax.Array] = None, *, impl: str = "scan",
           interpret: Optional[bool] = None) -> jax.Array:
    """SwiGLU MLP.  ``ff_mask`` zeroes pruned feature blocks (block-
    structured pruning) — either block-level [n_blocks] or expanded [d_ff].

    ``impl="pallas"`` routes through the fused block-pruned Pallas SwiGLU
    (kernels.pruned_matmul): pruned blocks skip MXU tiles in forward AND
    backward.  The pallas path needs the block-level mask (granularity =
    d_ff // n_blocks); the dense paths accept either and expand.  Single-
    token calls (decode) stay dense — a 1-row kernel call wastes the MXU,
    mirroring the decode_attention special case."""
    assert impl in KERNEL_IMPLS, impl
    d_ff = wi.shape[1]
    if impl == "pallas" and x.shape[-2] > 1:
        from repro.kernels.pruned_matmul import pruned_swiglu
        if ff_mask is None:
            bmask = jnp.ones((1,), jnp.float32)
        else:
            nb = ff_mask.shape[0]
            # an expanded [d_ff] mask would pass divisibility with blocks
            # of 1 — width-1 blocks defeat the MXU tiling; demand
            # block-level
            assert nb < d_ff and d_ff % nb == 0, (
                "pallas swiglu needs a block-level ff_mask",
                ff_mask.shape, d_ff)
            bmask = ff_mask
        if interpret is None:
            interpret = kernels.use_interpret()
        return pin_batch(pruned_swiglu(x, wi, wg, wo, bmask,
                                       interpret=interpret))
    h = pin_batch(jax.nn.silu(x @ wg) * (x @ wi))
    if ff_mask is not None:
        h = h * expand_ff_mask(ff_mask, d_ff).astype(h.dtype)
    return pin_batch(h @ wo)


def gelu_mlp(x: jax.Array, w1: jax.Array, b1: jax.Array, w2: jax.Array,
             b2: jax.Array, ff_mask: Optional[jax.Array] = None, *,
             impl: str = "scan",
             interpret: Optional[bool] = None) -> jax.Array:
    """Biased GELU MLP (whisper enc/dec FFN) with block-structured pruning.

    Same dispatch contract as ``swiglu``: the dense impls accept a
    block-level or expanded ``ff_mask``; ``impl="pallas"`` needs the
    block-level mask and runs both matmuls through the pruned Pallas kernel
    (mask over "n" for the up-projection, over "k" for the down-projection).
    The bias lands after the pruned up-projection and pruned columns are
    re-zeroed before GELU's output enters the down-projection, so kept
    columns match the dense path exactly."""
    assert impl in KERNEL_IMPLS, impl
    d_ff = w1.shape[1]
    if impl == "pallas" and x.shape[-2] > 1:
        from repro.kernels.pruned_matmul import pruned_matmul
        bmask = (jnp.ones((1,), jnp.float32) if ff_mask is None
                 else ff_mask)
        nb = bmask.shape[0]
        assert nb < d_ff and d_ff % nb == 0, (
            "pallas gelu_mlp needs a block-level ff_mask", bmask.shape,
            d_ff)
        bf = d_ff // nb
        if interpret is None:
            interpret = kernels.use_interpret()
        h = pruned_matmul(x, w1, bmask, mask_axis="n",
                          interpret=interpret) + b1
        h = jax.nn.gelu(h) * jnp.repeat(bmask, bf).astype(x.dtype)
        return pruned_matmul(h, w2, bmask, mask_axis="k",
                             interpret=interpret) + b2
    h = jax.nn.gelu(x @ w1 + b1)
    if ff_mask is not None:
        h = h * expand_ff_mask(ff_mask, d_ff).astype(x.dtype)
    return h @ w2 + b2


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------
def _repeat_kv(k: jax.Array, num_q_heads: int) -> jax.Array:
    """[b, s, kv, d] -> [b, s, q, d] by repeating groups."""
    b, s, kv, d = k.shape
    rep = num_q_heads // kv
    return jnp.repeat(k, rep, axis=2)


def attention_reference(q, k, v, *, causal: bool, sliding_window: int = 0,
                        q_offset: int = 0,
                        block_mask: Optional[jax.Array] = None,
                        positions_q: Optional[jax.Array] = None,
                        positions_kv: Optional[jax.Array] = None,
                        block_size: int = 128) -> jax.Array:
    """Naive O(s^2) attention; oracle for tests.  q:[b,sq,h,d] k,v:[b,sk,kv,d].
    ``block_mask`` [h, sq//bs, sk//bs] enables hash-based block sparsity."""
    b, sq, h, d = q.shape
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(d).astype(jnp.float32)
    pq = (jnp.arange(sq) + q_offset if positions_q is None
          else positions_q)
    pk = jnp.arange(k.shape[1]) if positions_kv is None else positions_kv
    if causal:
        scores = jnp.where(pq[:, None] >= pk[None, :], scores, NEG_INF)
    if sliding_window:
        scores = jnp.where(pq[:, None] - pk[None, :] < sliding_window,
                           scores, NEG_INF)
    if block_mask is not None:
        bs = block_size
        bm = block_mask if block_mask.ndim == 4 else block_mask[None]
        m = jnp.repeat(jnp.repeat(bm, bs, axis=-2), bs, axis=-1)
        sk = k.shape[1]
        if m.shape[-2] < sq or m.shape[-1] < sk:
            # trailing partial blocks reuse the last mask row/col (matches
            # the flash paths' clipped block-id gather)
            m = jnp.pad(m, ((0, 0), (0, 0),
                            (0, max(0, sq - m.shape[-2])),
                            (0, max(0, sk - m.shape[-1]))), mode="edge")
        scores = jnp.where(m[..., :sq, :sk] > 0, scores, NEG_INF)
    # guard fully-masked rows
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.max(scores, -1, keepdims=True) <= NEG_INF / 2,
                      0.0, probs)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def flash_attention(q, k, v, *, causal: bool, sliding_window: int = 0,
                    q_offset: int = 0,
                    block_mask: Optional[jax.Array] = None,
                    kv_block: int = 512, impl: str = "scan",
                    interpret: Optional[bool] = None) -> jax.Array:
    """Flash attention with a FLASH BACKWARD (custom VJP): the backward
    recomputes scores block-by-block from (q, k, v, out, lse) instead of
    storing per-block probability matrices — without this, differentiating
    the forward scan materialises the full O(sq·sk) score tensor per layer
    per slot (measured: the dominant memory term of every attention cell).

    ``impl`` selects the inner implementation (DistConfig.kernel_impl):
      * "reference" — the O(s^2) dense oracle;
      * "scan"      — the pure-JAX online-softmax scan (this module);
      * "pallas"    — the block-skipping Pallas kernels with the Pallas
        flash backward (kernels.block_sparse_attention); masked tiles do
        no MXU work in forward or backward.  Sliding-window / offset
        queries aren't expressible as block masks — those fall back to
        the scan (see DESIGN.md).
    """
    assert impl in KERNEL_IMPLS, impl
    if impl == "pallas" and sliding_window == 0 and q_offset == 0:
        return _pallas_attention(q, k, v, block_mask, causal, kv_block,
                                 interpret)
    if impl == "reference":
        return attention_reference(
            q, k, v, causal=causal, sliding_window=sliding_window,
            q_offset=q_offset, block_mask=block_mask, block_size=kv_block)
    out, _ = _flash_vjp(q, k, v, block_mask, causal, sliding_window,
                        q_offset, kv_block)
    return out


def _kernel_mask(block_mask, sq, sk, kv_block, *, causal, head_dim,
                 itemsize):
    """The Pallas kernels' tiles, block counts and mask for a call:
    (block_q, block_k, nqb, nkb, mask [b|1, h|1, nqb, nkb] or None for
    all tiles).

    A mask fixes the tiles to its block (``kv_block``); without one they
    follow the call's shape (``choose_attention_tiles``).  Accepts the
    model's mask layouts ([h, nqb, nkb] or [b, h|1, nqb, nkb]) and
    edge-extends them to the kernels' block counts."""
    if block_mask is None:
        from repro.kernels.block_sparse_attention import (
            choose_attention_tiles)
        bq, bk = choose_attention_tiles(sq, sk, head_dim, causal, itemsize)
        return bq, bk, -(-sq // bq), -(-sk // bk), None
    nqb = -(-sq // kv_block)
    nkb = -(-sk // kv_block)
    bm = block_mask if block_mask.ndim == 4 else block_mask[None]
    # trailing partial blocks reuse the last mask row/col (the scan path's
    # qb_ids gather clips the same way)
    qb = jnp.clip(jnp.arange(nqb), 0, bm.shape[2] - 1)
    kb = jnp.clip(jnp.arange(nkb), 0, bm.shape[3] - 1)
    return kv_block, kv_block, nqb, nkb, bm[:, :, qb][:, :, :, kb]


def _pallas_attention(q, k, v, block_mask, causal, kv_block,
                      interpret=None):
    """Route through the Pallas block-sparse kernel (dense = all-ones mask)
    with the mask broadcast to the kernel's [b, hq, nqb, nkb]."""
    from repro.kernels.block_sparse_attention import block_sparse_attention
    b, sq, hq, hd = q.shape
    bq, bk, nqb, nkb, bm = _kernel_mask(
        block_mask, sq, k.shape[1], kv_block, causal=causal, head_dim=hd,
        itemsize=q.dtype.itemsize)
    if bm is None:
        bm = jnp.ones((b, hq, nqb, nkb), jnp.float32)
    else:
        bm = jnp.broadcast_to(bm, (b, hq, nqb, nkb)).astype(jnp.float32)
    if interpret is None:
        interpret = kernels.use_interpret()
    return block_sparse_attention(q, k, v, bm, causal=causal, block_q=bq,
                                  block_k=bk, interpret=interpret)


def attention_tiles(block_mask, b: int, sq: int, sk: int, *, causal: bool,
                    head_dim: int, itemsize: int, kv_block: int = 512,
                    sliding_window: int = 0,
                    impl: str = "scan") -> jax.Array:
    """(query block, key block) tiles the Pallas kernels compute for one
    ``flash_attention`` call over ``b`` sequences, per head (heads share
    the mask): the tiles that pass the kernels' own gate (``tile_active``)
    on the mask they receive, at their tiles (``_kernel_mask``: the
    query's ``head_dim`` and ``itemsize`` choose them where no mask
    does).  Each tile is computed once by the forward kernel and once by
    each backward kernel.  0.0 where the call does not run the Pallas
    kernels."""
    if impl != "pallas" or sliding_window:
        return jnp.float32(0.0)
    from repro.kernels.block_sparse_attention.block_sparse_attention import (
        tile_active)
    bq, bk, nqb, nkb, bm = _kernel_mask(block_mask, sq, sk, kv_block,
                                        causal=causal, head_dim=head_dim,
                                        itemsize=itemsize)
    live = tile_active(jnp.int32(1) if bm is None else bm.astype(jnp.int32),
                       jnp.arange(nqb)[:, None], jnp.arange(nkb)[None, :],
                       causal=causal, block_q=bq, block_k=bk,
                       kv_len=sk, sk_pad=nkb * bk)
    live = jnp.broadcast_to(live, (bm.shape[:2] if bm is not None
                                   else (1, 1)) + (nqb, nkb))
    # a mask shared by the batch counts once per sequence
    return (jnp.sum(live.astype(jnp.float32)) * (b // live.shape[0])
            / live.shape[1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_vjp(q, k, v, block_mask, causal, sliding_window, q_offset,
               kv_block):
    return _flash_fwd_impl(q, k, v, block_mask, causal, sliding_window,
                           q_offset, kv_block)


def _flash_vjp_fwd(q, k, v, block_mask, causal, sliding_window, q_offset,
                   kv_block):
    out, lse = _flash_fwd_impl(q, k, v, block_mask, causal, sliding_window,
                               q_offset, kv_block)
    return (out, lse), (q, k, v, block_mask, out, lse)


def _flash_vjp_bwd(causal, sliding_window, q_offset, kv_block, res, cts):
    q, k, v, block_mask, out, lse = res
    dout = cts[0]
    b, sq, h, d = q.shape
    sk = k.shape[1]
    kv_heads = k.shape[2]
    rep = h // kv_heads
    pad = (-sk) % kv_block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nkb = k.shape[1] // kv_block
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    pq = jnp.arange(sq) + q_offset
    D = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                axis=-1).transpose(0, 2, 1)                     # [b,h,sq]
    qf = q.astype(jnp.float32)
    doutf = dout.astype(jnp.float32).transpose(0, 2, 1, 3)      # [b,h,sq,d]
    qh = qf.transpose(0, 2, 1, 3)                               # [b,h,sq,d]
    kb = k.reshape(b, nkb, kv_block, kv_heads, d)
    vb = v.reshape(b, nkb, kv_block, kv_heads, d)

    def body(dq, inp):
        kblk, vblk, jb = inp
        krep = jnp.repeat(kblk.astype(jnp.float32), rep, axis=2)
        # [b,h,sq,kv_block]
        s = jnp.einsum("bhqd,bkhd->bhqk", qh, krep) * scale
        pk = jb * kv_block + jnp.arange(kv_block)
        mask = pk[None, :] <= jnp.full((sq, 1), sk - 1)
        if causal:
            mask &= pq[:, None] >= pk[None, :]
        if sliding_window:
            mask &= pq[:, None] - pk[None, :] < sliding_window
        if block_mask is not None:
            qb_ids = jnp.arange(sq) // kv_block
            if block_mask.ndim == 3:
                bm = block_mask[:, qb_ids, jb]
                s = jnp.where(bm[None, :, :, None] > 0, s, NEG_INF)
            else:
                bm = block_mask[:, :, qb_ids, jb]
                s = jnp.where(bm[..., None] > 0, s, NEG_INF)
        s = jnp.where(mask[None, None], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])                 # [b,h,sq,kb]
        # masked entries: s=NEG_INF ⇒ p→0; fully-masked rows have
        # lse≈NEG_INF which would make p spuriously 1 — zero them
        p = jnp.where((s <= NEG_INF / 2)
                      | (lse[..., None] <= NEG_INF / 4), 0.0, p)
        vrep = jnp.repeat(vblk.astype(jnp.float32), rep, axis=2)
        dp = jnp.einsum("bhqd,bkhd->bhqk", doutf, vrep)
        ds = p * (dp - D[..., None]) * scale
        dq = dq + pin_batch(jnp.einsum("bhqk,bkhd->bhqd", ds, krep))
        dk_blk = jnp.einsum("bhqk,bhqd->bkhd", ds, qh)
        dv_blk = jnp.einsum("bhqk,bhqd->bkhd", p, doutf)
        # fold grouped heads back to kv heads
        dk_blk = dk_blk.reshape(b, kv_block, kv_heads, rep, d).sum(3)
        dv_blk = dv_blk.reshape(b, kv_block, kv_heads, rep, d).sum(3)
        return dq, (dk_blk, dv_blk)

    dq0 = jnp.zeros((b, h, sq, d), jnp.float32)
    dq, (dk_b, dv_b) = jax.lax.scan(
        body, dq0, (kb.transpose(1, 0, 2, 3, 4),
                    vb.transpose(1, 0, 2, 3, 4), jnp.arange(nkb)))
    dk = dk_b.transpose(1, 0, 2, 3, 4).reshape(b, nkb * kv_block, kv_heads,
                                               d)[:, :sk]
    dv = dv_b.transpose(1, 0, 2, 3, 4).reshape(b, nkb * kv_block, kv_heads,
                                               d)[:, :sk]
    dq = dq.transpose(0, 2, 1, 3)
    dbm = None if block_mask is None else jnp.zeros_like(block_mask)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), dbm)


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _flash_fwd_impl(q, k, v, block_mask, causal, sliding_window, q_offset,
                    kv_block):
    """Forward online-softmax scan; returns (out, lse [b,h,sq])."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    kv_heads = k.shape[2]
    if sk % kv_block:
        pad = kv_block - sk % kv_block
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nkb = k.shape[1] // kv_block
    rep = h // kv_heads
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    pq = jnp.arange(sq) + q_offset

    kb = k.reshape(b, nkb, kv_block, kv_heads, d)
    vb = v.reshape(b, nkb, kv_block, kv_heads, d)

    def body(carry, inp):
        acc, m_prev, l_prev = carry
        kblk, vblk, jb = inp                       # [b, kv_block, kv, d]
        kblk = jnp.repeat(kblk, rep, axis=2)
        vblk = jnp.repeat(vblk, rep, axis=2)
        pk = jb * kv_block + jnp.arange(kv_block)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kblk).astype(jnp.float32) * scale
        mask = pk[None, :] <= jnp.full((sq, 1), sk - 1)
        if causal:
            mask &= pq[:, None] >= pk[None, :]
        if sliding_window:
            mask &= pq[:, None] - pk[None, :] < sliding_window
        if block_mask is not None:
            # block_mask: [h, nqb, nkb] or [b, h, nqb, nkb], square blocks
            # of size kv_block
            qb_ids = jnp.arange(sq) // kv_block
            if block_mask.ndim == 3:
                bm = block_mask[:, qb_ids, jb]     # [h, sq]
                s = jnp.where(bm[None, :, :, None] > 0, s, NEG_INF)
            else:
                bm = block_mask[:, :, qb_ids, jb]  # [b, h, sq]
                s = jnp.where(bm[..., None] > 0, s, NEG_INF)
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc = pin_batch(
            acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(vblk.dtype),
                vblk).astype(jnp.float32))
        return (acc, m_new, l_new), None

    acc0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        body, (acc0, m0, l0),
        (kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4),
         jnp.arange(nkb)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = jnp.where(m[..., None] <= NEG_INF / 2, 0.0, out)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))          # [b,h,sq]
    return out.transpose(0, 2, 1, 3).astype(q.dtype), lse


def decode_attention(q, k_cache, v_cache, cache_len, *, sliding_window: int = 0,
                     window_offset: int = 0) -> jax.Array:
    """Single-token decode attention over a (possibly ring-buffer) cache.

    q: [b, 1, h, d]; k_cache/v_cache: [b, S, kv, d]; cache_len: count of
    valid entries — a scalar (all lanes at the same position) or a [b]
    vector (continuous batching: each request at its own position).  For
    sliding-window archs the cache IS the ring buffer (S == window) and
    window_offset gives the rotation; masking handles both.
    """
    b, s, kv, d = k_cache.shape
    h = q.shape[2]
    k = _repeat_kv(k_cache, h)
    v = _repeat_kv(v_cache, h)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(d).astype(jnp.float32)
    idx = jnp.arange(s)
    cl = jnp.asarray(cache_len)
    if cl.ndim == 0:
        valid = idx < cl                                   # [s]
        if sliding_window:
            # non-ring cache with windowed attention: last `window` live
            valid &= idx >= cl - sliding_window
        vmask = valid[None, None, None, :]
    else:
        valid = idx[None, :] < cl[:, None]                 # [b, s]
        if sliding_window:
            valid &= idx[None, :] >= cl[:, None] - sliding_window
        vmask = valid[:, None, None, :]
    scores = jnp.where(vmask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def gqa_project(x, wq, wk, wv, num_heads, num_kv_heads, head_dim):
    b, s, _ = x.shape
    q = (x @ wq).reshape(b, s, num_heads, head_dim)
    k = (x @ wk).reshape(b, s, num_kv_heads, head_dim)
    v = (x @ wv).reshape(b, s, num_kv_heads, head_dim)
    return q, k, v


def cross_entropy_with_head(h, head_w, labels, *, label_mask=None,
                            vocab_shard_size: Optional[int] = None,
                            vocab_offset: int = 0,
                            axis_name: Optional[str] = None):
    """Cross-entropy over (possibly vocab-sharded) head.  h: [..., d],
    head_w: [d, V_local], labels int32 [...].  When ``axis_name`` is given the
    head is vocab-sharded over that mesh axis (Megatron-style vocab-parallel
    loss): per-shard max/sumexp/label-logit are combined with collectives."""
    logits = (h @ head_w).astype(jnp.float32)              # [..., V_local]
    if axis_name is None:
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    else:
        local_max = jnp.max(logits, axis=-1)
        gmax = jax.lax.pmax(local_max, axis_name)
        sumexp = jnp.sum(jnp.exp(logits - gmax[..., None]), axis=-1)
        sumexp = jax.lax.psum(sumexp, axis_name)
        lse = gmax + jnp.log(sumexp)
        local_labels = labels - vocab_offset
        in_shard = (local_labels >= 0) & (local_labels < logits.shape[-1])
        safe = jnp.clip(local_labels, 0, logits.shape[-1] - 1)
        ll = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        ll = jax.lax.psum(jnp.where(in_shard, ll, 0.0), axis_name)
    nll = lse - ll
    if label_mask is not None:
        nll = nll * label_mask
        denom = jnp.maximum(jnp.sum(label_mask), 1.0)
    else:
        denom = float(nll.size)
    return jnp.sum(nll) / denom

"""Unified slot-block layer.

Every architecture is expressed as a sequence of *blocks* drawn from a small
type set (configs.base.BLOCK_*).  A pipeline stage owns ``L_max`` slots; each
slot holds the **union** of the arch's per-type parameter fields plus a
runtime type tag, so the layer→stage assignment can change at runtime
(DynMo migration) without recompilation.

Public interface
  slot_param_spec(cfg)            -> {field: ShapeDtypeStruct}   (per slot)
  shared_param_spec(cfg)          -> {field: ShapeDtypeStruct}   (per model)
  slot_cache_spec(cfg, mb, clen)  -> {field: ShapeDtypeStruct}   (per slot)
  init_slot / init_shared         -> concrete params
  apply_block(...)                -> (y, new_cache, stats)

``mode`` is static ("train" | "prefill" | "decode"); the block type tag is a
runtime int32 — multi-type archs dispatch with lax.switch.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import kernels
from repro.configs.base import (
    BLOCK_DEC, BLOCK_DENSE, BLOCK_ENC, BLOCK_HYBRID_ATTN, BLOCK_MAMBA,
    BLOCK_MLSTM, BLOCK_MOE, BLOCK_PAD, BLOCK_SLSTM, ModelConfig,
)
from repro.models import mamba as mamba_lib
from repro.models import xlstm as xlstm_lib
from repro.models.layers import (
    apply_rope, decode_attention, expand_ff_mask as _expand_ff_mask,
    attention_tiles, flash_attention, gelu_mlp, pin_batch, rms_norm,
    swiglu,
)

PRUNE_BLOCK = 128      # block-structured pruning granularity (MXU tile width)
MAMBA_HEAD = 64
MOE_CAPACITY_FACTOR = 1.25


# ---------------------------------------------------------------------------
# Dimension helpers
# ---------------------------------------------------------------------------
def _dims(cfg: ModelConfig) -> Dict[str, int]:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    d_in = 2 * d
    return dict(
        d=d, hd=hd, nq=cfg.num_heads, nkv=cfg.num_kv_heads, ff=cfg.d_ff,
        d_in=d_in, nh_m=max(1, d_in // MAMBA_HEAD),
        conv_dim=d_in + 2 * cfg.ssm_state,
        nh_x=cfg.num_heads, dh_x=d_in // max(1, cfg.num_heads),
        st=cfg.ssm_state, E=cfg.num_experts,
    )


def prunable_dim(cfg: ModelConfig) -> int:
    """Feature dimension subject to block-structured pruning."""
    if cfg.d_ff > 0:
        return cfg.d_ff
    return 2 * 2 * cfg.d_model       # mLSTM up-projection (2*d_in)


def n_prune_blocks(cfg: ModelConfig) -> int:
    return max(1, prunable_dim(cfg) // PRUNE_BLOCK)


def block_type_set(cfg: ModelConfig) -> Tuple[int, ...]:
    return tuple(sorted(set(cfg.block_pattern())))


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------
def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def slot_param_spec(cfg: ModelConfig, dtype=jnp.bfloat16) -> Dict[str, Any]:
    m = _dims(cfg)
    types = block_type_set(cfg)
    spec: Dict[str, Any] = {}
    d, hd, nq, nkv, ff = m["d"], m["hd"], m["nq"], m["nkv"], m["ff"]
    if BLOCK_DENSE in types or BLOCK_MOE in types:
        spec.update(
            attn_norm=_sds([d], dtype), wq=_sds([d, nq * hd], dtype),
            wk=_sds([d, nkv * hd], dtype), wv=_sds([d, nkv * hd], dtype),
            wo=_sds([nq * hd, d], dtype), ffn_norm=_sds([d], dtype))
    if BLOCK_DENSE in types:
        spec.update(wi=_sds([d, ff], dtype), wg=_sds([d, ff], dtype),
                    wof=_sds([ff, d], dtype))
    if BLOCK_MOE in types:
        E = m["E"]
        spec.update(router=_sds([d, E], jnp.float32),
                    ewi=_sds([E, d, ff], dtype), ewg=_sds([E, d, ff], dtype),
                    ewo=_sds([E, ff, d], dtype))
    if BLOCK_MAMBA in types or BLOCK_HYBRID_ATTN in types:
        d_in, nh, cdim, st = m["d_in"], m["nh_m"], m["conv_dim"], m["st"]
        spec.update(
            m_norm=_sds([d], dtype),
            m_in=_sds([d, 2 * d_in + 2 * st + nh], dtype),
            m_convw=_sds([cfg.d_conv, cdim], dtype),
            m_convb=_sds([cdim], dtype),
            m_Alog=_sds([nh], jnp.float32), m_D=_sds([nh], jnp.float32),
            m_dtb=_sds([nh], jnp.float32), m_out=_sds([d_in, d], dtype))
    if BLOCK_MLSTM in types:
        d_in, nh, dh = m["d_in"], m["nh_x"], m["dh_x"]
        spec.update(
            x_norm=_sds([d], dtype), x_up=_sds([d, 2 * d_in], dtype),
            x_q=_sds([nh, dh, dh], dtype), x_k=_sds([nh, dh, dh], dtype),
            x_v=_sds([nh, dh, dh], dtype),
            x_ig=_sds([d_in, nh], jnp.float32),
            x_fg=_sds([d_in, nh], jnp.float32),
            x_down=_sds([d_in, d], dtype), x_gnorm=_sds([d_in], dtype))
    if BLOCK_SLSTM in types:
        ffp = max(PRUNE_BLOCK, (4 * d // 3) // PRUNE_BLOCK * PRUNE_BLOCK)
        spec.update(
            s_norm=_sds([d], dtype), s_wx=_sds([d, 4 * d], dtype),
            s_r=_sds([4, d], jnp.float32), s_out=_sds([d, d], dtype),
            s_fnorm=_sds([d], dtype), s_up=_sds([d, 2 * ffp], dtype),
            s_down=_sds([ffp, d], dtype))
    if BLOCK_ENC in types:
        spec.update(
            e_ln1=_sds([d], dtype), e_ln1b=_sds([d], dtype),
            e_wq=_sds([d, nq * hd], dtype), e_bq=_sds([nq * hd], dtype),
            e_wk=_sds([d, nkv * hd], dtype),
            e_wv=_sds([d, nkv * hd], dtype), e_bv=_sds([nkv * hd], dtype),
            e_wo=_sds([nq * hd, d], dtype), e_bo=_sds([d], dtype),
            e_ln2=_sds([d], dtype), e_ln2b=_sds([d], dtype),
            e_w1=_sds([d, ff], dtype), e_b1=_sds([ff], dtype),
            e_w2=_sds([ff, d], dtype), e_b2=_sds([d], dtype))
    if BLOCK_DEC in types:
        spec.update(
            d_ln1=_sds([d], dtype), d_ln1b=_sds([d], dtype),
            d_wq=_sds([d, nq * hd], dtype), d_bq=_sds([nq * hd], dtype),
            d_wk=_sds([d, nkv * hd], dtype),
            d_wv=_sds([d, nkv * hd], dtype), d_bv=_sds([nkv * hd], dtype),
            d_wo=_sds([nq * hd, d], dtype), d_bo=_sds([d], dtype),
            d_ln2=_sds([d], dtype), d_ln2b=_sds([d], dtype),
            c_wq=_sds([d, nq * hd], dtype), c_bq=_sds([nq * hd], dtype),
            c_wk=_sds([d, nkv * hd], dtype),
            c_wv=_sds([d, nkv * hd], dtype), c_bv=_sds([nkv * hd], dtype),
            c_wo=_sds([nq * hd, d], dtype), c_bo=_sds([d], dtype),
            d_ln3=_sds([d], dtype), d_ln3b=_sds([d], dtype),
            d_w1=_sds([d, ff], dtype), d_b1=_sds([ff], dtype),
            d_w2=_sds([ff, d], dtype), d_b2=_sds([d], dtype))
    return spec


def shared_param_spec(cfg: ModelConfig, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Model-level (non-slot) params beyond embed/head/final_norm."""
    m = _dims(cfg)
    spec: Dict[str, Any] = {}
    if cfg.family == "hybrid" and cfg.shared_attn_period:
        d, hd, nq, nkv = m["d"], m["hd"], m["nq"], m["nkv"]
        spec.update(
            ga_norm=_sds([d], dtype), ga_wq=_sds([d, nq * hd], dtype),
            ga_wk=_sds([d, nkv * hd], dtype), ga_wv=_sds([d, nkv * hd], dtype),
            ga_wo=_sds([nq * hd, d], dtype))
    if cfg.is_encdec:
        spec.update(dec_pos=_sds([cfg.max_seq_len, m["d"]], dtype))
    return spec


def slot_cache_spec(cfg: ModelConfig, mb: int, cache_len: int,
                    dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Per-slot decode cache (union over the arch's type set).

    ``cache_len``: cache capacity.  Sliding-window archs get a ring buffer of
    min(cache_len, window)."""
    m = _dims(cfg)
    types = block_type_set(cfg)
    spec: Dict[str, Any] = {}
    nkv, hd = m["nkv"], m["hd"]
    cap = cache_len
    if cfg.sliding_window:
        cap = min(cache_len, cfg.sliding_window)
    if any(t in types for t in (BLOCK_DENSE, BLOCK_MOE, BLOCK_HYBRID_ATTN,
                                BLOCK_DEC, BLOCK_ENC)):
        spec.update(k=_sds([mb, cap, nkv, hd], dtype),
                    v=_sds([mb, cap, nkv, hd], dtype))
    if BLOCK_DEC in types:
        spec.update(ck=_sds([mb, cfg.encoder_seq, nkv, hd], dtype),
                    cv=_sds([mb, cfg.encoder_seq, nkv, hd], dtype))
    if BLOCK_MAMBA in types or BLOCK_HYBRID_ATTN in types:
        spec.update(
            conv=_sds([mb, cfg.d_conv - 1, m["conv_dim"]], dtype),
            ssm=_sds([mb, m["nh_m"], MAMBA_HEAD, m["st"]], jnp.float32))
    if BLOCK_MLSTM in types:
        nh, dh = m["nh_x"], m["dh_x"]
        spec.update(xC=_sds([mb, nh, dh, dh], jnp.float32),
                    xn=_sds([mb, nh, dh], jnp.float32),
                    xm=_sds([mb, nh], jnp.float32))
    if BLOCK_SLSTM in types:
        d = m["d"]
        spec.update(sc=_sds([mb, d], jnp.float32),
                    sn=_sds([mb, d], jnp.float32),
                    sm=_sds([mb, d], jnp.float32),
                    sh=_sds([mb, d], jnp.float32))
    return spec


def paged_slot_cache_spec(cfg: ModelConfig, pool_pages: int, page_size: int,
                          dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Per-slot block-paged decode cache: one physical K/V block pool shared
    by every lane of the slot, indexed through per-lane page tables.

    Layout ``[pool_pages + 1, page_size, n_kv, head_dim]`` — the final block
    is the trash block absorbing count-gated writes (invalid micro ticks,
    unmapped lanes).  Only attention-pure decoder archs page their cache:
    recurrent state (mamba/xlstm) is O(1) per lane and sliding-window caches
    are already rings.
    """
    m = _dims(cfg)
    types = set(block_type_set(cfg))
    if not types <= {BLOCK_DENSE, BLOCK_MOE}:
        raise ValueError(
            f"paged KV requires an attention-only arch, got types {types}")
    if cfg.sliding_window:
        raise ValueError("paged KV does not support sliding-window caches")
    nkv, hd = m["nkv"], m["hd"]
    return dict(kp=_sds([pool_pages + 1, page_size, nkv, hd], dtype),
                vp=_sds([pool_pages + 1, page_size, nkv, hd], dtype))


def stats_spec(cfg: ModelConfig) -> Dict[str, Any]:
    E = max(1, cfg.num_experts)
    return dict(expert_load=_sds([E], jnp.float32),
                moe_dropped=_sds([], jnp.float32),
                ff_active=_sds([], jnp.float32),
                attn_density=_sds([], jnp.float32),
                attn_tiles=_sds([], jnp.float32))


def _zero_stats(cfg: ModelConfig) -> Dict[str, jax.Array]:
    return {k: jnp.zeros(v.shape, v.dtype)
            for k, v in stats_spec(cfg).items()}


def init_slot(rng: jax.Array, cfg: ModelConfig,
              dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    spec = slot_param_spec(cfg, dtype)
    out = {}
    keys = jax.random.split(rng, len(spec))
    for k_, (name, sds) in zip(keys, sorted(spec.items())):
        if name.endswith(("norm", "gnorm", "fnorm")) or name.startswith(
                ("e_ln", "d_ln")) and not name.endswith("b"):
            out[name] = jnp.ones(sds.shape, sds.dtype)
        elif name.endswith(("b", "_bq", "_bv", "_bo")) or name in (
                "m_convb", "m_dtb"):
            out[name] = jnp.zeros(sds.shape, sds.dtype)
        elif name == "m_Alog":
            out[name] = jnp.log(jnp.linspace(1.0, 16.0, sds.shape[0]))
        elif name == "m_D":
            out[name] = jnp.ones(sds.shape, sds.dtype)
        elif name == "s_r":
            out[name] = jnp.zeros(sds.shape, sds.dtype)
        elif name in ("x_ig", "x_fg"):
            base = 3.0 if name == "x_fg" else -1.0
            out[name] = (jax.random.normal(k_, sds.shape, sds.dtype) * 0.02
                         + base)
        else:
            fan_in = sds.shape[-2] if len(sds.shape) >= 2 else sds.shape[-1]
            out[name] = (jax.random.normal(k_, sds.shape, jnp.float32)
                         * (0.02 if fan_in <= 0 else fan_in ** -0.5)
                         ).astype(sds.dtype)
    return out


def init_shared(rng: jax.Array, cfg: ModelConfig,
                dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    spec = shared_param_spec(cfg, dtype)
    out = {}
    keys = jax.random.split(rng, max(1, len(spec)))
    for k_, (name, sds) in zip(keys, sorted(spec.items())):
        if name.endswith("norm"):
            out[name] = jnp.ones(sds.shape, sds.dtype)
        else:
            fan_in = sds.shape[-2] if len(sds.shape) >= 2 else sds.shape[-1]
            out[name] = (jax.random.normal(k_, sds.shape, jnp.float32)
                         * fan_in ** -0.5).astype(sds.dtype)
    return out


# ---------------------------------------------------------------------------
# Hash-based dynamic block sparsity (paper §2.4 / §4.2.4, TPU-adapted)
# ---------------------------------------------------------------------------
def hash_block_mask(x, *, nbuckets: int, block: int, causal: bool = True):
    """Content-based block mask from sign-random-projection hashing.

    x: [b, s, d].  Tokens are bucketed by the hash of their block-mean hidden
    state; attention is restricted to (q-block, kv-block) pairs whose buckets
    match, plus the local diagonal band (exactness of nearby context).
    Returns mask [b, 1, nqb, nkb] float and the achieved density.
    """
    b, s, d = x.shape
    nb = max(1, s // block)
    xb = x[:, :nb * block].reshape(b, nb, block, d).mean(axis=2)
    xb = xb.astype(jnp.float32)
    nbits = max(1, int(nbuckets - 1).bit_length())
    # fixed pseudo-random projection (deterministic across steps)
    proj = jax.random.normal(jax.random.PRNGKey(17), (d, nbits), jnp.float32)
    bits = (xb @ proj) > 0                                     # [b, nb, nbits]
    bucket = jnp.sum(bits * (2 ** jnp.arange(nbits)), axis=-1) % nbuckets
    same = bucket[:, :, None] == bucket[:, None, :]            # [b, nb, nb]
    band = jnp.abs(jnp.arange(nb)[:, None] - jnp.arange(nb)[None, :]) <= 1
    mask = same | band[None]
    if causal:
        mask &= (jnp.arange(nb)[:, None] >= jnp.arange(nb)[None, :])
        denom = jnp.sum(jnp.tril(jnp.ones((nb, nb))))
    else:
        denom = float(nb * nb)
    density = jnp.sum(mask.astype(jnp.float32), axis=(1, 2)).mean() / denom
    return mask[:, None].astype(jnp.float32), density


# ---------------------------------------------------------------------------
# Attention core shared by dense/moe/hybrid/whisper blocks
# ---------------------------------------------------------------------------
def _attn_fwd(x, wq, wk, wv, wo, *, cfg, mode, cache, pos,
              rope: bool = True, causal: bool = True,
              block_mask=None, bq=None, bv=None, bo=None,
              kv_override=None, cache_keys=("k", "v"), dyncfg=None,
              kernel_impl: str = "scan"):
    """GQA attention with optional RoPE/SWA/bias/cache.  x: [mb, s, d];
    pos: [s] absolute positions (train/prefill) or scalar (decode).
    Returns (out, new_cache, density, tiles): ``tiles`` counts the (query
    block, key block) tiles the Pallas kernels compute in this forward
    (``layers.attention_tiles``), 0 in decode."""
    m = _dims(cfg)
    nq, nkv, hd = m["nq"], m["nkv"], m["hd"]
    b, s, _ = x.shape
    density = jnp.float32(1.0)
    tiles = jnp.float32(0.0)
    kv_block = 512
    if (dyncfg is not None and dyncfg.uses_sparse_attention
            and mode != "decode" and block_mask is None
            and s >= 2 * dyncfg.sparse_block):
        block_mask, density = hash_block_mask(
            x, nbuckets=dyncfg.sparse_nbuckets, block=dyncfg.sparse_block,
            causal=causal)
        kv_block = dyncfg.sparse_block
    q = (x @ wq)
    if bq is not None:
        q = q + bq
    q = q.reshape(b, s, nq, hd)
    if kv_override is not None:
        xkv = kv_override
    else:
        xkv = x
    k = (xkv @ wk).reshape(b, xkv.shape[1], nkv, hd)
    v = (xkv @ wv)
    if bv is not None:
        v = v + bv
    v = v.reshape(b, xkv.shape[1], nkv, hd)

    new_cache = cache
    if mode == "decode" and cache is not None and "kp" in cache:
        # block-paged cache: one physical pool per slot, per-lane page
        # tables.  Write the new K/V through the table (gated writes land in
        # the trash block), then attend by gathering blocks.
        kp, vp = cache["kp"], cache["vp"]
        pt = cache["pt"]                      # [b, J] int32, -1 = unmapped
        wok = cache["wok"]                    # scalar: tick carries live data
        page = kp.shape[1]
        trash = kp.shape[0] - 1
        cap = pt.shape[1] * page
        pvec = jnp.broadcast_to(jnp.reshape(pos, (-1,)), (b,))
        if rope:
            q = apply_rope(q, pvec[:, None], cfg.rope_theta)
            k = apply_rope(k, pvec[:, None], cfg.rope_theta)
        pw = jnp.minimum(pvec, cap - 1)
        lanes = jnp.arange(b)
        blk = pt[lanes, pw // page]
        ok = (wok > 0) & (blk >= 0)
        blk_eff = jnp.where(ok, blk, trash)
        off = pw % page
        kp = kp.at[blk_eff, off].set(k[:, 0].astype(kp.dtype))
        vp = vp.at[blk_eff, off].set(v[:, 0].astype(vp.dtype))
        clen = jnp.minimum(pvec + 1, cap)
        if kernel_impl == "pallas":
            from repro.kernels.paged_attention import paged_attention
            out = paged_attention(q, kp, vp, pt, clen,
                                  interpret=kernels.use_interpret())
        else:
            from repro.kernels.paged_attention import paged_attention_ref
            out = paged_attention_ref(q, kp, vp, pt, clen)
        new_cache = dict(cache)
        new_cache["kp"] = kp
        new_cache["vp"] = vp
    elif mode == "decode":
        kc, vc = cache[cache_keys[0]], cache[cache_keys[1]]
        cap = kc.shape[1]
        if jnp.ndim(pos) == 0:
            # pos is a scalar: every lane at the same absolute position
            if rope:
                q = apply_rope(q, jnp.full((b, 1), pos), cfg.rope_theta)
                k = apply_rope(k, jnp.full((b, 1), pos), cfg.rope_theta)
            widx = jnp.mod(pos, cap) if cfg.sliding_window else jnp.minimum(
                pos, cap - 1)
            kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype),
                                              (0, widx, 0, 0))
            vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype),
                                              (0, widx, 0, 0))
            clen = jnp.minimum(pos + 1, cap)
        else:
            # pos is a [b] vector: continuous batching — each request
            # writes its cache line and masks attention at its OWN position
            pvec = jnp.reshape(pos, (b,))
            if rope:
                q = apply_rope(q, pvec[:, None], cfg.rope_theta)
                k = apply_rope(k, pvec[:, None], cfg.rope_theta)
            widx = (jnp.mod(pvec, cap) if cfg.sliding_window
                    else jnp.minimum(pvec, cap - 1))
            lanes = jnp.arange(b)
            kc = kc.at[lanes, widx].set(k[:, 0].astype(kc.dtype))
            vc = vc.at[lanes, widx].set(v[:, 0].astype(vc.dtype))
            clen = jnp.minimum(pvec + 1, cap)
        out = decode_attention(q, kc, vc, clen)
        new_cache = dict(cache)
        new_cache[cache_keys[0]] = kc
        new_cache[cache_keys[1]] = vc
    else:
        if rope:
            pq = jnp.broadcast_to(pos[None, :], (b, s))
            q = apply_rope(q, pq, cfg.rope_theta)
            pk = jnp.broadcast_to(pos[None, :xkv.shape[1]], (b, xkv.shape[1]))
            k = apply_rope(k, pk, cfg.rope_theta)
        out = flash_attention(q, k, v, causal=causal,
                              sliding_window=cfg.sliding_window,
                              block_mask=block_mask, kv_block=kv_block,
                              impl=kernel_impl)
        tiles = attention_tiles(block_mask, b, s, xkv.shape[1],
                                causal=causal, head_dim=hd,
                                itemsize=q.dtype.itemsize, kv_block=kv_block,
                                sliding_window=cfg.sliding_window,
                                impl=kernel_impl)
        if mode == "prefill" and cache is not None:
            kc, vc = cache[cache_keys[0]], cache[cache_keys[1]]
            cap = kc.shape[1]
            new_cache = dict(cache)
            if cap >= s:
                new_cache[cache_keys[0]] = jax.lax.dynamic_update_slice(
                    kc, k.astype(kc.dtype), (0, 0, 0, 0))
                new_cache[cache_keys[1]] = jax.lax.dynamic_update_slice(
                    vc, v.astype(vc.dtype), (0, 0, 0, 0))
            else:                       # ring buffer: keep last `cap`
                new_cache[cache_keys[0]] = k[:, -cap:].astype(kc.dtype)
                new_cache[cache_keys[1]] = v[:, -cap:].astype(vc.dtype)
    out = pin_batch(out.reshape(b, out.shape[1], nq * hd) @ wo)
    if bo is not None:
        out = out + bo
    return out, new_cache, density, tiles


# ---------------------------------------------------------------------------
# MoE FFN (GShard-style capacity dispatch, cumsum position-in-expert)
# ---------------------------------------------------------------------------
def moe_ffn(p, x, cfg: ModelConfig, *, kernel_impl: str = "scan",
            expert_map=None):
    """x: [mb, s, d] -> (y, expert_load [E], aux_loss, dropped_frac).

    Top-k routing with capacity; dispatch is vmapped per batch row to keep
    sorting/scatters shard-local.  Routing (top-k, cumsum
    position-in-expert, capacity drops) is IDENTICAL for every impl —
    only the expert compute differs:

      "reference"/"scan": the dense GShard capacity einsum over the
        zero-padded [b, E, cap, d] buffer — every expert pays full
        capacity-sized FLOPs (the numeric oracle).
      "pallas": sort -> grouped ragged matmul -> unsort; each expert group
        costs row tiles proportional to its measured routed load (empty
        experts skip all tile work).  ``expert_map`` ([E] float, logical
        expert -> physical group; None = identity) permutes only the
        *physical group ordering* inside the kernel: per-token math is
        row-wise, so y is bit-identical under any placement — a live expert
        re-layout never perturbs training.  s == 1 (decode) takes the same
        path: the PR 1 dense fallback does not apply here.

    ``dropped_frac`` is the capacity-overflow drop fraction of routed
    (token, expert) pairs this call — same routing ⇒ same drops on every
    impl (asserted in tests)."""
    E, K = cfg.num_experts, cfg.experts_per_token
    b, s, d = x.shape
    cf = cfg.moe_capacity_factor or MOE_CAPACITY_FACTOR
    cap = int(cf * s * K / E + 0.999)
    cap = max(4, min(s, (cap + 3) // 4 * 4))

    logits = (x @ p["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # [b,s,E]
    w, sel = jax.lax.top_k(probs, K)                           # [b,s,K]
    w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)

    def route_row(selr, wr):
        # selr, wr: [s,K] -> flattened k-major routing decisions
        flat_e = selr.T.reshape(-1)                            # k-major [K*s]
        flat_t = jnp.tile(jnp.arange(s), (K,))
        flat_w = wr.T.reshape(-1)
        oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)        # [K*s, E]
        pos = jnp.cumsum(oh, axis=0) - oh                      # exclusive
        pos = jnp.sum(pos * oh, axis=-1)                       # [K*s]
        keep = pos < cap
        return flat_e, flat_t, flat_w, pos, keep

    if kernel_impl == "pallas":
        from repro.kernels.grouped_matmul import grouped_matmul
        interpret = kernels.use_interpret()
        if expert_map is None:
            pm = jnp.arange(E, dtype=jnp.int32)
        else:
            pm = expert_map.astype(jnp.int32)                  # [E] perm

        def dispatch_row(xr, selr, wr):
            flat_e, flat_t, flat_w, pos, keep = route_row(selr, wr)
            phys = pm[flat_e]
            slot = jnp.where(keep, phys * cap + pos, E * cap)
            buf = jnp.zeros((E * cap + 1, d), xr.dtype)
            buf = buf.at[slot].add(xr[flat_t])
            cnt = jnp.sum(jax.nn.one_hot(phys, E, dtype=jnp.int32)
                          * keep[:, None], axis=0)             # [E] kept
            return buf[:E * cap].reshape(E, cap, d), cnt, \
                (flat_t, flat_w, slot, keep)

        buf, cnt, aux = jax.vmap(dispatch_row)(x, sel, w)      # [b,E,cap,d]
        xg = buf.reshape(b * E, cap, d)                        # batch-major
        counts = cnt.reshape(b * E)
        # physical group g (= bi*E + p) runs the LOGICAL expert mapped to
        # it: gather weights through the inverse placement
        inv = jnp.zeros((E,), jnp.int32).at[pm].set(
            jnp.arange(E, dtype=jnp.int32))
        gmm = lambda a, wg: grouped_matmul(a, wg, counts,
                                           interpret=interpret)
        h = gmm(xg, p["ewg"][inv])
        h = jax.nn.silu(h) * gmm(xg, p["ewi"][inv])
        out = gmm(h.astype(xg.dtype), p["ewo"][inv])
        out = out.reshape(b, E, cap, d)
    else:
        def dispatch_row(xr, selr, wr):
            flat_e, flat_t, flat_w, pos, keep = route_row(selr, wr)
            slot = jnp.where(keep, flat_e * cap + pos, E * cap)
            buf = jnp.zeros((E * cap + 1, d), xr.dtype)
            buf = buf.at[slot].add(xr[flat_t])
            buf = buf[:E * cap].reshape(E, cap, d)
            return buf, (flat_t, flat_w, slot, keep)

        buf, aux = jax.vmap(dispatch_row)(x, sel, w)           # [b,E,cap,d]
        h = jnp.einsum("becd,edf->becf", buf, p["ewg"])
        h = jax.nn.silu(h) * jnp.einsum("becd,edf->becf", buf, p["ewi"])
        out = jnp.einsum("becf,efd->becd", h, p["ewo"])        # [b,E,cap,d]

    def combine_row(outr, auxr):
        flat_t, flat_w, slot, keep = auxr
        outf = outr.reshape(E * cap, d)
        vals = outf[jnp.minimum(slot, E * cap - 1)]
        vals = vals * (flat_w * keep)[:, None].astype(vals.dtype)
        y = jnp.zeros((s, d), outr.dtype).at[flat_t].add(vals)
        return y

    y = jax.vmap(combine_row)(out, aux)
    load = jnp.sum(jax.nn.one_hot(sel, E), axis=(0, 1, 2))     # [E]
    # capacity-overflow drops: routed (token, expert) pairs past each
    # expert's cap (previously silent) — keep masks are identical across
    # impls, so this is impl-independent by construction
    keep_all = jax.vmap(lambda selr, wr: route_row(selr, wr)[4])(sel, w)
    dropped = 1.0 - jnp.mean(keep_all.astype(jnp.float32))
    # auxiliary load-balancing loss (Mixtral-style), returned via stats
    me = jnp.mean(probs.reshape(-1, E), axis=0)
    ce = load / jnp.maximum(jnp.sum(load), 1.0)
    aux_loss = E * jnp.sum(me * ce)
    return y, load, aux_loss, dropped


# ---------------------------------------------------------------------------
# Per-type block forward
# ---------------------------------------------------------------------------
def _dense_block(p, x, *, cfg, mode, cache, pos, dyn, dyncfg,
                 kernel_impl="scan"):
    h, cache, density, tiles = _attn_fwd(
        rms_norm(x, p["attn_norm"], cfg.norm_eps),
        p["wq"], p["wk"], p["wv"], p["wo"], cfg=cfg, mode=mode,
        cache=cache, pos=pos, dyncfg=dyncfg, kernel_impl=kernel_impl)
    x = x + h
    hn = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
    # block-level mask: layers.swiglu expands for the dense impls and feeds
    # the pallas impl's tile gating directly
    ff_mask = dyn["ff_mask"] if cfg.d_ff else None
    x = x + swiglu(hn, p["wi"], p["wg"], p["wof"], ff_mask,
                   impl=kernel_impl)
    stats = _zero_stats(cfg)
    stats["ff_active"] = jnp.mean(dyn["ff_mask"])
    stats["attn_density"] = density
    stats["attn_tiles"] = tiles
    return x, cache, stats, jnp.float32(0.0)


def _moe_block(p, x, *, cfg, mode, cache, pos, dyn, dyncfg,
               kernel_impl="scan"):
    h, cache, density, tiles = _attn_fwd(
        rms_norm(x, p["attn_norm"], cfg.norm_eps),
        p["wq"], p["wk"], p["wv"], p["wo"], cfg=cfg, mode=mode,
        cache=cache, pos=pos, dyncfg=dyncfg, kernel_impl=kernel_impl)
    x = x + h
    hn = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
    y, load, aux_loss, dropped = moe_ffn(
        p, hn, cfg, kernel_impl=kernel_impl,
        expert_map=dyn.get("expert_map"))
    x = x + y
    stats = _zero_stats(cfg)
    stats["expert_load"] = load
    stats["moe_dropped"] = dropped
    stats["ff_active"] = jnp.float32(1.0)
    stats["attn_density"] = density
    stats["attn_tiles"] = tiles
    return x, cache, stats, aux_loss


def _mamba_block(p, x, *, cfg, mode, cache, pos, dyn, shared=None,
                 with_shared_attn=False, dyncfg=None, kernel_impl="scan"):
    m = _dims(cfg)
    d_in, nh, st = m["d_in"], m["nh_m"], m["st"]
    b, s, _ = x.shape
    hn = rms_norm(x, p["m_norm"], cfg.norm_eps)
    proj = hn @ p["m_in"]                                      # [b,s,...]
    z, xs, B, C, dt = jnp.split(
        proj, [d_in, 2 * d_in, 2 * d_in + st, 2 * d_in + 2 * st], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["m_dtb"])
    conv_in = jnp.concatenate([xs, B, C], axis=-1)
    if mode == "decode":
        conv_out, conv_state = mamba_lib.causal_conv(
            conv_in, p["m_convw"], p["m_convb"], state=cache["conv"])
    else:
        conv_out, conv_state = mamba_lib.causal_conv(
            conv_in, p["m_convw"], p["m_convb"])
    xs, B, C = jnp.split(conv_out, [d_in, d_in + st], axis=-1)
    xh = xs.reshape(b, s, nh, MAMBA_HEAD)
    if mode == "decode":
        y, ssm = mamba_lib.ssd_decode_step(
            xh[:, 0], dt[:, 0], p["m_Alog"], B[:, 0], C[:, 0], p["m_D"],
            cache["ssm"])
        y = y[:, None]
    else:
        init = None
        y, ssm = mamba_lib.ssd_chunked(xh, dt, p["m_Alog"], B, C, p["m_D"],
                                       init_state=init)
    y = y.reshape(b, s, d_in) * jax.nn.silu(z)
    x = x + y @ p["m_out"]
    new_cache = cache
    if mode in ("decode", "prefill") and cache is not None:
        new_cache = dict(cache)
        new_cache["conv"] = conv_state.astype(cache["conv"].dtype)
        new_cache["ssm"] = ssm
    tiles = jnp.float32(0.0)
    if with_shared_attn:
        h, new_cache, _, tiles = _attn_fwd(
            rms_norm(x, shared["ga_norm"], cfg.norm_eps),
            shared["ga_wq"], shared["ga_wk"], shared["ga_wv"],
            shared["ga_wo"], cfg=cfg, mode=mode,
            cache=new_cache, pos=pos, dyncfg=dyncfg,
            kernel_impl=kernel_impl)
        x = x + h
    stats = _zero_stats(cfg)
    stats["ff_active"] = jnp.float32(1.0)
    stats["attn_tiles"] = tiles
    return x, new_cache, stats, jnp.float32(0.0)


def _mlstm_block(p, x, *, cfg, mode, cache, pos, dyn):
    m = _dims(cfg)
    d_in, nh, dh = m["d_in"], m["nh_x"], m["dh_x"]
    b, s, _ = x.shape
    hn = rms_norm(x, p["x_norm"], cfg.norm_eps)
    up = hn @ p["x_up"]
    u, z = jnp.split(up, 2, axis=-1)                           # [b,s,d_in]
    mask = _expand_ff_mask(dyn["ff_mask"], 2 * d_in)
    u = u * mask[:d_in].astype(u.dtype)
    z = z * mask[d_in:].astype(z.dtype)
    uh = u.reshape(b, s, nh, dh)
    q = jnp.einsum("bshd,hde->bshe", uh, p["x_q"])
    k = jnp.einsum("bshd,hde->bshe", uh, p["x_k"])
    v = jnp.einsum("bshd,hde->bshe", uh, p["x_v"])
    ig = u @ p["x_ig"].astype(u.dtype)
    fg = u @ p["x_fg"].astype(u.dtype)
    new_cache = cache
    if mode == "decode":
        h, C, n, mm = xlstm_lib.mlstm_decode_step(
            q[:, 0], k[:, 0], v[:, 0], ig[:, 0], fg[:, 0],
            cache["xC"], cache["xn"], cache["xm"])
        h = h[:, None]
        new_cache = dict(cache)
        new_cache.update(xC=C, xn=n, xm=mm)
    else:
        if s <= 512:
            h = xlstm_lib.mlstm_parallel(q, k, v, ig, fg)
        else:
            h = xlstm_lib.mlstm_chunked(q, k, v, ig, fg)
        if mode == "prefill" and cache is not None:
            # rebuild state by chunked scan final state: cheap re-run of the
            # state recurrence (decode-accurate warm start)
            _, C, n, mm = _mlstm_final_state(q, k, v, ig, fg)
            new_cache = dict(cache)
            new_cache.update(xC=C, xn=n, xm=mm)
    h = h.reshape(b, s, d_in)
    h = rms_norm(h, p["x_gnorm"], cfg.norm_eps) * jax.nn.silu(z)
    x = x + h @ p["x_down"]
    stats = _zero_stats(cfg)
    stats["ff_active"] = jnp.mean(dyn["ff_mask"])
    return x, new_cache, stats, jnp.float32(0.0)


def _mlstm_final_state(q, k, v, ig, fg):
    b, s, nh, dh = q.shape

    def step(carry, inp):
        C, n, m = carry
        qt, kt, vt, it, ft = inp
        _, C, n, m = xlstm_lib.mlstm_decode_step(qt, kt, vt, it, ft, C, n, m)
        return (C, n, m), None

    C0 = jnp.zeros((b, nh, dh, dh), jnp.float32)
    n0 = jnp.zeros((b, nh, dh), jnp.float32)
    m0 = jnp.full((b, nh), -jnp.inf, jnp.float32)
    tr = lambda a: a.transpose(1, 0, *range(2, a.ndim))
    (C, n, m), _ = jax.lax.scan(step, (C0, n0, m0),
                                (tr(q), tr(k), tr(v), tr(ig), tr(fg)))
    return None, C, n, m


def _slstm_block(p, x, *, cfg, mode, cache, pos, dyn):
    b, s, d = x.shape
    hn = rms_norm(x, p["s_norm"], cfg.norm_eps)
    gates = (hn @ p["s_wx"]).reshape(b, s, 4, d)
    new_cache = cache
    if mode == "decode":
        init = (cache["sc"], cache["sn"], cache["sm"], cache["sh"])
        h, carry = xlstm_lib.slstm_scan(gates, p["s_r"], init=init)
        new_cache = dict(cache)
        new_cache.update(sc=carry[0], sn=carry[1], sm=carry[2], sh=carry[3])
    else:
        h, carry = xlstm_lib.slstm_scan(gates, p["s_r"])
        if mode == "prefill" and cache is not None:
            new_cache = dict(cache)
            new_cache.update(sc=carry[0], sn=carry[1], sm=carry[2],
                             sh=carry[3])
    x = x + h @ p["s_out"]
    hn = rms_norm(x, p["s_fnorm"], cfg.norm_eps)
    up = hn @ p["s_up"]
    a, g = jnp.split(up, 2, axis=-1)
    x = x + (jax.nn.silu(g) * a) @ p["s_down"]
    stats = _zero_stats(cfg)
    stats["ff_active"] = jnp.float32(1.0)
    return x, new_cache, stats, jnp.float32(0.0)


def _layer_norm(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _enc_block(p, x, *, cfg, mode, cache, pos, dyn, kernel_impl="scan"):
    h, _, _, tiles = _attn_fwd(
        _layer_norm(x, p["e_ln1"], p["e_ln1b"], cfg.norm_eps),
        p["e_wq"], p["e_wk"], p["e_wv"], p["e_wo"], cfg=cfg, mode="train",
        cache=None, pos=jnp.arange(x.shape[1]), rope=False, causal=False,
        bq=p["e_bq"], bv=p["e_bv"], bo=p["e_bo"], kernel_impl=kernel_impl)
    x = x + h
    hn = _layer_norm(x, p["e_ln2"], p["e_ln2b"], cfg.norm_eps)
    x = x + gelu_mlp(hn, p["e_w1"], p["e_b1"], p["e_w2"], p["e_b2"],
                     dyn["ff_mask"], impl=kernel_impl)
    stats = _zero_stats(cfg)
    stats["ff_active"] = jnp.mean(dyn["ff_mask"])
    stats["attn_tiles"] = tiles
    return x, cache, stats, jnp.float32(0.0)


def _dec_block(p, x, *, cfg, mode, cache, pos, dyn, enc_out,
               kernel_impl="scan"):
    # self attention (causal, learned positions added at embedding)
    h, cache, _, tiles = _attn_fwd(
        _layer_norm(x, p["d_ln1"], p["d_ln1b"], cfg.norm_eps),
        p["d_wq"], p["d_wk"], p["d_wv"], p["d_wo"],
        cfg=cfg, mode=mode, cache=cache, pos=pos, rope=False,
        causal=True, bq=p["d_bq"], bv=p["d_bv"], bo=p["d_bo"],
        kernel_impl=kernel_impl)
    x = x + h
    # cross attention
    hn = _layer_norm(x, p["d_ln2"], p["d_ln2b"], cfg.norm_eps)
    if mode == "decode":
        # cross K/V were cached at prefill
        m = _dims(cfg)
        q = (hn @ p["c_wq"] + p["c_bq"]).reshape(
            hn.shape[0], 1, m["nq"], m["hd"])
        out = decode_attention(q, cache["ck"], cache["cv"],
                               jnp.int32(cfg.encoder_seq))
        h = out.reshape(hn.shape[0], 1, m["nq"] * m["hd"]) @ p["c_wo"] \
            + p["c_bo"]
        new_cache = cache
    else:
        h, new_cache, _, cross_tiles = _attn_fwd(
            hn, p["c_wq"], p["c_wk"], p["c_wv"], p["c_wo"], cfg=cfg,
            mode=mode, cache=cache, pos=pos, rope=False, causal=False,
            bq=p["c_bq"], bv=p["c_bv"], bo=p["c_bo"], kv_override=enc_out,
            cache_keys=("ck", "cv"), kernel_impl=kernel_impl)
        tiles = tiles + cross_tiles
    x = x + h
    hn = _layer_norm(x, p["d_ln3"], p["d_ln3b"], cfg.norm_eps)
    x = x + gelu_mlp(hn, p["d_w1"], p["d_b1"], p["d_w2"], p["d_b2"],
                     dyn["ff_mask"], impl=kernel_impl)
    stats = _zero_stats(cfg)
    stats["ff_active"] = jnp.mean(dyn["ff_mask"])
    stats["attn_tiles"] = tiles
    return x, new_cache, stats, jnp.float32(0.0)


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------
def apply_block(cfg: ModelConfig, dyncfg, mode: str, p, shared, carry, tag,
                dyn, cache, pos, *, kernel_impl: str = "scan"):
    """Apply one slot.  ``tag`` is a runtime int32 BLOCK_* type id.
    ``kernel_impl`` (DistConfig.kernel_impl, static) selects the attention /
    SwiGLU inner implementation — see layers.flash_attention.

    ``carry`` is the pipeline activation dict: {"x": [mb, s, d]} plus
    {"enc": [mb, enc_seq, d]} for encoder–decoder archs (the encoder stream
    rides the same carry so enc blocks can live on any stage).

    Returns (carry', new_cache, stats, aux_loss).  PAD slots are identity."""
    types = block_type_set(cfg)

    def branch(t):
        def fn(operands):
            p_, carry_, dyn_, cache_ = operands
            x_ = carry_["x"]
            if t == BLOCK_DENSE:
                y, c, s_, a = _dense_block(
                    p_, x_, cfg=cfg, mode=mode, cache=cache_, pos=pos,
                    dyn=dyn_, dyncfg=dyncfg, kernel_impl=kernel_impl)
            elif t == BLOCK_MOE:
                y, c, s_, a = _moe_block(
                    p_, x_, cfg=cfg, mode=mode, cache=cache_, pos=pos,
                    dyn=dyn_, dyncfg=dyncfg, kernel_impl=kernel_impl)
            elif t == BLOCK_MAMBA:
                y, c, s_, a = _mamba_block(
                    p_, x_, cfg=cfg, mode=mode, cache=cache_, pos=pos,
                    dyn=dyn_, shared=shared)
            elif t == BLOCK_HYBRID_ATTN:
                y, c, s_, a = _mamba_block(
                    p_, x_, cfg=cfg, mode=mode, cache=cache_, pos=pos,
                    dyn=dyn_, shared=shared, with_shared_attn=True,
                    dyncfg=dyncfg, kernel_impl=kernel_impl)
            elif t == BLOCK_MLSTM:
                y, c, s_, a = _mlstm_block(
                    p_, x_, cfg=cfg, mode=mode, cache=cache_, pos=pos,
                    dyn=dyn_)
            elif t == BLOCK_SLSTM:
                y, c, s_, a = _slstm_block(
                    p_, x_, cfg=cfg, mode=mode, cache=cache_, pos=pos,
                    dyn=dyn_)
            elif t == BLOCK_ENC:
                if mode == "decode" or "enc" not in carry_:
                    return carry_, cache_, _zero_stats(cfg), jnp.float32(0.0)
                e, c, s_, a = _enc_block(
                    p_, carry_["enc"], cfg=cfg, mode=mode, cache=cache_,
                    pos=pos, dyn=dyn_, kernel_impl=kernel_impl)
                return {**carry_, "enc": e}, c, s_, a
            elif t == BLOCK_DEC:
                y, c, s_, a = _dec_block(
                    p_, x_, cfg=cfg, mode=mode, cache=cache_, pos=pos,
                    dyn=dyn_, enc_out=carry_.get("enc"),
                    kernel_impl=kernel_impl)
            else:
                raise ValueError(t)
            # shared params are f32 (boundary-psum dtype rule); keep the
            # pipeline carry in its configured dtype
            return {**carry_, "x": y.astype(x_.dtype)}, c, s_, a
        return fn

    def pad_fn(operands):
        p_, carry_, dyn_, cache_ = operands
        return carry_, cache_, _zero_stats(cfg), jnp.float32(0.0)

    operands = (p, carry, dyn, cache)
    if len(types) == 1:
        c2, c, st, al = branch(types[0])(operands)
        active = (tag != BLOCK_PAD)
        c2 = jax.tree.map(lambda new, old: jnp.where(active, new, old),
                          c2, carry)
        c = jax.tree.map(lambda new, old: jnp.where(active, new, old),
                         c, cache) if cache is not None else c
        st = jax.tree.map(lambda a: jnp.where(active, a, jnp.zeros_like(a)),
                          st)
        return c2, c, st, jnp.where(active, al, 0.0)

    branches = [pad_fn] + [branch(t) for t in types]
    idx_map = [0] * (max(types) + 1)
    for i, t in enumerate(types):
        idx_map[t] = i + 1
    idx = jnp.asarray(idx_map, jnp.int32)[jnp.clip(tag, 0, max(types))]
    return jax.lax.switch(idx, branches, operands)


# ---------------------------------------------------------------------------
# Freezable wrapper (runtime backward skip — layer-freezing dynamism)
# ---------------------------------------------------------------------------
def freezable(fn):
    """Wrap out = fn(p, operand) so that when frozen, the backward pass skips
    dW entirely at runtime (lax.cond in the VJP) — true compute saving,
    matching the paper's layer-freezing case.

    ``operand`` must be a pytree of float arrays only (ints encoded as floats
    by the caller) so both cond branches produce identical cotangent types.
    fn must not close over tracers — pass everything via p/operand."""
    @jax.custom_vjp
    def wrapped(frozen, p, operand):
        return fn(p, operand)

    def fwd(frozen, p, operand):
        return fn(p, operand), (frozen, p, operand)

    def bwd(res, g):
        frozen, p, operand = res

        def full(_):
            _, vjp = jax.vjp(fn, p, operand)
            return vjp(g)

        def skip(_):
            _, vjp = jax.vjp(
                lambda o: fn(jax.lax.stop_gradient(p), o), operand)
            (do,) = vjp(g)
            return jax.tree.map(jnp.zeros_like, p), do

        dp, do = jax.lax.cond(frozen > 0, skip, full, None)
        return None, dp, do

    wrapped.defvjp(fwd, bwd)
    return wrapped

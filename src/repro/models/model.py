"""Whole-model assembly on top of the slot-block layer.

Parameters
  params = {
    "embed":  [V, d],
    "head":   [d, V]            (absent when tied),
    "final_norm": [d],
    "stages": {field: [S, L_max, ...]},     # stacked slot params
    "shared": {...},                        # zamba2 shared attn, whisper pos
  }

Assignment (runtime input — rebalancing never recompiles)
  assignment = {
    "tags":       int32 [S, L_max]   BLOCK_* per slot (BLOCK_PAD = empty),
    "num_active": int32 [S],
  }

Dynamism state (runtime input)
  dyn = {"ff_mask": f32 [S, L_max, npb], "frozen": f32 [S, L_max],
         "mod_router": f32 [S, L_max, d]}          (router only when MoD)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (
    BLOCK_PAD, DistConfig, ModelConfig,
)
from repro.dynamics.config import DynamicsConfig
from repro.models import blocks as B
from repro.models.layers import cross_entropy_with_head, rms_norm


# ---------------------------------------------------------------------------
# Assignment
# ---------------------------------------------------------------------------
def uniform_boundaries(num_layers: int, num_stages: int) -> List[int]:
    """Megatron-style uniform contiguous split: layers per stage."""
    base = num_layers // num_stages
    rem = num_layers % num_stages
    return [base + (1 if s < rem else 0) for s in range(num_stages)]


def make_assignment(cfg: ModelConfig, dcfg: DistConfig,
                    layers_per_stage: Optional[Sequence[int]] = None
                    ) -> Dict[str, jax.Array]:
    """Build assignment arrays from a contiguous layers-per-stage split."""
    pattern = cfg.block_pattern()
    S, L_max = dcfg.num_stages, dcfg.slots_for(cfg)
    if layers_per_stage is None:
        layers_per_stage = uniform_boundaries(len(pattern), S)
    assert sum(layers_per_stage) == len(pattern), (
        f"{sum(layers_per_stage)} != {len(pattern)}")
    assert max(layers_per_stage) <= L_max, (
        f"stage over capacity: {max(layers_per_stage)} > {L_max}")
    tags = [[BLOCK_PAD] * L_max for _ in range(S)]
    i = 0
    for s, n in enumerate(layers_per_stage):
        for l in range(n):
            tags[s][l] = pattern[i]
            i += 1
    import numpy as np
    lps = np.array(layers_per_stage)
    depth_base = np.concatenate([[0], np.cumsum(lps)[:-1]])
    return {
        "tags": jnp.asarray(np.array(tags), jnp.int32),
        "num_active": jnp.asarray(lps, jnp.int32),
        "depth_base": jnp.asarray(depth_base, jnp.int32),
    }


def assignment_to_boundaries(assignment) -> List[int]:
    import numpy as np
    return list(np.asarray(assignment["num_active"]))


# ---------------------------------------------------------------------------
# Params / dyn-state / cache construction
# ---------------------------------------------------------------------------
def _dtype_of(dcfg: DistConfig):
    return jnp.bfloat16 if dcfg.param_dtype == "bfloat16" else jnp.float32


# NOTE (dtype rule, see DESIGN.md §3 / pipeline.py): params that are
# replicated over the manual `model` axis (embed, head, final_norm, shared)
# are stored in float32 — their gradient psum crosses the shard_map boundary
# and XLA-CPU's bf16 all-reduce promotion pass crashes.  Stage params (sharded
# over `model`, no boundary psum) stay in the configured dtype (bf16).
def param_spec(cfg: ModelConfig, dcfg: DistConfig) -> Dict[str, Any]:
    dt = _dtype_of(dcfg)
    S, L_max = dcfg.num_stages, dcfg.slots_for(cfg)
    slot = B.slot_param_spec(cfg, dt)
    stages = {k: jax.ShapeDtypeStruct((S, L_max) + v.shape, v.dtype)
              for k, v in slot.items()}
    spec = {
        "embed": jax.ShapeDtypeStruct((cfg.vocab_size, cfg.d_model),
                                      jnp.float32),
        "final_norm": jax.ShapeDtypeStruct((cfg.d_model,), jnp.float32),
        "stages": stages,
        "shared": B.shared_param_spec(cfg, jnp.float32),
    }
    if not cfg.tie_embeddings:
        spec["head"] = jax.ShapeDtypeStruct((cfg.d_model, cfg.vocab_size),
                                            jnp.float32)
    return spec


def init_params(rng: jax.Array, cfg: ModelConfig, dcfg: DistConfig,
                layers_per_stage: Optional[Sequence[int]] = None
                ) -> Dict[str, Any]:
    """Seeded random parameters laid out for ``layers_per_stage`` (uniform
    by default).  A layer's weights come from its global index, not from
    the slot it lands in, so every stage layout starts from the same model;
    padding slots draw from keys of their own."""
    import numpy as np
    dt = _dtype_of(dcfg)
    S, L_max = dcfg.num_stages, dcfg.slots_for(cfg)
    L = cfg.total_blocks()
    k_emb, k_head, k_slots, k_shared = jax.random.split(rng, 4)
    k_layers, k_pad = jax.random.split(k_slots)
    keys = jnp.concatenate([jax.random.split(k_layers, L),
                            jax.random.split(k_pad, S * L_max)])
    idx = L + np.arange(S * L_max).reshape(S, L_max)
    lps = (uniform_boundaries(L, S) if layers_per_stage is None
           else list(layers_per_stage))
    first = 0
    for s, n in enumerate(lps):
        idx[s, :n] = np.arange(first, first + n)
        first += n
    stages = jax.vmap(jax.vmap(lambda k: B.init_slot(k, cfg, dt)))(
        keys[idx])
    params = {
        "embed": jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model),
                                   jnp.float32) * 0.02,
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
        "stages": stages,
        "shared": B.init_shared(k_shared, cfg, jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["head"] = jax.random.normal(
            k_head, (cfg.d_model, cfg.vocab_size), jnp.float32) \
            * cfg.d_model ** -0.5
    return params


def init_dyn(cfg: ModelConfig, dcfg: DistConfig,
             dyncfg: DynamicsConfig) -> Dict[str, jax.Array]:
    S, L_max = dcfg.num_stages, dcfg.slots_for(cfg)
    npb = B.n_prune_blocks(cfg)
    dyn = {
        "ff_mask": jnp.ones((S, L_max, npb), jnp.float32),
        "frozen": jnp.zeros((S, L_max), jnp.float32),
    }
    if dyncfg.uses_mod:
        dyn["mod_router"] = jnp.zeros((S, L_max, cfg.d_model), jnp.float32)
        # enable MoD on every k-th slot is decided by the controller via
        # mod_on (tied to global layer index, migrates with the slot)
        dyn["mod_on"] = jnp.zeros((S, L_max), jnp.float32)
    if dyncfg.expert_relayout and cfg.num_experts:
        # logical expert -> physical kernel group, per slot (identity at
        # init).  Stored float32 so the leaf rides `freezable`'s float-only
        # operand rule; its [S, L_max] leading dims migrate/resize with
        # every other dyn leaf.  Only the pallas grouped path reads it —
        # and per-token math is placement-invariant, so a re-layout never
        # changes the model function (bit-identity tested).
        dyn["expert_map"] = jnp.tile(
            jnp.arange(cfg.num_experts, dtype=jnp.float32),
            (S, L_max, 1))
    return dyn


def dyn_spec(cfg: ModelConfig, dcfg: DistConfig,
             dyncfg: DynamicsConfig) -> Dict[str, Any]:
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        init_dyn(cfg, dcfg, dyncfg))


def cache_spec(cfg: ModelConfig, dcfg: DistConfig, num_micro: int, mb: int,
               cache_len: int) -> Dict[str, Any]:
    """Stacked decode cache: [S, L_max, num_micro, ...per-slot...]."""
    S, L_max = dcfg.num_stages, dcfg.slots_for(cfg)
    slot = B.slot_cache_spec(cfg, mb, cache_len)
    return {k: jax.ShapeDtypeStruct((S, L_max, num_micro) + v.shape, v.dtype)
            for k, v in slot.items()}


def init_cache(cfg: ModelConfig, dcfg: DistConfig, num_micro: int, mb: int,
               cache_len: int) -> Dict[str, jax.Array]:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_spec(cfg, dcfg, num_micro, mb, cache_len))


def paged_cache_spec(cfg: ModelConfig, dcfg: DistConfig, pool_pages: int,
                     page_size: int) -> Dict[str, Any]:
    """Stacked block-paged decode cache: [S, L_max, pool+1, page, kv, hd].

    Unlike the dense cache there is NO per-microbatch axis — all m*B lanes
    of a stage-slot share one physical pool, indexed through page tables
    that live host-side and ride into decode as an input.  Leading
    [S, L_max] means the pool re-splits across elastic resizes through the
    same stage-tree machinery as the dense cache.
    """
    S, L_max = dcfg.num_stages, dcfg.slots_for(cfg)
    slot = B.paged_slot_cache_spec(cfg, pool_pages, page_size)
    return {k: jax.ShapeDtypeStruct((S, L_max) + v.shape, v.dtype)
            for k, v in slot.items()}


def init_paged_cache(cfg: ModelConfig, dcfg: DistConfig, pool_pages: int,
                     page_size: int) -> Dict[str, jax.Array]:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        paged_cache_spec(cfg, dcfg, pool_pages, page_size))


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------
def embed(params, cfg: ModelConfig, tokens, *, prefix_emb=None,
          pos_offset=0):
    """tokens: [b, s] int32 -> carry dict.

    ``prefix_emb``: [b, p, d] precomputed modality embeddings (VLM patches /
    audio frames) prepended to the token stream (VLM) or used as the encoder
    stream (whisper)."""
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.is_encdec:
        # decoder learned positions; encoder stream = frame stub + sinusoid
        s = tokens.shape[1]
        pos = params["shared"]["dec_pos"][pos_offset:pos_offset + s] \
            if isinstance(pos_offset, int) else jax.lax.dynamic_slice_in_dim(
                params["shared"]["dec_pos"], pos_offset, 1, 0)
        x = x + pos[None].astype(x.dtype)
        carry = {"x": x}
        if prefix_emb is not None:
            enc = prefix_emb + _sinusoidal(prefix_emb.shape[1],
                                           cfg.d_model).astype(x.dtype)[None]
            carry["enc"] = enc
        return carry
    if cfg.family == "vlm" and prefix_emb is not None:
        x = jnp.concatenate([prefix_emb.astype(x.dtype), x], axis=1)
    return {"x": x}


def _sinusoidal(length: int, channels: int):
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    dim = jnp.arange(channels // 2, dtype=jnp.float32)[None, :]
    inv = jnp.exp(-jnp.log(10000.0) * dim / (channels // 2))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def lm_loss(params, cfg: ModelConfig, h, labels, label_mask=None,
            vocab_axis=None, vocab_offset=0):
    """h: [b, s, d] final hidden -> mean xent.  When ``vocab_axis`` is set the
    head is vocab-sharded over that mesh axis (vocab-parallel loss)."""
    hn = rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = params.get("head")
    if head is None:
        head = params["embed"].T
    return cross_entropy_with_head(
        hn, head, labels, label_mask=label_mask, axis_name=vocab_axis,
        vocab_offset=vocab_offset)


def lm_logits(params, cfg: ModelConfig, h):
    hn = rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = params.get("head")
    if head is None:
        head = params["embed"].T
    return (hn @ head).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Single-device sequential reference (oracle for pipeline equivalence tests)
# ---------------------------------------------------------------------------
def reference_loss(cfg: ModelConfig, dcfg: DistConfig,
                   dyncfg: DynamicsConfig, params, assignment, dyn, tokens,
                   labels, label_mask=None, prefix_emb=None):
    """Apply all blocks in global order on one device; same math as the
    pipelined loss (excluding MoE aux loss weighting, added identically)."""
    import numpy as np
    from repro.pipeline.pipeline import AUX_LOSS_COEF
    tags_np = np.asarray(assignment["tags"])
    carry = embed(params, cfg, tokens, prefix_emb=prefix_emb)
    dt = _dtype_of(dcfg)
    carry["x"] = carry["x"].astype(dt)
    if "enc" in carry:
        carry["enc"] = carry["enc"].astype(dt)
    if dyncfg.uses_early_exit:
        carry["exited"] = jnp.zeros(carry["x"].shape[:2], jnp.float32)
    pos = jnp.arange(carry["x"].shape[1])
    aux_total = jnp.float32(0.0)
    depth = 0
    prefix = cfg.num_patches if cfg.family == "vlm" else 0
    for s in range(tags_np.shape[0]):
        for l in range(tags_np.shape[1]):
            if tags_np[s, l] == BLOCK_PAD:
                continue
            p = jax.tree.map(lambda a: a[s, l], params["stages"])
            dyn_slot = jax.tree.map(lambda a: a[s, l], dyn)
            carry_in = carry
            carry, _, stats, aux = B.apply_block(
                cfg, dyncfg, "train", p, params["shared"], carry,
                jnp.int32(tags_np[s, l]), dyn_slot, None, pos,
                kernel_impl=dcfg.kernel_impl)
            if dyncfg.uses_mod:
                from repro.models.model import _mod_wrap
                carry, _ = _mod_wrap(cfg, dyncfg, dyn_slot, carry_in, carry)
            if dyncfg.uses_early_exit:
                carry, _ = _ee_update(cfg, dyncfg, carry_in, carry,
                                      jnp.float32(depth)
                                      / max(1, cfg.total_blocks()))
            aux_total = aux_total + aux
            depth += 1
    h = carry["x"][:, prefix:]
    if label_mask is None:
        label_mask = jnp.ones(labels.shape, jnp.float32)
    hn = rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = params.get("head")
    if head is None:
        head = params["embed"].T
    logits = hn.astype(jnp.float32) @ head.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    loss = jnp.sum((lse - ll) * label_mask) / jnp.maximum(
        jnp.sum(label_mask), 1.0)
    aux = aux_total / max(1, cfg.total_blocks())
    return loss + AUX_LOSS_COEF * aux


# ---------------------------------------------------------------------------
# Stage executor
# ---------------------------------------------------------------------------
def _mod_wrap(cfg, dyncfg, dyn_slot, carry_in, carry_out):
    """Mixture-of-Depths: route only top-capacity tokens through the block.

    Applied as an output mix: tokens not selected keep their input
    activation (residual bypass).  Selection comes from the slot's router.
    The *compute* saving is modelled at cost level (capacity fraction);
    the Pallas/serving path can gather-compact instead."""
    x_in, x_out = carry_in["x"], carry_out["x"]
    b, s, d = x_in.shape
    k = max(1, int(dyncfg.mod_capacity * s))
    scores = jnp.einsum("bsd,d->bs", x_in.astype(jnp.float32),
                        dyn_slot["mod_router"])
    thresh = jax.lax.top_k(scores, k)[0][:, -1:]
    sel = (scores >= thresh).astype(x_in.dtype)[..., None]
    on = dyn_slot["mod_on"] > 0
    mix = jnp.where(sel > 0, x_out, x_in)
    new_x = jnp.where(on, mix, x_out)
    frac = jnp.where(on, jnp.float32(k / s), 1.0)
    return {**carry_out, "x": new_x}, frac


def _ee_update(cfg, dyncfg, carry_in, carry_out, depth_frac):
    """Early exit: tokens whose hidden state has saturated stop updating.

    carry holds "exited" [b, s]; exited tokens keep their activation frozen
    (the cost model/simulator accounts the skipped compute)."""
    x_in, x_out = carry_in["x"], carry_out["x"]
    exited = carry_in.get("exited")
    if exited is None:
        return carry_out, jnp.float32(1.0)
    xi = x_in.astype(jnp.float32)
    xo = x_out.astype(jnp.float32)
    cos = jnp.sum(xi * xo, -1) / jnp.maximum(
        jnp.linalg.norm(xi, axis=-1) * jnp.linalg.norm(xo, axis=-1), 1e-6)
    can_exit = depth_frac >= dyncfg.ee_min_layer_frac
    newly = (cos > dyncfg.ee_threshold) & can_exit
    exited_new = jnp.maximum(exited, newly.astype(exited.dtype))
    x_keep = jnp.where(exited[..., None] > 0, x_in, x_out)
    active_frac = 1.0 - jnp.mean(exited)
    return {**carry_out, "x": x_keep, "exited": exited_new}, active_frac


def stage_forward(cfg: ModelConfig, dcfg: DistConfig, dyncfg: DynamicsConfig,
                  mode: str, stage_params, shared, tags, dyn_stage, carry,
                  cache_stage, pos, stage_depth_base):
    """Run one stage's L_max slots over the carry.

    stage_params: {field: [L_max, ...]}; tags: [L_max]; cache_stage: stacked
    per-slot cache or None.  Returns (carry, cache, stats [L_max, ...],
    aux_loss)."""
    L_max = tags.shape[0]
    total = cfg.total_blocks()

    def slot_fn(l, carry, cache_slot):
        p = jax.tree.map(lambda a: a[l], stage_params)
        dyn_slot = jax.tree.map(lambda a: a[l], dyn_stage)
        tag = tags[l]

        active = tag != BLOCK_PAD

        def run(carry):
            out_carry, out_cache, stats, aux = B.apply_block(
                cfg, dyncfg, mode, p, shared, carry, tag, dyn_slot,
                cache_slot, pos, kernel_impl=dcfg.kernel_impl)
            extra = jnp.float32(1.0)
            # EE/MoD wrappers only act on real (non-pad) slots
            if dyncfg.uses_mod and mode == "train":
                wrapped, extra = _mod_wrap(cfg, dyncfg, dyn_slot, carry,
                                           out_carry)
                out_carry = jax.tree.map(
                    lambda a, b: jnp.where(active, a, b), wrapped, out_carry)
            if dyncfg.uses_early_exit:
                depth_frac = (stage_depth_base + l).astype(jnp.float32) \
                    / max(1, total)
                wrapped, extra = _ee_update(cfg, dyncfg, carry, out_carry,
                                            depth_frac)
                out_carry = jax.tree.map(
                    lambda a, b: jnp.where(active, a, b), wrapped, out_carry)
            return out_carry, out_cache, stats, aux, extra

        if dyncfg.uses_freezing and mode == "train":
            # operand carries every traced input as floats (freezable's VJP
            # requires float-only cotangent trees and no tracer closures)
            operand = (carry, shared, dyn_slot, tag.astype(jnp.float32),
                       pos.astype(jnp.float32))

            def frz_fn(p_, op):
                carry_, shared_, dyn_slot_, tag_f, pos_f = op
                out_carry, _, stats, aux = B.apply_block(
                    cfg, dyncfg, mode, p_, shared_, carry_,
                    tag_f.astype(jnp.int32), dyn_slot_, None, pos_f,
                    kernel_impl=dcfg.kernel_impl)
                return out_carry, stats, aux

            out_carry, stats, aux = B.freezable(frz_fn)(
                dyn_slot["frozen"], p, operand)
            return out_carry, cache_slot, stats, aux, jnp.float32(1.0)
        return run(carry)

    if dcfg.remat == "block" and mode == "train":
        # per-slot rematerialization: the backward keeps each slot's input
        # carry and recomputes the block's internals from it
        slot_fn = jax.checkpoint(slot_fn, prevent_cse=False)

    if dcfg.slot_exec == "bounded_loop" and not dcfg.unroll_slots:
        # data-dependent trip count: a lightly-loaded stage does less work
        stats0 = jax.tree.map(
            lambda s: jnp.zeros((L_max,) + s.shape, s.dtype),
            B.stats_spec(cfg))
        num_active = jnp.sum((tags != BLOCK_PAD).astype(jnp.int32))

        def body(l, state):
            carry, cache, stats_acc, aux_acc = state
            cache_slot = (None if cache is None else
                          jax.tree.map(lambda a: a[l], cache))
            carry, new_cache, stats, aux, extra = slot_fn(l, carry,
                                                          cache_slot)
            if cache is not None:
                cache = jax.tree.map(
                    lambda full, ns: jax.lax.dynamic_update_index_in_dim(
                        full, ns, l, 0), cache, new_cache)
            stats_acc = jax.tree.map(
                lambda acc, s: jax.lax.dynamic_update_index_in_dim(
                    acc, s, l, 0), stats_acc, stats)
            return carry, cache, stats_acc, aux_acc + aux

        carry, cache_stage, stats, aux = jax.lax.fori_loop(
            0, num_active, body, (carry, cache_stage, stats0,
                                  jnp.float32(0.0)))
        return carry, cache_stage, stats, aux

    # masked scan (default) or full unroll
    def scan_body(state, inp):
        carry, aux_acc = state
        l, cache_slot = inp
        cache_slot = None if cache_stage is None else cache_slot
        carry, new_cache, stats, aux, extra = slot_fn(l, carry, cache_slot)
        return (carry, aux_acc + aux), (new_cache, stats)

    ls = jnp.arange(L_max)
    if dcfg.unroll_slots:
        outs = []
        state = (carry, jnp.float32(0.0))
        for l in range(L_max):
            cache_slot = (None if cache_stage is None else
                          jax.tree.map(lambda a: a[l], cache_stage))
            state, out = scan_body(state, (ls[l], cache_slot))
            outs.append(out)
        (carry, aux) = state
        new_caches = (None if cache_stage is None else jax.tree.map(
            lambda *xs: jnp.stack(xs), *[o[0] for o in outs]))
        stats = jax.tree.map(lambda *xs: jnp.stack(xs),
                             *[o[1] for o in outs])
    else:
        cache_xs = cache_stage
        if cache_stage is None:
            (carry, aux), (new_caches, stats) = jax.lax.scan(
                lambda st, l: scan_body(st, (l, None)),
                (carry, jnp.float32(0.0)), ls)
            new_caches = None
        else:
            (carry, aux), (new_caches, stats) = jax.lax.scan(
                scan_body, (carry, jnp.float32(0.0)), (ls, cache_xs))
    return carry, new_caches, stats, aux

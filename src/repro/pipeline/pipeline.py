"""Pipeline-parallel runtime: GPipe-style microbatch schedule over the
``model`` mesh axis via jax.shard_map (manual) with ``data``/``pod`` axes left
to XLA SPMD (auto) — FSDP/DP/vocab sharding ride on jit-level in_shardings.
DP axes of size 1 are made manual too: a region with no auto axis is what
Mosaic (Pallas TPU) kernels and host callbacks require.

The forward schedule is differentiable; jax.grad generates the reverse
pipeline (backward ppermutes run in the transposed direction), so 1F1B-like
interleaving is realised by XLA's scheduler within each tick.

dtype rule (XLA-CPU workaround, documented in DESIGN.md): any value whose
cotangent is psum'd over the *manual* axis at the shard_map boundary must be
float32 — i.e. embed/head/shared/final_norm params.  Stage params (sharded
over ``model``) stay bfloat16.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import DistConfig, ModelConfig
from repro.dynamics.config import DynamicsConfig
from repro.models import blocks as B
from repro.models import model as M

AUX_LOSS_COEF = 0.01


def _auto_axes(mesh) -> tuple:
    """The DP axes XLA's SPMD partitioner splits (size > 1); every other
    axis of the pipeline's shard_map is manual."""
    return tuple(a for a in mesh.axis_names
                 if a != "model" and mesh.shape[a] > 1)


def _shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` manual over the pipeline axis and the size-1 DP
    axes.  The compiled Pallas kernels cannot be partitioned by XLA, so on
    the TPU they run only where ``_auto_axes(mesh)`` is empty."""
    manual = set(mesh.axis_names) - set(_auto_axes(mesh))
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=manual,
                         check_vma=False)


@dataclasses.dataclass(frozen=True)
class PipelineShapes:
    """Concrete global shapes of one pipeline execution."""
    num_micro: int
    mb_global: int          # per-microbatch global batch (sharded over data)
    seq: int                # token positions fed to the decoder stream
    prefix: int = 0         # VLM patch prefix length (prepended)
    enc_seq: int = 0        # whisper encoder frames
    cache_len: int = 0      # decode cache capacity

    @property
    def seq_total(self) -> int:
        return self.seq + self.prefix


def plan_shapes(cfg: ModelConfig, dcfg: DistConfig, shape_kind: str,
                seq_len: int, global_batch: int, dp_degree: int
                ) -> PipelineShapes:
    """Derive microbatching from the shape cell and the mesh's DP degree."""
    if global_batch < dp_degree:
        # tiny-batch cells (e.g. long_500k B=1): batch not DP-shardable;
        # other dims (kv heads / cache capacity) shard over data instead
        shp = PipelineShapes(
            num_micro=1, mb_global=global_batch, seq=seq_len,
            prefix=cfg.num_patches if cfg.family == "vlm" else 0,
            enc_seq=cfg.encoder_seq if cfg.is_encdec else 0,
            cache_len=seq_len if shape_kind in ("decode", "prefill") else 0)
        return shp
    per_replica = max(1, global_batch // dp_degree)
    num_micro = min(per_replica, 4 * dcfg.num_stages)
    mb = max(1, per_replica // num_micro)
    num_micro = max(1, per_replica // mb)
    prefix = cfg.num_patches if cfg.family == "vlm" else 0
    enc_seq = cfg.encoder_seq if cfg.is_encdec else 0
    cache_len = seq_len if shape_kind in ("decode", "prefill") else 0
    return PipelineShapes(
        num_micro=num_micro, mb_global=mb * dp_degree,
        seq=seq_len, prefix=prefix, enc_seq=enc_seq, cache_len=cache_len)


def _ring(n):
    return [(i, (i + 1) % n) for i in range(n)]


def _make_stamp_or_none(stage_timer):
    """Stage-boundary host stamp for in-step timing; None when disabled
    (the common case — zero ops are added to the step)."""
    if stage_timer is None:
        return None
    from repro.obs.timing import make_stamp
    return make_stamp(stage_timer)


def _make_pin(mesh, dcfg):
    """Sharding pin for pipeline-carry leaves: batch dim over the DP axes.

    XLA's auto propagation sometimes assigns conflicting shardings to the
    carry across while-loop iterations and falls back to full
    rematerialization (replication) — pinning dim 0 at every tick boundary
    keeps the layout stable.  No-op when the batch dim is not divisible or
    no DP axis is left to the partitioner."""
    from jax.sharding import NamedSharding
    daxes = _auto_axes(mesh)
    if not daxes or not dcfg.pin_carry_sharding:
        return lambda tree: tree
    dp = 1
    for a in daxes:
        dp *= mesh.shape[a]
    spec_axes = daxes if len(daxes) > 1 else daxes[0]

    def pin(x):
        if x.ndim >= 1 and x.shape[0] % dp == 0 and x.shape[0] >= dp:
            # the constraint must be built on the *context* (abstract) mesh:
            # inside shard_map 'model' is Manual there, not Auto
            am = jax.sharding.get_abstract_mesh()
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(am, P(spec_axes,
                                       *([None] * (x.ndim - 1)))))
        return x

    return lambda tree: jax.tree.map(pin, tree)


def _stage_slice(tree):
    return jax.tree.map(lambda a: a[0], tree)


def _init_carry(cfg, dyncfg, shapes: PipelineShapes, dtype, decode=False):
    mbg = shapes.mb_global
    s = 1 if decode else shapes.seq_total
    carry = {"x": jnp.zeros((mbg, s, cfg.d_model), dtype)}
    if cfg.is_encdec and not decode:
        carry["enc"] = jnp.zeros((mbg, shapes.enc_seq, cfg.d_model), dtype)
    if dyncfg.uses_early_exit and not decode:
        carry["exited"] = jnp.zeros((mbg, s), jnp.float32)
    return carry


# ---------------------------------------------------------------------------
# Training / evaluation loss
# ---------------------------------------------------------------------------
def build_loss_fn(cfg: ModelConfig, dcfg: DistConfig, dyncfg: DynamicsConfig,
                  mesh, shapes: PipelineShapes, mode: str = "train",
                  stage_timer=None):
    """Returns loss_fn(params, assignment, dyn, batch) -> (loss, stats).

    batch = {"tokens": [m, B, seq] i32, "labels": [m, B, seq] i32,
             "label_mask": [m, B, seq] f32, optional "prefix_emb"
             [m, B, P, d] f32, optional "frames" [m, B, enc_seq, d] f32}.
    stats: per-stage per-slot profiler aggregates {field: [S, L_max, ...]}.
    stage_timer: optional ``obs.timing.StageTimer`` — when set, every tick
    stamps host timestamps at the stage boundaries (in-step stage timing,
    DESIGN.md §15); numerically a no-op.
    """
    S = dcfg.num_stages
    dt = jnp.bfloat16 if dcfg.param_dtype == "bfloat16" else jnp.float32

    pin = _make_pin(mesh, dcfg)
    stamp = _make_stamp_or_none(stage_timer)

    def pipe(params, assignment, dyn, batch):
        stages = _stage_slice(params["stages"])
        tags = assignment["tags"][0]
        dyn_s = _stage_slice(dyn)
        shared = params["shared"]
        idx = jax.lax.axis_index("model")
        n = mesh.shape["model"]      # static axis extent (version-portable)
        T = shapes.num_micro + S - 1
        pos = jnp.arange(shapes.seq_total)
        depth_base = assignment["depth_base"][0]

        buf = _init_carry(cfg, dyncfg, shapes, dt)
        aux_acc = jnp.float32(0.0)
        stats0 = jax.tree.map(
            lambda sds: jnp.zeros((tags.shape[0],) + sds.shape, sds.dtype),
            B.stats_spec(cfg))

        def ingest(t):
            ti = jnp.clip(t, 0, shapes.num_micro - 1)
            tok = jax.lax.dynamic_index_in_dim(batch["tokens"], ti, 0, False)
            if os.environ.get("REPRO_DEBUG_NO_EMBED"):
                return jax.tree.map(jnp.zeros_like, buf)
            prefix = None
            if "prefix_emb" in batch:
                prefix = jax.lax.dynamic_index_in_dim(
                    batch["prefix_emb"], ti, 0, False).astype(dt)
            if "frames" in batch:
                prefix = jax.lax.dynamic_index_in_dim(
                    batch["frames"], ti, 0, False).astype(dt)
            carry = M.embed(params, cfg, tok, prefix_emb=prefix)
            carry["x"] = carry["x"].astype(dt)
            if "enc" in carry:
                carry["enc"] = carry["enc"].astype(dt)
            if dyncfg.uses_early_exit:
                carry["exited"] = jnp.zeros(
                    (tok.shape[0], shapes.seq_total), jnp.float32)
            return carry

        def stage_fn(carry, stats_acc_unused=None):
            return M.stage_forward(
                cfg, dcfg, dyncfg, mode, stages, shared, tags, dyn_s, carry,
                None, pos, depth_base)

        if dcfg.remat == "full":
            stage_fn = jax.checkpoint(stage_fn)

        def tick(state, t):
            buf, aux_acc, stats_acc = state
            # embedding gather (and its vocab-shard collective) runs on
            # stage 0 only — real lax.cond branch, not a masked select
            fresh = jax.lax.cond(
                idx == 0, ingest,
                lambda _t: jax.tree.map(jnp.zeros_like, buf), t)
            carry = jax.tree.map(
                lambda a, b: jnp.where(idx == 0, a, b), fresh, buf)
            if stamp is not None:
                carry = {**carry, "x": stamp(carry["x"], idx, jnp.int32(0))}
            carry, _, stats, aux = stage_fn(carry)
            if stamp is not None:
                carry = {**carry, "x": stamp(carry["x"], idx, jnp.int32(1))}
            # ---- last stage emits this tick's finished microbatch hidden;
            # the loss (head matmul) runs ONCE after the schedule, so its
            # logits are never live across ticks (memory) and probes count
            # it per-microbatch, not per-tick (roofline accuracy)
            emit_valid = ((t - (n - 1)) >= 0) & (idx == n - 1)
            h_out = jnp.where(emit_valid,
                              carry["x"][:, shapes.prefix:],
                              jnp.zeros_like(carry["x"][:, shapes.prefix:]))
            mvalid = ((t - idx) >= 0) & ((t - idx) < shapes.num_micro)
            aux_acc = aux_acc + jnp.where(mvalid, aux, 0.0)
            stats_acc = jax.tree.map(
                lambda acc, s_: acc + jnp.where(mvalid, s_,
                                                jnp.zeros_like(s_)),
                stats_acc, stats)
            carry = pin(carry)
            buf = jax.tree.map(
                lambda a: jax.lax.ppermute(a, "model", _ring(n)), carry)
            return (buf, aux_acc, stats_acc), pin({"h": h_out})["h"]

        state = (buf, aux_acc, stats0)
        if dcfg.unroll_ticks:
            hs = []
            for t in range(T):
                state, h_out = tick(state, jnp.int32(t))
                hs.append(h_out)
            h_seq = jnp.stack(hs[S - 1:S - 1 + shapes.num_micro])
        else:
            state, hs = jax.lax.scan(tick, state, jnp.arange(T))
            h_seq = jax.lax.slice_in_dim(hs, S - 1, S - 1 + shapes.num_micro,
                                         axis=0)
        _, aux_acc, stats_acc = state

        # ---- vocab loss on the last stage only (single real branch)
        def full_loss(h_seq):
            def one(carry_acc, inp):
                h, lab, lmask = inp

                def body(h, lab, lmask):
                    hn = M.rms_norm(h, params["final_norm"], cfg.norm_eps)
                    head = params.get("head")
                    if head is None:
                        head = params["embed"].T
                    logits = hn.astype(jnp.float32) @ head.astype(
                        jnp.float32)
                    lse = jax.nn.logsumexp(logits, axis=-1)
                    ll = jnp.take_along_axis(logits, lab[..., None],
                                             -1)[..., 0]
                    return (jnp.sum((lse - ll) * lmask), jnp.sum(lmask))

                nll, cnt = jax.checkpoint(body)(h, lab, lmask)
                return (carry_acc[0] + nll, carry_acc[1] + cnt), None

            acc0 = (jnp.float32(0.0), jnp.float32(0.0))
            if dcfg.unroll_ticks:
                acc = acc0
                for i in range(shapes.num_micro):
                    acc, _ = one(acc, (h_seq[i], batch["labels"][i],
                                       batch["label_mask"][i]))
            else:
                acc, _ = jax.lax.scan(
                    one, acc0,
                    (h_seq, batch["labels"], batch["label_mask"]))
            return acc

        if os.environ.get("REPRO_DEBUG_NO_LOSS"):
            nll = jnp.sum(h_seq.astype(jnp.float32) ** 2)
            cnt = jnp.float32(1.0)
        else:
            nll, cnt = jax.lax.cond(
                idx == n - 1, full_loss,
                lambda _h: (jnp.float32(0.0), jnp.float32(0.0)), h_seq)
        loss = jax.lax.psum(nll, "model") / jnp.maximum(
            jax.lax.psum(cnt, "model"), 1.0)
        aux = jax.lax.psum(aux_acc, "model") / (
            shapes.num_micro * max(1, cfg.total_blocks()))
        loss = loss + AUX_LOSS_COEF * aux
        return loss, stats_acc

    in_specs = (
        {"embed": P(), "final_norm": P(), "shared": P(),
         "stages": P("model"),
         **({"head": P()} if not cfg.tie_embeddings else {})},
        P("model"),       # assignment arrays lead with stage axis
        P("model"),       # dyn arrays lead with stage axis
        P(),              # batch replicated over model (sharded over data)
    )
    return _shard_map(
        pipe, mesh=mesh, in_specs=in_specs,
        out_specs=(P(), P("model")))


# ---------------------------------------------------------------------------
# Decode (serve_step): one token for every request, pipelined microbatches
# ---------------------------------------------------------------------------
def build_decode_fn(cfg: ModelConfig, dcfg: DistConfig,
                    dyncfg: DynamicsConfig, mesh, shapes: PipelineShapes,
                    stage_timer=None, *, paged: bool = False,
                    temperature: float = 0.0,
                    num_micro: Optional[int] = None):
    """Returns decode_fn(params, assignment, dyn, cache, tokens, pos[,
    page_table][, seeds])
    -> (next_ids [m, B] i32, logprobs [m, B] f32, new_cache,
    moe_drop_sum f32 — MoE capacity-drop fractions summed over
    (moe slot, microbatch) passes; 0 for non-MoE archs).

    tokens: [m, B] current token per request; pos: scalar position (every
    lane at the same point, the one-shot serving path) or [m, B] per-lane
    absolute positions (continuous batching: each request decodes at its
    own position; cache writes and attention masks are per-lane).
    cache: stacked {field: [S, L_max, m, B, ...]}.

    ``paged``: the cache is the block-paged pool {kp, vp: [S, L_max,
    pool+1, page, kv, hd]} (no micro axis — all lanes share it) and the fn
    takes ``page_table`` [m, B, J] int32 (-1 = unmapped) as an extra arg;
    pool writes on invalid ticks are steered into the trash block instead
    of being masked out after the fact.

    ``temperature``: > 0 adds a ``seeds`` [m, B] int32 arg and samples the
    emitted token from softmax(logits / temperature) with a per-lane key;
    0 keeps the exact argmax graph (bit-identical to before).

    ``num_micro``: compile-time live microbatch count (defaults to
    shapes.num_micro).  Inputs/outputs keep their full [num_micro_full, B]
    shapes, but the tick loop runs only ``num_micro + S - 1`` ticks so
    all-empty trailing microbatch rows cost nothing.
    """
    S = dcfg.num_stages
    dt = jnp.bfloat16 if dcfg.param_dtype == "bfloat16" else jnp.float32
    m_live = shapes.num_micro if num_micro is None else num_micro
    if not (1 <= m_live <= shapes.num_micro):
        raise ValueError(f"num_micro={m_live} outside [1, "
                         f"{shapes.num_micro}]")

    pin = _make_pin(mesh, dcfg)
    stamp = _make_stamp_or_none(stage_timer)

    def pipe(params, assignment, dyn, cache, tokens, pos, *extra):
        ei = 0
        if paged:
            page_table = extra[ei]
            ei += 1
        if temperature > 0.0:
            seeds = extra[ei]
            ei += 1
        stages = _stage_slice(params["stages"])
        tags = assignment["tags"][0]
        dyn_s = _stage_slice(dyn)
        cache_s = _stage_slice(cache)           # {field: [L_max, m, B, ...]}
        shared = params["shared"]
        idx = jax.lax.axis_index("model")
        n = mesh.shape["model"]      # static axis extent (version-portable)
        m = m_live
        T = m + S - 1
        per_lane = jnp.ndim(pos) == 2           # [m, B] positions
        if per_lane and cfg.is_encdec:
            raise NotImplementedError(
                "per-lane decode positions need a per-lane dec_pos gather; "
                "encoder-decoder serving uses the scalar-pos path")
        if paged and not per_lane:
            raise NotImplementedError(
                "paged decode requires per-lane positions")

        buf = _init_carry(cfg, dyncfg, shapes, dt, decode=True)
        ids_out = jnp.zeros((shapes.num_micro, shapes.mb_global), jnp.int32)
        lp_out = jnp.zeros((shapes.num_micro, shapes.mb_global),
                           jnp.float32)
        drop_out = jnp.float32(0.0)   # MoE capacity-drop fraction, summed
        #   over (moe slot, microbatch) passes — host side divides by the
        #   pass count; zero for non-MoE archs

        def ingest(t):
            ti = jnp.clip(t, 0, m - 1)
            tok = jax.lax.dynamic_index_in_dim(tokens, ti, 0, False)
            x = jnp.take(params["embed"].astype(jnp.float32), tok, axis=0)
            if cfg.is_encdec:
                pe = jax.lax.dynamic_slice_in_dim(
                    params["shared"]["dec_pos"].astype(jnp.float32),
                    jnp.clip(pos, 0, cfg.max_seq_len - 1), 1, 0)
                x = x + pe[0][None]
            return {"x": x[:, None, :].astype(dt)}

        def tick(state, t):
            buf, cache_s, ids_out, lp_out, drop_out = state
            mi = jnp.clip(t - idx, 0, m - 1)
            mvalid = ((t - idx) >= 0) & ((t - idx) < m)
            fresh = jax.lax.cond(
                idx == 0, ingest,
                lambda _t: jax.tree.map(jnp.zeros_like, buf), t)
            carry = jax.tree.map(
                lambda a, b: jnp.where(idx == 0, a, b), fresh, buf)
            if paged:
                # pool leaves have no micro axis; thread the tick's page
                # table + write-ok flag in as cache entries so they ride
                # the per-slot gather / masked scan like any other leaf
                pt_mb = jax.lax.dynamic_index_in_dim(
                    page_table, mi, 0, False)          # [B, J]
                L_m = tags.shape[0]
                cache_mb = dict(cache_s)
                cache_mb["pt"] = jnp.broadcast_to(
                    pt_mb[None], (L_m,) + pt_mb.shape)
                cache_mb["wok"] = jnp.broadcast_to(
                    mvalid.astype(jnp.int32), (L_m,))
            else:
                cache_mb = jax.tree.map(lambda a: a[:, mi], cache_s)
            pos_mb = (jax.lax.dynamic_index_in_dim(pos, mi, 0, False)
                      if per_lane else pos)
            if stamp is not None:
                carry = {**carry, "x": stamp(carry["x"], idx, jnp.int32(0))}
            carry, new_cache_mb, st, _ = M.stage_forward(
                cfg, dcfg, dyncfg, "decode", stages, shared, tags, dyn_s,
                carry, cache_mb, pos_mb, idx * tags.shape[0])
            if stamp is not None:
                carry = {**carry, "x": stamp(carry["x"], idx, jnp.int32(1))}
            drop_out = drop_out + (jnp.sum(st["moe_dropped"])
                                   * mvalid.astype(jnp.float32))
            if paged:
                # invalid-tick writes already landed in the trash block
                # (wok gating), so the new pool is taken as-is
                cache_s = {f: new_cache_mb[f] for f in cache_s}
            else:
                cache_s = jax.tree.map(
                    lambda full, nc, old:
                    jax.lax.dynamic_update_index_in_dim(
                        full, jnp.where(mvalid, nc, old), mi, 1),
                    cache_s, new_cache_mb, cache_mb)
            # emit at last stage only (real branch; head matmul skipped
            # elsewhere)
            li = jnp.clip(t - (n - 1), 0, m - 1)
            emit = ((t - (n - 1)) >= 0) & (idx == n - 1)

            def do_head(h):
                logits = M.lm_logits(params, cfg, h)
                if temperature > 0.0:
                    # per-lane sampling: each lane folds its own seed into
                    # a key, so lanes are independent and replayable
                    sd = jax.lax.dynamic_index_in_dim(seeds, li, 0, False)

                    def samp(s_, lg):
                        return jax.random.categorical(
                            jax.random.PRNGKey(s_),
                            lg / jnp.float32(temperature))
                    nid_ = jax.vmap(samp)(sd, logits).astype(jnp.int32)
                else:
                    nid_ = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                lp_ = jax.nn.log_softmax(logits, axis=-1)
                return nid_, jnp.take_along_axis(lp_, nid_[:, None],
                                                 -1)[:, 0]

            nid, nlp = jax.lax.cond(
                emit, do_head,
                lambda h: (jnp.zeros((h.shape[0],), jnp.int32),
                           jnp.zeros((h.shape[0],), jnp.float32)),
                carry["x"][:, 0])
            ids_out = jax.lax.dynamic_update_index_in_dim(
                ids_out, jnp.where(emit, nid, ids_out[li]), li, 0)
            lp_out = jax.lax.dynamic_update_index_in_dim(
                lp_out, jnp.where(emit, nlp, lp_out[li]), li, 0)
            carry = pin(carry)
            buf = jax.tree.map(
                lambda a: jax.lax.ppermute(a, "model", _ring(n)), carry)
            return (buf, cache_s, ids_out, lp_out, drop_out), None

        if dcfg.unroll_ticks:
            state = (buf, cache_s, ids_out, lp_out, drop_out)
            for t in range(T):
                state, _ = tick(state, jnp.int32(t))
            (buf, cache_s, ids_out, lp_out, drop_out) = state
        else:
            (buf, cache_s, ids_out, lp_out, drop_out), _ = jax.lax.scan(
                tick, (buf, cache_s, ids_out, lp_out, drop_out),
                jnp.arange(T))
        # ids live on the last stage; broadcast (tiny)
        ids_out = jax.lax.psum(
            jnp.where(idx == n - 1, ids_out, jnp.zeros_like(ids_out)),
            "model")
        lp_out = jax.lax.psum(
            jnp.where(idx == n - 1, lp_out, jnp.zeros_like(lp_out)), "model")
        drop_out = jax.lax.psum(drop_out, "model")
        new_cache = jax.tree.map(lambda a: a[None], cache_s)
        return ids_out, lp_out, new_cache, drop_out

    n_extra = int(paged) + int(temperature > 0.0)
    in_specs = (
        {"embed": P(), "final_norm": P(), "shared": P(),
         "stages": P("model"),
         **({"head": P()} if not cfg.tie_embeddings else {})},
        P("model"), P("model"), P("model"), P(), P()) + (P(),) * n_extra
    return _shard_map(
        pipe, mesh=mesh, in_specs=in_specs,
        out_specs=(P(), P(), P("model"), P()))


# ---------------------------------------------------------------------------
# Prefill: forward pass that fills the decode cache
# ---------------------------------------------------------------------------
def build_prefill_fn(cfg: ModelConfig, dcfg: DistConfig,
                     dyncfg: DynamicsConfig, mesh, shapes: PipelineShapes,
                     stage_timer=None):
    """Returns prefill_fn(params, assignment, dyn, cache, batch)
    -> (last_ids [m, B] i32, new_cache, moe_drop_sum f32)."""
    S = dcfg.num_stages
    dt = jnp.bfloat16 if dcfg.param_dtype == "bfloat16" else jnp.float32

    pin = _make_pin(mesh, dcfg)
    stamp = _make_stamp_or_none(stage_timer)

    def pipe(params, assignment, dyn, cache, batch):
        stages = _stage_slice(params["stages"])
        tags = assignment["tags"][0]
        dyn_s = _stage_slice(dyn)
        cache_s = _stage_slice(cache)
        shared = params["shared"]
        idx = jax.lax.axis_index("model")
        n = mesh.shape["model"]      # static axis extent (version-portable)
        m = shapes.num_micro
        T = m + S - 1
        pos = jnp.arange(shapes.seq_total)

        buf = _init_carry(cfg, dyncfg, shapes, dt)
        ids_out = jnp.zeros((m, shapes.mb_global), jnp.int32)
        drop_out = jnp.float32(0.0)   # MoE capacity drops, as in decode

        def ingest(t):
            ti = jnp.clip(t, 0, m - 1)
            tok = jax.lax.dynamic_index_in_dim(batch["tokens"], ti, 0, False)
            prefix = None
            if "prefix_emb" in batch:
                prefix = jax.lax.dynamic_index_in_dim(
                    batch["prefix_emb"], ti, 0, False).astype(dt)
            if "frames" in batch:
                prefix = jax.lax.dynamic_index_in_dim(
                    batch["frames"], ti, 0, False).astype(dt)
            carry = M.embed(params, cfg, tok, prefix_emb=prefix)
            carry["x"] = carry["x"].astype(dt)
            if "enc" in carry:
                carry["enc"] = carry["enc"].astype(dt)
            if dyncfg.uses_early_exit:
                carry["exited"] = jnp.zeros(
                    (tok.shape[0], shapes.seq_total), jnp.float32)
            return carry

        def tick(state, t):
            buf, cache_s, ids_out, drop_out = state
            mi = jnp.clip(t - idx, 0, m - 1)
            mvalid = ((t - idx) >= 0) & ((t - idx) < m)
            fresh = jax.lax.cond(
                idx == 0, ingest,
                lambda _t: jax.tree.map(jnp.zeros_like, buf), t)
            carry = jax.tree.map(
                lambda a, b: jnp.where(idx == 0, a, b), fresh, buf)
            cache_mb = jax.tree.map(lambda a: a[:, mi], cache_s)
            if stamp is not None:
                carry = {**carry, "x": stamp(carry["x"], idx, jnp.int32(0))}
            carry, new_cache_mb, st, _ = M.stage_forward(
                cfg, dcfg, dyncfg, "prefill", stages, shared, tags, dyn_s,
                carry, cache_mb, pos, idx * tags.shape[0])
            if stamp is not None:
                carry = {**carry, "x": stamp(carry["x"], idx, jnp.int32(1))}
            drop_out = drop_out + (jnp.sum(st["moe_dropped"])
                                   * mvalid.astype(jnp.float32))
            cache_s = jax.tree.map(
                lambda full, nc, old: jax.lax.dynamic_update_index_in_dim(
                    full, jnp.where(mvalid, nc, old), mi, 1),
                cache_s, new_cache_mb, cache_mb)
            li = jnp.clip(t - (n - 1), 0, m - 1)
            emit = ((t - (n - 1)) >= 0) & (idx == n - 1)
            nid = jax.lax.cond(
                emit,
                lambda h: jnp.argmax(M.lm_logits(params, cfg, h),
                                     axis=-1).astype(jnp.int32),
                lambda h: jnp.zeros((h.shape[0],), jnp.int32),
                carry["x"][:, -1])
            ids_out = jax.lax.dynamic_update_index_in_dim(
                ids_out, jnp.where(emit, nid, ids_out[li]), li, 0)
            carry = pin(carry)
            buf = jax.tree.map(
                lambda a: jax.lax.ppermute(a, "model", _ring(n)), carry)
            return (buf, cache_s, ids_out, drop_out), None

        if dcfg.unroll_ticks:
            state = (buf, cache_s, ids_out, drop_out)
            for t in range(T):
                state, _ = tick(state, jnp.int32(t))
            (buf, cache_s, ids_out, drop_out) = state
        else:
            (buf, cache_s, ids_out, drop_out), _ = jax.lax.scan(
                tick, (buf, cache_s, ids_out, drop_out), jnp.arange(T))
        ids_out = jax.lax.psum(
            jnp.where(idx == n - 1, ids_out, jnp.zeros_like(ids_out)),
            "model")
        drop_out = jax.lax.psum(drop_out, "model")
        return ids_out, jax.tree.map(lambda a: a[None], cache_s), drop_out

    in_specs = (
        {"embed": P(), "final_norm": P(), "shared": P(),
         "stages": P("model"),
         **({"head": P()} if not cfg.tie_embeddings else {})},
        P("model"), P("model"), P("model"), P())
    return _shard_map(
        pipe, mesh=mesh, in_specs=in_specs,
        out_specs=(P(), P("model"), P()))

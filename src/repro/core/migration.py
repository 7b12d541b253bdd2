"""Layer migration between pipeline stages (paper §4.1, TPU-native).

A rebalance produces a new contiguous layers-per-stage split.  Because stage
state lives in statically-shaped slot buffers ``[S, L_max, ...]`` sharded
over the ``model`` axis, migration is a *gather along the stage axis* with a
host-computed (dst ← src) index map — XLA lowers it to collective-permute /
all-to-all between the affected stages.  **No recompilation**: the new
assignment arrays are ordinary inputs.

The same plan moves weights, optimizer moments, dynamism state, and (when
serving) the KV cache — everything keyed on [S, L_max, ...] leading dims.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import BLOCK_PAD


@dataclasses.dataclass
class MigrationPlan:
    src_stage: np.ndarray     # int32 [S, L_max]
    src_slot: np.ndarray      # int32 [S, L_max]
    valid: np.ndarray         # bool  [S, L_max] (False = dst slot is PAD)
    moved_layers: int         # how many layers change stage
    moved_bytes_per_layer_hint: int = 0

    def as_jnp(self):
        return (jnp.asarray(self.src_stage), jnp.asarray(self.src_slot),
                jnp.asarray(self.valid))


def _locate(lps) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized locate: global layer g -> (stage[g], slot[g]) under a
    contiguous split."""
    lps = np.asarray(lps, np.int64)
    stages = np.repeat(np.arange(len(lps)), lps)
    starts = np.concatenate([[0], np.cumsum(lps)[:-1]])
    slots = np.arange(int(lps.sum())) - starts[stages]
    return stages, slots


def build_plan(old_lps: Sequence[int], new_lps: Sequence[int],
               L_max: int) -> MigrationPlan:
    """Map each destination slot to its source slot under contiguous splits.

    Global layer g lives at (stage, slot) = locate(lps, g); plan[dst] = src.
    Pure numpy prefix-sum construction — the controller rebuilds a plan
    every rebalance (each iteration for MoE/MoD, §3.3.1), so this is on the
    decision-latency critical path."""
    total_old, total_new = sum(old_lps), sum(new_lps)
    assert total_old == total_new, (total_old, total_new)
    S = len(new_lps)
    assert max(new_lps) <= L_max, "destination split exceeds slot capacity"

    src_st, src_sl = _locate(old_lps)
    dst_st, dst_sl = _locate(new_lps)
    src_stage = np.zeros((S, L_max), np.int32)
    src_slot = np.zeros((S, L_max), np.int32)
    valid = np.zeros((S, L_max), bool)
    src_stage[dst_st, dst_sl] = src_st
    src_slot[dst_st, dst_sl] = src_sl
    valid[dst_st, dst_sl] = True
    moved = int(np.sum(src_st != dst_st))
    return MigrationPlan(src_stage, src_slot, valid, moved)


def apply_plan(tree: Any, plan: MigrationPlan, sharding=None) -> Any:
    """Gather [S_old, L_old, ...] arrays to the new [S, L_max, ...] layout.
    Invalid (PAD) destination slots hold zeros (their tags mark them
    inactive).

    A leaf spread over several devices is rebuilt on ``sharding`` (default:
    its own) by ``_gather_onto``, device by device; ``sharding`` may name
    another device set (a live shrink or grow).  An eager gather over the
    sharded stage axis would replicate whole leaves on every device, which
    full-width state does not fit."""
    ss, sl, valid = plan.as_jnp()

    def gather(a):
        spread = (isinstance(a, jax.Array)
                  and not isinstance(a, jax.core.Tracer)
                  and len(a.sharding.device_set) > 1)
        if sharding is not None or spread:
            return _gather_onto(a, plan, a.sharding if sharding is None
                                else sharding)
        out = a[ss, sl]                      # [S, L_max, ...]
        mask = valid.reshape(valid.shape + (1,) * (out.ndim - 2))
        return jnp.where(mask, out, jnp.zeros_like(out))

    return jax.tree.map(gather, tree)


def _gather_onto(a: jax.Array, plan: MigrationPlan, sharding) -> jax.Array:
    """One leaf of ``apply_plan`` built on ``sharding`` without the host:
    each destination device receives device-to-device copies of the
    source-stage blocks its own stages draw from, then gathers its rows
    locally, so no device holds another stage's blocks."""
    ss, sl, valid = plan.src_stage, plan.src_slot, plan.valid
    blocks = {}                      # source stage -> [1, L_old, ...] buffer
    for sh in a.addressable_shards:
        start, stop, _ = sh.index[0].indices(a.shape[0])
        for s in range(start, stop):
            if s not in blocks:
                blocks[s] = (sh.data if stop - start == 1
                             else sh.data[s - start:s - start + 1])
    shape = ss.shape + a.shape[2:]
    bufs = []
    for dev, idx in sharding.addressable_devices_indices_map(shape).items():
        lo, hi, _ = idx[0].indices(shape[0])
        rows = []
        for d in range(lo, hi):
            need = sorted(set(ss[d][valid[d]].tolist())) or [0]
            local = jnp.concatenate(
                [jax.device_put(blocks[s], dev) for s in need])
            pos = np.searchsorted(need, ss[d]).clip(0, len(need) - 1)
            got = local[pos, sl[d]]                      # [L_max, ...]
            mask = valid[d].reshape((-1,) + (1,) * (got.ndim - 1))
            rows.append(jnp.where(mask, got, jnp.zeros_like(got))[None])
        bufs.append(rows[0] if len(rows) == 1 else jnp.concatenate(rows))
    return jax.make_array_from_single_device_arrays(shape, sharding, bufs)


def _apply_plan_to_opt(opt_state: Any, plan: MigrationPlan) -> Any:
    """Optimizer state mirrors the param tree; only its ``stages`` subtrees
    are stage-keyed — everything else (step count, embed/head moments) stays
    put."""
    def walk(node):
        if isinstance(node, dict):
            return {k: (apply_plan(v, plan) if k == "stages" else walk(v))
                    for k, v in node.items()}
        return node
    return walk(opt_state)


def migrate(params_stages: Dict[str, jax.Array], opt_stages: Any,
            dyn: Dict[str, jax.Array], old_lps: Sequence[int],
            new_lps: Sequence[int], tags_pattern: Sequence[int],
            L_max: int, cache: Any = None):
    """One-call migration of all stage-keyed state + fresh assignment arrays.

    Returns (params_stages, opt_stages, dyn, assignment, cache, plan)."""
    from repro.models.model import make_assignment  # avoid cycle
    plan = build_plan(old_lps, new_lps, L_max)
    new_params = apply_plan(params_stages, plan)
    new_opt = (_apply_plan_to_opt(opt_stages, plan)
               if opt_stages is not None else None)
    new_dyn = apply_plan(dyn, plan)
    new_cache = apply_plan(cache, plan) if cache is not None else None
    # assignment arrays rebuilt host-side from the pattern + new split
    S = len(new_lps)
    tags = np.full((S, L_max), BLOCK_PAD, np.int32)
    dst_st, dst_sl = _locate(new_lps)
    tags[dst_st, dst_sl] = np.asarray(tags_pattern, np.int32)
    lps = np.asarray(new_lps, np.int64)
    assignment = {
        "tags": jnp.asarray(tags),
        "num_active": jnp.asarray(lps, jnp.int32),
        "depth_base": jnp.asarray(
            np.concatenate([[0], np.cumsum(lps)[:-1]]), jnp.int32),
    }
    return new_params, new_opt, new_dyn, assignment, new_cache, plan

"""Elastic training engine (paper §3.4, Alg. 2 — live consolidation).

``ElasticEngine`` owns the per-stage-count *execution world* — the mesh over
a device subset, the pipeline shapes, the jitted train step, and the
optimizer init — built lazily and cached per active stage count.  A repack
decision from the controller triggers a **live shrink** in the same process:

  1. stage-keyed state is flattened to global layer order and re-split for
     the smaller stage count (one device-side gather per leaf — the weights
     never round-trip through host memory);
  2. the result is placed onto a ``model``-axis submesh over the surviving
     device subset (released devices hold no state afterwards);
  3. the cached (or freshly compiled) smaller world continues training.

The GPipe schedule pays ``num_micro + S - 1`` ticks, so shrinking S is a
real throughput win at equal tokens — packed-empty *shadow* stages (the old
in-mesh repack path) kept paying the full tick count.  The symmetric grow
path re-expands when the ``WorkerPool`` grants recovered workers back.

The checkpoint-coordinated path (repro.checkpoint.elastic + restart) remains
the fallback for multi-node jobs where the job manager must actually
reschedule processes (§3.4.2); see DESIGN.md §Elastic runtime.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.cluster.rpc import (InProcessJobManager, JobManagerClient,
                               JobManagerUnavailable)
from repro.configs.base import DistConfig, ModelConfig
from repro.core.migration import apply_plan, build_plan
from repro.dynamics.config import DynamicsConfig
from repro.launch.mesh import make_submesh
from repro.models import model as M
from repro.obs.trace import span
from repro.optim.optimizers import OptConfig, make_optimizer
from repro.pipeline.pipeline import (PipelineShapes, build_decode_fn,
                                     build_loss_fn, build_prefill_fn)
from repro.runtime.fault_tolerance import WorkerPool


@jax.jit
def _pack_pages(pool, scratch_k, scratch_v, table, mask):
    """Scatter prompt pages from a dense prefill scratch into the pool.

    pool: {kp, vp: [S, L, pool+1, page, kv, hd]}; scratch_k/v:
    [S, L, m, B, cap, kv, hd] with cap == J * page; table/mask: [m, B, J].
    Unmasked or unmapped (-1) entries are steered at the trash block.
    """
    kp, vp = pool["kp"], pool["vp"]
    page = kp.shape[3]
    trash = kp.shape[2] - 1
    m, b, j = table.shape
    blk = jnp.where(mask & (table >= 0), table, trash).reshape(m * b * j)

    def pages(sc):
        s_, l_, m_, b_, cap, kv, hd = sc.shape
        return sc.reshape(s_, l_, m_ * b_ * (cap // page), page, kv, hd)

    return {"kp": kp.at[:, :, blk].set(pages(scratch_k).astype(kp.dtype)),
            "vp": vp.at[:, :, blk].set(pages(scratch_v).astype(vp.dtype))}


@jax.jit
def _copy_block(pool, src, dst):
    """Duplicate one physical block (CoW fork) in every stage-slot pool."""
    return {k: v.at[:, :, dst].set(v[:, :, src]) for k, v in pool.items()}


@jax.jit
def _add_tiles(total, tiles):
    """``total`` plus a step's per-slot attention tiles (uint32; the sum
    wraps past 2**32 tiles, so it is folded to the host well before)."""
    return total + jnp.sum(tiles.astype(jnp.uint32))


def make_train_step(cfg: ModelConfig, dcfg: DistConfig,
                    dyncfg: DynamicsConfig, mesh, shapes: PipelineShapes,
                    opt_cfg: Optional[OptConfig] = None, stage_timer=None):
    """Returns (init_opt_fn, train_step) with
    train_step(params, opt_state, assignment, dyn, batch, lr)
      -> (params, opt_state, loss, stats, gnorm).
    ``stage_timer`` threads an ``obs.timing.StageTimer`` into the pipelined
    loss (in-step stage timing, DESIGN.md §15)."""
    opt_cfg = opt_cfg or OptConfig(name=dcfg.optimizer)
    loss_fn = build_loss_fn(cfg, dcfg, dyncfg, mesh, shapes,
                            stage_timer=stage_timer)
    init_fn, update_fn = make_optimizer(opt_cfg)

    def train_step(params, opt_state, assignment, dyn, batch, lr):
        (loss, stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, assignment, dyn, batch)
        params, opt_state, gnorm = update_fn(
            grads, opt_state, params, lr, frozen=dyn.get("frozen"))
        return params, opt_state, loss, stats, gnorm

    return init_fn, train_step


def fold_stats(stats, num_stages: int):
    """Materialize the per-slot stats tree on host and restore the
    [S, L_max, ...] layout the profiler expects — shard_map's stacked
    out_spec flattens the stage axis into the slot axis ([S·L_max, ...]).
    This is a full device→host sync of the stats tree: call it on
    controller cadence only, never per step (§3.3.1)."""
    import numpy as np

    def fold(a):
        a = np.asarray(a)
        return a.reshape((num_stages, a.shape[0] // num_stages)
                         + a.shape[1:])

    return jax.tree.map(fold, stats)


@dataclasses.dataclass
class EngineWorld:
    """Everything tied to one active stage count: compiled once, cached.

    The serving path shares the cache: ``prefill``/``decode`` are built
    lazily per world next to the train step, so an elastic server reuses
    the same submesh/epoch/job-manager machinery as the trainer."""
    stages: int
    dcfg: DistConfig
    mesh: Any
    init_opt: Any
    step: Any                  # jitted, donating (params, opt_state)
    eval_loss: Any = None      # lazily-jitted loss-only fn (no update)
    prefill: Any = None        # lazily-jitted serving prefill
    decode: Any = None         # {live_micros: jitted decode} (donates cache)
    stage_probe: Any = None    # lazily-jitted single-stage forward (timers)
    timer: Any = None          # obs.timing.StageTimer (in-step timing on)
    stepped: bool = False      # first step() on this world pays compile


@dataclasses.dataclass
class EngineState:
    """The training/serving state the engine threads through worlds.
    ``cache`` is the stacked decode KV cache ([S, L_max, ...] leaves) when
    the engine serves; it re-splits with the rest on every resize."""
    params: Any
    opt_state: Any
    dyn: Any
    assignment: Any
    lps: List[int]
    stages: int
    cache: Any = None


@dataclasses.dataclass
class ResizeEvent:
    step: int
    kind: str                  # shrink | grow | evict
    from_stages: int
    to_stages: int
    workers: List[int]         # released (shrink) or granted (grow) ids
    seconds: float
    ticks_before: int
    ticks_after: int


class ElasticEngine:
    """Owns the per-stage-count execution worlds and the live resize paths.

    ``data`` × ``stages`` devices are taken from the front of ``devices``
    (process-global by default); stage s maps to worker column s.  Shrinking
    keeps the first ``data*S_new`` devices and releases the tail to the
    ``WorkerPool``; growing requests them back.
    """

    def __init__(self, cfg: ModelConfig, dcfg: DistConfig,
                 dyncfg: DynamicsConfig, shapes: PipelineShapes, *,
                 opt_cfg: Optional[OptConfig] = None, data: int = 1,
                 devices: Optional[Sequence[Any]] = None,
                 pool: Optional[WorkerPool] = None,
                 job_manager: Optional[JobManagerClient] = None,
                 in_step_timing: bool = False,
                 paged=None, temperature: float = 0.0):
        self.cfg, self.base_dcfg, self.dyncfg = cfg, dcfg, dyncfg
        self.shapes = shapes
        self.opt_cfg = opt_cfg
        self.data = data
        self.in_step_timing = in_step_timing
        # serving options: ``paged`` is a PagedKVConfig (block-paged KV pool
        # instead of per-lane contiguous lines); ``temperature`` > 0 builds
        # sampling decode variants (0 keeps the argmax graph bit-exact)
        self.paged = paged
        self.temperature = float(temperature)
        self.last_step_compiled = False
        self.last_moe_drop = None   # serve telemetry (see _note_moe_drop)
        # attention tiles of the train steps: a device-resident sum since
        # the last fold into the host count (``attn_tiles_total``)
        self._tiles_dev = None
        self._tiles_world: Optional[EngineWorld] = None
        self._tiles_host = 0
        self.devices = (list(devices) if devices is not None
                        else list(jax.devices()))
        if job_manager is None:
            # in-process default: same WorkerPool semantics as always
            self.pool: Optional[WorkerPool] = pool or WorkerPool(
                dcfg.num_stages)
            self.jm: JobManagerClient = InProcessJobManager(self.pool)
        else:
            # the real pool lives behind the RPC boundary (its process owns
            # it); release/grant cross it via the client
            self.jm = job_manager
            self.pool = pool
        self.stage_workers: List[int] = list(range(dcfg.num_stages))
        # worker id -> device column (a list of ``data`` devices).  Bound
        # positionally at init; a worker GRANTED later under a never-seen
        # id (the job manager provisioned a fresh process, not a revival)
        # is bound to a free column on arrival — device discovery survives
        # process-set changes instead of assuming id == device index.
        S0 = dcfg.num_stages
        assert len(self.devices) >= data * S0, (
            f"need {data * S0} devices, have {len(self.devices)}")
        self._columns: List[List[Any]] = [
            [self.devices[d * S0 + s] for d in range(data)]
            for s in range(S0)]
        self.worker_column: Dict[int, int] = {w: w for w in range(S0)}
        self._worlds: Dict[Any, EngineWorld] = {}
        # ops the job manager must eventually hear about, queued while it
        # is unreachable (degraded mode: training continues, bookkeeping
        # catches up when the manager comes back)
        self._pending_jm: List[Any] = []
        self.degraded_events: List[str] = []
        self.resizes: List[ResizeEvent] = []
        self.last_shrink_step: Optional[int] = None
        # world epoch: bumped by every resize; the control plane fences
        # decision plans with it so a plan decided against a stale world
        # (wrong stage count / layer split) is never applied
        self.epoch = 0
        # mirror every pool transition (including ones other engines or the
        # heartbeat path trigger on a shared pool) into an engine-local log
        self.pool_events: List[str] = []
        self._pool_hook = lambda event, worker: self.pool_events.append(
            f"{event}:{worker}")
        if self.pool is not None:
            self.pool.subscribe(self._pool_hook)

    def close(self) -> None:
        """Detach from a (possibly shared) pool; a discarded engine must not
        be pinned alive by the pool's hook list."""
        if self.pool is not None:
            self.pool.unsubscribe(self._pool_hook)

    # -- worlds ------------------------------------------------------------
    @property
    def worlds(self) -> List[EngineWorld]:
        """Every execution world built so far, oldest first."""
        return list(self._worlds.values())

    def dcfg_for(self, stages: int) -> DistConfig:
        return dataclasses.replace(self.base_dcfg, num_stages=stages)

    def ticks(self, stages: int) -> int:
        return self.shapes.num_micro + stages - 1

    def _devices_for(self, workers: Sequence[int]) -> List[Any]:
        """Flat (data-major) device list for a worker list: stage s runs on
        worker ``workers[s]``'s bound column."""
        cols = [self._columns[self.worker_column[w]] for w in workers]
        return [cols[s][d] for d in range(self.data)
                for s in range(len(workers))]

    def _bind_new_workers(self, granted: Sequence[int]
                          ) -> tuple:
        """Bind device columns for granted workers.  Known ids keep their
        binding; NEVER-seen ids (the manager provisioned a fresh process)
        get a free column.  Returns (accepted, rejected) — a grant with no
        free hardware column behind it cannot be executed and must go back
        to the manager."""
        used = {self.worker_column[w] for w in self.stage_workers
                if w in self.worker_column}
        accepted: List[int] = []
        rejected: List[int] = []
        for w in granted:
            col = self.worker_column.get(w)
            if col is not None and col not in used:
                used.add(col)
                accepted.append(w)
                continue
            # unknown id — or a stale binding whose column was re-assigned
            # while this worker was away: (re-)bind to a free column
            free = [c for c in range(len(self._columns)) if c not in used]
            if not free:
                rejected.append(w)
                continue
            self.worker_column[w] = free[0]
            used.add(free[0])
            accepted.append(w)
        return accepted, rejected

    def bind_workers(self, workers: Sequence[int]) -> None:
        """Adopt a restored stage→worker map (checkpoint resume): workers
        are bound to columns positionally, replacing the init bindings."""
        assert len(workers) <= len(self._columns)
        self.stage_workers = list(workers)
        for s, w in enumerate(self.stage_workers):
            self.worker_column[w] = s

    def world(self, stages: int,
              workers: Optional[Sequence[int]] = None) -> EngineWorld:
        if workers is None:
            workers = self.stage_workers[:stages]
        assert len(workers) == stages, (workers, stages)
        devs = self._devices_for(workers)
        key = (stages, tuple(d.id for d in devs))
        w = self._worlds.get(key)
        if w is None:
            dcfg = self.dcfg_for(stages)
            mesh = make_submesh(self.data, stages, devices=devs)
            timer = None
            if self.in_step_timing:
                from repro.obs.timing import StageTimer
                timer = StageTimer(stages)
            init_opt, step_fn = make_train_step(
                self.cfg, dcfg, self.dyncfg, mesh, self.shapes, self.opt_cfg,
                stage_timer=timer)
            w = EngineWorld(stages=stages, dcfg=dcfg, mesh=mesh,
                            init_opt=init_opt,
                            step=jax.jit(step_fn, donate_argnums=(0, 1)),
                            timer=timer)
            self._worlds[key] = w
        return w

    # -- degraded-mode job-manager calls (DESIGN.md §12) -------------------
    def _flush_pending_jm(self) -> bool:
        """Replay queued release/fail bookkeeping in order; True when the
        queue drained (manager reachable again)."""
        while self._pending_jm:
            kind, arg = self._pending_jm[0]
            try:
                if kind == "release":
                    self.jm.release(arg)
                else:
                    self.jm.fail(arg)
            except JobManagerUnavailable:
                return False
            self._pending_jm.pop(0)
            self.degraded_events.append(f"replayed {kind}:{arg}")
        return True

    def _jm_release(self, workers: Sequence[int]) -> None:
        workers = list(workers)
        if self._flush_pending_jm():
            try:
                self.jm.release(workers)
                return
            except JobManagerUnavailable:
                pass
        self._pending_jm.append(("release", workers))
        self.degraded_events.append(f"release deferred: {workers}")

    def _jm_fail(self, worker: int) -> None:
        if self._flush_pending_jm():
            try:
                self.jm.fail(worker)
                return
            except JobManagerUnavailable:
                pass
        self._pending_jm.append(("fail", worker))
        self.degraded_events.append(f"fail deferred: {worker}")

    # -- placement ---------------------------------------------------------
    def _place(self, world: EngineWorld, params, opt_state, dyn, assignment,
               cache=None, plan=None):
        """device_put onto the world's submesh with the pipeline's layout:
        stage-keyed leaves sharded over ``model`` (leading stage dim),
        everything else replicated — matches the shard_map in_specs, so the
        jitted step needs no input reshard.  With a migration ``plan`` (a
        live resize) the stage-keyed leaves are re-split onto the world's
        devices instead (``migration.apply_plan``)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        stage_sh = NamedSharding(world.mesh, P("model"))
        repl_sh = NamedSharding(world.mesh, P())
        put_as_is = lambda t: jax.tree.map(
            lambda a: jax.device_put(a, stage_sh), t)
        put_st = (put_as_is if plan is None
                  else lambda t: apply_plan(t, plan, stage_sh))
        put_rp = lambda t: jax.tree.map(
            lambda a: jax.device_put(a, repl_sh), t)
        params = {k: (put_st(v) if k == "stages" else put_rp(v))
                  for k, v in params.items()}

        def walk_opt(node):
            if isinstance(node, dict):
                return {k: (put_st(v) if k == "stages" else walk_opt(v))
                        for k, v in node.items()}
            return jax.device_put(node, repl_sh)

        opt_state = walk_opt(opt_state) if opt_state is not None else None
        cache = put_st(cache) if cache is not None else None
        return (params, opt_state, put_st(dyn), put_as_is(assignment),
                cache)

    # -- lifecycle ---------------------------------------------------------
    def init_state(self, rng: jax.Array, *, with_opt: bool = True,
                   with_cache: bool = False, stages: Optional[int] = None,
                   lps: Optional[Sequence[int]] = None) -> EngineState:
        """``with_opt=False`` skips the optimizer (serving: no moments);
        ``with_cache=True`` allocates the stacked decode KV cache from the
        engine's shapes (requires ``shapes.cache_len > 0``).  ``stages`` /
        ``lps`` override the base world — the checkpoint-resume path builds
        templates at the stage count the run died at, not at the spec's
        maximum.  When ``stages`` is given the caller must have bound the
        matching workers first (``bind_workers``)."""
        stages = stages if stages is not None else self.base_dcfg.num_stages
        world = self.world(stages)
        lps = (list(lps) if lps is not None
               else M.uniform_boundaries(self.cfg.total_blocks(), stages))
        params = M.init_params(rng, self.cfg, world.dcfg, lps)
        assignment = M.make_assignment(self.cfg, world.dcfg, lps)
        dyn = M.init_dyn(self.cfg, world.dcfg, self.dyncfg)
        opt_state = world.init_opt(params) if with_opt else None
        cache = None
        if with_cache:
            assert self.shapes.cache_len > 0, "shapes.cache_len required"
            if self.paged is not None:
                cache = M.init_paged_cache(self.cfg, world.dcfg,
                                           self.paged.pool_pages,
                                           self.paged.page_size)
            else:
                cache = M.init_cache(self.cfg, world.dcfg,
                                     self.shapes.num_micro,
                                     self.shapes.mb_global,
                                     self.shapes.cache_len)
        params, opt_state, dyn, assignment, cache = self._place(
            world, params, opt_state, dyn, assignment, cache)
        return EngineState(params, opt_state, dyn, assignment, lps, stages,
                           cache)

    def step(self, state: EngineState, batch, lr):
        """One jitted train step in the state's current world; mutates
        ``state.params``/``state.opt_state`` in place, returns
        (loss, stats, gnorm) — stats stay on device (the caller decides when
        to pay the host sync)."""
        w = self.world(state.stages)
        self.last_step_compiled = not w.stepped
        w.stepped = True
        # leaves rebuilt by a rebalance, a pruning event or a host-side edit
        # carry shardings the compiled step has not seen, and would make it
        # recompile; placing them onto the world's layout costs nothing for
        # leaves already there
        with span("engine.place", cat="engine"):
            (state.params, state.opt_state, state.dyn, state.assignment,
             _) = self._place(w, state.params, state.opt_state, state.dyn,
                              state.assignment)
        with span("engine.dispatch", cat="engine"):
            with w.mesh:
                params, opt_state, loss, stats, gnorm = w.step(
                    state.params, state.opt_state, state.assignment,
                    state.dyn, batch, lr)
            state.params, state.opt_state = params, opt_state
            if self._tiles_world is not w:
                self.attn_tiles_total()     # the old world's sum, folded
                self._tiles_world = w
            self._tiles_dev = _add_tiles(
                self._tiles_dev if self._tiles_dev is not None
                else jnp.uint32(0), stats["attn_tiles"])
        return loss, stats, gnorm

    def attn_tiles_total(self) -> int:
        """(query block, key block) tiles the attention kernels computed in
        the forward of every train step so far, summed over layers and
        sequences.  Reads the device sum: a host sync."""
        if self._tiles_dev is not None:
            self._tiles_host += int(self._tiles_dev)
            self._tiles_dev = None
        return self._tiles_host

    @staticmethod
    def stats_to_host(state: EngineState, stats):
        """`fold_stats` for the state's current stage count."""
        return fold_stats(stats, len(state.lps))

    def eval_loss(self, state: EngineState, batch):
        """Loss-only evaluation (no optimizer update) in the current world —
        used by the resize parity checks and the demo."""
        w = self.world(state.stages)
        if w.eval_loss is None:
            w.eval_loss = jax.jit(build_loss_fn(
                self.cfg, w.dcfg, self.dyncfg, w.mesh, self.shapes))
        with w.mesh:
            loss, _ = w.eval_loss(state.params, state.assignment, state.dyn,
                                  batch)
        return loss

    # -- serving -----------------------------------------------------------
    def serve_fns(self, stages: int, live_micros: Optional[int] = None):
        """(prefill, decode) for the given stage count, built lazily on the
        world next to its train step — the elastic server's resize path gets
        compiled serving fns per world exactly like the trainer does.
        ``decode`` donates the cache argument (arg 3).

        Decode variants are cached per live microbatch count: a variant
        compiled for ``live_micros < num_micro`` runs ``live + S - 1`` ticks
        instead of ``num_micro + S - 1``, so all-empty trailing microbatch
        rows cost nothing (inputs keep their full shapes)."""
        w = self.world(stages)
        mv = self.shapes.num_micro if live_micros is None else live_micros
        if w.prefill is None:
            w.prefill = jax.jit(build_prefill_fn(
                self.cfg, w.dcfg, self.dyncfg, w.mesh, self.shapes,
                stage_timer=w.timer))
            w.decode = {}
        if mv not in w.decode:
            w.decode[mv] = jax.jit(build_decode_fn(
                self.cfg, w.dcfg, self.dyncfg, w.mesh, self.shapes,
                stage_timer=w.timer, paged=self.paged is not None,
                temperature=self.temperature, num_micro=mv),
                donate_argnums=(3,))
        return w.prefill, w.decode[mv]

    def prefill(self, state: EngineState, batch, cache=None):
        """Run prefill in the state's world; returns (last_ids, new_cache).
        The caller owns cache merging (continuous batching overwrites only
        admitted lanes).  ``cache`` overrides ``state.cache`` as the target
        — the paged server prefills into a disposable dense scratch, then
        packs the admitted lanes' pages into the pool.
        ``self.last_moe_drop`` holds the call's mean MoE capacity-drop
        fraction (device scalar; None for non-MoE archs)."""
        pf, _ = self.serve_fns(state.stages)
        target = state.cache if cache is None else cache
        with self.world(state.stages).mesh:
            ids, new_cache, drop = pf(state.params, state.assignment,
                                      state.dyn, target, batch)
        self._note_moe_drop(drop)
        return ids, new_cache

    def decode(self, state: EngineState, tokens, pos, *, page_table=None,
               seeds=None, live_micros: Optional[int] = None):
        """One decode step in the state's world; replaces ``state.cache``
        (the jitted fn donates the old buffer) and returns (ids, logprobs).
        ``page_table`` [m, B, J] int32 is required iff the engine is paged;
        ``seeds`` [m, B] int32 iff temperature > 0; ``live_micros`` selects
        the per-micro-count decode variant.
        ``self.last_moe_drop`` as in :meth:`prefill`."""
        _, dec = self.serve_fns(state.stages, live_micros)
        args = [state.params, state.assignment, state.dyn, state.cache,
                tokens, pos]
        if self.paged is not None:
            assert page_table is not None, "paged decode needs a page table"
            args.append(jnp.asarray(page_table, jnp.int32))
        if self.temperature > 0.0:
            assert seeds is not None, "sampling decode needs per-lane seeds"
            args.append(jnp.asarray(seeds, jnp.int32))
        with self.world(state.stages).mesh:
            ids, lp, cache, drop = dec(*args)
        state.cache = cache
        self._note_moe_drop(drop)
        return ids, lp

    # -- paged-KV device helpers ------------------------------------------
    def make_dense_scratch(self, stages: int):
        """A dense, stage-sharded decode cache for the paged prefill path.
        Its contents are disposable: prefill writes whole lanes, pack_pages
        copies the admitted lanes' pages out, nothing else reads it."""
        world = self.world(stages)
        cache = M.init_cache(self.cfg, world.dcfg, self.shapes.num_micro,
                             self.shapes.mb_global, self.shapes.cache_len)
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(world.mesh, P("model"))
        return jax.tree.map(lambda a: jax.device_put(a, sh), cache)

    def pack_pages(self, state: EngineState, scratch, table, mask):
        """Scatter prompt pages from the dense prefill scratch into the
        block pool.  ``table``/``mask``: [m, B, J] — a page is copied iff
        masked and mapped; everything else is steered at the trash block.
        Duplicate targets (prefix-shared pages admitted together) carry
        bit-identical bytes, so scatter order cannot matter."""
        with self.world(state.stages).mesh:
            state.cache = _pack_pages(
                state.cache, scratch["k"], scratch["v"],
                jnp.asarray(table, jnp.int32), jnp.asarray(mask, bool))
        return state.cache

    def copy_block(self, state: EngineState, src: int, dst: int):
        """Copy-on-write fork: duplicate one physical block across every
        stage-slot pool."""
        with self.world(state.stages).mesh:
            state.cache = _copy_block(state.cache, jnp.int32(src),
                                      jnp.int32(dst))
        return state.cache

    def _note_moe_drop(self, drop):
        """Normalize a serve call's summed MoE drop signal to a mean
        fraction.  Stays a device scalar — the server pays the host sync
        only when it reads the telemetry."""
        from repro.configs.base import BLOCK_MOE
        n_moe = sum(1 for t in self.cfg.block_pattern() if t == BLOCK_MOE)
        if n_moe == 0:
            self.last_moe_drop = None
            return
        self.last_moe_drop = drop / float(n_moe * self.shapes.num_micro)

    # -- measured per-stage timers ----------------------------------------
    def in_step_stage_times(self, state: EngineState):
        """Per-stage busy seconds per step from the live pipelined step
        (DESIGN.md §15) — no extra execution: reads and resets the current
        world's ``StageTimer`` accumulation since the last call.  Returns
        None when in-step timing is off or no full window has accumulated
        yet (e.g. right after a resize onto a fresh world)."""
        w = self.world(state.stages)
        if w.timer is None:
            return None
        return w.timer.snapshot(ticks_per_step=self.ticks(state.stages))

    def measure_stage_times(self, state: EngineState, batch):
        """Measured per-stage forward wall times (seconds, [S]).

        Runs each stage's ``stage_forward`` in isolation over the first
        microbatch, timing on the host with ``block_until_ready`` — the
        profiler's "measured" fidelity tier.  The probe executes with
        ``slot_exec="bounded_loop"`` regardless of the world's executor:
        it must measure the stage's *live* work (the active slots), which
        is the quantity the straggler detector compares against the
        balancer's expected per-stage loads — masked-scan padding cost is
        uniform across stages and carries no load signal.  One probe fn
        serves every stage (slot buffers are uniformly [L_max, ...]-
        shaped), so this compiles once per world; it is still a full host
        sync per stage, which is why the trainer gates it on controller
        cadence.
        """
        import numpy as np

        w = self.world(state.stages)
        if w.stage_probe is None:
            cfg, dyncfg = self.cfg, self.dyncfg
            dcfg = dataclasses.replace(w.dcfg, slot_exec="bounded_loop")

            def probe(stage_params, shared, tags, dyn_s, carry, depth_base):
                pos = jnp.arange(carry["x"].shape[1])
                out, _, _, _ = M.stage_forward(
                    cfg, dcfg, dyncfg, "train", stage_params, shared, tags,
                    dyn_s, carry, None, pos, depth_base)
                return out

            w.stage_probe = jax.jit(probe)
        dt = jnp.bfloat16 if w.dcfg.param_dtype == "bfloat16" \
            else jnp.float32
        carry = M.embed(state.params, self.cfg, batch["tokens"][0])
        carry["x"] = carry["x"].astype(dt)
        if "enc" in carry:
            carry["enc"] = carry["enc"].astype(dt)
        if self.dyncfg.uses_early_exit:
            carry["exited"] = jnp.zeros(carry["x"].shape[:2], jnp.float32)
        starts = np.concatenate([[0], np.cumsum(state.lps)[:-1]])
        times = np.zeros(state.stages)
        shared = state.params["shared"]
        for warm in (True, False):      # first pass compiles + warms caches
            for s in range(state.stages):
                sp = jax.tree.map(lambda a: a[s], state.params["stages"])
                dyn_s = jax.tree.map(lambda a: a[s], state.dyn)
                tags_s = state.assignment["tags"][s]
                t0 = time.perf_counter()
                out = w.stage_probe(sp, shared, tags_s, dyn_s, carry,
                                    jnp.int32(starts[s]))
                jax.block_until_ready(out)
                if not warm:
                    times[s] = time.perf_counter() - t0
                    carry = out      # flow the carry stage-to-stage
        return times

    # -- live resize -------------------------------------------------------
    def resize(self, state: EngineState, new_stages: int,
               new_lps: Optional[Sequence[int]] = None,
               workers: Optional[Sequence[int]] = None) -> EngineState:
        """Reshape all stage-keyed state to ``new_stages`` and place it onto
        that world's submesh — no checkpoint, no restart, no host round-trip.
        A serving cache rides the same re-split plan (its [S, L_max] leading
        dims are gathered exactly like params), so in-flight KV state
        survives the resize bit-identically.  Falls back to a uniform split
        when ``new_lps`` violates the target world's slot capacity."""
        world = self.world(new_stages, workers)
        slots = world.dcfg.slots_for(self.cfg)
        if (new_lps is None or len(new_lps) != new_stages
                or max(new_lps) > slots):
            new_lps = M.uniform_boundaries(self.cfg.total_blocks(),
                                           new_stages)
        lps = list(new_lps)
        params, opt_state, dyn, assignment, cache = self._place(
            world, state.params, state.opt_state, state.dyn,
            M.make_assignment(self.cfg, world.dcfg, lps), state.cache,
            plan=build_plan(state.lps, lps, slots))
        self.epoch += 1
        return EngineState(params, opt_state, dyn, assignment, lps,
                           new_stages, cache)

    def shrink(self, state: EngineState, target_stages: int,
               new_lps: Optional[Sequence[int]] = None,
               step: int = -1) -> EngineState:
        """Live consolidation: rebuild on fewer workers, release the tail of
        the stage→worker map back to the job manager."""
        assert target_stages < state.stages
        t0 = time.perf_counter()
        new_state = self.resize(state, target_stages, new_lps)
        released = self.stage_workers[target_stages:]
        self.stage_workers = self.stage_workers[:target_stages]
        self._jm_release(released)
        self.resizes.append(ResizeEvent(
            step=step, kind="shrink", from_stages=state.stages,
            to_stages=target_stages, workers=list(released),
            seconds=time.perf_counter() - t0,
            ticks_before=self.ticks(state.stages),
            ticks_after=self.ticks(target_stages)))
        self.last_shrink_step = step
        return new_state

    def evict(self, state: EngineState, workers: Sequence[int],
              step: int = -1) -> EngineState:
        """Failure path: rebuild the pipeline WITHOUT ``workers`` (dead —
        reported to the job manager as failed, not released; they are not
        grantable until the manager revives them).  Unlike ``shrink`` the
        lost workers may sit anywhere in the stage→worker map."""
        lost = [w for w in workers if w in self.stage_workers]
        if not lost:
            return state
        target = len(self.stage_workers) - len(lost)
        assert target >= 1, "cannot evict every worker"
        t0 = time.perf_counter()
        survivors = [w for w in self.stage_workers if w not in set(lost)]
        # the new world runs on the SURVIVORS' devices (the dead workers'
        # hardware is gone) — not on a positional device prefix
        new_state = self.resize(state, target, workers=survivors)
        self.stage_workers = survivors
        for w in lost:
            self._jm_fail(w)
        self.resizes.append(ResizeEvent(
            step=step, kind="evict", from_stages=state.stages,
            to_stages=target, workers=list(lost),
            seconds=time.perf_counter() - t0,
            ticks_before=self.ticks(state.stages),
            ticks_after=self.ticks(target)))
        self.last_shrink_step = step
        return new_state

    def grow(self, state: EngineState, n_workers: int,
             step: int = -1, steal: bool = False) -> EngineState:
        """Re-expansion: request workers back from the pool and rebuild the
        pipeline over the larger device subset.  Grows by however many the
        pool actually grants (possibly zero).  An unreachable manager
        degrades to "no grant, training continues"; a granted id with no
        free device column behind it is handed back.

        ``steal=True`` escalates the ask through the cluster scheduler's
        steal verb (DESIGN.md §14): free capacity is granted immediately
        and the shortfall preempts a lower-priority tenant — only
        meaningful on a tenant-registered multi-tenant manager; falls back
        to a plain request otherwise."""
        t0 = time.perf_counter()
        self._flush_pending_jm()
        ask = (self.jm.steal if steal and hasattr(self.jm, "steal")
               else self.jm.request)
        try:
            granted = ask(n_workers)
            if not granted and self._pending_jm and self._flush_pending_jm():
                # the request got through, so the manager is back — but its
                # pool hadn't heard our deferred releases yet (the breaker
                # blocked the flush, the request was the probe that closed
                # it).  Bookkeeping is settled now; ask once more.
                granted = ask(n_workers)
        except JobManagerUnavailable:
            self.degraded_events.append(
                f"grow denied at step {step}: manager unreachable")
            return state
        granted, rejected = self._bind_new_workers(granted)
        if rejected:
            self.degraded_events.append(
                f"grant rejected (no free device column): {rejected}")
            self._jm_release(rejected)
        if not granted:
            return state
        target = state.stages + len(granted)
        new_state = self.resize(state, target,
                                workers=self.stage_workers + granted)
        self.stage_workers = self.stage_workers + granted
        self.resizes.append(ResizeEvent(
            step=step, kind="grow", from_stages=state.stages,
            to_stages=target, workers=list(granted),
            seconds=time.perf_counter() - t0,
            ticks_before=self.ticks(state.stages),
            ticks_after=self.ticks(target)))
        return new_state

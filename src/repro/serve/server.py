"""Elastic continuous-batching server on ``ElasticEngine`` worlds.

The server owns one ``EngineState`` whose ``cache`` field is the live KV
state; prefill/decode run on the engine's per-stage-count worlds (compiled
once per world, exactly like the trainer's step), and resizes happen at
the *safe point between decode ticks* — no microbatch is in flight, so the
re-split gathers every lane's KV line onto the new world bit-identically.

Scaling is signal-driven through ``cluster.autoscaler.Autoscaler``'s load
path: queue depth / p95-latency pressure grows the pipeline (workers
re-granted by the job manager), sustained low occupancy with an empty
queue shrinks it (workers released through the ``JobManagerClient``
boundary — same RPC the trainer uses, so ``--job-manager file`` puts a
real process on the other side of a serving resize too).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.cluster.autoscaler import Autoscaler
from repro.cluster.rpc import JobManagerClient
from repro.kernels.paged_attention import paged_tile_work
from repro.configs.base import DistConfig, ModelConfig
from repro.dynamics.config import DynamicsConfig
from repro.launch.engine import ElasticEngine
from repro.obs.trace import span
from repro.pipeline.pipeline import PipelineShapes
from repro.serve.requests import Request, RequestQueue
from repro.serve.scheduler import Scheduler


def _merge_lanes(old, new, mask: np.ndarray):
    """Take admitted lanes' KV lines from ``new``; keep the rest.  Leaves
    are [S, L_max, m, B, ...]; ``mask`` is [m, B]."""
    mj = jnp.asarray(mask)

    def merge(o, n):
        mm = mj.reshape((1, 1) + mj.shape + (1,) * (o.ndim - 4))
        return jnp.where(mm, n, o)

    return jax.tree.map(merge, old, new)


def _permute_lanes(cache, src_of_dst: np.ndarray, m: int, B: int):
    """Apply a defrag lane permutation to every cache leaf."""
    perm = jnp.asarray(src_of_dst)

    def p(a):
        flat = a.reshape(a.shape[:2] + (m * B,) + a.shape[4:])
        return jnp.take(flat, perm, axis=2).reshape(a.shape)

    return jax.tree.map(p, cache)


def _pct(xs: Sequence[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs), q)) if len(xs) else 0.0


class ElasticServer:
    """Continuous-batching inference with live worker elasticity."""

    def __init__(self, cfg: ModelConfig, dcfg: DistConfig,
                 dyncfg: DynamicsConfig, shapes: PipelineShapes, *,
                 data: int = 1, job_manager: Optional[JobManagerClient] = None,
                 scaler: Optional[Autoscaler] = None, min_stages: int = 1,
                 eos_id: Optional[int] = None, defrag_every: int = 0,
                 seed: int = 0, measure_stage_times: bool = False,
                 initial_workers: Optional[Sequence[int]] = None,
                 in_step_timing: bool = False, tracer=None, metrics=None,
                 paged=None, temperature: float = 0.0,
                 micro_variants: bool = True):
        assert shapes.cache_len >= shapes.seq, "cache must hold the prompt"
        # paged: a serve.kv.PagedKVConfig — KV lives in a block pool indexed
        # by per-lane page tables instead of per-lane contiguous lines.
        # temperature > 0 samples per lane (0 = argmax, bit-exact).
        # micro_variants: decode with the per-live-micro-count variant so
        # drained trailing microbatch rows skip their pipeline ticks.
        self.paged = paged
        self.temperature = float(temperature)
        self.micro_variants = micro_variants
        self.seed = seed
        self.engine = ElasticEngine(cfg, dcfg, dyncfg, shapes, data=data,
                                    job_manager=job_manager,
                                    in_step_timing=in_step_timing,
                                    paged=paged, temperature=temperature)
        if initial_workers is not None:
            # multi-tenant start: serve on exactly the workers the cluster
            # scheduler granted (arbitrary global ids, possibly fewer than
            # the spec's max stages) — same bind + sized-init path the
            # checkpoint resume uses
            self.engine.bind_workers([int(w) for w in initial_workers])
            self.state = self.engine.init_state(
                jax.random.PRNGKey(seed), with_opt=False, with_cache=True,
                stages=len(list(initial_workers)))
        else:
            self.state = self.engine.init_state(
                jax.random.PRNGKey(seed), with_opt=False, with_cache=True)
        self.shapes = shapes
        self.scaler = scaler
        self.min_stages = max(1, min_stages)
        self.max_stages = dcfg.num_stages
        self.eos_id = eos_id
        self.defrag_every = defrag_every
        self.measure_stage_times = measure_stage_times
        self.in_step_timing = in_step_timing
        self.tracer = tracer     # obs.trace.Tracer (None = tracing off)
        self.metrics = metrics   # obs.metrics.MetricsRegistry (optional)
        self._sched: Optional[Scheduler] = None
        # paged prefill scratch: a dense stage-sharded cache prefill writes
        # whole lanes into before pack_pages scatters the admitted lanes'
        # prompt pages into the pool; rebuilt per stage count, disposable
        self._scratch = None
        self._scratch_stages = -1

    def close(self) -> None:
        self.engine.close()

    # -- fault path (DESIGN.md §12) ----------------------------------------
    def crash_worker(self, worker: int, tick: int) -> None:
        """A serving worker died mid-flight: its stage's KV shard is gone,
        and every live lane's KV line passed through it.  Requeue all
        in-flight requests (generated tokens carried — re-admission
        rebuilds their KV from the token prefix) and evict the worker; the
        next tick re-admits onto the smaller world.  The degraded run
        completes the exact same request set token-identically, just
        later."""
        if worker not in self.engine.stage_workers:
            return
        if self.state.stages <= 1:
            raise RuntimeError(
                "last serving worker crashed — nothing to rebuild on")
        requeued = (self._sched.requeue_live(tick)
                    if self._sched is not None else [])
        self.state = self.engine.evict(self.state, [worker], step=tick)
        if self.scaler is not None:
            self.scaler.note_resize(tick, self.state.stages)
        print(f"tick {tick:4d} CRASH worker {worker}: requeued "
              f"{len(requeued)} in-flight requests, serving on "
              f"{self.state.stages} stages")

    # -- safe-point resize -------------------------------------------------
    def resize(self, target_stages: int, tick: int, reason: str,
               steal: bool = False) -> bool:
        """Shrink/grow between decode ticks.  Returns True if the world
        changed (grow may be denied by the job manager).  ``steal`` lets an
        urgent grow preempt a lower-priority tenant through the cluster
        scheduler (no-op on single-tenant managers)."""
        st = self.state
        prev = st.stages
        sp = span("serve.resize", cat="resize", tick=tick,
                  target=target_stages, reason=reason, steal=steal)
        if target_stages < prev:
            self.state = self.engine.shrink(st, target_stages, step=tick)
        elif target_stages > prev:
            # an urgent steal goes through jm.steal inside grow(); the RPC
            # transport ships this span's context so the victim's preempt
            # chains onto it cross-process (DESIGN.md §15)
            self.state = self.engine.grow(st, target_stages - prev,
                                          step=tick, steal=steal)
        changed = self.state.stages != prev
        sp.end(stages=self.state.stages, changed=changed)
        if self.metrics is not None and changed:
            rz = self.engine.resizes[-1]
            self.metrics.inc("dynmo_resizes_total", kind=rz.kind,
                             policy="steal" if steal else reason,
                             help="engine resizes by kind")
        if changed:
            rz = self.engine.resizes[-1]
            print(f"tick {tick:4d} {rz.kind.upper()} {rz.from_stages}->"
                  f"{rz.to_stages} stages ({reason}); workers {rz.workers}; "
                  f"pool active={self.engine.jm.num_active}")
            if self.scaler is not None:
                self.scaler.note_resize(tick, self.state.stages)
        return changed

    # -- main loop ----------------------------------------------------------
    def serve(self, requests: List[Request], *, max_ticks: int = 100000,
              resize_at: Optional[Dict[int, int]] = None,
              autoscale: bool = False, injector=None) -> Dict[str, Any]:
        """Drive the request trace to completion.  ``resize_at`` scripts
        {tick: target_stages} safe-point resizes (tests/demos);
        ``autoscale`` lets the attached scaler drive them from load;
        ``injector`` (faults.ChaosInjector) fires scheduled faults at the
        tick safe points — a crashed worker goes through ``crash_worker``."""
        alloc = None
        if self.paged is not None:
            from repro.serve.kv import PageAllocator
            alloc = PageAllocator(
                self.paged.pool_pages, self.paged.page_size,
                max_pages_per_req=(self.shapes.cache_len
                                   // self.paged.page_size),
                prefix_cache=self.paged.prefix_cache)
        sched = Scheduler(self.shapes.num_micro, self.shapes.mb_global,
                          self.shapes.seq, self.shapes.cache_len,
                          RequestQueue(requests), eos_id=self.eos_id,
                          defrag_every=self.defrag_every, allocator=alloc,
                          sample_seed=(self.seed if self.temperature > 0
                                       else None))
        self._sched = sched
        if injector is not None:
            injector.bind(crash_worker=self.crash_worker)
        m, B = self.shapes.num_micro, self.shapes.mb_global
        resizes_before = len(self.engine.resizes)
        tick = 0
        tick_wall: List[float] = []
        tick_tokens: List[int] = []
        token_lat: List[float] = []
        stages_hist: List[int] = []
        depth_hist: List[int] = []
        occ_hist: List[float] = []
        page_occ_hist: List[float] = []
        peak_lanes = 0
        peak_pages = 0
        tiles_live = tiles_total = 0
        moe_drops = []   # device scalars; synced once after the trace drains
        t_run = time.perf_counter()
        while tick < max_ticks and not sched.done:
            t0 = time.perf_counter()
            emitted = 0
            sp_tick = span("serve.tick", cat="serve", tick=tick,
                           stages=self.state.stages)
            adm = sched.plan_admissions(tick)
            if adm is not None and self.tracer is not None:
                self.tracer.instant("serve.admit", cat="serve", tick=tick,
                                    lanes=len(adm.full_len_lanes))
            if adm is not None:
                batch = {"tokens": jnp.asarray(adm.prefill_tokens)}
                if alloc is not None:
                    # prefill into the disposable dense scratch, then
                    # scatter the admitted lanes' prompt pages into the
                    # pool through the admission page table
                    if self._scratch_stages != self.state.stages:
                        self._scratch = self.engine.make_dense_scratch(
                            self.state.stages)
                        self._scratch_stages = self.state.stages
                    ids, self._scratch = self.engine.prefill(
                        self.state, batch, cache=self._scratch)
                    self.engine.pack_pages(self.state, self._scratch,
                                           adm.page_table, adm.pack_mask)
                else:
                    ids, new_cache = self.engine.prefill(self.state, batch)
                    self.state.cache = _merge_lanes(self.state.cache,
                                                    new_cache,
                                                    adm.admit_mask)
                sched.note_prefill(adm, np.asarray(ids), tick)
                emitted += len(adm.full_len_lanes)
                if self.engine.last_moe_drop is not None:
                    moe_drops.append(self.engine.last_moe_drop)
            dec = sched.plan_decode()
            if dec is not None:
                for src, dst in dec.copies:      # CoW forks land on device
                    self.engine.copy_block(self.state, src, dst)
                mlive = ((max(dec.lanes) // B) + 1
                         if self.micro_variants else None)
                ids, _lp = self.engine.decode(self.state,
                                              jnp.asarray(dec.tokens),
                                              jnp.asarray(dec.pos),
                                              page_table=dec.page_table,
                                              seeds=dec.seeds,
                                              live_micros=mlive)
                sched.note_decode(dec, np.asarray(ids), tick)
                emitted += len(dec.lanes)
                peak_lanes = max(peak_lanes, len(dec.lanes))
                if alloc is not None:
                    lv, tt = paged_tile_work(
                        dec.page_table,
                        dec.pos.reshape(-1) + 1, alloc.page_size)
                    tiles_live += lv
                    tiles_total += tt
                if self.engine.last_moe_drop is not None:
                    moe_drops.append(self.engine.last_moe_drop)
            perm = sched.maybe_defrag(tick)
            if perm is not None and alloc is None:
                # dense lines move with their lanes; the paged pool never
                # moves — lanes only carry table rows, rebuilt every tick
                self.state.cache = _permute_lanes(self.state.cache, perm,
                                                  m, B)
            wall = time.perf_counter() - t0
            sp_tick.end(tokens=emitted, queue=sched.queue_depth)
            tick_wall.append(wall)
            tick_tokens.append(emitted)
            token_lat.extend([wall] * emitted)
            stages_hist.append(self.state.stages)
            depth_hist.append(sched.queue_depth)
            occ_hist.append(sched.occupancy)
            if alloc is not None:
                page_occ_hist.append(alloc.occupancy)
                peak_pages = max(peak_pages, alloc.live_pages)
                if self.metrics is not None:
                    self.metrics.set("dynmo_kv_page_occupancy",
                                     alloc.occupancy,
                                     help="KV pool occupancy fraction")
                    self.metrics.set("dynmo_kv_pages_live",
                                     alloc.live_pages,
                                     help="KV pool pages in use")
                    self.metrics.set("dynmo_kv_pages_free", alloc.num_free,
                                     help="KV pool pages free")
            if self.metrics is not None:
                self.metrics.inc("dynmo_serve_ticks_total",
                                 help="decode ticks executed")
                self.metrics.inc("dynmo_serve_tokens_total", emitted,
                                 help="tokens emitted")
                self.metrics.set("dynmo_queue_depth", sched.queue_depth,
                                 help="waiting requests")
                self.metrics.set("dynmo_occupancy", sched.occupancy,
                                 help="lane occupancy fraction")
                self.metrics.observe("dynmo_tick_seconds", wall,
                                     help="serve tick wall seconds")
            # ---- safe point: the tick's flight is fully retired
            if resize_at and tick in resize_at:
                self.resize(resize_at[tick], tick, "scripted")
            elif autoscale and self.scaler is not None:
                # latency signal = p95 per-token over the recent window
                # (what AutoscalerConfig.latency_slo_s is specified
                # against) — never the raw tick wall, which spikes on
                # every fresh-world compile and covers many tokens
                recent = token_lat[-64:]
                d = self.scaler.observe_load(
                    tick, self.state.stages, queue_depth=sched.queue_depth,
                    occupancy=sched.occupancy,
                    latency_s=_pct(recent, 95) if recent else 0.0,
                    page_occupancy=sched.page_occupancy)
                if d.action == "shrink":
                    self.resize(max(self.min_stages,
                                    self.state.stages - d.workers),
                                tick, d.reason)
                elif d.action == "grow":
                    self.resize(min(self.max_stages,
                                    self.state.stages + d.workers),
                                tick, d.reason, steal=d.urgent)
            if injector is not None:
                # scheduled faults fire at the same safe point resizes do:
                # the tick's flight is fully retired, so a crash loses KV
                # state only — never an in-flight microbatch
                injector.on_step(tick, workers=self.engine.stage_workers)
            tick += 1
        wall_s = time.perf_counter() - t_run
        total_tokens = sum(len(r.tokens) for r in sched.completions)
        measured = None
        src = None
        if self.in_step_timing:
            # live per-stage seconds from the in-step stamps accumulated
            # over the trace's prefill/decode calls — no probe execution
            ist = self.engine.in_step_stage_times(self.state)
            if ist is not None:
                measured = list(map(float, ist))
                src = "in_step"
        if measured is None and self.measure_stage_times:
            # per-stage prefill-shaped wall times via the engine's stage
            # probe (off the serving hot loop: one probe after the trace
            # drains, on whatever world the server ended up holding)
            probe_batch = {"tokens": np.zeros(
                (m, B, self.shapes.seq), np.int32)}
            measured = list(map(float, self.engine.measure_stage_times(
                self.state, probe_batch)))
            src = "probe"
        report = {
            "completions": [
                {"rid": r.rid, "kind": r.kind, "arrival": r.arrival,
                 "admitted": r.admitted, "finished": r.finished,
                 "plen": r.plen, "requeues": r.requeues,
                 "tokens": list(map(int, r.tokens))}
                for r in sorted(sched.completions, key=lambda r: r.rid)],
            "ticks": tick,
            "tick_wall_s": tick_wall,
            "tick_tokens": tick_tokens,
            "stages_history": stages_hist,
            "queue_depth_history": depth_hist,
            "occupancy_history": occ_hist,
            "resizes": [dataclasses.asdict(e)
                        for e in self.engine.resizes[resizes_before:]],
            "pool_log": list(self.engine.jm.log)
            if hasattr(self.engine.jm, "log") else [],
            "autoscale_decisions": (
                [dataclasses.asdict(d) for d in self.scaler.decisions]
                if self.scaler is not None else []),
            "requeued_total": sched.requeued_total,
            "total_tokens": total_tokens,
            "wall_s": wall_s,
            "tokens_per_s": total_tokens / max(1e-9, wall_s),
            "latency_p50_s": _pct(token_lat, 50),
            "latency_p95_s": _pct(token_lat, 95),
            "measured_stage_times": measured,
            "stage_time_source": src,
            # MoE capacity-overflow telemetry: mean drop fraction over every
            # prefill/decode call of the trace (None for non-MoE archs)
            "moe_dropped_mean": (float(np.mean([float(d)
                                                for d in moe_drops]))
                                 if moe_drops else None),
            # paged-KV telemetry (zeros/empty in dense mode);
            # peak_live_lanes is tracked either way — it is the
            # concurrency headline the paged-vs-dense bench compares
            "peak_live_lanes": peak_lanes,
            "page_occupancy_history": page_occ_hist,
            "kv_page_size": alloc.page_size if alloc is not None else 0,
            "kv_pages_total": alloc.pool_pages if alloc is not None else 0,
            "peak_live_pages": peak_pages,
            "prefix_hits": alloc.prefix_hits if alloc is not None else 0,
            "cow_forks": alloc.cow_forks if alloc is not None else 0,
            "page_tile_live": tiles_live,
            "page_tile_total": tiles_total,
        }
        if alloc is not None and self.metrics is not None:
            self.metrics.inc("dynmo_prefix_hits_total", alloc.prefix_hits,
                             help="prompt pages shared via prefix cache")
            self.metrics.inc("dynmo_cow_forks_total", alloc.cow_forks,
                             help="copy-on-write page forks")
        return report

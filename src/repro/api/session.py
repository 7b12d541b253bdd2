"""``Session`` — executes a ``RunSpec``; the runtime half of the API.

The trainer and the server used to each hand-assemble the same lifecycle:
build mesh/engine -> attach ControlPlane -> attach Autoscaler -> connect a
JobManagerClient -> tear everything down in the right order.  ``Session``
owns that lifecycle once:

    spec = RunSpec.load("configs/scenarios/early_exit.json")
    with Session(spec) as s:
        report = s.train()          # or s.serve()
    for ev in s.events:             # structured telemetry stream
        print(ev.kind, ev.step, ev.data)

``train``/``serve`` return the same report dicts the legacy entry points
did (every existing test/bench reads them); ``session.events`` is the
structured stream — one ``SessionEvent`` per resize / rebalance /
autoscale decision / log line — that new tooling should consume instead.

Teardown order matters and is centralized in ``close()``: control plane
first (its worker thread must stop deciding against a dying engine), then
the engine/server (detach pool hooks), then the job-manager client (tells
a file-RPC server process to exit), then the server process wait.
"""
from __future__ import annotations

import os

# honor the forced-host-device knob at the front door too (the launch CLIs
# set it in their own preambles; a program importing repro.api directly —
# examples, notebooks — must get it before the lazy jax import below)
if (os.environ.get("REPRO_TRAIN_DEVICES")
        and "xla_force_host_platform_device_count"
        not in os.environ.get("XLA_FLAGS", "")):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count="
        + os.environ["REPRO_TRAIN_DEVICES"])

import dataclasses
import tempfile
import time
import warnings
from typing import Any, Dict, List, Optional

from repro.api.specs import RunSpec
from repro.obs.events import EVENT_SCHEMA, stamp_record
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import span


@dataclasses.dataclass
class SessionEvent:
    """One telemetry record: ``kind`` in {"log", "rebalance", "resize",
    "autoscale", "safepoint", "relayout", "serve_summary",
    "train_summary", "tenant_register", "preempt", "absorb", "steal",
    "yield"} — the last five are the multi-tenant cluster stream
    (DESIGN.md §14).

    Since schema v4 every record also carries the unified event fields
    (DESIGN.md §15): ``schema``/``source``/``wall`` plus tracing identity
    when the session has a tracer.  The legacy ``kind``/``step``/``data``
    triple is unchanged — old consumers keep working."""
    kind: str
    step: int
    data: Dict[str, Any]
    schema: str = EVENT_SCHEMA
    source: str = "session"
    wall: Optional[float] = None
    trace_id: Optional[str] = None
    span_id: Optional[str] = None
    parent_id: Optional[str] = None
    lc: Optional[int] = None
    cause_trace_id: Optional[str] = None


class Session:
    """Context manager that executes one ``RunSpec``."""

    def __init__(self, spec: RunSpec):
        self.spec = spec
        self.events: List[SessionEvent] = []
        self._cp = None          # cluster.service.ControlPlane
        self._engine = None      # launch.engine.ElasticEngine
        self._server = None      # serve.server.ElasticServer
        self._jm = None          # cluster.rpc.JobManagerClient
        self._jm_proc = None
        self._jm_dir = None
        self._closed = False
        self.injector = None     # faults.ChaosInjector when chaos is on
        self.state = None        # the trainer's final EngineState
        self._resume_dir: Optional[str] = None
        self._resume_step: Optional[int] = None
        # ---- observability (DESIGN.md §15) --------------------------------
        self.metrics = MetricsRegistry()   # always live; ~free when unread
        self.tracer = None                 # obs.trace.Tracer when obs.trace
        self._metrics_srv = None           # http server when obs.metrics_port

    @classmethod
    def resume(cls, ckpt_dir: str, *,
               step: Optional[int] = None) -> "Session":
        """Rebuild a crashed run from its newest complete safe point.  The
        safe point carries the producing ``RunSpec``, so the caller needs
        nothing but the directory; ``train()`` then restores tensors,
        stage→worker topology, pool state, and control-plane hysteresis and
        continues from the step after the safe point — bit-identically to
        the run that never crashed (DESIGN.md §12)."""
        from repro.checkpoint.safepoint import peek
        idx = peek(ckpt_dir, step)
        spec = RunSpec.from_dict(idx["meta"]["spec"])
        s = cls(spec)
        s._resume_dir = ckpt_dir
        s._resume_step = int(idx["step"])
        return s

    @property
    def engine(self):
        """The ``ElasticEngine`` of the last train/serve run (None before)."""
        if self._server is not None:
            return self._server.engine
        return self._engine

    # -- lifecycle ---------------------------------------------------------
    def __enter__(self) -> "Session":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self.state = None        # release the device buffers
        self._obs_end()
        if self._cp is not None:
            self._cp.close()
        if self._server is not None:
            self._server.close()
        elif self._engine is not None:
            # deliver bookkeeping deferred while the manager was down —
            # best-effort; an unreachable manager must not block teardown
            self._engine._flush_pending_jm()
            self._engine.close()
        if self._jm is not None:
            self._jm.close()             # tells a file-RPC server to exit
        if self._jm_proc is not None:
            try:
                self._jm_proc.wait(timeout=10)
            except Exception:
                self._jm_proc.kill()

    def _emit(self, kind: str, step: int, *, cause_ctx=None,
              **data) -> SessionEvent:
        rec: Dict[str, Any] = {}
        stamp_record(rec, source="session", kind=kind, tracer=self.tracer,
                     ctx=cause_ctx)
        ev = SessionEvent(kind, step, data, wall=rec.get("wall"),
                          trace_id=rec.get("trace_id"),
                          span_id=rec.get("span_id"),
                          parent_id=rec.get("parent_id"), lc=rec.get("lc"),
                          cause_trace_id=rec.get("cause_trace_id"))
        self.events.append(ev)
        return ev

    # -- observability lifecycle (DESIGN.md §15) ---------------------------
    def _obs_begin(self, mode: str):
        """Build the tracer / metrics endpoint per ``spec.obs``.  The
        trace id derives from run identity (mode + tenant + seed), never
        pids or clocks, so a fixed-seed run's logical event sequence is
        reproducible (tested)."""
        obs = self.spec.obs
        if obs.trace:
            from repro.obs.trace import Tracer, set_current_tracer
            if self.tracer is None:
                tenant = self.spec.cluster.tenant_id or "solo"
                self.tracer = Tracer(
                    f"{mode}-{tenant}-s{self.spec.seed}",
                    meta={"mode": mode, "tenant": tenant,
                          "seed": self.spec.seed})
            # deep layers (RPC clients, control plane, injector) find the
            # tracer here instead of via constructor threading
            set_current_tracer(self.tracer)
        if obs.metrics_port and self._metrics_srv is None:
            from repro.obs.metrics import serve_metrics
            self._metrics_srv = serve_metrics(self.metrics,
                                              obs.metrics_port)
        return self.tracer

    def _obs_end(self) -> None:
        obs = self.spec.obs
        if self._metrics_srv is not None:
            self._metrics_srv.shutdown()
            self._metrics_srv = None
        if self.tracer is not None:
            if obs.trace_out:
                self.tracer.export(obs.trace_out)
            from repro.obs.trace import current_tracer, set_current_tracer
            if current_tracer() is self.tracer:
                set_current_tracer(None)
        if obs.metrics_out:
            self.metrics.save(obs.metrics_out)

    # -- shared assembly ---------------------------------------------------
    def model_config(self):
        from repro.configs.base import get_config, reduced_config
        m = self.spec.model
        cfg = get_config(m.arch)
        if m.layers is not None:
            cfg = reduced_config(cfg, num_layers=m.layers, d_model=m.d_model,
                                 num_heads=m.num_heads,
                                 num_kv_heads=m.num_kv_heads,
                                 d_ff=m.d_ff or 2 * m.d_model,
                                 vocab_size=m.vocab_size)
        return cfg

    def _dist_config(self):
        from repro.configs.base import DistConfig
        p = self.spec.parallel
        return DistConfig(num_stages=p.stages, slot_slack=p.slot_slack,
                          remat=p.remat, param_dtype=p.param_dtype,
                          kernel_impl=p.kernel_impl)

    def _connect_job_manager(self, plan=None, injector=None,
                             pool_state=None):
        """'file' spawns the WorkerPool server in a separate process and
        returns a client speaking atomic req/resp JSON files to it; 'http'
        connects to ``cluster.manager_url`` when set (two Sessions in two
        processes contending over ONE manager — DESIGN.md §14) or spawns a
        private HTTP manager; 'inproc' returns None (the engine wraps its
        own pool).  ``pool_state`` (from a safe point) is seeded into the
        fresh directory as the server's journal, so the respawned server
        starts from the crashed run's pool topology; with an RPC-chaos
        ``plan`` the client is the chaos transport."""
        import json

        from repro.cluster.rpc import FileJobManager, spawn_file_manager
        c = self.spec.cluster
        if c.job_manager == "inproc":
            return None
        if c.job_manager == "http":
            from repro.cluster.http_rpc import (HttpJobManager,
                                                spawn_http_manager)
            if c.manager_url:
                # shared manager owned by someone else: never shut it down
                self._jm = HttpJobManager(c.manager_url,
                                          timeout_s=c.rpc_timeout_s,
                                          shutdown_on_close=False)
                return self._jm
            if c.job_manager_dir:
                os.makedirs(c.job_manager_dir, exist_ok=True)
                run_dir = tempfile.mkdtemp(prefix="run_",
                                           dir=c.job_manager_dir)
            else:
                run_dir = tempfile.mkdtemp(prefix="dynmo_jm_")
            if pool_state is not None:
                with open(os.path.join(run_dir, "state.json"), "w") as f:
                    json.dump({"pool": pool_state, "answered": {}}, f)
            self._jm_dir = run_dir
            self._jm_proc, url = spawn_http_manager(
                run_dir, self.spec.parallel.stages, spares=c.spares)
            self._jm = HttpJobManager(url, timeout_s=c.rpc_timeout_s,
                                      shutdown_on_close=True)
            return self._jm
        # always a FRESH directory (a unique subdir when the caller names a
        # location): leftover req/resp files from a previous run would be
        # replayed by the new server and misread by the new client
        if c.job_manager_dir:
            os.makedirs(c.job_manager_dir, exist_ok=True)
            jm_dir = tempfile.mkdtemp(prefix="run_", dir=c.job_manager_dir)
        else:
            jm_dir = tempfile.mkdtemp(prefix="dynmo_jm_")
        if pool_state is not None:
            with open(os.path.join(jm_dir, "state.json"), "w") as f:
                json.dump({"pool": pool_state, "answered": {}}, f)
        self._jm_dir = jm_dir
        self._jm_proc = spawn_file_manager(jm_dir, self.spec.parallel.stages,
                                           spares=c.spares)
        if plan is not None and plan.any_rpc:
            from repro.faults import ChaosFileJobManager
            self._jm = ChaosFileJobManager(jm_dir, plan, injector,
                                           timeout_s=c.rpc_timeout_s)
        else:
            self._jm = FileJobManager(jm_dir, timeout_s=c.rpc_timeout_s)
        return self._jm

    def _register_tenant(self, jm, *, kind: str, workers: int,
                         max_workers: int, min_workers: int):
        """Register this Session with the cluster scheduler when the spec
        names a tenant.  Returns the granted worker ids (to bind the engine
        onto) or None when running single-tenant."""
        c = self.spec.cluster
        if jm is None or not c.tenant_id \
                or not hasattr(jm, "register_tenant"):
            return None
        granted = jm.register_tenant(
            c.tenant_id, priority=c.priority, kind=kind, workers=workers,
            max_workers=max_workers, min_workers=min_workers)
        if not granted:
            raise RuntimeError(
                f"cluster scheduler granted no workers to tenant "
                f"{c.tenant_id!r} (pool exhausted?)")
        self._emit("tenant_register", -1, tenant=c.tenant_id,
                   priority=c.priority, tenant_kind=kind,
                   granted=list(granted))
        return granted

    # =======================================================================
    # Training
    # =======================================================================
    def train(self, steps: Optional[int] = None, *,
              shrink_at: Optional[Dict[int, int]] = None) -> Dict[str, Any]:
        """Run the DynMo training loop for ``steps`` (default: spec.steps).
        ``shrink_at`` scripts {step: target_stages} voluntary safe-point
        shrinks (tests/demos) through the same epoch-fenced injection an
        external preemption directive uses — the bit-identity oracle for
        the multi-tenant steal path (DESIGN.md §14).
        Returns the report dict (losses, events, resizes, telemetry)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from repro.cluster.autoscaler import Autoscaler, AutoscalerConfig
        from repro.cluster.service import ControlPlane, StatsSnapshot
        from repro.core.controller import ControllerConfig, DynMoController
        from repro.data.loader import DataConfig, make_loader
        from repro.dynamics import pruning as prn
        from repro.dynamics.trajectories import zhu_gupta_sparsity
        from repro.launch.engine import ElasticEngine
        from repro.optim.schedule import cosine_schedule
        from repro.pipeline.pipeline import PipelineShapes
        from repro.runtime.fault_tolerance import (HeartbeatMonitor,
                                                   StragglerDetector)

        spec = self.spec
        obs = spec.obs
        tracer = self._obs_begin("train")
        mreg = self.metrics
        steps = steps if steps is not None else spec.steps
        stages = spec.parallel.stages
        seq = spec.parallel.seq
        dynamism = spec.dynamics.kind
        straggler = spec.controller.straggler
        measure_stage_times = spec.controller.measure_stage_times
        repack_target = spec.controller.repack.target
        grow_back = spec.cluster.grow_back
        if grow_back is not None:
            warnings.warn(
                "cluster.grow_back / --grow-back is deprecated: fixed-step "
                "re-expansion is superseded by signal-driven scaling "
                "(cluster.autoscale / --autoscale)", DeprecationWarning,
                stacklevel=2)

        cfg = self.model_config()
        dcfg = self._dist_config()
        dyncfg = spec.dynamics.to_config()
        shapes = PipelineShapes(num_micro=spec.parallel.num_micro,
                                mb_global=spec.parallel.mb_global, seq=seq)
        tokens_per_step = (spec.parallel.num_micro
                           * spec.parallel.mb_global * seq)

        # ---- resume point (safe-point metadata drives everything below)
        resume_idx = None
        start_step = 0
        if self._resume_dir:
            from repro.checkpoint.safepoint import peek
            resume_idx = peek(self._resume_dir, self._resume_step)
            start_step = int(resume_idx["step"]) + 1
        rmeta = resume_idx["meta"] if resume_idx is not None else {}

        # ---- chaos: resolve the fault plan before anything it may target
        # (named fplan — the controller's DecisionPlan reuses ``plan``
        # inside the step loop)
        fplan = injector = None
        if spec.faults.enabled:
            from repro.faults import ChaosInjector, resolve_plan
            if spec.faults.worker_crash and not spec.cluster.autoscale:
                raise ValueError(
                    "faults.worker_crash requires cluster.autoscale: the "
                    "heartbeat -> autoscaler -> evict pipeline IS the "
                    "recovery path chaos exercises")
            fplan = resolve_plan(
                spec.faults, horizon=steps,
                workers=(stages if spec.cluster.autoscale else 1),
                file_manager=spec.cluster.job_manager == "file")
            injector = ChaosInjector(fplan, start_step=start_step,
                                     resumed=resume_idx is not None)
            self.injector = injector

        jm = self._connect_job_manager(
            plan=fplan, injector=injector,
            pool_state=(rmeta.get("pool")
                        if spec.cluster.job_manager == "file" else None))
        pool = None
        if jm is None:
            from repro.runtime.fault_tolerance import WorkerPool
            if resume_idx is not None and rmeta.get("pool"):
                pool = WorkerPool.from_state(rmeta["pool"])
            elif spec.cluster.spares:
                pool = WorkerPool(stages, spares=spec.cluster.spares)
        engine = ElasticEngine(cfg, dcfg, dyncfg, shapes,
                               data=spec.parallel.data, pool=pool,
                               job_manager=jm,
                               in_step_timing=obs.in_step_timing)
        self._engine = engine
        if injector is not None:
            import signal

            def _kill_manager():
                if self._jm_proc is not None:
                    self._jm_proc.kill()
                    self._jm_proc.wait()

            def _respawn_manager():
                from repro.cluster.rpc import spawn_file_manager
                self._jm_proc = spawn_file_manager(self._jm_dir, stages,
                                                   spares=spec.cluster
                                                   .spares)

            cbs = {"kill_self":
                   lambda: os.kill(os.getpid(), signal.SIGKILL)}
            if spec.cluster.job_manager == "file":
                cbs["kill_manager"] = _kill_manager
                cbs["respawn_manager"] = _respawn_manager
            injector.bind(**cbs)
        if resume_idx is not None:
            # rebuild at the stage count the run died at, then overwrite
            # the randomly-initialized tensors with the safe point's shards
            # (bit-exact) and re-place them on the restored world's submesh
            from repro.checkpoint.safepoint import restore
            engine.bind_workers([int(w) for w in rmeta["stage_workers"]])
            state = engine.init_state(
                jax.random.PRNGKey(spec.seed),
                stages=int(resume_idx["num_stages"]),
                lps=[int(x) for x in resume_idx["layers_per_stage"]])
            p, o, d, _ = restore(
                self._resume_dir,
                (state.params, state.opt_state, state.dyn),
                int(resume_idx["step"]))
            w = engine.world(state.stages)
            (state.params, state.opt_state, state.dyn, state.assignment,
             _) = engine._place(w, p, o, d, state.assignment)
            engine.epoch = int(rmeta.get("epoch", 0))
        else:
            tenant_min = max(1, repack_target)
            granted = self._register_tenant(
                jm, kind="train", workers=stages, max_workers=stages,
                min_workers=tenant_min)
            if granted is not None:
                # train on exactly the granted workers (arbitrary global
                # ids — another tenant may hold 0..k): same bind +
                # sized-init path the checkpoint resume uses
                engine.bind_workers([int(w) for w in granted])
                state = engine.init_state(jax.random.PRNGKey(spec.seed),
                                          stages=len(granted))
            else:
                state = engine.init_state(jax.random.PRNGKey(spec.seed))

        ccfg = ControllerConfig(method=spec.controller.balancer,
                                rebalance_every=spec.controller
                                .rebalance_every,
                                repack=spec.controller.repack.enabled,
                                repack_policy=spec.controller.repack.policy,
                                repack_target=max(1, repack_target),
                                expert_relayout=dyncfg.expert_relayout,
                                expert_watermark=dyncfg.expert_watermark,
                                expert_min_tokens=dyncfg.expert_min_tokens)
        if spec.controller.repack.enabled:
            # per-worker memory budget: capacity factor × the dtype-correct
            # per-stage footprint of the UNPRUNED model under a uniform
            # split — consolidation becomes feasible once dynamism shrinks
            # the model
            from repro.core.cost_model import stage_memory_budget
            ccfg.repack_mem_cap = stage_memory_budget(
                cfg, tokens_per_step, seq, dcfg.bytes_per_param, stages,
                cap_factor=spec.controller.repack.mem_cap)
        if resume_idx is not None and rmeta.get("repack_enabled") is False:
            # the crashed run had already latched repack off (a grow keeps
            # granted workers); the resumed one must not re-plan a shrink
            ccfg.repack = False
        det = StragglerDetector(stages) \
            if (straggler or measure_stage_times) else None
        ctrl = DynMoController(cfg, dcfg, dyncfg, ccfg, straggler=det)
        cp = ControlPlane(ctrl, async_mode=spec.controller.async_decide,
                          epoch_fn=lambda: engine.epoch)
        self._cp = cp
        if resume_idx is not None:
            cp.rebind(engine.dcfg_for(state.stages), state.lps)

        # ---- autoscaler: heartbeats + throughput watermark; the monitor
        # runs on a step-granular simulated clock so CI is deterministic
        monitor = scaler = None
        sim_clock = [0.0]
        if spec.cluster.autoscale:
            monitor = HeartbeatMonitor(
                stages, timeout_s=spec.cluster.heartbeat_timeout,
                clock=lambda: sim_clock[0])
            scaler = Autoscaler(
                AutoscalerConfig(min_stages=max(1, repack_target),
                                 max_stages=stages,
                                 watermark=spec.cluster.autoscale_watermark),
                monitor)
            if resume_idx is not None and rmeta.get("scaler"):
                scaler.load_state(rmeta["scaler"])

        loader = make_loader(cfg, DataConfig(spec.parallel.num_micro,
                                             spec.parallel.mb_global, seq,
                                             seed=spec.seed),
                             start_step=start_step)
        ckpt = safept = None
        if spec.ckpt_every:
            from repro.checkpoint.safepoint import SafepointManager
            safept = SafepointManager(spec.ckpt_dir, every=spec.ckpt_every)
        elif spec.ckpt_dir:
            from repro.checkpoint.checkpoint import CheckpointManager
            ckpt = CheckpointManager(spec.ckpt_dir,
                                     every=max(10, steps // 5))

        def after_resize(step: int, kind: str) -> None:
            cp.rebind(engine.dcfg_for(state.stages), state.lps)
            if scaler is not None:
                scaler.note_resize(step, state.stages)
            rz = engine.resizes[-1]
            if monitor is not None and rz.kind == "shrink":
                # released workers leave the heartbeat set deliberately; a
                # later revive is the recovery signal the autoscaler grows
                # on
                for w in rz.workers:
                    monitor.expire(w)
            if monitor is not None and rz.kind == "grow":
                # regranted workers (any grow path) must beat again —
                # without the revive they would stay marked failed and a
                # later real death of the same worker could never be
                # detected
                for w in rz.workers:
                    monitor.revive(w)
            self._emit("resize", step, resize_kind=kind,
                       from_stages=rz.from_stages, to_stages=rz.to_stages,
                       workers=list(rz.workers),
                       ticks_before=rz.ticks_before,
                       ticks_after=rz.ticks_after)
            print(f"step {step:4d} {kind.upper()} {rz.from_stages}->"
                  f"{rz.to_stages} stages; workers {rz.workers}; "
                  f"pool active={engine.jm.num_active}; schedule "
                  f"{rz.ticks_before}->{rz.ticks_after} ticks")

        # multi-tenant: poll the cluster scheduler's directive mailbox each
        # step (preempt = shrink at this safe point; offer = absorb free
        # workers back off-peak, DESIGN.md §14)
        multi_tenant = (jm is not None and spec.cluster.tenant_id
                        and getattr(jm, "tenant", None))
        tenant_min = max(1, repack_target)
        last_cluster_resize = start_step - 1
        absorb_cooldown = max(1, spec.controller.rebalance_every)

        losses, events, step_times, stages_hist = [], [], [], []
        relayouts: List[Dict[str, Any]] = []
        expert_skew_last = moe_dropped_last = None
        last_measured = None
        # ---- step-time accounting (DESIGN.md §15): warm-up steps (the
        # first step on each freshly-built world pays the jit compile) and
        # controller-cadence decide time are tracked SEPARATELY from the
        # steady-state step times, so tok/s and per-step histograms are
        # not skewed by one 30 s compile
        stage_time_source = None
        preempt_ctx = None
        warmup_steps, warmup_s, decide_s = 0, 0.0, 0.0
        steady_times: List[float] = []
        # steady throughput runs from the end of the last warm-up step to
        # the end of the last step, host work between steps included
        t_steady0 = t_done = None
        steady_since = 0
        tiles_folded = 0

        def fold_tiles() -> None:
            nonlocal tiles_folded
            tiles = engine.attn_tiles_total()
            mreg.inc("dynmo_attn_tiles_total", tiles - tiles_folded,
                     help="attention (query block, key block) tiles the "
                          "block-sparse kernels computed, forward")
            tiles_folded = tiles

        def iterations():
            """(step, batch) per loop iteration, which runs inside its
            ``train.iter`` span; the loader's wait is ``train.data``."""
            batches = iter(loader)
            for step in range(start_step, steps):
                with span("train.iter", cat="train", step=step):
                    with span("train.data", cat="data"):
                        batch = next(batches, None)
                    if batch is None:
                        return
                    yield step, batch

        root_span = span("train", cat="session", steps=steps, stages=stages)
        t0 = time.perf_counter()
        for step, batch in iterations():
            t_step = time.perf_counter()
            with span("train.batch", cat="data"):
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
                lr = cosine_schedule(jnp.float32(step), steps, 3e-4,
                                     warmup=10)
            with span("train.step", cat="train", step=step,
                      stages=state.stages) as sp_step:
                loss, stats, gnorm = engine.step(state, batch, lr)
                # the step time ends when the device has finished the
                # update; the per-slot stats tree stays on device until
                # controller cadence (§3.3.1)
                with span("train.wait", cat="train"):
                    jax.block_until_ready((loss, state.params,
                                           state.opt_state))
                    losses.append(float(loss))
                sp_step.end(compiled=engine.last_step_compiled)
            t_done = time.perf_counter()
            dt = t_done - t_step
            step_times.append(dt)
            stages_hist.append(state.stages)
            if engine.last_step_compiled:
                warmup_steps += 1
                warmup_s += dt
                t_steady0, steady_since = t_done, 0
            else:
                steady_times.append(dt)
                steady_since += 1
                mreg.observe("dynmo_step_seconds", dt,
                             help="steady-state train step wall seconds")
            mreg.inc("dynmo_train_steps_total",
                     help="train steps executed")
            mreg.set("dynmo_stages", state.stages,
                     help="current pipeline stage count")

            # ---- dynamism events (black-box to the controller)
            with span("train.dynamism", cat="dynamics"):
                if dynamism == "pruning" and step and step % 10 == 0:
                    sp = zhu_gupta_sparsity(
                        step * 100, dataclasses.replace(
                            dyncfg, prune_start_iter=0,
                            prune_end_iter=steps * 100, prune_frequency=1))
                    keep = prn.target_keep_blocks(
                        cfg, cfg.total_blocks(), sp)
                    dyn = dict(state.dyn)
                    dyn["ff_mask"] = prn.global_block_prune(
                        cfg, state.params["stages"],
                        state.assignment["tags"], keep)
                    state.dyn = dyn
                if dynamism == "freezing" and step and step % 10 == 0:
                    front = int(cfg.total_blocks() * min(0.6, step / steps))
                    fr = np.zeros_like(np.asarray(state.dyn["frozen"]))
                    g = 0
                    tags_np = np.asarray(state.assignment["tags"])
                    for s in range(tags_np.shape[0]):
                        for l in range(tags_np.shape[1]):
                            if tags_np[s, l] != 0:
                                if g < front:
                                    fr[s, l] = 1.0
                                g += 1
                    dyn = dict(state.dyn)
                    dyn["frozen"] = jnp.asarray(fr)
                    state.dyn = dyn

            # ---- heartbeats (simulated per-step liveness: active workers
            # beat; released/dead ones go silent and time out)
            if monitor is not None:
                sim_clock[0] = float(step)
                beat = engine.stage_workers if injector is None \
                    else injector.heartbeat_workers(engine.stage_workers)
                for w in beat:
                    monitor.beat(w)
                if (spec.cluster.simulate_recover is not None
                        and step == spec.cluster.simulate_recover):
                    for w in range(stages):
                        if w not in engine.stage_workers:
                            monitor.revive(w)

            # ---- publish stats to the control plane on cadence (the only
            # device→host stats sync; in async mode this is a pointer swap)
            if ctrl.cadence(step + 1):
                t_decide = time.perf_counter()
                sp_dec = span("controller.decide", cat="controller",
                              step=step)
                measured = None
                src = None
                if obs.in_step_timing:
                    # live per-stage seconds folded from the in-step
                    # stage-boundary stamps (DESIGN.md §15) — costs no
                    # extra execution; the probe below stays available
                    # behind controller.measure_stage_times as the
                    # parity oracle
                    measured = engine.in_step_stage_times(state)
                    if measured is not None:
                        src = "in_step"
                if measured is None and measure_stage_times:
                    # real per-stage wall times from the engine's stage
                    # probe — cadence-gated here so the hot path stays
                    # sync-free (the probe is a per-stage host sync)
                    measured = engine.measure_stage_times(state, batch)
                    if measured is not None:
                        src = "probe"
                if measured is not None:
                    last_measured = measured
                    stage_time_source = src
                    for s in range(len(measured)):
                        mreg.set("dynmo_stage_time_seconds",
                                 float(measured[s]),
                                 help="per-stage busy seconds per step",
                                 stage=s, source=src)
                if straggler:
                    # simulation knob: a straggling WORKER multiplies its
                    # stage's wall time; feed the detector the same shape a
                    # real per-worker timer would report (or skew the
                    # measured times when both are on).  Keyed by WORKER
                    # id — after an evict/resize the slow machine keeps its
                    # id but sits at a different stage index
                    if measured is None:
                        share = np.asarray(state.lps, np.float64)
                        measured = share / share.sum() * step_times[-1]
                    measured = measured * np.array(
                        [straggler.get(engine.stage_workers[s], 1.0)
                         for s in range(state.stages)])
                if injector is not None:
                    # chaos straggler spikes: same per-worker multiplier
                    # shape as the simulation knob above, sourced from the
                    # fault plan
                    mult = injector.spike_for(engine.stage_workers)
                    if mult is not None:
                        if measured is None:
                            share = np.asarray(state.lps, np.float64)
                            measured = share / share.sum() * step_times[-1]
                        measured = measured * np.asarray(mult)
                with span("controller.stats_to_host", cat="controller"):
                    host_stats = engine.stats_to_host(state, stats)
                    fold_tiles()
                with span("controller.publish", cat="controller"):
                    cp.publish(StatsSnapshot(
                        iteration=step + 1, epoch=engine.epoch,
                        stats=host_stats,
                        tags=np.asarray(state.assignment["tags"]),
                        num_micro=shapes.num_micro, tokens=tokens_per_step,
                        seq=seq, frozen=np.asarray(state.dyn["frozen"]),
                        stage_times=measured))
                    if spec.controller.async_drain:
                        cp.drain()
                decide_s += time.perf_counter() - t_decide
                sp_dec.end(source=src)

            # ---- cluster-scheduler directives (multi-tenant): a steal by
            # a higher-priority tenant arrives as a preemption directive
            # and is turned into an externally-originated ResizePlan — the
            # SAME epoch-fenced mailbox the controller uses, applied at
            # this step's safe point just below.  Level-triggered: if a
            # concurrent resize fences the injected plan off, the next poll
            # re-delivers the directive.
            if multi_tenant:
                from repro.cluster.rpc import JobManagerUnavailable
                try:
                    directives = jm.poll_cluster()
                except (JobManagerUnavailable, RuntimeError):
                    directives = None
                if directives and directives["preempt"] > 0:
                    target = max(tenant_min,
                                 state.stages - directives["preempt"])
                    if target < state.stages:
                        cp.inject_resize(engine.epoch, target)
                        last_cluster_resize = step
                        # the scheduler forwards the thief's span context
                        # ("cause"): parent this preemption on it so the
                        # cross-process steal→preempt→shrink chain
                        # correlates in the merged trace (DESIGN.md §15)
                        cause = (directives.get("cause")
                                 if isinstance(directives, dict) else None)
                        self._emit("preempt", step, cause_ctx=cause,
                                   due=directives["preempt"],
                                   target_stages=target)
                        if tracer is not None:
                            preempt_ctx = tracer.instant(
                                "cluster.preempt", cat="cluster",
                                parent_id=(cause or {}).get("span_id"),
                                cause_trace_id=(cause or {}).get(
                                    "trace_id"),
                                due=directives["preempt"],
                                target_stages=target)
                elif (directives and directives["offer"] > 0
                        and state.stages < stages
                        and step - last_cluster_resize >= absorb_cooldown):
                    prev = state.stages
                    state = engine.grow(
                        state, min(directives["offer"],
                                   stages - state.stages), step=step)
                    if state.stages > prev:   # scheduler may grant nothing
                        cp.with_ctrl(
                            lambda c: setattr(c.ccfg, "repack", False))
                        after_resize(step, "absorb")
                        self._emit("absorb", step,
                                   workers=state.stages - prev)
                        last_cluster_resize = step

            # ---- scripted voluntary shrink (tests/demos): same injection
            # point and mailbox as an external preemption, so a scripted
            # run is the loss-trajectory oracle for a stolen one
            if shrink_at and step in shrink_at \
                    and shrink_at[step] < state.stages:
                cp.inject_resize(engine.epoch, shrink_at[step],
                                 policy="scripted")

            # ---- safe point: apply the newest finished plan (epoch-
            # fenced; a plan decided against a pre-resize world is
            # rejected)
            plan = cp.poll(engine.epoch)
            if plan is not None:
                if plan.event is not None:
                    expert_skew_last = plan.event.expert_skew
                    moe_dropped_last = plan.event.expert_dropped
                if plan.event is not None and plan.event.rebalanced:
                    events.append(plan.event)
                    self._emit("rebalance", step,
                               iteration=plan.event.iteration,
                               imbalance_before=plan.event.imbalance_before,
                               imbalance_after=plan.event.imbalance_after,
                               moved_layers=plan.event.moved_layers)
                if (plan.resize is not None
                        and plan.resize.target_stages < state.stages):
                    parent = ((preempt_ctx or {}).get("span_id")
                              if plan.resize.policy == "preempt" else None)
                    sp_rz = span("resize.shrink", cat="resize",
                                 parent_id=parent, step=step,
                                 policy=plan.resize.policy,
                                 target=plan.resize.target_stages)
                    state = engine.shrink(state, plan.resize.target_stages,
                                          plan.resize.layers_per_stage,
                                          step=step)
                    after_resize(step, f"shrink[{plan.resize.policy}]")
                    mreg.inc("dynmo_resizes_total", kind="shrink",
                             policy=plan.resize.policy,
                             help="engine resizes by kind")
                    sp_rz.end(stages=state.stages)
                    if plan.resize.policy == "preempt":
                        preempt_ctx = None
                elif plan.new_lps is not None:
                    p, o, d, new_assignment, _ = cp.apply(
                        plan, state.params, state.opt_state, state.dyn)
                    state.params, state.opt_state, state.dyn = p, o, d
                    state.assignment = new_assignment
                    state.lps = list(cp.ctrl.lps)
                # ---- expert re-layout: orthogonal to the stage plans
                # above (it only rewrites the expert_map dyn leaf, which
                # survives a same-plan shrink because it is per-expert,
                # not per-stage)
                if (plan.expert_relayout is not None
                        and "expert_map" in state.dyn):
                    rl = plan.expert_relayout
                    dyn = dict(state.dyn)
                    em = dyn["expert_map"]
                    # broadcast the [E] placement over the existing sharded
                    # [S, L_max, E] leaf (em*0 + new keeps its placement;
                    # a fresh jnp array would land unsharded)
                    dyn["expert_map"] = em * 0 + jnp.asarray(
                        rl.new.as_array())
                    state.dyn = dyn
                    cp.with_ctrl(lambda c: c.commit_relayout(rl))
                    rec = {"step": step, "iteration": rl.iteration,
                           "skew": rl.skew, "tokens": rl.total_tokens,
                           "moved_experts": rl.moved_experts,
                           "placement": list(rl.new.placement)}
                    relayouts.append(rec)
                    self._emit("relayout", step, **rec)
                    print(f"step {step:4d} RELAYOUT skew "
                          f"{rl.skew:.2f} moved {rl.moved_experts} "
                          f"experts -> {list(rl.new.placement)}")

            # ---- autoscaler: heartbeat + watermark signals
            if scaler is not None:
                # "logical" clock: feed the watermark a schedule-derived
                # step time (GPipe tick count) instead of wall-clock —
                # deterministic on shared CI machines
                wm_dt = step_times[-1]
                if spec.cluster.watermark_clock == "logical":
                    wm_dt = engine.ticks(state.stages) * 1e-3
                d = scaler.observe(step, wm_dt, state.stages,
                                   engine.stage_workers, tokens_per_step)
                if d.action != "none":
                    self._emit("autoscale", step, action=d.action,
                               workers=d.workers, reason=d.reason,
                               ids=list(d.ids))
                if d.action == "evict":
                    state = engine.evict(state, d.ids, step=step)
                    after_resize(step, "evict")
                elif d.action == "grow" and state.stages < stages:
                    prev = state.stages
                    state = engine.grow(state, d.workers, step=step)
                    if state.stages > prev:   # pool may grant nothing
                        # granted workers stay for this job: stop planning
                        # resizes so ordinary rebalancing keeps running
                        cp.with_ctrl(
                            lambda c: setattr(c.ccfg, "repack", False))
                        after_resize(step, "grow")
                elif (d.action == "shrink"
                        and state.stages > max(1, repack_target)):
                    state = engine.shrink(
                        state, max(max(1, repack_target),
                                   state.stages - d.workers), step=step)
                    after_resize(step, "shrink[watermark]")

            # ---- legacy fixed-step growth (deprecated; superseded by
            # cluster.autoscale)
            if (grow_back and engine.last_shrink_step is not None
                    and state.stages < stages
                    and step >= engine.last_shrink_step + grow_back):
                prev_stages = state.stages
                state = engine.grow(state, stages - state.stages, step=step)
                if state.stages > prev_stages:
                    cp.with_ctrl(lambda c: setattr(c.ccfg, "repack", False))
                    after_resize(step, "grow")
            if ckpt:
                ckpt.maybe_save(step, state.params, state.opt_state,
                                state.dyn, state.lps)
            if safept is not None and safept.due(step):
                with span("safepoint", cat="checkpoint",
                          step=step) as sp_ck:
                    path = safept.save(
                        step, state, spec=spec, engine=engine,
                        scaler=scaler, repack_enabled=cp.with_ctrl(
                            lambda c: bool(c.ccfg.repack)),
                        jm_dir=self._jm_dir)
                    sp_ck.end(path=path)
                self._emit("safepoint", step, path=path,
                           stages=state.stages)
            if injector is not None:
                # fire scheduled faults AFTER the safe point: a trainer
                # kill at step k leaves the k-aligned safe point on disk
                # for Session.resume
                injector.on_step(step, workers=engine.stage_workers)
            if step % spec.log_every == 0:
                self._emit("log", step, loss=float(loss),
                           gnorm=float(gnorm), stages=state.stages,
                           lps=list(state.lps))
                print(f"step {step:4d} loss {float(loss):.4f} "
                      f"gnorm {float(gnorm):.3f} S={state.stages} "
                      f"lps={state.lps}")
        wall = time.perf_counter() - t0
        fold_tiles()
        root_span.end(steps_run=len(losses))
        steady_s = float(sum(steady_times))
        steady_tok_s = (tokens_per_step * steady_since
                        / (t_done - t_steady0) if steady_since else None)
        if steady_tok_s is not None:
            mreg.set("dynmo_tokens_per_s", steady_tok_s,
                     help="steady-state training throughput")
        timing = {
            "warmup_steps": warmup_steps, "warmup_s": warmup_s,
            "decide_s": decide_s,
            "steady_steps": len(steady_times), "steady_s": steady_s,
            "steady_step_mean_s": (steady_s / len(steady_times)
                                   if steady_times else None),
            "steady_step_p50_s": (float(np.percentile(steady_times, 50))
                                  if steady_times else None),
            "steady_step_p95_s": (float(np.percentile(steady_times, 95))
                                  if steady_times else None),
            "steady_tokens_per_s": steady_tok_s,
        }
        report = {
            "losses": losses, "events": events, "wall_s": wall,
            "final_lps": list(state.lps), "params": state.params,
            "assignment": state.assignment,
            "tokens_per_step": tokens_per_step,
            "step_times": step_times, "stages_history": stages_hist,
            "resizes": [dataclasses.asdict(e) for e in engine.resizes],
            "pool_log": list(engine.jm.log),
            "final_stages": state.stages,
            "measured_stage_times": (list(map(float, last_measured))
                                     if last_measured is not None else None),
            "stage_time_source": stage_time_source,
            "timing": timing,
            "controller": {
                "mode": ("async" if spec.controller.async_decide
                         else "inline"),
                "published": cp.published, "decided": cp.decided,
                "dropped": cp.dropped,
                "stale_rejected": cp.stale_rejected},
            # ---- expert-parallel telemetry (MoE archs; None otherwise)
            "relayouts": relayouts,
            "expert_skew_last": expert_skew_last,
            "moe_dropped_last": moe_dropped_last,
            "expert_layout": (list(cp.ctrl.expert_layout.placement)
                              if cp.ctrl.expert_layout is not None
                              else None),
            "autoscale_decisions": ([dataclasses.asdict(d)
                                     for d in scaler.decisions]
                                    if scaler is not None else []),
            "spec": self.spec.to_dict(),
            # ---- fault-tolerance telemetry (DESIGN.md §12)
            "start_step": start_step,
            "resumed_from": (int(resume_idx["step"])
                             if resume_idx is not None else None),
            "safepoints": list(safept.saved) if safept is not None else [],
            "faults": injector.report() if injector is not None else [],
            "fault_plan": fplan.to_dict() if fplan is not None else None,
            "degraded_events": list(engine.degraded_events),
            "rpc": ({"stats": dict(jm.rpc_stats),
                     "breaker": jm.breaker.state_dict()}
                    if jm is not None else None),
        }
        self.state = state
        self._emit("train_summary", steps - 1,
                   loss_first=losses[0] if losses else None,
                   loss_last=losses[-1] if losses else None,
                   wall_s=wall, resizes=len(engine.resizes),
                   final_stages=state.stages)
        return report

    # =======================================================================
    # Serving
    # =======================================================================
    def make_trace(self):
        """The request trace described by ``spec.serve`` (bursty square-wave
        arrivals, mixed prompt/gen lengths, optional early-exit fraction)."""
        from repro.serve import make_trace
        s = self.spec.serve
        cfg = self.model_config()
        return make_trace(s.requests, prompt_len=s.prompt_len,
                          max_gen=s.gen, vocab_size=cfg.vocab_size,
                          seed=self.spec.seed,
                          min_prompt=s.min_prompt or max(1,
                                                         s.prompt_len // 2),
                          burst_period=s.burst_period, burst_len=s.burst_len,
                          burst_rate=s.burst_rate, lull_rate=s.lull_rate,
                          early_exit_frac=s.early_exit_frac)

    def serve(self, trace=None, *, resize_at: Optional[Dict[int, int]] = None
              ) -> Dict[str, Any]:
        """Serve ``trace`` (default: the spec's generated trace) through the
        continuous-batching scheduler on elastic engine worlds.  Returns the
        server's report dict."""
        from repro.cluster.autoscaler import Autoscaler, AutoscalerConfig
        from repro.pipeline.pipeline import PipelineShapes
        from repro.serve import ElasticServer

        spec = self.spec
        s = spec.serve
        tracer = self._obs_begin("serve")
        cfg = self.model_config()
        dcfg = self._dist_config()
        dyncfg = spec.dynamics.to_config()
        shapes = PipelineShapes(spec.parallel.num_micro,
                                spec.parallel.mb_global, s.prompt_len,
                                cache_len=s.prompt_len + s.gen)
        paged = None
        if s.kv_page_size > 0:
            from repro.serve.kv import PagedKVConfig
            # kv_pool_pages=0 auto-sizes to the dense-equivalent footprint
            # (every lane could hold a full cache line) — same bytes as
            # dense, so paged-by-default changes layout, not capacity
            lanes = spec.parallel.num_micro * spec.parallel.mb_global
            pool = s.kv_pool_pages or lanes * (shapes.cache_len
                                               // s.kv_page_size)
            paged = PagedKVConfig(page_size=s.kv_page_size, pool_pages=pool,
                                  prefix_cache=s.prefix_cache)
        if trace is None:
            trace = self.make_trace()

        # ---- chaos: the fault horizon is the trace's expected drain time
        # (arrival span + tokens/lanes), not max_ticks — auto-derived events
        # must land while requests are actually in flight
        plan = injector = None
        if spec.faults.enabled:
            from repro.faults import ChaosInjector, resolve_plan
            lanes = spec.parallel.num_micro * spec.parallel.mb_global
            est = (max((r.arrival for r in trace), default=0)
                   + sum(r.gen for r in trace) // max(1, lanes)
                   + len(trace))
            plan = resolve_plan(spec.faults,
                                horizon=max(8, min(s.max_ticks, est)),
                                workers=spec.parallel.stages,
                                file_manager=spec.cluster.job_manager
                                == "file")
            injector = ChaosInjector(plan)
            self.injector = injector

        scaler = None
        if spec.cluster.autoscale:
            scaler = Autoscaler(AutoscalerConfig(
                min_stages=max(1, s.min_stages),
                max_stages=spec.parallel.stages,
                patience=s.patience, cooldown=s.cooldown,
                queue_high=s.queue_high, occupancy_low=s.occupancy_low,
                latency_slo_s=s.latency_slo_s))
        jm = self._connect_job_manager(plan=plan, injector=injector)
        # multi-tenant: start on the scheduler's grant (usually min_stages
        # — serve small, steal under load) instead of the spec's maximum
        granted = self._register_tenant(
            jm, kind="serve", workers=s.min_stages,
            max_workers=spec.parallel.stages, min_workers=s.min_stages)
        if injector is not None and spec.cluster.job_manager == "file":

            def _kill_manager():
                if self._jm_proc is not None:
                    self._jm_proc.kill()
                    self._jm_proc.wait()

            def _respawn_manager():
                from repro.cluster.rpc import spawn_file_manager
                self._jm_proc = spawn_file_manager(
                    self._jm_dir, spec.parallel.stages,
                    spares=spec.cluster.spares)

            injector.bind(kill_manager=_kill_manager,
                          respawn_manager=_respawn_manager)
        srv = ElasticServer(cfg, dcfg, dyncfg, shapes, job_manager=jm,
                            scaler=scaler, min_stages=s.min_stages,
                            seed=spec.seed, defrag_every=s.defrag_every,
                            measure_stage_times=spec.controller
                            .measure_stage_times,
                            initial_workers=granted,
                            in_step_timing=spec.obs.in_step_timing,
                            tracer=tracer, metrics=self.metrics,
                            paged=paged, temperature=s.temperature)
        self._server = srv
        with span("serve", cat="session",
                  requests=len(trace)) as root_span:
            report = srv.serve(trace, autoscale=spec.cluster.autoscale,
                               resize_at=resize_at, max_ticks=s.max_ticks,
                               injector=injector)
            root_span.end(ticks=report["ticks"],
                          completions=len(report["completions"]))
        self.metrics.set("dynmo_tokens_per_s", report["tokens_per_s"],
                         help="serving throughput")
        self.metrics.set("dynmo_latency_p95_s", report["latency_p95_s"],
                         help="serving p95 request latency")
        report["spec"] = spec.to_dict()
        report["faults"] = injector.report() if injector is not None else []
        report["fault_plan"] = plan.to_dict() if plan is not None else None
        report["degraded_events"] = list(srv.engine.degraded_events)
        report["rpc"] = ({"stats": dict(jm.rpc_stats),
                          "breaker": jm.breaker.state_dict()}
                         if jm is not None else None)
        for rz in report["resizes"]:
            self._emit("resize", rz["step"], resize_kind=rz["kind"],
                       from_stages=rz["from_stages"],
                       to_stages=rz["to_stages"],
                       workers=list(rz["workers"]))
            if granted is not None and rz["kind"] == "shrink":
                # tenant-scoped release IS a yield: the freed workers go
                # back through the scheduler to whoever is owed/offered
                self._emit("yield", rz["step"],
                           workers=list(rz["workers"]),
                           tenant=spec.cluster.tenant_id)
        for d in report["autoscale_decisions"]:
            self._emit("autoscale", d["step"], action=d["action"],
                       workers=d["workers"], reason=d["reason"],
                       ids=list(d["ids"]))
            if (granted is not None and d["action"] == "grow"
                    and d.get("urgent")):
                self._emit("steal", d["step"], workers=d["workers"],
                           reason=d["reason"],
                           tenant=spec.cluster.tenant_id)
        self._emit("serve_summary", report["ticks"],
                   completions=len(report["completions"]),
                   total_tokens=report["total_tokens"],
                   tokens_per_s=report["tokens_per_s"],
                   latency_p95_s=report["latency_p95_s"])
        return report

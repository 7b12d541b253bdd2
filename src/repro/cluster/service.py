"""Asynchronous DynMo decision service (paper §3.3.1).

The profile→decide loop must stay off the training critical path so that
per-iteration cadence (MoE/MoD) pays zero step latency.  ``ControlPlane``
runs ``DynMoController.decide`` on a background thread behind a
double-buffered stats mailbox:

  * the training thread *publishes* the host-synced ``[S, L_max]`` stats
    snapshot on controller cadence — an O(1) pointer swap, never a wait on
    the decision;
  * the worker thread folds the snapshot through the profiler, runs the
    balancer/repack decision, and posts the plan into a latest-wins outbox;
  * the training thread *polls* the outbox at its next safe point (between
    steps) and applies the plan there.

Epoch fencing: every engine resize (shrink/grow/evict) advances the world
epoch.  A plan decided against a stale world — wrong stage count or layer
split after a resize — is rejected by epoch at ``poll`` (or skipped before
deciding, when the plane can see the live epoch via ``epoch_fn``); it is
never applied.

In ``async_mode=False`` the same ``_decide`` body runs synchronously on the
publishing thread, so the inline and asynchronous paths produce bit-identical
decisions from the same snapshot by construction (parity-tested).
``drain()`` makes the asynchronous mode deterministic for tests and loss
parity runs: it blocks until the worker has emptied the mailbox.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from repro.core.controller import (ControllerEvent, DynMoController,
                                   ResizePlan)
from repro.core.expert_layout import ExpertRelayoutPlan
from repro.core.profiler import profile_from_stats


@dataclasses.dataclass
class StatsSnapshot:
    """Host-side view of one profiling iteration, tagged with the engine
    epoch it was observed in.  Everything the worker thread needs to run
    profile→decide without touching live training state."""
    iteration: int
    epoch: int
    stats: Dict[str, np.ndarray]        # folded [S, L_max, ...] (host)
    tags: np.ndarray                    # [S, L_max] slot→global-layer map
    num_micro: int
    tokens: int
    seq: int
    frozen: Optional[np.ndarray] = None
    stage_times: Optional[np.ndarray] = None   # measured per-stage seconds
    #   (feeds the controller's StragglerDetector when one is attached)


@dataclasses.dataclass
class DecisionPlan:
    """One controller decision, fenced by the epoch of the world it was
    decided against.  Either ``new_lps`` (in-mesh migration) or ``resize``
    (live shrink) is set — the controller never emits both.
    ``expert_relayout`` is orthogonal (it moves no stage state, only the
    expert_map dyn leaf) and may accompany either."""
    epoch: int
    iteration: int
    new_lps: Optional[List[int]]
    resize: Optional[ResizePlan]
    event: ControllerEvent
    decide_s: float                     # worker-side profile+decide seconds
    expert_relayout: Optional[ExpertRelayoutPlan] = None


class ControlPlane:
    """Runs the controller's decisions off the training thread.

    The training thread talks to the controller ONLY through this object:
    ``publish`` / ``poll`` for decisions, ``apply`` / ``rebind`` /
    ``with_ctrl`` for safe-point state mutation — all controller access is
    serialized on one lock, so a decide in flight never observes a
    half-applied migration.
    """

    def __init__(self, ctrl: DynMoController, *, async_mode: bool = True,
                 epoch_fn: Optional[Callable[[], int]] = None,
                 name: str = "dynmo-control-plane"):
        self.ctrl = ctrl
        self.async_mode = async_mode
        self.epoch_fn = epoch_fn
        self._ctrl_lock = threading.Lock()   # decide vs apply/rebind
        self._cv = threading.Condition()     # guards inbox/outbox/busy/stop
        self._inbox: Optional[StatsSnapshot] = None
        self._outbox: Optional[DecisionPlan] = None
        self._busy = False
        self._stop = False
        self._error: Optional[BaseException] = None
        # counters (telemetry + tests)
        self.published = 0
        self.decided = 0
        self.dropped = 0            # snapshots overwritten before consumption
        self.stale_rejected = 0     # plans fenced off by epoch
        self._thread: Optional[threading.Thread] = None
        if async_mode:
            self._thread = threading.Thread(target=self._loop, name=name,
                                            daemon=True)
            self._thread.start()

    # -- training-thread API ----------------------------------------------
    def publish(self, snap: StatsSnapshot) -> None:
        """Hand a stats snapshot to the decision worker.  Never blocks on
        the decision; an unconsumed older snapshot is overwritten
        (latest-wins — the controller always decides on the freshest
        profile, paper §3.3.1)."""
        self.published += 1
        if not self.async_mode:
            plan = self._decide(snap)
            with self._cv:
                self._outbox = plan
            return
        with self._cv:
            if self._inbox is not None:
                self.dropped += 1
            self._inbox = snap
            self._cv.notify_all()

    def poll(self, epoch: int) -> Optional[DecisionPlan]:
        """Fetch the newest finished plan, or None.  ``epoch`` is the
        caller's CURRENT world epoch: a plan decided against an older world
        is rejected here and never reaches the training state."""
        self._reraise()
        with self._cv:
            plan, self._outbox = self._outbox, None
        if plan is None:
            return None
        if plan.epoch != epoch:
            self.stale_rejected += 1
            return None
        return plan

    def inject_resize(self, epoch: int, target_stages: int, *,
                      policy: str = "preempt") -> DecisionPlan:
        """Put an externally-originated shrink into the outbox (DESIGN.md
        §14): a cluster-scheduler preemption arrives through the SAME
        epoch-fenced mailbox as controller decisions, so the training loop
        applies it at its next safe point with zero new machinery — and a
        plan fenced off by a concurrent resize is simply re-injected at the
        next directive poll (the scheduler's directives are level-
        triggered), never lost.  Latest-wins like any other plan."""
        plan = DecisionPlan(
            epoch=epoch, iteration=-1, new_lps=None,
            resize=ResizePlan(iteration=-1, target_stages=target_stages,
                              layers_per_stage=None, released_stages=[],
                              policy=policy, mem_per_stage=[]),
            event=None, decide_s=0.0)
        with self._cv:
            self._outbox = plan
        return plan

    def drain(self, timeout: float = 60.0) -> None:
        """Block until the worker has consumed the inbox and finished any
        in-flight decision.  Deterministic mode: publish → drain → poll is
        step-for-step identical to the inline path (used by the parity
        tests and ``run_training(async_drain=True)``)."""
        if not self.async_mode:
            return
        deadline = time.monotonic() + timeout
        with self._cv:
            while self._inbox is not None or self._busy:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError("control-plane drain timed out")
                self._cv.wait(min(0.05, remaining))
        self._reraise()

    def _reraise(self) -> None:
        """Surface a worker-thread failure on the training thread — an
        async run must crash as loudly as the inline path would, not
        silently stop making decisions."""
        if self._error is not None:
            err, self._error = self._error, None
            raise RuntimeError(
                "control-plane decision worker failed") from err

    # -- safe-point state mutation ----------------------------------------
    def apply(self, plan: DecisionPlan, params, opt_state, dyn, cache=None):
        """Apply a rebalance plan's migration at a safe point (training
        thread).  Serialized against in-flight decides."""
        with self._ctrl_lock:
            return self.ctrl.apply(plan.new_lps, params, opt_state, dyn,
                                   cache)

    def rebind(self, dcfg, layers_per_stage) -> None:
        """Re-anchor the controller after an engine resize (new world)."""
        with self._ctrl_lock:
            self.ctrl.rebind(dcfg, layers_per_stage)

    def with_ctrl(self, fn: Callable[[DynMoController], Any]) -> Any:
        """Run ``fn(ctrl)`` under the controller lock — for any other
        mutation the training loop needs (e.g. disabling repack after a
        grow)."""
        with self._ctrl_lock:
            return fn(self.ctrl)

    # -- decision body (shared by inline and worker paths) -----------------
    def _decide(self, snap: StatsSnapshot) -> Optional[DecisionPlan]:
        if self.epoch_fn is not None and self.epoch_fn() != snap.epoch:
            # the world already changed under this snapshot: don't waste a
            # decide on it (and don't pollute controller state/events)
            self.stale_rejected += 1
            return None
        t0 = time.perf_counter()
        from repro.obs.trace import span
        sp = span("controlplane.decide", cat="controller",
                  iteration=snap.iteration, epoch=snap.epoch)
        with self._ctrl_lock:
            ctrl = self.ctrl
            if (snap.stage_times is not None
                    and ctrl.straggler is not None):
                ctrl.straggler.update(snap.stage_times)
            profile = profile_from_stats(
                ctrl.cfg, snap.stats, snap.tags, snap.num_micro,
                snap.tokens, snap.seq, frozen=snap.frozen,
                bytes_per_param=ctrl.dcfg.bytes_per_param)
            new_lps, ev = ctrl.decide(profile, snap.iteration)
            resize = ctrl.take_resize()
            relayout = ctrl.take_expert_relayout()
        self.decided += 1
        sp.end(rebalanced=bool(ev is not None and ev.rebalanced),
               resize=resize is not None)
        return DecisionPlan(epoch=snap.epoch, iteration=snap.iteration,
                            new_lps=new_lps, resize=resize, event=ev,
                            decide_s=time.perf_counter() - t0,
                            expert_relayout=relayout)

    # -- worker thread -----------------------------------------------------
    def _loop(self) -> None:
        while True:
            with self._cv:
                while self._inbox is None and not self._stop:
                    self._cv.wait(0.2)
                if self._stop:
                    return
                snap, self._inbox = self._inbox, None
                self._busy = True
            plan = None
            try:
                plan = self._decide(snap)
            except BaseException as e:   # noqa: BLE001 — handed to trainer
                self._error = e
            finally:
                with self._cv:
                    if plan is not None:
                        self._outbox = plan
                    self._busy = False
                    self._cv.notify_all()

    def close(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=5.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

"""Drives the system's own training entry through a warm-up and a timed
window: ``RunSpec -> Session.train -> ElasticEngine -> pipeline -> blocks ->
Pallas kernels``, the path a user calls.

The system has no time limit and no data hook on ``Session.train``, so the
driver leans on two of its names for the length of the call:

* ``repro.data.loader.make_loader``, which ``Session.train`` imports when it
  is called, is replaced by the window's loader: it hands out the warm-up
  batches, then window batches until the window's seconds have passed, and
  then stops, so that ``train`` returns with its full report.
* ``ElasticEngine.step`` is wrapped, to copy the initial parameters before
  the first step, to read each compared step's mask density per layer, the
  optimizer's first moment after the first step and the change of the
  parameters after three, and to mark each step in the profiler trace.  The wrapper
  adds no device work to the window's steps.

The window starts when the loader is asked for the first window batch and
ends when it is asked for the batch after the last one, so it holds whole
steps and all the host work between them (controller, migrations, loader).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """Backend compilations (persistent-cache loads included), each with
    the host time it ended."""

    def __init__(self):
        import jax
        self.events: List[tuple] = []

        def on_duration(event, duration, **_):
            if event == COMPILE_EVENT:
                self.events.append((time.perf_counter(), float(duration)))
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def between(self, t0: float, t1: float) -> List[tuple]:
        return [e for e in self.events if t0 <= e[0] <= t1]


# ---------------------------------------------------------------------------
# per-leaf norms of the system's state, in the reference's leaf names
# ---------------------------------------------------------------------------
def layer_slots(assignment) -> np.ndarray:
    """Flat slot (stage * slots + slot) of each global layer."""
    tags = np.asarray(assignment["tags"])
    na = np.asarray(assignment["num_active"])
    db = np.asarray(assignment["depth_base"])
    slots = tags.shape[1]
    idx = np.empty(int(na.sum()), np.int32)
    for s in range(tags.shape[0]):
        idx[db[s]:db[s] + na[s]] = s * slots + np.arange(na[s])
    return idx


def _jitted():
    import jax
    import jax.numpy as jnp

    def slot_norms(stages):
        return {f: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)),
                                    axis=tuple(range(2, a.ndim))))
                for f, a in stages.items()}

    def moved(a, b, ia, ib):
        fa = a.reshape((-1,) + a.shape[2:])[ia].astype(jnp.float32)
        fb = b.reshape((-1,) + b.shape[2:])[ib].astype(jnp.float32)
        return jnp.sqrt(jnp.sum(jnp.square(fb - fa),
                                axis=tuple(range(1, fa.ndim))))

    def dist(a, b):
        return jnp.sqrt(jnp.sum(jnp.square(b.astype(jnp.float32)
                                           - a.astype(jnp.float32))))

    def norm(a):
        return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))

    return jax.jit(slot_norms), jax.jit(moved), jax.jit(dist), jax.jit(norm)


MODEL_LEAVES = ("embed", "head", "final_norm")


def tree_norms(tree, assignment, scale: float = 1.0) -> Dict[str, float]:
    """Norms of a params-shaped tree (parameters or a moment) per leaf."""
    slot_norms, _, _, norm = _jitted()
    idx = layer_slots(assignment)
    out = {k: float(norm(tree[k])) * scale for k in MODEL_LEAVES
           if k in tree}
    for f, a in slot_norms(tree["stages"]).items():
        flat = np.asarray(a).reshape(-1)
        for i, j in enumerate(idx):
            out[f"layer{i}.{f}"] = float(flat[j]) * scale
    return out


def change_norms(p0, a0, p1, a1) -> Dict[str, float]:
    """Per-leaf norm of p1 - p0, each layer found through its own
    assignment on either side (a migration moves layers between slots)."""
    import jax.numpy as jnp
    _, moved, dist, _ = _jitted()
    i0 = jnp.asarray(layer_slots(a0))
    i1 = jnp.asarray(layer_slots(a1))
    out = {k: float(dist(p0[k], p1[k])) for k in MODEL_LEAVES if k in p0}
    for f in p0["stages"]:
        vals = np.asarray(moved(p0["stages"][f], p1["stages"][f], i0, i1))
        for i, v in enumerate(vals):
            out[f"layer{i}.{f}"] = float(v)
    return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Outcome:
    losses: List[float]
    step_times: List[float]
    warmup: int
    window_steps: int = 0
    t_start: Optional[float] = None
    t_end: Optional[float] = None
    grad_norms: Optional[Dict[str, float]] = None
    delta_norms: Optional[Dict[str, float]] = None
    program_bytes: Optional[Dict[str, int]] = None
    # per compared step, each layer's mask density (summed over the step's
    # micro-batches, averaged over the rows of one)
    densities: List[List[float]] = dataclasses.field(default_factory=list)
    report: Optional[dict] = None

    @property
    def window_s(self) -> float:
        return self.t_end - self.t_start

    @property
    def window_step_times(self) -> List[float]:
        return self.step_times[self.warmup:self.warmup + self.window_steps]


class Drive:
    """One ``Session.train`` call of the cell.

    ``warmup`` steps run before the window; with ``seconds=None`` no window
    runs and the call stops after the warm-up (the correctness sweeps).
    ``fault`` (tests and sweeps only) edits each step's (batch, lr)."""

    COMPARED = 3

    def __init__(self, cell, seed: int, batches: Callable[[int], dict], *,
                 warmup: int, seconds: Optional[float],
                 trace_dir: Optional[str] = None,
                 fault: Optional[Callable] = None):
        self.cell, self.seed, self.batches = cell, int(seed), batches
        self.warmup, self.seconds = warmup, seconds
        self.trace_dir, self.fault = trace_dir, fault
        self.b1 = cell.reference().ADAMW["b1"]
        self.calls = 0
        self.p0 = self.a0 = None
        self.out = Outcome([], [], warmup)
        self._window_ann = None

    # -- the loader the session gets -----------------------------------
    def loader(self, cfg, dc, start_step: int = 0):
        import jax
        step = 0
        while True:
            if step == self.warmup and self.seconds is not None:
                if self.trace_dir is not None:
                    jax.profiler.start_trace(self.trace_dir)
                    self._window_ann = jax.profiler.TraceAnnotation(
                        "bench.window")
                    self._window_ann.__enter__()
                self.out.t_start = time.perf_counter()
            if step > self.warmup or (step == self.warmup
                                      and self.seconds is None):
                now = time.perf_counter()
                if self.seconds is None or (
                        now - self.out.t_start >= self.seconds):
                    self.out.t_end = now
                    self.out.window_steps = step - self.warmup
                    if self._window_ann is not None:
                        self._window_ann.__exit__(None, None, None)
                    return
            with jax.profiler.TraceAnnotation("bench.loader"):
                batch = self.batches(step)
            yield batch
            step += 1

    # -- the engine step, wrapped --------------------------------------
    def _step(self, orig):
        import jax
        import jax.numpy as jnp
        drive = self

        def step(engine, state, batch, lr):
            k = drive.calls
            drive.calls += 1
            assignment = jax.device_get(state.assignment) if (
                k < drive.COMPARED) else None
            if k == 0:
                drive.p0 = jax.tree.map(jnp.copy, state.params)
                drive.a0 = assignment
            elif k == 1:
                drive.out.grad_norms = tree_norms(
                    state.opt_state["m"], state.assignment,
                    1.0 / (1.0 - drive.b1))
            elif k == drive.COMPARED:
                drive._take_change(state)
            if drive.fault is not None:
                batch, lr = drive.fault(batch, lr)
            with jax.profiler.TraceAnnotation("bench.step"):
                loss, stats, gnorm = orig(engine, state, batch, lr)
            if assignment is not None:
                dens = np.asarray(stats["attn_density"]).reshape(-1)
                drive.out.densities.append(
                    [float(dens[j]) for j in layer_slots(assignment)])
            return loss, stats, gnorm
        return step

    def _take_change(self, state):
        self.out.delta_norms = change_norms(self.p0, self.a0, state.params,
                                            state.assignment)
        self.p0 = None

    # -- run -----------------------------------------------------------
    def run(self, spec) -> Outcome:
        import jax
        import repro.data.loader as loader_mod
        from repro.api import Session
        from repro.launch.engine import ElasticEngine

        orig_loader, orig_step = loader_mod.make_loader, ElasticEngine.step
        loader_mod.make_loader = self.loader
        ElasticEngine.step = self._step(orig_step)
        try:
            with Session(spec) as s:
                self.cell.check_program_config(s.model_config())
                rep = s.train()
                if self.trace_dir is not None and self.seconds is not None:
                    jax.profiler.stop_trace()
                if self.out.delta_norms is None and self.p0 is not None:
                    self._take_change(s.state)
                self.out.losses = [float(x) for x in rep["losses"]]
                self.out.step_times = [float(x) for x in rep["step_times"]]
                self.out.report = {"final_lps": rep["final_lps"],
                                   "rebalances": [
                    (ev.step, ev.data["moved_layers"]) for ev in s.events
                    if ev.kind == "rebalance"]}
                if self.seconds is not None:
                    self.out.program_bytes = program_bytes(s, self.cell)
                del rep
        finally:
            loader_mod.make_loader = orig_loader
            ElasticEngine.step = orig_step
            self.p0 = None
        return self.out


def program_bytes(session, cell) -> Dict[str, int]:
    """Memory of the compiled train step of the live world (lowered again
    from the live state, which finds the compiled program in the cache)."""
    import jax.numpy as jnp
    state = session.state
    world = session.engine.world(state.stages)
    p = cell.parallel
    tok = jnp.zeros((p["num_micro"], p["mb_global"], p["seq"]), jnp.int32)
    batch = {"tokens": tok, "labels": tok,
             "label_mask": jnp.ones(tok.shape, jnp.float32)}
    with world.mesh:
        compiled = world.step.lower(
            state.params, state.opt_state, state.assignment, state.dyn,
            batch, jnp.float32(0.0)).compile()
    m = compiled.memory_analysis()
    return {"arguments": int(m.argument_size_in_bytes),
            "outputs": int(m.output_size_in_bytes),
            "temporaries": int(m.temp_size_in_bytes),
            "aliased": int(m.alias_size_in_bytes)}

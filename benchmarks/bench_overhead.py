"""Figure 4 (right): DynMo overhead breakdown — profiling, balancing
algorithm, layer migration — as a fraction of end-to-end training time.
Paper: single-digit percent across cases.

Also home of the control-plane latency bench (``main_controller``,
BENCH_controller.json): per-step decision cost paid by the TRAINING thread,
inline vs async, at ``rebalance_every=1`` — the §3.3.1 acceptance number
(async train-thread cost ~ 0: publishing a snapshot is a pointer swap)."""
from __future__ import annotations

import time

from benchmarks.common import CASE_ARCH, sim_case


def run(quick: bool = False):
    iters = 2000 if quick else 10000
    out = {}
    for kind, arch in CASE_ARCH.items():
        r = sim_case(kind, arch, "diffusion", "time", True,
                     sample_every=200 if quick else 100, iters=iters)
        tot = max(1e-12, r.total_time)
        out[kind] = {
            "profile": r.overhead_breakdown["profile"] / tot,
            "algorithm": r.overhead_breakdown["algorithm"] / tot,
            "migration": r.overhead_breakdown["migration"] / tot,
            "total": r.overhead_frac,
        }
    return out


def main(quick: bool = False):
    res = run(quick)
    print("name,us_per_call,derived")
    for kind, d in res.items():
        for part in ("profile", "algorithm", "migration", "total"):
            print(f"overhead_{part}_{kind},0,{d[part]:.5f}")
    return res


# ---------------------------------------------------------------------------
# control-plane decision latency: inline vs async (per training step)
# ---------------------------------------------------------------------------
def run_controller(quick: bool = False):
    import numpy as np
    from repro.cluster.service import ControlPlane, StatsSnapshot
    from repro.configs import DistConfig, get_config, reduced_config
    from repro.core.controller import ControllerConfig, DynMoController
    from repro.dynamics.config import DynamicsConfig
    from repro.models import model as M

    steps = 60 if quick else 400
    stages, layers = 8, 64
    cfg = reduced_config(get_config("smollm-360m"), num_layers=layers,
                         d_model=64, d_ff=2048)
    dcfg = DistConfig(num_stages=stages, slot_slack=3, remat="none",
                      param_dtype="float32")
    tags = np.asarray(M.make_assignment(cfg, dcfg)["tags"])
    live = tags != 0
    num_micro = 4
    rng = np.random.RandomState(0)

    def snapshot(it, epoch=0):
        grad = np.linspace(0.1, 1.0, stages)[:, None] * np.ones_like(
            tags, float)
        ff = np.where(live, num_micro * np.clip(
            grad + rng.uniform(-0.1, 0.1, tags.shape), 0.02, 1.0), 0.0)
        stats = {"ff_active": ff,
                 "attn_density": np.where(live, 0.2 * num_micro, 0.0),
                 "expert_load": np.zeros(tags.shape + (1,))}
        return StatsSnapshot(iteration=it, epoch=epoch, stats=stats,
                             tags=tags, num_micro=num_micro, tokens=8192,
                             seq=128)

    results = {}
    for mode in ("inline", "async"):
        ctrl = DynMoController(
            cfg, dcfg, DynamicsConfig(kind="pruning"),
            ControllerConfig(method="diffusion", rebalance_every=1))
        cp = ControlPlane(ctrl, async_mode=(mode == "async"))
        try:
            train_thread_s, decide_s = [], []
            for it in range(1, steps + 1):
                snap = snapshot(it)
                t0 = time.perf_counter()
                cp.publish(snap)                 # what the step pays
                train_thread_s.append(time.perf_counter() - t0)
                if mode == "async":
                    cp.drain()                   # decisions still complete
                plan = cp.poll(0)
                if plan is not None:
                    decide_s.append(plan.decide_s)
            results[mode] = (sum(train_thread_s) / steps,
                             sum(decide_s) / max(1, len(decide_s)))
            assert cp.decided == steps
        finally:
            cp.close()
    rows = []
    for mode, (tt, dd) in results.items():
        rows.append((f"controller_train_thread_{mode}", tt * 1e6, tt))
        rows.append((f"controller_decision_{mode}", dd * 1e6, dd))
    # the acceptance ratio: how much per-step decision latency the training
    # thread sheds by going async at rebalance_every=1
    rows.append(("controller_async_train_thread_reduction", 0.0,
                 results["inline"][0] / max(1e-12, results["async"][0])))
    return rows


def main_controller(quick: bool = False):
    rows = run_controller(quick)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.3f},{derived:.9f}")
    return rows


# ---------------------------------------------------------------------------
# observability overhead: host cost of spans, instants and metrics
# ---------------------------------------------------------------------------
def run_obs(quick: bool = False):
    """Host-side microbenches of the observability layer (DESIGN.md §15):
    a program span with tracing off (no profiler, no tracer), a tracer
    span, a tracer instant, a counter inc, a histogram observe and a
    unified-event stamp.  The per-step cost on the chip is measured by the
    chip benchmark (PERF.md)."""
    import jax  # noqa: F401  (spans open profiler annotations once loaded)

    from repro.obs.events import stamp_record
    from repro.obs.metrics import MetricsRegistry
    from repro.obs.trace import Tracer, span

    iters = 20000 if quick else 200000
    rows = []

    t0 = time.perf_counter()
    for i in range(iters):
        with span("bench.span", step=i):
            pass
    dt = (time.perf_counter() - t0) / iters
    rows.append(("obs_program_span_off", dt * 1e6, dt))

    tr = Tracer("bench")
    t0 = time.perf_counter()
    for i in range(iters):
        with tr.span("bench.span", step=i):
            pass
    dt = (time.perf_counter() - t0) / iters
    rows.append(("obs_span_open_close", dt * 1e6, dt))

    tr = Tracer("bench")
    t0 = time.perf_counter()
    for i in range(iters):
        tr.instant("bench.instant", step=i)
    dt = (time.perf_counter() - t0) / iters
    rows.append(("obs_instant", dt * 1e6, dt))

    reg = MetricsRegistry()
    t0 = time.perf_counter()
    for i in range(iters):
        reg.inc("bench_total", kind="x")
    dt = (time.perf_counter() - t0) / iters
    rows.append(("obs_metrics_inc", dt * 1e6, dt))

    t0 = time.perf_counter()
    for i in range(iters):
        reg.observe("bench_seconds", 0.01 * (i % 7))
    dt = (time.perf_counter() - t0) / iters
    rows.append(("obs_metrics_observe", dt * 1e6, dt))

    t0 = time.perf_counter()
    for i in range(iters):
        stamp_record({"step": i}, source="session", kind="log", tracer=tr)
    dt = (time.perf_counter() - t0) / iters
    rows.append(("obs_stamp_record", dt * 1e6, dt))
    return rows


def main_obs(quick: bool = False):
    rows = run_obs(quick)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.3f},{derived:.9f}")
    return rows


if __name__ == "__main__":
    main()
    main_controller()
    main_obs()

"""Multi-tenant cluster benchmark (DESIGN.md §14): one 6-worker pool shared
by a training tenant (priority 0, elastic 2..4 stages) and a serving tenant
(priority 10, elastic 2..4 stages) under a diurnal request trace — versus a
STATIC SPLIT of the same hardware (train pinned to 2, serve owning 4, no
worker ever crossing the fence).

Both runs serve the identical trace.  In the shared run the serve bursts
steal training workers through the HTTP cluster scheduler (the trainer
shrinks at its next safe point) and the lulls yield them back (the trainer
absorbs); the scheduler's wall-stamped grant timeline integrates to the
pool-utilization headline.  The static run wastes exactly what the paper
predicts: the serve lull capacity is stranded (nobody can take it) and the
trainer can never burst above its fixed half.

Records train tokens/s, serve p95 token latency, and time-weighted pool
utilization for both layouts -> BENCH_cluster.json.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

POOL = 6          # 4 train + 2 serve at rest; serve bursts to 4

_TRAIN_CHILD = """
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
import json
from repro.api import Session
from repro.launch.train import train_spec

spec = train_spec("smollm-360m", steps=%(steps)d, stages=4, layers=8,
                  d_model=%(d_model)d, seq=32, num_micro=2, mb_global=2,
                  dynamism="none", rebalance_every=4, log_every=1000,
                  repack_target=2, job_manager=%(jm)r,
                  manager_url=%(url)r, tenant_id=%(tenant)r, priority=0)
with Session(spec) as s:
    rep = s.train()
toks = 2 * 2 * 32 * len(rep["losses"])
print("BENCH_JSON " + json.dumps({
    "tokens_per_s": toks / rep["wall_s"], "wall_s": rep["wall_s"],
    "steps": len(rep["losses"]), "stages_history": rep["stages_history"],
    "resizes": [(r["kind"], r["step"], r["from_stages"], r["to_stages"])
                for r in rep["resizes"]],
    "event_kinds": [ev.kind for ev in s.events],
    "spec": spec.to_dict()}))
"""

_SERVE_CHILD = """
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
import json
from repro.api import Session
from repro.launch.serve import serve_spec

spec = serve_spec("smollm-360m", stages=4, micro=2, mb_global=2,
                  prompt_len=8, gen=%(gen)d, layers=8, d_model=%(d_model)d,
                  requests=%(requests)d, burst_period=24, burst_len=6,
                  burst_rate=4, lull_rate=0, early_exit_frac=0.25,
                  autoscale=True, min_stages=2, queue_high=2,
                  occupancy_low=0.6, patience=2, cooldown=3,
                  latency_slo_s=0.5, job_manager=%(jm)r,
                  manager_url=%(url)r, tenant_id=%(tenant)r, priority=10)
with Session(spec) as s:
    rep = s.serve()
print("BENCH_JSON " + json.dumps({
    "tokens_per_s": rep["tokens_per_s"], "wall_s": rep["wall_s"],
    "latency_p50_s": rep["latency_p50_s"],
    "latency_p95_s": rep["latency_p95_s"],
    "stages_history": rep["stages_history"],
    "tick_wall_s": rep["tick_wall_s"],
    "resizes": [(r["kind"], r["step"], r["from_stages"], r["to_stages"])
                for r in rep["resizes"]],
    "urgent_grows": sum(1 for d in rep["autoscale_decisions"]
                        if d["action"] == "grow" and d.get("urgent")),
    "event_kinds": [ev.kind for ev in s.events],
    "spec": spec.to_dict()}))
"""


def _spawn(code: str, **fmt) -> subprocess.Popen:
    return subprocess.Popen(
        [sys.executable, "-c", code % fmt],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env={**os.environ, "PYTHONPATH": SRC, "REPRO_TRAIN_DEVICES": "4",
             "JAX_PLATFORMS": "cpu"})


def _collect(proc: subprocess.Popen, who: str, timeout: int = 1800) -> dict:
    out, _ = proc.communicate(timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(f"{who} child failed:\n{out[-4000:]}")
    for line in out.splitlines():
        if line.startswith("BENCH_JSON "):
            return json.loads(line[len("BENCH_JSON "):])
    raise RuntimeError(f"no BENCH_JSON from {who}:\n{out[-2000:]}")


def _utilization_from_timeline(events, t_lo: float, t_hi: float) -> float:
    """Time-weighted mean of (workers granted to any tenant) / pool size
    over [t_lo, t_hi], integrated from the scheduler's wall-stamped grant
    timeline."""
    if t_hi <= t_lo:
        return 0.0
    area = 0.0
    prev_t, prev_held = t_lo, 0
    for ev in sorted(events, key=lambda e: e["t"]):
        held = sum(ev["granted"].values())
        t = min(max(ev["t"], t_lo), t_hi)
        area += prev_held * (t - prev_t)
        prev_t, prev_held = t, held
    area += prev_held * (t_hi - prev_t)
    return area / ((t_hi - t_lo) * POOL)


def _wall_mean_stages(rep: dict) -> float:
    """Serve stage count weighted by per-tick wall time (ticks are wildly
    uneven: compiles vs steady decode)."""
    num = sum(s * w for s, w in zip(rep["stages_history"],
                                    rep["tick_wall_s"]))
    den = sum(rep["tick_wall_s"])
    return num / max(1e-9, den)


def _run_shared(steps: int, requests: int, gen: int, d_model: int):
    import tempfile
    import time

    from repro.cluster.http_rpc import HttpJobManager, spawn_http_manager
    run_dir = tempfile.mkdtemp(prefix="bench_cluster_")
    mgr, url = spawn_http_manager(run_dir, POOL, spares=0,
                                  idle_timeout_s=1800)
    try:
        kw = dict(jm="http", url=url, d_model=d_model)
        train = _spawn(_TRAIN_CHILD, steps=steps, tenant="train", **kw)
        serve = _spawn(_SERVE_CHILD, requests=requests, gen=gen,
                       tenant="serve", **kw)
        t_rep = _collect(train, "shared-train")
        s_rep = _collect(serve, "shared-serve")
        probe = HttpJobManager(url, client_id="bench-probe",
                               shutdown_on_close=True)
        events = probe.cluster_metrics()["events"]
        probe.close()
        mgr.wait(timeout=30)
    finally:
        if mgr.poll() is None:
            mgr.kill()
    # utilization over the contention window: first moment both tenants
    # hold workers -> the first deregistration (deregister pops the tenant
    # before recording its close-out yields, so the first snapshot with <2
    # tenants marks the end of two-tenant contention — the one-tenant tail
    # would otherwise read as stranded capacity nobody is contending for)
    t_first = {}
    for ev in events:
        if ev["ev"] == "grant" and ev["tenant"] not in t_first:
            t_first[ev["tenant"]] = ev["t"]
    t_lo = max(t_first.values()) if len(t_first) >= 2 else 0.0
    t_hi = max(e["t"] for e in events)
    for ev in sorted(events, key=lambda e: e["t"]):
        if ev["t"] > t_lo and len(ev["granted"]) < 2:
            t_hi = ev["t"]
            break
    util = _utilization_from_timeline(events, t_lo, t_hi)
    return t_rep, s_rep, util, events


def _run_static(steps: int, requests: int, gen: int, d_model: int):
    """The same workloads on a hard 2/4 split: each side owns a private
    in-process pool, so lull capacity is stranded by construction."""
    kw = dict(jm="inproc", url=None, tenant=None, d_model=d_model)
    train = _spawn(_TRAIN_CHILD.replace("stages=4", "stages=2"),
                   steps=steps, **kw)
    serve = _spawn(_SERVE_CHILD, requests=requests, gen=gen, **kw)
    t_rep = _collect(train, "static-train")
    s_rep = _collect(serve, "static-serve")
    # train side: 2 workers pinned, always "held"; serve side: holds its 4
    # only while scaled up — shrunk-away workers help nobody
    util = (2.0 + _wall_mean_stages(s_rep)) / POOL
    return t_rep, s_rep, util


def run(quick: bool = False):
    # the serve trace must SPAN the trainer's compile-gated timeline
    # (resizes land seconds apart on CPU): short traces drain before the
    # trainer's safe-point release and the steal/yield choreography never
    # completes, so the request counts here are wall-clock driven
    steps = 60 if quick else 120
    requests = 150 if quick else 300
    gen = 12 if quick else 16
    d_model = 64 if quick else 128
    sh_train, sh_serve, util_shared, events = _run_shared(
        steps, requests, gen, d_model)
    st_train, st_serve, util_static = _run_static(
        steps, requests, gen, d_model)

    steals = sum(1 for e in events if e["ev"] == "steal")
    yields = sum(1 for e in events if e["ev"] == "yield")
    if sh_serve["urgent_grows"] < 1:
        raise RuntimeError(
            f"no urgent grow (steal) fired in the shared run: "
            f"{sh_serve['resizes']}")
    if "preempt" not in sh_train["event_kinds"]:
        raise RuntimeError(
            f"the trainer never saw a preemption directive: "
            f"{sh_train['event_kinds']}")
    rows = [
        ("cluster_pool_workers", 0.0, float(POOL)),
        ("cluster_util_shared", 0.0, util_shared),
        ("cluster_util_static", 0.0, util_static),
        ("cluster_util_gain", 0.0, util_shared / max(1e-9, util_static)),
        ("cluster_train_tok_s_shared", 0.0, sh_train["tokens_per_s"]),
        ("cluster_train_tok_s_static", 0.0, st_train["tokens_per_s"]),
        ("cluster_serve_tok_s_shared", 0.0, sh_serve["tokens_per_s"]),
        ("cluster_serve_tok_s_static", 0.0, st_serve["tokens_per_s"]),
        ("cluster_serve_p95_ms_shared", sh_serve["latency_p95_s"] * 1e6,
         sh_serve["latency_p95_s"] * 1e3),
        ("cluster_serve_p95_ms_static", st_serve["latency_p95_s"] * 1e6,
         st_serve["latency_p95_s"] * 1e3),
        ("cluster_steals", 0.0, float(steals)),
        ("cluster_yields", 0.0, float(yields)),
        ("cluster_train_preempts", 0.0,
         float(sh_train["event_kinds"].count("preempt"))),
        ("cluster_train_absorbs", 0.0,
         float(sh_train["event_kinds"].count("absorb"))),
    ]
    spec = {"shared_train": sh_train["spec"],
            "shared_serve": sh_serve["spec"]}
    return rows, spec


def main(quick: bool = False):
    rows, spec = run(quick)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived:.3f}")
    return rows, spec


if __name__ == "__main__":
    main(quick="--quick" in sys.argv)

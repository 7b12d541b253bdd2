"""The observability layer (DESIGN.md §15): tracer golden fixture,
metrics snapshot golden, logical-clock determinism, the unified event
schema, the /metrics endpoint, and in-step vs probe stage-time parity."""
import json
import os
import sys
import urllib.request

import pytest

sys.path.insert(0, os.path.dirname(__file__))

from conftest import REPO, run_in_subprocess
from repro.obs.events import EVENT_SCHEMA, stamp_record
from repro.obs.metrics import (MetricsRegistry, scheduler_to_prometheus,
                               serve_metrics)
from repro.obs.trace import Tracer, current_tracer, set_current_tracer

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
TRACE_GOLDEN = os.path.join(GOLDEN_DIR, "trace_events.json")
METRICS_GOLDEN = os.path.join(GOLDEN_DIR, "metrics_snapshot.json")


# ---------------------------------------------------------------------------
# tracer: golden fixture + determinism
# ---------------------------------------------------------------------------
def _scripted_tracer() -> Tracer:
    """A fixed span scenario under an injected 1ms-per-call clock and
    pid=0 — everything but thread ids is deterministic."""
    t = [0.0]

    def clock():
        t[0] += 0.001
        return t[0]

    tr = Tracer("golden-run", clock=clock, pid=0, meta={"mode": "test"})
    with tr.span("train", steps=2):
        with tr.span("train.step", cat="step", step=0):
            pass
        ctx = tr.instant("checkpoint.saved", cat="checkpoint", step=0)
        sp = tr.span("resize.shrink", cat="resize",
                     parent_id=ctx["span_id"], target_stages=2)
        sp.end(stages=2)
    return tr


def _normalized_chrome(tr: Tracer) -> dict:
    """Thread ids and the wall-clock anchor are the only nondeterministic
    fields left; zero them for the byte-pinned comparison."""
    doc = tr.to_chrome()
    for ev in doc["traceEvents"]:
        ev["tid"] = 0
    doc["otherData"].pop("wall0", None)
    return doc


def test_trace_golden():
    """The Chrome trace-event export of the scripted scenario is pinned.
    If this fails you changed the trace schema — update DESIGN.md §15 and
    regenerate with ``PYTHONPATH=src python -c "import json, sys;
    sys.path.insert(0, 'tests'); from test_obs import _scripted_tracer,
    _normalized_chrome; json.dump(_normalized_chrome(_scripted_tracer()),
    open('tests/golden/trace_events.json', 'w'), indent=1)"``."""
    with open(TRACE_GOLDEN) as f:
        golden = json.load(f)
    assert _normalized_chrome(_scripted_tracer()) == golden


def test_trace_golden_validates():
    """The golden fixture passes the CI trace validator (so the validator
    and the exporter can't drift apart silently)."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import check_trace
    assert check_trace.main([TRACE_GOLDEN, "--expect-chain",
                             "checkpoint.saved,resize.shrink"]) == 0


def test_trace_event_sequence_deterministic():
    """Two runs of the same scenario produce the identical wall-free
    logical-clock sequence — the determinism contract fixed-seed session
    runs rely on."""
    a = _scripted_tracer().event_sequence()
    b = _scripted_tracer().event_sequence()
    assert a == b
    assert [lc for _, _, lc, _, _ in a] == sorted(
        lc for _, _, lc, _, _ in a), "logical clocks not monotone"


def test_span_nesting_and_cross_process_parenting():
    tr = Tracer("t1", pid=0)
    with tr.span("outer") as outer:
        with tr.span("inner") as inner:
            assert inner.parent_id == outer.span_id
        ctx = tr.instant("leaf")
    # a foreign process parents its span on the shipped ctx
    tr2 = Tracer("t2", pid=1)
    sp = tr2.span("remote", parent_id=ctx["span_id"])
    sp.end()
    ev = tr2.to_chrome()["traceEvents"][0]
    assert ev["args"]["parent_id"] == ctx["span_id"]
    assert ev["args"]["span_id"].startswith("t2.")


# ---------------------------------------------------------------------------
# unified event schema
# ---------------------------------------------------------------------------
def test_stamp_record_local_foreign_and_both():
    tr = Tracer("run-a", pid=0)
    # local tracer: fresh identity + logical clock
    rec = stamp_record({"x": 1}, source="session", kind="log", tracer=tr)
    assert rec["schema"] == EVENT_SCHEMA and rec["source"] == "session"
    assert rec["trace_id"] == "run-a" and isinstance(rec["lc"], int)
    assert "wall" in rec
    # foreign ctx only (e.g. the manager process): adopt the sender's ids
    ctx = tr.instant("rpc.steal")
    far = stamp_record({}, source="scheduler", kind="steal", ctx=ctx,
                       wall=False)
    assert far["trace_id"] == "run-a"
    assert far["parent_id"] == ctx["span_id"] and "wall" not in far
    # local tracer AND a foreign cause: keep identity, parent on the cause
    tr_b = Tracer("run-b", pid=0)
    both = stamp_record({}, source="session", kind="preempt", tracer=tr_b,
                        ctx=ctx)
    assert both["trace_id"] == "run-b"
    assert both["parent_id"] == ctx["span_id"]
    assert both["cause_trace_id"] == "run-a"


def test_current_tracer_is_process_global():
    tr = Tracer("global", pid=0)
    set_current_tracer(tr)
    try:
        assert current_tracer() is tr
        rec = stamp_record({}, source="fault", kind="rpc_loss")
        assert rec["trace_id"] == "global"
    finally:
        set_current_tracer(None)
    assert current_tracer() is None


def test_program_span_records_into_the_current_tracer_and_nests():
    """``obs.trace.span`` records into the current tracer under the plain
    name, nests by thread, takes ``end(**args)`` once, and records nothing
    with no tracer set."""
    from repro.obs.trace import span
    with span("train.iter") as sp:
        pass
    sp.end()                                 # no tracer: nothing, no error
    tr = Tracer("spans", pid=0)
    set_current_tracer(tr)
    try:
        with span("train", steps=2):
            with span("train.step", cat="train", step=0) as st:
                inner = span("engine.place", cat="engine")
                inner.end(leaves=3)
                inner.end(leaves=4)          # a second end is a no-op
                st.end(compiled=True)
    finally:
        set_current_tracer(None)
    evs = {e["name"]: e for e in tr.to_chrome()["traceEvents"]}
    assert sorted(evs) == ["engine.place", "train", "train.step"]
    ids = {n: e["args"]["span_id"] for n, e in evs.items()}
    assert evs["train.step"]["args"]["parent_id"] == ids["train"]
    assert evs["engine.place"]["args"]["parent_id"] == ids["train.step"]
    assert evs["engine.place"]["args"]["leaves"] == 3
    assert evs["train.step"]["args"]["compiled"] is True
    assert evs["train.step"]["cat"] == "train"


_SESSION_2_STEPS = """
import glob, json, os, tempfile
import jax
from jax.profiler import ProfileData
from repro.api import RunSpec, Session
from repro.obs import trace as obs_trace

made = []
init = obs_trace.Tracer.__init__
def counting_init(self, *a, **k):
    made.append(a)
    init(self, *a, **k)
obs_trace.Tracer.__init__ = counting_init

spec = RunSpec.from_dict({
    "model": {"arch": "smollm-360m", "layers": 2, "d_model": 64,
              "num_heads": 4, "num_kv_heads": 2, "vocab_size": 128},
    "parallel": {"stages": 1, "num_micro": 1, "mb_global": 1, "seq": 32},
    "controller": {"rebalance_every": 1},
    "steps": %(steps)d, "log_every": 1000})
profile = %(profile)r
d = tempfile.mkdtemp()
with Session(spec) as s:
    if profile:
        jax.profiler.start_trace(d)
    rep = s.train()
    if profile:
        jax.profiler.stop_trace()
    assert s.tracer is None and obs_trace.current_tracer() is None
assert made == [], made
names = set()
if profile:
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            names |= {ev.name for ev in line.events
                      if ev.name.startswith("dynmo.")}
print("RESULT " + json.dumps({"names": sorted(names), **rep["timing"]}))
"""


def _result(out: str) -> dict:
    line = [ln for ln in out.splitlines() if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_session_spans_land_in_the_profiler_trace():
    """A 2-step ``Session.train`` under the JAX profiler, with no tracer:
    the profiler's trace holds the program's spans as ``dynmo.<name>``."""
    out = run_in_subprocess(_SESSION_2_STEPS % {"steps": 2, "profile": True},
                            devices=1, timeout=600)
    names = _result(out)["names"]
    assert set(names) >= {
        "dynmo.train", "dynmo.train.iter", "dynmo.train.data",
        "dynmo.train.batch", "dynmo.train.step", "dynmo.engine.place",
        "dynmo.engine.dispatch", "dynmo.train.wait", "dynmo.train.dynamism",
        "dynmo.controller.decide", "dynmo.controller.stats_to_host",
        "dynmo.controller.publish"}, names


def test_train_without_tracing_makes_no_tracer_and_counts_host_work():
    """With no profiler and ``obs.trace`` off, ``Session.train`` makes no
    ``Tracer`` (so records no tracer event), and its steady throughput
    counts the host work between steady steps: it is no higher than the
    tokens over the steady steps' own times."""
    out = run_in_subprocess(_SESSION_2_STEPS % {"steps": 4,
                                                "profile": False},
                            devices=1, timeout=600)
    r = _result(out)
    assert r["names"] == [] and r["steady_steps"] == 3
    assert 0 < r["steady_tokens_per_s"] <= 32 * 3 / r["steady_s"]


# ---------------------------------------------------------------------------
# metrics: snapshot golden + exposition + endpoint
# ---------------------------------------------------------------------------
def _scripted_registry() -> MetricsRegistry:
    reg = MetricsRegistry()
    reg.inc("dynmo_train_steps_total", 3, help="train steps", mode="train")
    reg.inc("dynmo_resizes_total", kind="shrink", policy="preempt")
    reg.set("dynmo_stages", 4, help="live stage count")
    reg.set("dynmo_stage_time_seconds", 0.25, stage="0", source="in_step")
    for v in (0.004, 0.04, 0.4, 4.0):
        reg.observe("dynmo_step_seconds", v, help="steady step seconds")
    return reg


def test_metrics_snapshot_golden():
    """The JSON snapshot (the CI artifact format) is pinned.  Regenerate
    with ``PYTHONPATH=src python -c "import sys; sys.path.insert(0,
    'tests'); from test_obs import _scripted_registry;
    _scripted_registry().save('tests/golden/metrics_snapshot.json')"``."""
    with open(METRICS_GOLDEN) as f:
        golden = json.load(f)
    assert _scripted_registry().snapshot() == golden


def test_prometheus_exposition():
    text = _scripted_registry().to_prometheus()
    assert "# TYPE dynmo_train_steps_total counter" in text
    assert 'dynmo_train_steps_total{mode="train"} 3' in text
    assert "# TYPE dynmo_stages gauge" in text
    assert "dynmo_stages 4" in text
    assert "# TYPE dynmo_step_seconds histogram" in text
    assert 'dynmo_step_seconds_bucket{le="0.005"} 1' in text
    assert 'dynmo_step_seconds_bucket{le="+Inf"} 4' in text
    assert "dynmo_step_seconds_count 4" in text
    assert text.endswith("\n")


def test_metrics_endpoint_serves_registry():
    reg = _scripted_registry()
    srv = serve_metrics(reg, 0)          # ephemeral port
    try:
        port = srv.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            assert "version=0.0.4" in r.headers["Content-Type"]
            body = r.read().decode()
        assert body == reg.to_prometheus()
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=10)
    finally:
        srv.shutdown()


def test_scheduler_to_prometheus_matches_events():
    """The manager's /metrics counters are derived from the SAME events
    list the metrics RPC verb returns — per-(tenant, event) counts always
    agree (the cluster_smoke gate, unit-sized)."""
    from repro.cluster.scheduler import ClusterScheduler, WorkerPool
    sched = ClusterScheduler(WorkerPool(4))
    sched.register("train", priority=0, workers=3)
    sched.register("serve", priority=10, workers=1)
    sched.steal("serve", 2)
    text = scheduler_to_prometheus(sched)
    for ev in sched.events:
        needle = (f'dynmo_scheduler_events_total{{event="{ev["ev"]}",'
                  f'tenant="{ev["tenant"]}"}}')
        assert needle in text, (needle, text)
    assert 'dynmo_workers_granted{tenant="serve"}' in text
    assert "dynmo_pool_active 4" in text
    # the events themselves carry the unified schema
    assert all(ev.get("schema") == EVENT_SCHEMA and ev.get("kind")
               for ev in sched.events)


# ---------------------------------------------------------------------------
# end-to-end: session wiring, determinism, in-step vs probe parity
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_session_obs_end_to_end_and_deterministic():
    """One subprocess, two identical fixed-seed training runs with the
    full obs stack on: the report's stage times come from the live step
    (source == in_step), the timing block splits warm-up from steady
    state, every event carries the unified schema, the exported trace
    validates in check_trace.py, and the two runs' logical-clock
    sequences are identical."""
    out = run_in_subprocess("""
import dataclasses, json, os
from repro.api import RunSpec, Session

def one_run(tag):
    spec = RunSpec.from_dict({
        "schema_version": 4,
        "model": {"arch": "smollm-360m", "layers": 8, "d_model": 64,
                  "num_heads": 4, "num_kv_heads": 2, "vocab_size": 256},
        "parallel": {"stages": 4, "num_micro": 4, "mb_global": 4,
                     "seq": 16},
        "controller": {"rebalance_every": 3},
        "obs": {"trace": True, "in_step_timing": True,
                "trace_out": f"/tmp/obs_e2e_{tag}.json",
                "metrics_out": f"/tmp/obs_m_{tag}.json"},
        "steps": 7, "log_every": 3})
    with Session(spec) as s:
        rep = s.train()
        seq = s.tracer.event_sequence()
    return rep, seq, [dataclasses.asdict(ev) for ev in s.events]

rep, seq_a, events = one_run("a")
assert rep["stage_time_source"] == "in_step", rep["stage_time_source"]
mt = rep["measured_stage_times"]
assert mt is not None and len(mt) == 4 and all(t > 0 for t in mt)
t = rep["timing"]
assert t["warmup_steps"] >= 1 and t["steady_steps"] >= 1
assert t["warmup_s"] > t["steady_step_mean_s"]   # compile >> one step
assert t["decide_s"] >= 0 and t["steady_tokens_per_s"] > 0
for ev in events:
    assert ev["schema"] == "obs.event/1" and ev["source"] == "session"
    assert ev["trace_id"] and ev["span_id"] and ev["lc"] is not None
snap = json.load(open("/tmp/obs_m_a.json"))
assert snap["schema"] == "obs.metrics/1"
names = {c["name"] for c in snap["counters"]}
assert "dynmo_train_steps_total" in names
assert any(h["name"] == "dynmo_step_seconds" and h["count"] >= 1
           for h in snap["histograms"])

import sys
sys.path.insert(0, os.path.join(%(repo)r, "scripts"))
import check_trace
assert check_trace.main(["/tmp/obs_e2e_a.json", "--expect-event", "train",
                         "--expect-event", "train.step",
                         "--expect-event", "controller.decide"]) == 0

_, seq_b, _ = one_run("b")
assert seq_a == seq_b, "fixed-seed logical-clock sequence diverged"
print("PASS", len(seq_a), "events")
""" % {"repo": REPO}, devices=4, timeout=900)
    assert "PASS" in out


@pytest.mark.slow
def test_in_step_times_agree_with_probe_ranking():
    """On a deliberately skewed [8, 1, 1, 1] split the in-step stamps and
    the isolation probe must agree on the stage-time RANKING (the
    controller consumes relative loads, not absolute seconds) — the
    acceptance criterion for replacing the probe on cadence."""
    out = run_in_subprocess("""
import jax
import numpy as np
from repro.configs import DistConfig, get_config, reduced_config
from repro.dynamics.config import DynamicsConfig
from repro.launch.engine import ElasticEngine
from repro.pipeline.pipeline import PipelineShapes

cfg = reduced_config(get_config("smollm-360m"), num_layers=11, d_model=128,
                     num_heads=4, num_kv_heads=2, d_ff=256, vocab_size=256)
dcfg = DistConfig(num_stages=4, slot_slack=6, remat="none",
                  param_dtype="float32")
shapes = PipelineShapes(num_micro=4, mb_global=4, seq=64)
engine = ElasticEngine(cfg, dcfg, DynamicsConfig(), shapes,
                       in_step_timing=True)
state = engine.init_state(jax.random.PRNGKey(0), lps=[8, 1, 1, 1])
from repro.data.loader import DataConfig, make_loader
loader = make_loader(cfg, DataConfig(num_micro=4, mb_global=4, seq=64))
batch = next(loader)
assert engine.in_step_stage_times(state) is None   # no window yet
for _ in range(4):
    loss, stats, gnorm = engine.step(state, batch, 1e-3)
jax.block_until_ready(loss)
in_step = np.asarray(engine.in_step_stage_times(state))
probe = np.asarray(engine.measure_stage_times(state, batch))
assert in_step.shape == (4,) and (in_step > 0).all(), in_step
# stage 0 carries 8 of 11 layers: both sources must call it slowest,
# and the full ranking must put it strictly above every 1-layer stage
assert in_step.argmax() == 0 and probe.argmax() == 0, (in_step, probe)
assert all(in_step[0] > in_step[i] for i in (1, 2, 3)), in_step
print("PASS in_step", in_step, "probe", probe)
""", devices=4, timeout=900)
    assert "PASS" in out

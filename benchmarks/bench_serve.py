"""Elastic serving benchmark: a bursty arrival trace served twice — once on
a fixed mesh, once with the autoscaler shrinking/growing the engine worlds —
with identical generated tokens (asserted).  Records tok/s and p50/p95
per-token latency overall, plus the tok/s comparison restricted to the
LOW-LOAD window (the elastic run's first shrink→grow span): the shrunk
pipeline pays ``num_micro + S' - 1`` ticks per decode instead of
``num_micro + S - 1``, so the elastic server clears the drained batch
faster *while holding fewer workers*.

Subprocess-isolated (XLA's host device count must be fixed pre-import).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

_CHILD = """
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
import copy
import dataclasses
import json
import numpy as np
from repro.api import Session
from repro.launch.serve import serve_spec
from repro.serve.requests import Request

gen_long = %(gen_long)d
# the elastic run's spec; the fixed baseline is the same spec with
# autoscaling off (recorded in BENCH_serve.json)
spec = serve_spec("smollm-360m", stages=4, micro=2, mb_global=2,
                  prompt_len=8, gen=gen_long, layers=8,
                  d_model=%(d_model)d, autoscale=True, min_stages=2,
                  patience=2, cooldown=3, queue_high=2,
                  occupancy_low=0.6, seed=0)
vocab = 512
rng = np.random.RandomState(0)
prompt = lambda n: rng.randint(0, vocab, n).astype(np.int32)
# burst of short early-exit requests + a long tail that keeps decoding
# through the drained (shrunken) phase, then a second burst -> grow back
# (hand-built long-tail arrivals; not expressible as a make_trace spec)
trace = []
for i in range(6):
    trace.append(Request(rid=i, arrival=0, prompt=prompt(8),
                         gen=2 + i %% 3, kind="early_exit"))
for i in range(2):
    trace.append(Request(rid=6 + i, arrival=0, prompt=prompt(6),
                         gen=gen_long))
t2 = gen_long + 14
for i in range(6):
    trace.append(Request(rid=8 + i, arrival=t2 + i // 4, prompt=prompt(8),
                         gen=4))

def run(autoscale):
    sp = dataclasses.replace(spec, cluster=dataclasses.replace(
        spec.cluster, autoscale=autoscale))
    with Session(sp) as s:
        return s.serve(trace=copy.deepcopy(trace))

keep = ("completions", "resizes", "tick_wall_s", "tick_tokens",
        "stages_history", "pool_log", "total_tokens", "wall_s",
        "tokens_per_s", "latency_p50_s", "latency_p95_s",
        "autoscale_decisions")
el = run(True)
fx = run(False)
out = {"elastic": {k: el[k] for k in keep},
       "fixed": {k: fx[k] for k in keep},
       "spec": spec.to_dict()}
print("BENCH_JSON " + json.dumps(out))
"""


def _run_child(gen_long: int, d_model: int) -> dict:
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD % {
            "gen_long": gen_long, "d_model": d_model}],
        capture_output=True, text=True, timeout=1800,
        env={**os.environ, "PYTHONPATH": SRC, "REPRO_TRAIN_DEVICES": "4",
             "JAX_PLATFORMS": "cpu"})
    if proc.returncode != 0:
        raise RuntimeError(f"serve bench child failed:\n"
                           f"{proc.stdout}\n{proc.stderr[-3000:]}")
    for line in proc.stdout.splitlines():
        if line.startswith("BENCH_JSON "):
            return json.loads(line[len("BENCH_JSON "):])
    raise RuntimeError(f"no BENCH_JSON in child output:\n{proc.stdout}")


def _window_tps(rep: dict, lo: int, hi: int) -> float:
    toks = sum(rep["tick_tokens"][lo:hi])
    wall = sum(rep["tick_wall_s"][lo:hi])
    return toks / max(1e-9, wall)


def run(quick: bool = False):
    out = _run_child(gen_long=20 if quick else 32,
                     d_model=64 if quick else 128)
    el, fx = out["elastic"], out["fixed"]
    # generated tokens must be identical request-for-request
    for a, b in zip(el["completions"], fx["completions"]):
        if a["tokens"] != b["tokens"]:
            raise RuntimeError(f"token mismatch rid {a['rid']}: "
                               f"{a['tokens']} vs {b['tokens']}")
    assert el["total_tokens"] == fx["total_tokens"]
    shrinks = [r for r in el["resizes"] if r["kind"] == "shrink"]
    grows = [r for r in el["resizes"] if r["kind"] == "grow"]
    if not shrinks:
        raise RuntimeError(f"no autoscale shrink fired: {el['resizes']}")
    # low-load window: after the LAST shrink settles (skip the fresh
    # world's compile ticks) until just before the grow-back burst (whose
    # admission prefill compiles too); idle lull ticks inside contribute
    # ~0 wall and 0 tokens to both runs alike
    lo = shrinks[-1]["step"] + 3
    hi = grows[0]["step"] - 2 if grows else len(el["tick_wall_s"])
    if hi - lo < 3:
        raise RuntimeError(
            f"low-load window too short ({lo}..{hi}); resizes "
            f"{[(r['kind'], r['step']) for r in el['resizes']]}")
    el_low = _window_tps(el, lo, hi)
    fx_low = _window_tps(fx, lo, hi)
    released = sum(1 for e in el["pool_log"] if e.startswith("release:"))
    rows = [
        ("serve_total_tokens", 0.0, float(el["total_tokens"])),
        ("serve_token_identity", 0.0, 1.0),
        ("serve_shrinks", 0.0, float(len(shrinks))),
        ("serve_grows", 0.0, float(len(grows))),
        ("serve_released_workers", 0.0, float(released)),
        ("serve_tok_s_elastic", 0.0, el["tokens_per_s"]),
        ("serve_tok_s_fixed", 0.0, fx["tokens_per_s"]),
        ("serve_tok_s_elastic_low_load", 0.0, el_low),
        ("serve_tok_s_fixed_low_load", 0.0, fx_low),
        ("serve_low_load_speedup", 0.0, el_low / max(1e-9, fx_low)),
        ("serve_p50_latency_ms_elastic", el["latency_p50_s"] * 1e6,
         el["latency_p50_s"] * 1e3),
        ("serve_p95_latency_ms_elastic", el["latency_p95_s"] * 1e6,
         el["latency_p95_s"] * 1e3),
        ("serve_p50_latency_ms_fixed", fx["latency_p50_s"] * 1e6,
         fx["latency_p50_s"] * 1e3),
        ("serve_p95_latency_ms_fixed", fx["latency_p95_s"] * 1e6,
         fx["latency_p95_s"] * 1e3),
    ]
    return rows, out["spec"]


def main(quick: bool = False):
    rows, spec = run(quick)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived:.3f}")
    # (rows, spec): run.py snapshots BENCH_serve.json with the exact
    # RunSpec that produced these numbers
    return rows, spec


# ---------------------------------------------------------------------------
# Paged-KV headline: dense vs paged at the SAME KV byte budget
# ---------------------------------------------------------------------------
_CHILD_PAGED = """
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
import copy
import json
import numpy as np
from repro.api import Session
from repro.launch.serve import serve_spec
from repro.serve.requests import Request

# one KV byte budget, two memory models.  Dense binds a full
# prompt_len+gen cache line to every lane: 4 lanes x 16 tokens = 64 token
# slots.  Paged gets a 16-page x 4-token pool — the SAME 64 token slots —
# but serves an 8-lane batch shape, admitting as many concurrent requests
# as actually-touched pages (short gens + shared prompt prefixes) fit.
page, cache = 4, 16
dense = serve_spec("smollm-360m", stages=4, micro=2, mb_global=2,
                   prompt_len=8, gen=8, layers=%(layers)d,
                   d_model=%(d_model)d, seed=0)
paged = serve_spec("smollm-360m", stages=4, micro=2, mb_global=4,
                   prompt_len=8, gen=8, layers=%(layers)d,
                   d_model=%(d_model)d, seed=0, kv_page_size=page,
                   kv_pool_pages=16, prefix_cache=True)
rng = np.random.RandomState(0)
shared = rng.randint(0, 512, 8).astype(np.int32)   # two full prompt pages
trace = []
for i in range(%(requests)d):
    trace.append(Request(rid=i, arrival=i // 8, prompt=shared.copy(),
                         gen=3 + i %% 2))

def run(sp):
    with Session(sp) as s:
        return s.serve(trace=copy.deepcopy(trace))

keep = ("completions", "total_tokens", "tokens_per_s", "peak_live_lanes",
        "peak_live_pages", "kv_pages_total", "kv_page_size", "prefix_hits",
        "cow_forks", "page_tile_live", "page_tile_total", "ticks")
dn = run(dense)
pg = run(paged)
out = {"dense": {k: dn[k] for k in keep},
       "paged": {k: pg[k] for k in keep},
       "prompt_pages_requested": sum(len(r.prompt) // page for r in trace),
       "spec": paged.to_dict()}
print("BENCH_JSON " + json.dumps(out))
"""


def _run_paged_child(requests: int, layers: int, d_model: int) -> dict:
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD_PAGED % {
            "requests": requests, "layers": layers, "d_model": d_model}],
        capture_output=True, text=True, timeout=1800,
        env={**os.environ, "PYTHONPATH": SRC, "REPRO_TRAIN_DEVICES": "4",
             "JAX_PLATFORMS": "cpu"})
    if proc.returncode != 0:
        raise RuntimeError(f"paged bench child failed:\n"
                           f"{proc.stdout}\n{proc.stderr[-3000:]}")
    for line in proc.stdout.splitlines():
        if line.startswith("BENCH_JSON "):
            return json.loads(line[len("BENCH_JSON "):])
    raise RuntimeError(f"no BENCH_JSON in child output:\n{proc.stdout}")


def run_paged(quick: bool = False):
    out = _run_paged_child(requests=12 if quick else 16,
                           layers=4 if quick else 8,
                           d_model=64 if quick else 128)
    dn, pg = out["dense"], out["paged"]
    # tokens are identical request-for-request: the memory model (and the
    # wider paged batch shape) must be invisible to every request
    td = {c["rid"]: c["tokens"] for c in dn["completions"]}
    tp = {c["rid"]: c["tokens"] for c in pg["completions"]}
    if td != tp:
        bad = [r for r in td if td[r] != tp.get(r)]
        raise RuntimeError(f"paged/dense token mismatch on rids {bad}")
    # THE headline: at the same KV byte budget, paging + prefix sharing
    # must hold strictly more requests in flight than dense lanes can
    if pg["peak_live_lanes"] <= dn["peak_live_lanes"]:
        raise RuntimeError(
            f"paged peak lanes {pg['peak_live_lanes']} not above dense "
            f"{dn['peak_live_lanes']} at equal KV bytes")
    hit_rate = out["prefix_hits_rate"] = (
        pg["prefix_hits"] / max(1, out["prompt_pages_requested"]))
    tile_frac = pg["page_tile_live"] / max(1, pg["page_tile_total"])
    rows = [
        ("paged_token_identity", 0.0, 1.0),
        ("paged_kv_token_slots", 0.0,
         float(pg["kv_pages_total"] * pg["kv_page_size"])),
        ("paged_peak_lanes", 0.0, float(pg["peak_live_lanes"])),
        ("dense_peak_lanes", 0.0, float(dn["peak_live_lanes"])),
        ("paged_lane_gain", 0.0,
         pg["peak_live_lanes"] / max(1, dn["peak_live_lanes"])),
        ("paged_peak_live_pages", 0.0, float(pg["peak_live_pages"])),
        ("paged_prefix_hits", 0.0, float(pg["prefix_hits"])),
        ("paged_prefix_hit_rate", 0.0, hit_rate),
        ("paged_cow_forks", 0.0, float(pg["cow_forks"])),
        # count-gating: fraction of page-table tiles that cost MXU work
        ("paged_tile_live_frac", 0.0, tile_frac),
        ("paged_ticks", 0.0, float(pg["ticks"])),
        ("dense_ticks", 0.0, float(dn["ticks"])),
        ("paged_tok_s", 0.0, pg["tokens_per_s"]),
        ("dense_tok_s", 0.0, dn["tokens_per_s"]),
    ]
    return rows, out["spec"]


def main_paged(quick: bool = False):
    rows, spec = run_paged(quick)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived:.3f}")
    return rows, spec


if __name__ == "__main__":
    if "--paged" in sys.argv:
        main_paged(quick="--quick" in sys.argv)
    else:
        main(quick="--quick" in sys.argv)

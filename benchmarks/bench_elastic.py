"""Live elasticity benchmark (paper §3.4 end-to-end): real pipelined
training on 4 forced host devices with pruning + repack enabled; records
tokens/s and per-step wall time before/after the engine's in-process 4→2
shrink, the schedule tick counts, and the released-worker count.

Runs the trainer in a subprocess because XLA's host device count must be
fixed before jax initializes — the bench harness itself keeps 1 device.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

_CHILD = """
import json
from repro.api import Session
from repro.launch.train import train_spec
spec = train_spec(
    "smollm-360m", steps=%(steps)d, stages=4, layers=8, d_model=128,
    seq=32, num_micro=%(micro)d, mb_global=2, dynamism="pruning",
    repack=True, rebalance_every=5, log_every=1000)
with Session(spec) as s:
    out = s.train()
print("BENCH_JSON " + json.dumps({
    "losses": out["losses"],
    "step_times": out["step_times"],
    "stages_history": out["stages_history"],
    "resizes": out["resizes"],
    "pool_log": out["pool_log"],
    "tokens_per_step": out["tokens_per_step"],
    "final_stages": out["final_stages"],
    "spec": spec.to_dict(),
}))
"""


def _run_child(steps: int, micro: int) -> dict:
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD % {"steps": steps, "micro": micro}],
        capture_output=True, text=True, timeout=1800,
        env={**os.environ, "PYTHONPATH": SRC, "REPRO_TRAIN_DEVICES": "4",
             "JAX_PLATFORMS": "cpu"})
    if proc.returncode != 0:
        raise RuntimeError(f"elastic bench child failed:\n"
                           f"{proc.stdout}\n{proc.stderr[-3000:]}")
    for line in proc.stdout.splitlines():
        if line.startswith("BENCH_JSON "):
            return json.loads(line[len("BENCH_JSON "):])
    raise RuntimeError(f"no BENCH_JSON in child output:\n{proc.stdout}")


def _mean(xs):
    return sum(xs) / max(1, len(xs))


def run(quick: bool = False):
    steps = 24 if quick else 40
    micro = 8                      # bubble (m+S-1)/m visible: 11 vs 9 ticks
    out = _run_child(steps, micro)
    hist = out["stages_history"]
    times = out["step_times"]
    tps = out["tokens_per_step"]
    shrinks = [r for r in out["resizes"] if r["kind"] == "shrink"]
    if not shrinks:
        raise RuntimeError(f"no shrink happened in {steps} steps: {hist}")
    rz = shrinks[0]
    cut = rz["step"] + 1           # first post-shrink step index
    # drop compile steps: the first 2 of the run, the first 1 after resize
    pre = times[2:cut]
    post = times[cut + 1:]
    if not pre or not post:
        raise RuntimeError(
            f"shrink at step {rz['step']} leaves no comparable window "
            f"(pre={len(pre)} post={len(post)} of {len(times)} steps); "
            f"raise steps")
    released = sum(1 for e in out["pool_log"] if e.startswith("release:"))
    rows = [
        ("elastic_ticks_pre_shrink", 0.0, float(rz["ticks_before"])),
        ("elastic_ticks_post_shrink", 0.0, float(rz["ticks_after"])),
        ("elastic_released_workers", 0.0, float(released)),
        ("elastic_resize_ms", rz["seconds"] * 1e6, rz["seconds"] * 1e3),
        ("elastic_step_ms_pre", _mean(pre) * 1e6, _mean(pre) * 1e3),
        ("elastic_step_ms_post", _mean(post) * 1e6, _mean(post) * 1e3),
        ("elastic_tokens_per_s_pre", _mean(pre) * 1e6, tps / _mean(pre)),
        ("elastic_tokens_per_s_post", _mean(post) * 1e6, tps / _mean(post)),
        ("elastic_speedup_post_over_pre", 0.0, _mean(pre) / _mean(post)),
        ("elastic_loss_drop_across_shrink", 0.0,
         out["losses"][max(0, cut - 2)] - out["losses"][-1]),
    ]
    return rows, out["spec"]


def main(quick: bool = False):
    rows, spec = run(quick)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived:.3f}")
    # (rows, spec): run.py snapshots BENCH_elastic.json with the exact
    # RunSpec that produced these numbers
    return rows, spec


if __name__ == "__main__":
    main(quick="--quick" in sys.argv)

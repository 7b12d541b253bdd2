#!/usr/bin/env python3
"""Bring-up smoke for the DynMo trainer and server on TPU v5e.

Drives the system through the entry points a user calls (``RunSpec`` ->
``Session`` -> ``ElasticEngine`` -> pipeline) at the full published width
of ``gpt-paper-24l`` (paper §5: 24 layers, d_model 1024, 32 heads of 32,
d_ff 4096, vocab 50257, seq 2048), with seeded random weights and seeded
synthetic tokens.  Nothing is cut: float32 parameters and Adam state fill
about 6 GiB of the chip, and the micro-batches are sized to fit.

  python chip_smoke.py            one chip: train with the Pallas kernels and
                                  block pruning, repeat step 0 with the scan
                                  kernels, then serve requests on paged KV
  python chip_smoke.py --chips 4  one 4-chip host: 4 pipeline stages with a
                                  DynMo rebalance and a live 4->2
                                  consolidation, against the same steps on
                                  1 stage; nothing else

The script exits non-zero, and prints no result line, when JAX finds no
TPU (it never falls back to the CPU), when the repository's ``src`` is not
beside it, or when any phase fails.  On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
ARCH = "gpt-paper-24l"
SEQ = 2048
SEED = 0
# Pallas vs scan kernels at step 0.  Both run the same model on the same
# batch; they differ in where the f32 matmuls round.  XLA's default TPU
# precision rounds f32 operands to bf16 (2^-8 relative) while the Pallas
# kernels keep the f32 accumulation in VMEM, so per-element differences
# reach ~1e-2 relative but are random in sign and average out over the
# 2 x 2048 tokens of the loss; the gradient norm sums squares of those
# differences and so gets ten times the room.  A wrong tile mask, scale or
# gate moves either far more.
KERNEL_RTOL = {"loss": 5e-3, "gnorm": 5e-2}
# 4-stage pipeline (rebalanced, then consolidated to 2) vs 1 stage: the same
# kernels, the same step-0 loss, but differently partitioned programs.  A
# one-ulp f32 difference before a default-precision matmul can flip an
# operand's bf16 rounding (2^-8), and the early, large Adam steps amplify
# that: on a v5e host the gap grew from 5e-7 at step 1 to 8.5e-4 at step 11.
# Two slots swapped by a faulty migration moved the loss by 3e-3 on the
# first step after the rebalance (tiny-width check on the CPU).
STAGE_RTOL = 1e-3
TPU_CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def rel_diff(a: float, b: float) -> float:
    return abs(a - b) / max(abs(a), abs(b), 1e-12)


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------
def model_spec():
    from repro.api.specs import ModelSpec
    return ModelSpec(arch=ARCH)          # layers=None: the registry config


def train_spec(model, *, kernel_impl: str, steps: int, stages: int,
               num_micro: int, seq: int = SEQ, straggler=None):
    """DynMo training with block pruning.  The controller decides every 2
    steps; the pruning schedule first cuts FFN blocks at step 10."""
    from repro.api.specs import (ControllerSpec, DynamicsSpec, ParallelSpec,
                                 RunSpec)
    return RunSpec(
        model=model,
        parallel=ParallelSpec(stages=stages, num_micro=num_micro,
                              mb_global=1, seq=seq,
                              slot_slack=0 if stages == 1 else 2,
                              remat="block", param_dtype="float32",
                              kernel_impl=kernel_impl),
        dynamics=DynamicsSpec(kind="pruning"),
        controller=ControllerSpec(rebalance_every=2, straggler=straggler),
        steps=steps, seed=SEED, log_every=1)


def serve_spec(model, *, requests: int = 4, prompt_len: int = 64,
               gen: int = 16, page: int = 16):
    from repro.api.specs import ParallelSpec, RunSpec, ServeSpec
    return RunSpec(
        model=model,
        parallel=ParallelSpec(stages=1, num_micro=2, mb_global=2,
                              slot_slack=0, param_dtype="float32",
                              kernel_impl="pallas"),
        serve=ServeSpec(requests=requests, prompt_len=prompt_len, gen=gen,
                        kv_page_size=page),
        seed=SEED)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
class CompileClock:
    """Seconds JAX spends in backend compilation (persistent-cache loads
    included), read from jax.monitoring."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0

        def on_duration(event, duration, **_):
            if event == self.EVENT:
                self.seconds += duration
        jax.monitoring.register_event_duration_secs_listener(on_duration)


def compiled_step(session, num_micro: int, seq: int):
    """The compiled train step of the session's last world, lowered again
    from the live state (a persistent-cache hit)."""
    import jax
    import jax.numpy as jnp
    state = session.state
    world = session.engine.world(state.stages)
    tok = jnp.zeros((num_micro, 1, seq), jnp.int32)
    batch = {"tokens": tok, "labels": tok,
             "label_mask": jnp.ones(tok.shape, jnp.float32)}
    with world.mesh:
        return world.step.lower(state.params, state.opt_state,
                                state.assignment, state.dyn, batch,
                                jnp.float32(0.0)).compile()


def run_train(spec, clock: CompileClock, *, shrink_at=None,
              kernels: bool = False):
    """One Session.train; returns plain numbers only, so the session's
    device buffers are released when it closes."""
    from repro.api import Session
    c0 = clock.seconds
    with Session(spec) as s:
        rep = s.train(shrink_at=shrink_at)
        out = {
            "losses": [float(x) for x in rep["losses"]],
            "gnorms": [float(ev.data["gnorm"]) for ev in s.events
                       if ev.kind == "log"],
            "timing": rep["timing"],
            "step_times": list(rep["step_times"]),
            "rebalances": [(ev.step, ev.data["moved_layers"])
                           for ev in s.events if ev.kind == "rebalance"],
            "resizes": [(r["step"], r["from_stages"], r["to_stages"])
                        for r in rep["resizes"]],
            "decided": rep["controller"]["decided"],
            "compile_s": clock.seconds - c0,
            "worlds": {w.stages: [int(d.id) for d in w.mesh.devices.flat]
                       for w in s.engine.worlds},
        }
        if kernels:
            step = compiled_step(s, spec.parallel.num_micro,
                                 spec.parallel.seq)
            mem = step.memory_analysis()
            out["tpu_custom_calls"] = step.as_text().count(TPU_CUSTOM_CALL)
            out["program_bytes"] = {
                "arguments": mem.argument_size_in_bytes,
                "temporaries": mem.temp_size_in_bytes}
        del rep
    gc.collect()
    return out


def run_serve(spec):
    from repro.api import Session
    with Session(spec) as s:
        trace = s.make_trace()
        rep = s.serve(trace=trace)
        vocab = s.model_config().vocab_size
        gen = {r.rid: r.gen for r in trace}
        done = {c["rid"]: c["tokens"] for c in rep["completions"]}
        out = {"requests": len(trace), "completed": len(done),
               "tokens": rep["total_tokens"], "ticks": rep["ticks"],
               "wall_s": rep["wall_s"],
               "complete": sorted(done) == sorted(gen)
               and all(len(done[r]) == gen[r] for r in gen),
               "in_vocab": all(0 <= t < vocab for ts in done.values()
                               for t in ts)}
    gc.collect()
    return out


def peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# the two entry paths
# ---------------------------------------------------------------------------
def one_chip(devices, model, seq: int = SEQ) -> None:
    clock = CompileClock()
    steps = 12
    print(f"== train: {ARCH}, 1 stage, kernel_impl=pallas, pruning, "
          f"{steps} steps, 2 x 1 x {seq} tokens/step", flush=True)
    pal = run_train(train_spec(model, kernel_impl="pallas", steps=steps,
                               stages=1, num_micro=2, seq=seq), clock,
                    kernels=True)
    t = pal["timing"]
    print(f"compile_s {pal['compile_s']:.3f}")
    print(f"first_step_s {pal['step_times'][0]:.3f} (compile included)")
    print(f"step_s {[round(x, 4) for x in pal['step_times']]}")
    print(f"steady_step_mean_s {t['steady_step_mean_s']}  "
          f"steady_step_p50_s {t['steady_step_p50_s']}  "
          f"steady_tokens_per_s {t['steady_tokens_per_s']}")
    print(f"losses {pal['losses']}")
    print(f"controller decisions {pal['decided']}")
    print(f"tpu_custom_calls in train step {pal['tpu_custom_calls']}")
    print(f"train step program bytes {pal['program_bytes']}")
    print(f"peak_bytes_in_use {peak_bytes(devices[0])}", flush=True)
    check(all(map(math.isfinite, pal["losses"] + pal["gnorms"])),
          "non-finite loss or gradient norm")
    check(pal["tpu_custom_calls"] > 0,
          "the compiled train step holds no Pallas kernel")
    check(pal["decided"] >= 1, "the DynMo controller never decided")

    print("== step 0 again with kernel_impl=scan", flush=True)
    scan = run_train(train_spec(model, kernel_impl="scan", steps=1,
                                stages=1, num_micro=2, seq=seq), clock)
    for key, p, q in (("loss", pal["losses"][0], scan["losses"][0]),
                      ("gnorm", pal["gnorms"][0], scan["gnorms"][0])):
        d = rel_diff(p, q)
        print(f"step0 {key}: pallas {p!r} scan {q!r} rel_diff {d:.3e} "
              f"(tolerance {KERNEL_RTOL[key]:.0e})")
        check(d <= KERNEL_RTOL[key],
              f"pallas and scan step-0 {key} disagree: {d:.3e}")

    print("== serve: paged KV (16-token pages), kernel_impl=pallas",
          flush=True)
    srv = run_serve(serve_spec(model))
    print(f"served {srv['completed']}/{srv['requests']} requests, "
          f"{srv['tokens']} tokens in {srv['ticks']} ticks, "
          f"{srv['wall_s']:.3f} s")
    check(srv["complete"], "a request did not complete its tokens")
    check(srv["in_vocab"], "a generated token is outside the vocabulary")
    print(f"peak_bytes_in_use {peak_bytes(devices[0])}", flush=True)


def four_chips(devices, model, seq: int = SEQ) -> None:
    clock = CompileClock()
    steps, shrink = 14, 12
    print(f"== train: {ARCH}, 4 stages, straggling worker 1 (x2), "
          f"consolidate 4->2 at step {shrink}, {steps} steps", flush=True)
    multi = run_train(train_spec(model, kernel_impl="pallas", steps=steps,
                                 stages=4, num_micro=2, seq=seq,
                                 straggler={1: 2.0}),
                      clock, shrink_at={shrink: 2})
    for stages, ids in sorted(multi["worlds"].items(), reverse=True):
        print(f"{stages}-stage world: stage s on device id {ids}")
    print(f"rebalances (step, moved layers) {multi['rebalances']}")
    print(f"resizes (step, from, to) {multi['resizes']}")
    print(f"compile_s {multi['compile_s']:.3f}")
    print(f"step_s {[round(x, 4) for x in multi['step_times']]}")
    check(any(moved > 0 and step < shrink
              for step, moved in multi["rebalances"]),
          "no DynMo rebalance moved a layer before the consolidation")
    check((shrink, 4, 2) in multi["resizes"],
          "the live 4->2 consolidation did not happen")

    print(f"== the same {steps} steps on 1 stage (device id "
          f"{devices[0].id})", flush=True)
    single = run_train(train_spec(model, kernel_impl="pallas", steps=steps,
                                  stages=1, num_micro=2, seq=seq), clock)
    worst = 0.0
    for i, (a, b) in enumerate(zip(multi["losses"], single["losses"])):
        d = rel_diff(a, b)
        worst = max(worst, d)
        print(f"step {i:2d} loss 4-stage {a!r} 1-stage {b!r} "
              f"rel_diff {d:.3e}")
    print(f"worst rel_diff {worst:.3e} (tolerance {STAGE_RTOL:.0e})")
    check(len(multi["losses"]) == len(single["losses"]) == steps,
          "a run stopped early")
    check(all(map(math.isfinite, multi["losses"] + single["losses"])),
          "non-finite loss")
    check(worst <= STAGE_RTOL,
          f"4-stage and 1-stage losses disagree: {worst:.3e}")
    print(f"peak_bytes_in_use {[peak_bytes(d) for d in devices]}",
          flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(REPO, "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        fail(f"the repository's src/ is not beside this script ({e})")
    cache = enable_compile_cache()
    import jax
    devices = jax.devices()
    d0 = devices[0]
    print(f"device platform={d0.platform} kind={d0.device_kind!r} "
          f"count={len(devices)} jax={jax.__version__} cache={cache}",
          flush=True)
    check(d0.platform == "tpu",
          f"no TPU found: JAX runs on {d0.platform!r}; this smoke needs a "
          f"TPU and does not fall back to the CPU")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} TPU chips, JAX sees "
          f"{len(devices)}")
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(devices, model_spec())
    print(f"total_s {time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()

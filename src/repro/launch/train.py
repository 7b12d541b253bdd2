"""CLI trainer — a thin adapter over ``repro.api`` (RunSpec + Session).

The training loop itself lives in ``repro.api.session.Session.train``; this
module only (1) resolves a ``RunSpec`` from the CLI (``--config run.json``,
auto-generated dotted spec flags, the historical flag surface as aliases,
and ``--set path=value`` overrides — see ``repro.api.cli``) and (2) keeps
``run_training(...)`` as a **deprecation-shim** kwarg API: it builds the
equivalent ``RunSpec`` internally, so every pre-existing caller produces
bit-identical runs to the spec path.

Usage (CPU integration scale, 4 forced host devices):
  REPRO_TRAIN_DEVICES=4 PYTHONPATH=src python -m repro.launch.train \
      --config configs/scenarios/early_exit.json
  REPRO_TRAIN_DEVICES=4 PYTHONPATH=src python -m repro.launch.train \
      --arch smollm-360m --layers 8 --d-model 128 --stages 4 --steps 30 \
      --dynamism pruning --repack --async-controller --autoscale \
      --job-manager file --simulate-recover 18 \
      --set controller.repack.policy=first_fit
"""
from __future__ import annotations

import os
if os.environ.get("REPRO_TRAIN_DEVICES"):       # must precede jax import
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count="
        + os.environ["REPRO_TRAIN_DEVICES"])

import argparse
from typing import Any, Dict, Optional

from repro.api.cli import (TRAIN_ALIASES, TRAIN_CLI_DEFAULTS,
                           add_alias_flags, add_config_args, add_spec_flags,
                           build_spec, maybe_dump)
from repro.api.session import Session
from repro.api.specs import (ClusterSpec, ControllerSpec, DynamicsSpec,
                             ModelSpec, ParallelSpec, RepackSpec, RunSpec)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.engine import ElasticEngine, make_train_step  # noqa: F401
# make_train_step / ElasticEngine are re-exported for back-compat
# (tests/examples import them from here); engine.py owns step assembly.


def train_spec(arch: str, *, steps: int = 50, stages: int = 4,
               num_micro: int = 4, mb_global: int = 4, seq: int = 64,
               layers: Optional[int] = None, d_model: int = 128,
               dynamism: str = "none", rebalance_every: int = 10,
               balancer: str = "diffusion", ckpt_dir: Optional[str] = None,
               log_every: int = 10, seed: int = 0,
               kernel_impl: str = "scan",
               dyn_overrides: Optional[Dict[str, Any]] = None,
               repack: bool = False, repack_policy: str = "adjacent",
               repack_mem_cap: float = 1.1, repack_target: int = 1,
               grow_back: Optional[int] = None,
               async_controller: bool = False, async_drain: bool = False,
               autoscale: bool = False,
               autoscale_watermark: bool = False,
               heartbeat_timeout: float = 3.0,
               simulate_recover: Optional[int] = None,
               job_manager: str = "inproc",
               job_manager_dir: Optional[str] = None,
               tenant_id: Optional[str] = None, priority: int = 0,
               manager_url: Optional[str] = None,
               straggler: Optional[Dict[int, float]] = None,
               measure_stage_times: bool = False) -> RunSpec:
    """The ``RunSpec`` equivalent of the legacy ``run_training`` kwargs —
    the single place the old vocabulary maps onto the spec schema."""
    return RunSpec(
        model=ModelSpec(arch=arch, layers=layers, d_model=d_model),
        parallel=ParallelSpec(stages=stages, num_micro=num_micro,
                              mb_global=mb_global, seq=seq,
                              kernel_impl=kernel_impl),
        dynamics=DynamicsSpec(kind=dynamism, **(dyn_overrides or {})),
        controller=ControllerSpec(
            balancer=balancer, rebalance_every=rebalance_every,
            repack=RepackSpec(enabled=repack, policy=repack_policy,
                              mem_cap=repack_mem_cap,
                              target=max(1, repack_target)),
            async_decide=async_controller, async_drain=async_drain,
            straggler=straggler,
            measure_stage_times=measure_stage_times),
        cluster=ClusterSpec(job_manager=job_manager,
                            job_manager_dir=job_manager_dir,
                            tenant_id=tenant_id, priority=priority,
                            manager_url=manager_url,
                            autoscale=autoscale,
                            autoscale_watermark=autoscale_watermark,
                            heartbeat_timeout=heartbeat_timeout,
                            simulate_recover=simulate_recover,
                            grow_back=grow_back),
        steps=steps, seed=seed, log_every=log_every, ckpt_dir=ckpt_dir)


def run_training(arch: str, **kwargs) -> Dict[str, Any]:
    """Legacy kwarg entry point (deprecation shim).

    Builds the equivalent ``RunSpec`` and runs it through a ``Session`` —
    new code should do that directly:

        with Session(train_spec(arch, ...)) as s:
            report = s.train()
    """
    spec = train_spec(arch, **kwargs)
    with Session(spec) as s:
        return s.train()


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="DynMo trainer (config-first: --config RUN.JSON; "
                    "flags below override spec fields)")
    add_config_args(ap)
    ap.add_argument("--resume", default=None, metavar="CKPT_DIR",
                    help="resume from the newest safe point in this "
                         "directory; the safe point carries the producing "
                         "RunSpec, so every other flag is ignored")
    ap.add_argument("--events-out", default=None, metavar="PATH",
                    help="write the session's structured telemetry stream "
                         "(one JSON record per rebalance / resize / "
                         "relayout / autoscale / log event) to this file")
    add_alias_flags(ap, TRAIN_ALIASES)
    add_spec_flags(ap)
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.resume:
        sess = Session.resume(args.resume)
    else:
        spec = build_spec(args, TRAIN_ALIASES,
                          cli_defaults=TRAIN_CLI_DEFAULTS)
        if maybe_dump(args, spec):
            return
        sess = Session(spec)
    with sess as s:
        out = s.train()
    if args.events_out:
        import dataclasses
        import json
        with open(args.events_out, "w") as f:
            json.dump([dataclasses.asdict(ev) for ev in sess.events], f,
                      indent=1)
        print(f"wrote {len(sess.events)} events to {args.events_out}")
    ctl = out["controller"]
    print(f"done: loss {out['losses'][0]:.4f} -> {out['losses'][-1]:.4f} "
          f"in {out['wall_s']:.1f}s; rebalances={len(out['events'])}; "
          f"resizes={len(out['resizes'])}; "
          f"relayouts={len(out['relayouts'])}; "
          f"final stages={out['final_stages']}; "
          f"controller[{ctl['mode']}] decided={ctl['decided']} "
          f"dropped={ctl['dropped']} stale={ctl['stale_rejected']}")
    for rz in out["resizes"]:
        print(f"  {rz['kind']} @step {rz['step']}: {rz['from_stages']}->"
              f"{rz['to_stages']} stages, workers {rz['workers']}, "
              f"{rz['seconds']*1e3:.0f}ms, ticks {rz['ticks_before']}->"
              f"{rz['ticks_after']}")
    for d in out["autoscale_decisions"]:
        print(f"  autoscale @step {d['step']}: {d['action']} "
              f"x{d['workers']} ({d['reason']})")


if __name__ == "__main__":
    main()

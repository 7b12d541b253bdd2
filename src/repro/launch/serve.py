"""Serving CLI — a thin adapter over ``repro.api`` plus the legacy oracle:

  * ``run_elastic_serving`` / ``--elastic`` — the ``repro.serve`` subsystem
    (continuous batching on ``ElasticEngine`` worlds with load-driven
    autoscaling).  The lifecycle lives in ``Session.serve``; the kwarg
    entry point is a deprecation shim that builds the equivalent
    ``RunSpec`` (``serve_spec``), so flag path, config path, and Python
    API produce identical runs.
  * ``run_serving`` — the legacy one-shot generator (one fixed batch,
    prefill + gen decode rounds, optional DynMo rebalance between rounds);
    kept as the parity oracle for the continuous scheduler.

CPU-scale usage:
  REPRO_TRAIN_DEVICES=4 PYTHONPATH=src python -m repro.launch.serve \
      --arch smollm-360m --layers 8 --stages 4 --gen 16 --dynamism early_exit
  REPRO_TRAIN_DEVICES=4 PYTHONPATH=src python -m repro.launch.serve \
      --elastic --autoscale --requests 24 --burst-period 16 --burst-len 4
  REPRO_TRAIN_DEVICES=4 PYTHONPATH=src python -m repro.launch.serve \
      --elastic --config my_serve.json --set serve.queue_high=4
"""
from __future__ import annotations

import os
if os.environ.get("REPRO_TRAIN_DEVICES"):       # must precede jax import
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count="
        + os.environ["REPRO_TRAIN_DEVICES"])

import argparse
import time
from typing import Any, Dict, Optional

import numpy as np

from repro.api.cli import (SERVE_ALIASES, SERVE_CLI_DEFAULTS,
                           add_alias_flags, add_config_args, add_spec_flags,
                           build_spec, maybe_dump)
from repro.api.session import Session
from repro.api.specs import (ClusterSpec, ControllerSpec, DynamicsSpec,
                             ModelSpec, ParallelSpec, RunSpec, ServeSpec)
from repro.launch.compile_cache import enable_compile_cache


def run_serving(arch: str, *, stages: int = 4, micro: int = 2,
                mb_global: int = 4, prompt_len: int = 32, gen: int = 8,
                layers: Optional[int] = 8, d_model: int = 128,
                dynamism: str = "none", rebalance_every: int = 0,
                seed: int = 0, mesh=None):
    import jax
    import jax.numpy as jnp
    from repro.configs import DistConfig, get_config, reduced_config
    from repro.core.controller import ControllerConfig, DynMoController
    from repro.core.cost_model import LayerDynState, cost_vector
    from repro.core.profiler import LayerProfile
    from repro.dynamics.config import DynamicsConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models import model as M
    from repro.pipeline.pipeline import (PipelineShapes, build_decode_fn,
                                         build_prefill_fn)

    cfg = get_config(arch)
    if layers is not None:
        cfg = reduced_config(cfg, num_layers=layers, d_model=d_model,
                             num_heads=4, num_kv_heads=2, d_ff=2 * d_model,
                             vocab_size=512)
    dcfg = DistConfig(num_stages=stages, slot_slack=2, remat="none",
                      param_dtype="float32")
    dyncfg = DynamicsConfig(kind=dynamism)
    mesh = mesh or make_host_mesh(data=1, model=stages)
    cache_len = prompt_len + gen
    shapes = PipelineShapes(micro, mb_global, prompt_len,
                            cache_len=cache_len)

    params = M.init_params(jax.random.PRNGKey(seed), cfg, dcfg)
    assignment = M.make_assignment(cfg, dcfg)
    dyn = M.init_dyn(cfg, dcfg, dyncfg)
    cache = M.init_cache(cfg, dcfg, micro, mb_global, cache_len)
    prefill = jax.jit(build_prefill_fn(cfg, dcfg, dyncfg, mesh, shapes))
    decode = jax.jit(build_decode_fn(cfg, dcfg, dyncfg, mesh, shapes),
                     donate_argnums=(3,))
    ctrl = DynMoController(
        cfg, dcfg, dyncfg,
        ControllerConfig(method="partition", cost_by="time",
                         rebalance_every=max(1, rebalance_every)))

    rng = np.random.RandomState(seed)
    tokens = jnp.asarray(
        rng.randint(0, cfg.vocab_size, (micro, mb_global, prompt_len)),
        jnp.int32)
    outs = []
    t0 = time.perf_counter()
    with mesh:
        ids, cache, _ = prefill(params, assignment, dyn, cache,
                                {"tokens": tokens})
        outs.append(np.asarray(ids))
        for g in range(1, gen):
            ids, lp, cache, _ = decode(params, assignment, dyn, cache, ids,
                                       jnp.int32(prompt_len + g - 1))
            outs.append(np.asarray(ids))
            if rebalance_every and g % rebalance_every == 0:
                # serving-time profile: survival-curve cost vector
                L = cfg.total_blocks()
                states = [LayerDynState() for _ in range(L)]
                t = cost_vector(cfg, mb_global, prompt_len + g, states,
                                by="time")
                prof = LayerProfile(
                    t, cost_vector(cfg, mb_global, prompt_len + g, states,
                                   by="param") * dcfg.bytes_per_param,
                    np.zeros(stages), states)
                new_lps, ev = ctrl.decide(prof, g)
                if new_lps is not None:
                    params, _, dyn, assignment, cache = ctrl.apply(
                        new_lps, params, None, dyn, cache)
    wall = time.perf_counter() - t0
    gen_tokens = np.stack(outs, axis=-1)
    tps = micro * mb_global * gen / wall
    return {"tokens": gen_tokens, "wall_s": wall, "tokens_per_s": tps,
            "final_lps": ctrl.lps}


def serve_spec(arch: str, *, stages: int = 4, micro: int = 2,
               mb_global: int = 4, prompt_len: int = 32,
               gen: int = 8, layers: Optional[int] = 8,
               d_model: int = 128, dynamism: str = "none",
               requests: int = 16, min_prompt: Optional[int] = None,
               burst_period: int = 0, burst_len: int = 0,
               burst_rate: int = 4, lull_rate: int = 1,
               early_exit_frac: float = 0.0, seed: int = 0,
               autoscale: bool = False, min_stages: int = 1,
               queue_high: int = 8, occupancy_low: float = 0.35,
               patience: int = 2, cooldown: int = 4,
               defrag_every: int = 0, job_manager: str = "inproc",
               job_manager_dir: Optional[str] = None,
               tenant_id: Optional[str] = None, priority: int = 0,
               manager_url: Optional[str] = None,
               latency_slo_s: float = 0.0,
               kernel_impl: str = "scan",
               measure_stage_times: bool = False,
               max_ticks: int = 100000,
               kv_page_size: int = 0, kv_pool_pages: int = 0,
               prefix_cache: bool = False,
               temperature: float = 0.0) -> RunSpec:
    """The ``RunSpec`` equivalent of the legacy ``run_elastic_serving``
    kwargs — the single place the old vocabulary maps onto the schema."""
    return RunSpec(
        model=ModelSpec(arch=arch, layers=layers, d_model=d_model),
        parallel=ParallelSpec(stages=stages, num_micro=micro,
                              mb_global=mb_global,
                              kernel_impl=kernel_impl),
        dynamics=DynamicsSpec(kind=dynamism),
        controller=ControllerSpec(measure_stage_times=measure_stage_times),
        cluster=ClusterSpec(job_manager=job_manager,
                            job_manager_dir=job_manager_dir,
                            autoscale=autoscale, tenant_id=tenant_id,
                            priority=priority, manager_url=manager_url),
        serve=ServeSpec(requests=requests, prompt_len=prompt_len, gen=gen,
                        min_prompt=min_prompt, burst_period=burst_period,
                        burst_len=burst_len, burst_rate=burst_rate,
                        lull_rate=lull_rate,
                        early_exit_frac=early_exit_frac,
                        defrag_every=defrag_every,
                        min_stages=max(1, min_stages),
                        queue_high=queue_high,
                        occupancy_low=occupancy_low, patience=patience,
                        cooldown=cooldown, latency_slo_s=latency_slo_s,
                        max_ticks=max_ticks, kv_page_size=kv_page_size,
                        kv_pool_pages=kv_pool_pages,
                        prefix_cache=prefix_cache, temperature=temperature),
        seed=seed)


def run_elastic_serving(arch: str, *, resize_at=None,
                        **kwargs) -> Dict[str, Any]:
    """Legacy kwarg entry point (deprecation shim).

    Builds the equivalent ``RunSpec`` and serves it through a ``Session``
    — new code should do that directly:

        with Session(serve_spec(arch, ...)) as s:
            report = s.serve()
    """
    spec = serve_spec(arch, **kwargs)
    with Session(spec) as s:
        return s.serve(resize_at=resize_at)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="DynMo serving (config-first: --config RUN.JSON; "
                    "flags below override spec fields)")
    ap.add_argument("--elastic", action="store_true",
                    help="serve a request trace through the continuous-"
                         "batching scheduler on elastic engine worlds")
    ap.add_argument("--rebalance-every", type=int, default=0,
                    help="legacy one-shot path only: DynMo rebalance "
                         "between decode rounds")
    ap.add_argument("--events-out", default=None, metavar="PATH",
                    help="write the session's structured telemetry stream "
                         "(one JSON record per resize / autoscale / "
                         "tenant_register / steal / yield event) to this "
                         "file")
    add_config_args(ap)
    add_alias_flags(ap, SERVE_ALIASES)
    add_spec_flags(ap)
    args = ap.parse_args(argv)
    enable_compile_cache()
    spec = build_spec(args, SERVE_ALIASES, cli_defaults=SERVE_CLI_DEFAULTS)
    if maybe_dump(args, spec):
        return
    if args.elastic or args.config:
        with Session(spec) as s:
            rep = s.serve()
        if args.events_out:
            import dataclasses
            import json
            with open(args.events_out, "w") as f:
                json.dump([dataclasses.asdict(ev) for ev in s.events], f,
                          indent=1)
            print(f"wrote {len(s.events)} events to {args.events_out}")
        kinds = [r["kind"] for r in rep["resizes"]]
        print(f"served {len(rep['completions'])} requests / "
              f"{rep['total_tokens']} tokens in {rep['wall_s']:.1f}s "
              f"({rep['tokens_per_s']:.1f} tok/s); "
              f"p50/p95 token latency "
              f"{rep['latency_p50_s'] * 1e3:.0f}/"
              f"{rep['latency_p95_s'] * 1e3:.0f}ms; "
              f"resizes={kinds}; "
              f"stages {rep['stages_history'][0]}->"
              f"{rep['stages_history'][-1]}")
        if rep.get("measured_stage_times") is not None:
            print(f"  measured stage times "
                  f"{[f'{t*1e3:.1f}ms' for t in rep['measured_stage_times']]}")
        for d in rep["autoscale_decisions"]:
            print(f"  autoscale @tick {d['step']}: {d['action']} "
                  f"({d['reason']})")
        return
    out = run_serving(
        spec.model.arch, stages=spec.parallel.stages,
        micro=spec.parallel.num_micro, mb_global=spec.parallel.mb_global,
        prompt_len=spec.serve.prompt_len, gen=spec.serve.gen,
        layers=spec.model.layers, d_model=spec.model.d_model,
        dynamism=spec.dynamics.kind, rebalance_every=args.rebalance_every,
        seed=spec.seed)
    print(f"generated {out['tokens'].shape} in {out['wall_s']:.1f}s "
          f"({out['tokens_per_s']:.1f} tok/s); final lps={out['final_lps']}")


if __name__ == "__main__":
    main()

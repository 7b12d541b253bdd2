#!/usr/bin/env python3
"""Where a cell's device idled, by the system's own host spans, and how
close its attention kernels came to their roofline.

    python3 bench/attribute.py --workload <cell> --seed <n> --seconds <s>

Makes one traced run of the cell as ``bench/run.py --trace 1`` does and
prints its result line, then a last line ``{"attribution": ...}``:

  idle_*_share        device idle under the system's spans, grouped as
                      ``bench.lib.spans.GROUPS`` (% of the window; the five
                      sum to ``device_idle_share``)
  window_tiles        live attention tiles of the window's steps, read from
                      the system (``ElasticEngine.attn_tiles_total`` before
                      the window's first step and after its last)
  attn_roofline       the tiles' least kernel time (``bench.lib.tiles``)
                      over the attention kernels' device time (%)
  longest             the longest idle intervals, each with its start after
                      the window's and its seconds by span

On a system that marks no spans or counts no tiles those numbers are null.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def attribute(cell, seed: int, seconds: float, **run_kw):
    """One traced run of ``cell``: (its result line, the attribution).
    ``run_kw`` go to ``runner.run`` (the CPU tests')."""
    import jax
    from bench.lib import peaks, runner, spans, tiles
    from bench.lib import trace as trace_mod
    from repro.launch.engine import ElasticEngine

    kept = {}
    reduce, step = trace_mod.reduce, ElasticEngine.step

    def reduce_and_attribute(path, devices=None):
        kept["spans"] = spans.reduce(path, devices)
        kept["trace"] = reduce(path, devices)
        return kept["trace"]

    def counted_step(engine, state, batch, lr):
        if kept.get("calls", 0) == cell.cell["warmup_steps"]:
            kept["engine"] = engine
            if hasattr(engine, "attn_tiles_total"):
                kept["tiles0"] = engine.attn_tiles_total()
        kept["calls"] = kept.get("calls", 0) + 1
        return step(engine, state, batch, lr)

    trace_mod.reduce, ElasticEngine.step = reduce_and_attribute, counted_step
    try:
        result = runner.run(cell, seed, seconds, True, **run_kw)
    finally:
        trace_mod.reduce, ElasticEngine.step = reduce, step

    out = dict(spans.shares(kept["spans"]))
    out["sum_of_idle_shares"] = sum(out.values()) if out else None
    out["device_idle_share"] = result["metrics"].get(
        "device_idle_share", {}).get("value")
    out["window_steps"] = result["attempted"]
    out["tokens_per_s_traced"] = (result["attempted"] * cell.tokens_per_step
                                  / kept["spans"]["window_s"])
    tiles_n = None
    if "tiles0" in kept:
        tiles_n = kept["engine"].attn_tiles_total() - kept["tiles0"]
    out["window_tiles"] = tiles_n
    out["block"] = tiles.block_of(cell)
    kernel_s = sum(d["kernels"].get("attention", (0.0, 0))[0]
                   for d in kept["trace"]["devices"].values())
    out["attn_kernel_s"] = kernel_s
    out["attn_roofline"] = None
    if tiles_n and kernel_s > 0:
        peak = peaks.peaks(jax.devices()[0].device_kind)
        out["attn_roofline"] = 100.0 * tiles.least_seconds(
            cell.config, tiles_n, out["block"], peak) / kernel_s
    out["idle_by_span"] = {dev: d["idle_by_span"] for dev, d in
                           kept["spans"]["devices"].items()}
    out["longest"] = {dev: d["longest"] for dev, d in
                      kept["spans"]["devices"].items()}
    return result, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.lib.spec import Cell
    cell = Cell(args.workload)
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result, out = attribute(cell, args.seed, args.seconds)
    print(json.dumps(result), flush=True)
    print(json.dumps({"attribution": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

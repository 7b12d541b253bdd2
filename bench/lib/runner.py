"""One run of one cell: set-up, warm-up, the timed window, the comparison
with the plain reference, and the result line."""
from __future__ import annotations

import gc
import importlib.util
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Optional

import numpy as np

from bench.lib import compare, peaks, spec as spec_mod, traffic
from bench.lib import trace as trace_mod
from bench.lib.drive import Drive

HORIZON = 1_000_000     # Session steps: never reached, so no schedule moves
# the comparison's readings, limited or not, as each run records them
READINGS = ("loss_gap", "first_loss_gap", "grad_gap", "grad_gap_median",
            "grad_leaf", "update_gap", "update_gap_median", "update_leaf",
            "mask_margin", "mask_margin_max", "mask_layers_flipped",
            "mask_unexplained")


def process_age() -> float:
    """Seconds since this process started (Linux), else 0."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


def run_spec(cell, seed: int, *, param_dtype: Optional[str] = None):
    """The RunSpec of the cell: the user's entry into the system."""
    from repro.api.specs import (ControllerSpec, DynamicsSpec, ModelSpec,
                                 ParallelSpec, RunSpec)
    c = cell.cell
    model = ModelSpec(arch=cell.config["registry"],
                      **cell.config.get("registry_reduction", {}))
    par = dict(c["parallel"])
    if param_dtype is not None:
        par["param_dtype"] = param_dtype
    return RunSpec(model=model, parallel=ParallelSpec(**par),
                   dynamics=DynamicsSpec(**c["dynamics"]),
                   controller=ControllerSpec(**c["controller"]),
                   steps=HORIZON, seed=int(seed), log_every=HORIZON)


def reference(cell, seed: int, batches, out) -> dict:
    """The plain reference over the cell's compared steps, on the first
    device; where a step holds one sequence it explains the system's
    live-tile count of each layer (``dense_reference``)."""
    ref = cell.reference()
    steps = len(out.densities)
    targets = None
    if cell.sparse is not None and cell.one_sequence:
        nb = cell.parallel["seq"] // cell.sparse["block"]
        causal = nb * (nb + 1) / 2
        targets = np.rint(np.asarray(out.densities) * causal).astype(
            np.int32)
    return ref.run(seed, cell.config, [batches(i) for i in range(steps)],
                   cell.sparse, targets)


def batches_of(cell, seed: int):
    p = cell.parallel
    return traffic.make(cell.mix, seed, cell.config["vocab_size"],
                        p["num_micro"], p["mb_global"], p["seq"])


def compared(cell, seed: int, out, batches) -> dict:
    """Free the system's state, run the reference, and compare."""
    gc.collect()
    if len(out.densities) < Drive.COMPARED:
        raise ValueError("the run stopped before the compared steps")
    ref = reference(cell, seed, batches, out)
    g = compare.gaps(out.losses, out.grad_norms, out.delta_norms, ref)
    g["reference_losses"] = ref["losses"]
    g["system_losses"] = out.losses[:len(ref["losses"])]
    g["density"] = ref["density"]
    if cell.sparse is not None and cell.one_sequence:
        need = np.asarray(ref["needed_margin"])        # [step, layer]
        g["needed_margin"] = need.tolist()
        g["mask_margin"] = float(np.median(need))
        g["mask_margin_max"] = float(need.max())
        g["mask_layers_flipped"] = int(np.sum(need > 0))
        g["mask_unexplained"] = int(np.sum(need >= 1.0))
    return g


class RunData:
    """What the per-layer metric readers read."""

    def __init__(self, cell, out, red, peak, compiles):
        self.cell, self.out, self.trace, self.peak = cell, out, red, peak
        self.compiles_in_window = compiles
        self.chips = cell.chips
        self.config = cell.config

    @property
    def tokens_per_s(self) -> float:
        return (self.out.window_steps * self.cell.tokens_per_step
                / self.out.window_s)


def read_metric(name: str, data: RunData):
    path = os.path.join(spec_mod.BENCH, "metrics", name + ".py")
    s = importlib.util.spec_from_file_location("bench_metric_" + name, path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod.read(data)


def run(cell, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, fault: Optional[Callable] = None,
        log=print) -> dict:
    t_proc = time.perf_counter() - process_age()
    import jax
    from bench.lib.drive import CompileLog
    devices = jax.devices()
    d0 = devices[0]
    if require_tpu and d0.platform != "tpu":
        raise SystemExit(f"no TPU: JAX runs on {d0.platform!r}; the "
                         f"benchmark does not fall back to it")
    if len(devices) < cell.chips:
        raise SystemExit(f"cell {cell.name} needs {cell.chips} chips, JAX "
                         f"sees {len(devices)}")
    peak = peaks.peaks(d0.device_kind) if require_tpu else None
    clog = CompileLog()
    batches = batches_of(cell, seed)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    drive = Drive(cell, seed, batches, warmup=cell.cell["warmup_steps"],
                  seconds=seconds, trace_dir=trace_dir, fault=fault)
    out = drive.run(run_spec(cell, seed))
    setup_s = out.t_start - t_proc
    used = devices[:cell.chips]
    stats = [d.memory_stats() or {} for d in used]
    peak_bytes = max((s.get("peak_bytes_in_use", 0) for s in stats),
                     default=0)
    log(f"peak_bytes_in_use {[s.get('peak_bytes_in_use') for s in stats]}",
        file=sys.stderr)
    log(f"step program bytes {out.program_bytes}", file=sys.stderr)
    window_compiles = clog.between(out.t_start, out.t_end)
    red = None
    if trace:
        red = trace_mod.reduce(trace_mod.find(trace_dir),
                               devices=[d.id for d in used])
        shutil.rmtree(trace_dir, ignore_errors=True)
    losses_w = out.losses[out.warmup:out.warmup + out.window_steps]
    failed = sum(1 for x in losses_w if not np.isfinite(x))
    data = RunData(cell, out, red, peak, len(window_compiles))
    metrics = {}
    if not trace:
        values = {"tokens_per_s": data.tokens_per_s, "setup_s": setup_s}
        for m in cell.e2e_metrics():
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        for m in cell.layer_metrics():
            v = read_metric(m["name"], data)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    log(f"window: {out.window_steps} steps in {out.window_s:.6f} s after "
        f"{out.warmup} warm-up steps; setup {setup_s:.3f} s; "
        f"compiles in window {len(window_compiles)}; "
        f"rebalances {out.report['rebalances']}; lps "
        f"{out.report['final_lps']}", file=sys.stderr)
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak_bytes)}
    if red is not None:
        busy = [red["devices"][d.id]["busy_s"] for d in used
                if d.id in red["devices"]]
        device["busy_s"] = float(np.mean(busy)) if busy else 0.0
        device["window_s"] = red["window_s"]
    del drive
    g = compared(cell, seed, out, batches)
    correct, checks = compare.judge(g, cell.cell["limits"])
    correct = correct and failed == 0
    result = {"correct": correct, "attempted": out.window_steps,
              "failed": failed, "metrics": metrics, "device": device}
    if red is not None:
        result["breakdown"] = trace_mod.breakdown(red)
    result["reference"] = {
        "losses": g["reference_losses"], "system_losses": g["system_losses"],
        **{k: g.get(k) for k in READINGS}, "quiet_leaves": g["quiet_leaves"],
        "mask_density_per_layer": g["density"]}
    result["checks"] = checks
    return result

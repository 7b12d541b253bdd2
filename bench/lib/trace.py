"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

The window is the host span ``bench.window``.  On every device plane, the
operations of its ``XLA Ops`` line are clipped to the window; operations
that hold others (a loop around its body) count through their children.

  busy_s      union of the operation intervals on the device
  ops         seconds and calls per operation name
  kernels     seconds and calls per kernel of ``KERNELS``
  gaps        each idle interval of the device inside the window, named by
              the innermost of the harness's host spans around its middle

An operation's event carries its HLO text (``%name = type op(...)``); it
is known by the name before `` = ``.  Pallas kernels carry no ``name=``
today; the compiled program names each custom call after the function that
made it (``pruned_matmul.12``, ``block_sparse_attention.3`` or, outside a
larger program, ``jvp_jit_block_sparse_attention__.1``), which ``KERNELS``
matches.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

KERNELS = {
    "ffn_matmul": re.compile(r"^pruned_matmul(\.\d+)?$"),
    "attention": re.compile(r"block_sparse_attention"),
}
# host spans, innermost first, and the names the gaps get
SPANS = (("bench.loader", "loader"), ("bench.step", "engine.step"))
# inside the window and outside both: the session's own host work
# (controller, dynamism, migrations, bookkeeping)
ELSEWHERE = "session host work"
OPS_LINE = "XLA Ops"


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(text: str) -> str:
    """``%pruned_matmul.9 = f32[..] custom-call(..)`` -> ``pruned_matmul.9``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def kernel_of(name: str) -> Optional[str]:
    for k, pat in KERNELS.items():
        if pat.search(name):
            return k
    return None


def _device_id(plane_name: str) -> Optional[int]:
    m = re.fullmatch(r"/device:TPU:(\d+)", plane_name)
    return int(m.group(1)) if m else None


def _leaves(events: List[Tuple[str, float, float]]):
    """Drop events that contain the next one (loops, calls)."""
    events.sort(key=lambda e: (e[1], -e[2]))
    out = []
    for i, (name, t0, t1) in enumerate(events):
        if i + 1 < len(events) and events[i + 1][1] < t1 and \
                events[i + 1][2] <= t1:
            continue
        out.append((name, t0, t1))
    return out


def _union(intervals):
    merged = []
    for t0, t1 in sorted(intervals):
        if merged and t0 <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t1)
        else:
            merged.append([t0, t1])
    return merged


def reduce(path: str, devices: Optional[List[int]] = None) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans: Dict[str, List[Tuple[float, float]]] = {}
    device_events: Dict[int, List[Tuple[str, float, float]]] = {}
    for plane in pd.planes:
        dev = _device_id(plane.name)
        for line in plane.lines:
            if dev is None:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
            elif line.name == OPS_LINE and (devices is None
                                            or dev in devices):
                device_events[dev] = [
                    (op_name(ev.name), ev.start_ns,
                     ev.start_ns + ev.duration_ns) for ev in line.events]
    if "bench.window" not in spans:
        raise ValueError("the trace holds no bench.window span")
    w0, w1 = spans["bench.window"][0]
    out = {"window_s": (w1 - w0) * 1e-9, "devices": {}}
    for dev, evs in sorted(device_events.items()):
        evs = [(n, max(t0, w0), min(t1, w1)) for n, t0, t1 in _leaves(evs)
               if t1 > w0 and t0 < w1]
        ops: Dict[str, List[float]] = {}
        kernels: Dict[str, List[float]] = {}
        for name, t0, t1 in evs:
            o = ops.setdefault(name, [0.0, 0])
            o[0] += (t1 - t0) * 1e-9
            o[1] += 1
            k = kernel_of(name)
            if k is not None:
                kk = kernels.setdefault(k, [0.0, 0])
                kk[0] += (t1 - t0) * 1e-9
                kk[1] += 1
        busy = _union([(t0, t1) for _, t0, t1 in evs])
        gaps, prev = [], w0
        for t0, t1 in busy + [[w1, w1]]:
            if t0 > prev:
                gaps.append((_span_at((prev + t0) / 2, spans),
                             (t0 - prev) * 1e-9))
            prev = max(prev, t1)
        out["devices"][dev] = {
            "busy_s": sum(t1 - t0 for t0, t1 in busy) * 1e-9,
            "ops": ops, "kernels": kernels, "gaps": gaps}
    return out


def _span_at(t: float, spans) -> str:
    for key, label in SPANS:
        for t0, t1 in spans.get(key, ()):
            if t0 <= t <= t1:
                return label
    return ELSEWHERE


def breakdown(red: dict, top: int = 10) -> dict:
    """The device operations that took most time (seconds per chip, mean
    over the chips) and the longest idle gaps by what the host was doing."""
    devs = red["devices"]
    n = max(1, len(devs))
    tot: Dict[str, float] = {}
    for d in devs.values():
        for name, (sec, _) in d["ops"].items():
            tot[name] = tot.get(name, 0.0) + sec / n
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(((f"{label} (TPU {dev})", sec)
                   for dev, d in devs.items() for label, sec in d["gaps"]),
                  key=lambda g: -g[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}

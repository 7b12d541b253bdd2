"""Device idle time put down to the program's own host spans.

The system marks its host work with profiler annotations named
``dynmo.<span>`` (``repro.obs.trace.span``): ``dynmo.train``, one
``dynmo.train.iter`` per loop iteration, and inside it ``train.data``,
``train.batch``, ``train.step`` (``engine.place``, ``engine.dispatch``,
``train.wait``), ``controller.decide`` (``controller.stats_to_host``,
``controller.publish``), ``train.dynamism``, ``safepoint``, ``resize.*``.

Each idle interval of a device inside ``bench.window`` is split by overlap
over the innermost ``dynmo.*`` span that covers each part (the one that
started last); a part that no span below ``dynmo.train`` covers is put
down to ``none``.  The parts of all intervals sum to the device's idle
time, so the shares of ``GROUPS`` sum to ``device_idle_share`` of the same
trace.

A trace of a program without these annotations (an older checkout) reads
all idle time as ``none``; ``shares`` then returns nothing.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from bench.lib import trace as trace_mod

PREFIX = "dynmo."
ROOT = "dynmo.train"
NONE = "none"
# metric -> the spans whose idle time it reads; ``loop`` takes every other
# span below ``dynmo.train``, ``unattributed`` the parts under none
GROUPS = {
    "idle_data_share": ("train.data", "train.batch"),
    "idle_engine_share": ("engine.place", "engine.dispatch"),
    "idle_controller_share": ("controller.decide",
                              "controller.stats_to_host",
                              "controller.publish", "controlplane.decide"),
}
LOOP = "idle_loop_share"
UNATTRIBUTED = "idle_unattributed_share"

Interval = Tuple[float, float]


def idle_by_span(idle: List[Interval],
                 spans: List[Tuple[str, float, float]]) -> Dict[str, float]:
    """Seconds of ``idle`` (nanosecond intervals) under each span name,
    each part under the innermost span covering it, ``none`` where none
    does.  ``spans`` are (name, start, end) without the ``dynmo.`` prefix,
    the root span left out."""
    out: Dict[str, float] = {}
    for t0, t1 in idle:
        over = [s for s in spans if s[1] < t1 and s[2] > t0]
        cuts = sorted({t0, t1} | {t for _, a, b in over for t in (a, b)
                                  if t0 < t < t1})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            cover = [s for s in over if s[1] <= mid <= s[2]]
            name = (max(cover, key=lambda s: (s[1], -s[2]))[0] if cover
                    else NONE)
            out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out


def reduce(path: str, devices=None, top: int = 5) -> dict:
    """Per device: idle seconds by span (``idle_by_span``), idle and window
    seconds, and the ``top`` longest idle intervals, each with its start
    after the window's and its seconds by span."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window = None
    spans: List[Tuple[str, float, float]] = []
    device_events: Dict[int, list] = {}
    for plane in pd.planes:
        dev = trace_mod._device_id(plane.name)
        for line in plane.lines:
            if dev is None:
                for ev in line.events:
                    t = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    if ev.name == "bench.window":
                        window = window or t
                    elif ev.name.startswith(PREFIX) and ev.name != ROOT:
                        spans.append((ev.name[len(PREFIX):],) + t)
            elif line.name == trace_mod.OPS_LINE and (devices is None
                                                      or dev in devices):
                device_events[dev] = [(ev.name, ev.start_ns,
                                       ev.start_ns + ev.duration_ns)
                                      for ev in line.events]
    if window is None:
        raise ValueError("the trace holds no bench.window span")
    w0, w1 = window
    out = {"window_s": (w1 - w0) * 1e-9, "spans": len(spans), "devices": {}}
    for dev, evs in sorted(device_events.items()):
        # the device's busy time exactly as ``trace.reduce`` takes it
        busy = trace_mod._union([(max(a, w0), min(b, w1))
                                 for _, a, b in trace_mod._leaves(evs)
                                 if b > w0 and a < w1])
        idle, prev = [], w0
        for t0, t1 in busy + [[w1, w1]]:
            if t0 > prev:
                idle.append((prev, t0))
            prev = max(prev, t1)
        longest = sorted(idle, key=lambda g: g[0] - g[1])[:top]
        out["devices"][dev] = {
            "idle_s": sum(b - a for a, b in idle) * 1e-9,
            "idle_by_span": idle_by_span(idle, spans),
            "longest": [{"after_s": (a - w0) * 1e-9, "seconds": (b - a) * 1e-9,
                         "by_span": idle_by_span([(a, b)], spans)}
                        for a, b in longest]}
    return out


def shares(red: dict) -> Dict[str, float]:
    """The five idle shares (% of the window, mean over devices); empty
    where the trace holds no program span."""
    devs = list(red["devices"].values())
    if not devs or not red["spans"]:
        return {}
    total = dict.fromkeys(list(GROUPS) + [LOOP, UNATTRIBUTED], 0.0)
    for d in devs:
        for name, sec in d["idle_by_span"].items():
            metric = next((m for m, names in GROUPS.items() if name in names),
                          UNATTRIBUTED if name == NONE else LOOP)
            total[metric] += sec
    return {m: 100.0 * s / len(devs) / red["window_s"]
            for m, s in total.items()}

"""Unified observability layer (DESIGN.md §15).

Three pillars, one package:

  * ``obs.timing``  — in-step stage timing: host-callback timestamps at
    stage boundaries *inside* the pipelined jitted step, so the
    controller's cost vector reflects the step it just ran (ROADMAP open
    item 5).  Imported lazily by the pipeline/engine (it needs jax).
  * ``obs.trace``   — ``span(name)``, the program's one span API: a JAX
    profiler annotation ``dynmo.<name>`` on the profiler's clock, and a
    span of the current ``Tracer`` (trace_id / span_id / parent, wall +
    logical-clock stamps) exported as Chrome trace-event JSON, loadable in
    Perfetto.  Stdlib-only.
  * ``obs.metrics`` — a counters/gauges/histograms registry with
    Prometheus text exposition and a JSON snapshot for CI.  Stdlib-only.
  * ``obs.events``  — the unified event-record schema shared by the
    session telemetry stream, the fault-event log, and the cluster
    scheduler's grant timeline.

``obs.timing`` is deliberately NOT imported here: the cluster manager
processes import ``obs.trace``/``obs.metrics`` and must not pull in jax.
"""
from repro.obs.events import EVENT_SCHEMA, stamp_record
from repro.obs.metrics import MetricsRegistry, scheduler_to_prometheus
from repro.obs.trace import Tracer, current_tracer, set_current_tracer, span

__all__ = [
    "EVENT_SCHEMA", "stamp_record", "MetricsRegistry",
    "scheduler_to_prometheus", "Tracer", "current_tracer",
    "set_current_tracer", "span",
]

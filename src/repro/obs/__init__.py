"""End-to-end observability for the serving path (`repro.obs`) — DESIGN §9.

Two pieces, both designed around the "never instrument inside jit" rule:

* :mod:`repro.obs.tracer` — a bounded flight-recorder :class:`Tracer`
  (numpy struct-of-arrays ring buffer, span/instant events, vectorized
  batch appends, live ``scope`` spans that are also profiler annotations)
  exporting Chrome trace-event JSON loadable in Perfetto;
  :class:`NullTracer` is the default, so the traced-off path is free.
* :mod:`repro.obs.metrics` — a :class:`MetricsRegistry` of counters,
  gauges, and fixed-bucket histograms behind one ``snapshot() -> dict``,
  subsuming the ad-hoc per-layer telemetry (simulator counters,
  ``ResolveStats`` aggregation, queue tallies, transport bandwidth).
"""

from .metrics import (LATENCY_EDGES_S, Counter, Gauge, Histogram,
                      MetricsRegistry)
from .tracer import (ADMISSION, ENGINE, FRAMES, NULL_TRACER, QUEUE, SOLVER,
                     TRANSPORT, NullTracer, Tracer)

__all__ = [
    "ADMISSION", "ENGINE", "FRAMES", "NULL_TRACER", "QUEUE", "SOLVER",
    "TRANSPORT", "Counter", "Gauge", "Histogram", "LATENCY_EDGES_S",
    "MetricsRegistry", "NullTracer", "Tracer",
]

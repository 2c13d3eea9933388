"""repro.obs — tracing + metrics for the serve engine.

Three layers, strictly ordered by cost:

* :mod:`repro.obs.clock` — one injectable monotonic time source;
* :mod:`repro.obs.trace` — lock-free ring-buffer tracer (hot path:
  numpy scalar stores only) with Perfetto export in
  :mod:`repro.obs.export`;
* :mod:`repro.obs.metrics` — typed counters/gauges/histograms behind a
  single (shareable) lock, snapshot-consistent.

No submodule imports jax at import time (a tracer built with
``annotate=True`` imports ``jax.profiler`` then); keep it that way so the
tracer and metrics stay importable (and cheap) everywhere, including under
the engine lock.
"""
from .clock import ManualClock, monotonic, reset_source, set_source
from .metrics import (
    BYTES_EDGES,
    LATENCY_EDGES_S,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from .trace import NOARG, NULL_TRACER, ServeTracer, Tracer, hot_path

__all__ = [
    "ManualClock",
    "monotonic",
    "reset_source",
    "set_source",
    "BYTES_EDGES",
    "LATENCY_EDGES_S",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NOARG",
    "NULL_TRACER",
    "ServeTracer",
    "Tracer",
    "hot_path",
]

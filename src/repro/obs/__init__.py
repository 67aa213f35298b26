"""Pmem-native telemetry plane.

Three layers (ISSUE 8 / ROADMAP "Telemetry plane"):

  * ``metrics``  — process-local registry: counters, gauges,
    bounded-memory histograms; ``StatsView`` read-through aliases keep
    the legacy dict-shaped stats surfaces alive.
  * ``trace``    — correlation IDs + span trees reconstructed from
    recorder events; ``annotate``, the profiler span every program span
    goes through.
  * ``recorder`` — crash-persistent per-node pmem flight recorder
    (fixed-slot ring under MetaLog's committed-tail discipline).

``plane.TelemetryPlane`` ties them together; ``report`` is the
post-crash replay CLI (``python -m repro.obs.report``).
"""
from repro.obs.metrics import (Counter, Gauge, Histogram,  # noqa: F401
                               Registry, StatsView)
from repro.obs.plane import TelemetryPlane  # noqa: F401
from repro.obs.recorder import FlightRecorder  # noqa: F401
from repro.obs.trace import (Span, annotate, build_traces,  # noqa: F401
                             ctx, new_id)

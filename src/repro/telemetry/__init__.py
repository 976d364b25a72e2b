"""Unified observability layer: one subsystem, two projections.

1. **In-scan recorders** (`recorder.py`) — fixed-shape, `lax.scan`-safe
   tracks compiled into the simulator's slot step when a
   `TelemetryConfig` is passed (``telemetry=`` on
   `simulate`/`sweep`/`run_study`): downsampled time series, a FIFO-
   coupled task-sojourn histogram, and a queue-length histogram, from
   which p50/p95/p99 delay and the queue-length distribution flow out as
   metrics keys.  With ``telemetry=None`` nothing is compiled and sample
   paths stay bitwise (pure observation even when on: no random bits
   consumed).

2. **Host-side event tracing** (`events.py`) — a ring-buffered
   `EventRecorder` the serving engine, the data pipeline, the host
   replication lifecycle and the benches emit typed events into, with a
   Chrome trace-event JSON exporter viewable in Perfetto.  Its span
   helper (`maybe_span`) also annotates the `jax.profiler` trace, where
   the simulator's host spans (`sim.prepare`, `sim.fetch`, `sim.trace`)
   sit beside its device scopes (`sim.arrivals`, `sim.route`, ...).

See docs/observability.md for recorder configuration, the histogram
error bound, and the trace-event schema.
"""

from repro.telemetry.events import (CLOCK_UNIT_US, EventRecorder, load_trace,
                                    maybe_span, validate_chrome_trace)
from repro.telemetry.recorder import (OVERFLOW_WARN_FRAC,
                                      TELEMETRY_METRIC_KEYS, SimTelemetry,
                                      TelemetryConfig, TelemetryLike,
                                      TelState, as_telemetry_config,
                                      fcfs_sojourns, maybe_warn_overflow,
                                      percentiles_from_hist)

__all__ = [
    "CLOCK_UNIT_US", "EventRecorder", "load_trace", "maybe_span",
    "validate_chrome_trace", "OVERFLOW_WARN_FRAC", "TELEMETRY_METRIC_KEYS",
    "SimTelemetry", "TelemetryConfig", "TelemetryLike", "TelState",
    "as_telemetry_config", "fcfs_sojourns", "maybe_warn_overflow",
    "percentiles_from_hist",
]

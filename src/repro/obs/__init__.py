"""repro.obs — end-to-end observability for the serving stack.

Two halves (DESIGN.md §11 performance, §12 correctness):

**Performance** —
  * ``trace``    — a low-overhead, thread-safe span tracer with
    Chrome-trace-format export (``chrome://tracing`` / Perfetto) and the
    ``timeit`` micro-helper, the one host-timing idiom.  A span takes
    work counts at exit (``sp.set(sweeps=n)``), spans of one request
    share an identifier in their args (``qid``, ``first_seq``/
    ``last_seq``), and while tracing is on every span is also a
    ``jax.profiler.TraceAnnotation``, so a profiler session shows the
    program's spans beside the device ops;
  * ``frontier`` — the per-iteration convergence-telemetry schema the
    XLA and kernel engine loops record when asked (``telemetry=True``):
    affected count, L∞ residual, frontier growth/prune, active work
    units per iteration as a compact ``[iters, k]`` array;
  * ``export``   — Prometheus-text and JSON-lines exporters plus a tiny
    scrape server over ``ServeMetrics``.

**Correctness** —
  * ``sentinel`` — per-batch on-device invariant checks (rank mass,
    nonnegativity, residual, EWMA anomaly scores) and the bitwise rank
    digest; violations become structured ``Incident`` records;
  * ``shadow``   — sampled background verification of every Kth
    snapshot against the f64 XLA reference solve (live DF-P drift);
  * ``recorder`` — a flight recorder (batch ring + checkpoint anchors)
    with deterministic bit-for-bit ``replay``;
  * ``slo``      — SLO objectives with multi-window burn-rate alerts;
  * ``monitor``  — ``CorrectnessMonitor``, the facade ``ServeEngine``
    drives (``ServeEngine(..., monitor=...)``).

Tracing and telemetry are **off by default and free when off**: the
global tracer is disabled (spans are shared no-op context managers, no
profiler annotations, no device syncs), and the loops' ``telemetry`` flag is static, so the
untraced hot path compiles to the identical device-program schedule.
The correctness monitor is opt-in per engine and adds one fused
invariant program per batch; the shadow solve runs off the hot path.
"""
from repro.obs.export import JsonlSink, MetricsExporter, prometheus_text
from repro.obs.frontier import FIELDS as TELEMETRY_FIELDS
from repro.obs.frontier import NUM_FIELDS as TELEMETRY_NUM_FIELDS
from repro.obs.frontier import FrontierTelemetry
from repro.obs.trace import (Tracer, get_tracer, set_tracer, span,
                             start_tracing, stop_tracing, traced, tracing,
                             timeit)
from repro.obs.sentinel import (Incident, InvariantSentinel,
                                SentinelConfig, rank_digest)
from repro.obs.shadow import ShadowReport, ShadowVerifier
from repro.obs.slo import BurnRateAlert, SloSet, SloTracker
from repro.obs.recorder import (BatchRecord, FlightRecorder, ReplayReport,
                                load_bundle, replay)
from repro.obs.monitor import CorrectnessMonitor, MonitorConfig

__all__ = [
    "BatchRecord", "BurnRateAlert", "CorrectnessMonitor",
    "FlightRecorder", "FrontierTelemetry", "Incident",
    "InvariantSentinel", "JsonlSink", "MetricsExporter", "MonitorConfig",
    "ReplayReport", "SentinelConfig", "ShadowReport", "ShadowVerifier",
    "SloSet", "SloTracker", "Tracer", "TELEMETRY_FIELDS",
    "TELEMETRY_NUM_FIELDS", "get_tracer", "load_bundle",
    "prometheus_text", "rank_digest", "replay", "set_tracer", "span",
    "start_tracing", "stop_tracing", "traced", "tracing", "timeit",
]

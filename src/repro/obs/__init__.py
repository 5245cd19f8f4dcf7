"""``repro.obs`` — unified tracing + metrics for the GANAX stack.

GANAX's claim is about *where the cycles go*; this package is how the
reproduction answers that per layer, per request, and per run instead
of only through end-of-run ``BENCH_*.json`` aggregates.  Two halves:

* **Span tracer** (:mod:`repro.obs.tracer`) — ``obs.trace(name,
  **attrs)`` context manager/decorator with thread-local span stacks
  and monotonic-clock timing.  **Off by default** and near-free when
  disabled; spans are host-side only (no JAX primitives), so enabling
  tracing never changes a jaxpr, and a span inside a jitted function
  records trace time exactly once — never per compiled execution.
* **Metrics registry** (:mod:`repro.obs.metrics`) — counters, gauges,
  fixed-bucket histograms (p50/p90/p99), keyed on (name, labels).
  Metrics are always live (cheap lock + add), replacing the scattered
  ad-hoc counters that used to live on ``GanServer``, the planner, and
  the μop cache; ``register_collector``/:func:`collect` snapshot
  external stat sources (copies, never aliases).

Enabling::

    REPRO_OBS=1             # in-memory sink (programmatic inspection)
    REPRO_OBS=run.jsonl     # live JSONL trace file
    obs.enable(sink=...)    # explicit: None=memory, path=JSONL, object

Reading a trace::

    python -m repro.obs run.jsonl                  # text summary
    python -m repro.obs run.jsonl --perfetto out.trace.json
    # open out.trace.json in https://ui.perfetto.dev

``obs.profile(outdir)`` additionally captures the device-side JAX
profiler trace (``jax.profiler.start_trace``/``stop_trace``); while
tracing is enabled every span is mirrored into it as a
``jax.profiler.TraceAnnotation``, on the device trace's clock.

Instrumented subsystems and their metric names are tabulated in the
README's "Observability" section.
"""

from __future__ import annotations

import os

from repro.obs.export import (from_trace_events, read_records,
                              summarize, to_trace_events, write_jsonl,
                              write_trace_events)
from repro.obs.jaxbridge import profile
from repro.obs.metrics import (DEFAULT_LATENCY_BOUNDS_US, Counter,
                               Gauge, Histogram, Registry)
from repro.obs.tracer import (JsonlSink, MemorySink, Span, disable,
                              emit_span, enable, event, flush_metrics,
                              get_sink, is_enabled, now_us, registry,
                              trace)

__all__ = [
    "trace", "event", "enable", "disable", "is_enabled", "get_sink",
    "flush_metrics", "Span", "MemorySink", "JsonlSink",
    "now_us", "emit_span",
    "counter", "gauge", "histogram", "snapshot", "collect",
    "register_collector", "registry", "Registry", "Counter", "Gauge",
    "Histogram", "DEFAULT_LATENCY_BOUNDS_US",
    "to_trace_events", "from_trace_events", "read_records",
    "write_jsonl", "write_trace_events", "summarize",
    "profile",
]


# -- module-level conveniences over the process-wide registry ---------------

def counter(name: str, **labels) -> Counter:
    return registry.counter(name, **labels)


def gauge(name: str, **labels) -> Gauge:
    return registry.gauge(name, **labels)


def histogram(name: str, bounds=None, **labels) -> Histogram:
    return registry.histogram(name, bounds=bounds, **labels)


def snapshot() -> dict:
    """Deep-copied plain-data view of every metric."""
    return registry.snapshot()


def collect() -> dict:
    """Copied stats from every registered external collector (μop
    cache, autotuning planner, ...)."""
    return registry.collect()


def register_collector(name, fn) -> None:
    registry.register_collector(name, fn)


# -- environment opt-in -----------------------------------------------------
# REPRO_OBS=1/true/yes/on → enabled with an in-memory sink;
# any other non-empty, non-zero value → live JSONL file at that path.
_env = os.environ.get("REPRO_OBS", "").strip()
if _env and _env.lower() not in ("0", "false", "no", "off"):
    enable(None if _env.lower() in ("1", "true", "yes", "on") else _env)
del _env

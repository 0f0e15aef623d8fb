"""Observability: structured tracing + live metrics for every layer.

The reference exposes runtime behavior only as printf-style reports
(``kv_stats``/``cummulative_stats``, ``src/mapreduce.cpp:2937-3066``).
This package is the machine-readable twin, in two halves:

* **tracing** (PR 1, post-hoc): a thread-safe tracer with nested spans
  that every layer reports into (MR ops in ``core/mapreduce.py``,
  collectives in ``parallel/shuffle.py``, H2D staging in
  ``parallel/ingest.py``, script commands in ``oink/script.py``),
  pluggable sinks (in-memory ring, size-rotated JSONL file, callbacks),
  a Chrome trace-event (Perfetto-loadable) exporter, and a per-op
  summarizer.
* **metrics** (PR 3, live): a thread-safe registry of labeled
  counters/gauges/histograms fed automatically from the tracer
  (``metrics.py``), exposed via ``mr.stats()["metrics"]``, a Prometheus
  endpoint (``httpd.py``, ``MRTPU_METRICS_PORT``) and periodic JSONL
  snapshots — plus a flight recorder (``flight.py``) that dumps a
  forensic artifact on unhandled exceptions or SIGUSR1.

Enable tracing via ``MRTPU_TRACE=/path/trace.jsonl``,
``MapReduce(trace=...)``, or ``get_tracer().enable()``.  When disabled,
``tracer.span()`` returns a shared no-op singleton — zero allocation,
zero per-op cost.

See ``doc/observability.md`` for the span model, the metric catalog and
the Perfetto how-to.
"""

from .tracer import (NULL_SPAN, Span, Tracer, configure_from_env,
                     get_tracer, programs)
from .sinks import (CallbackSink, JsonlSink, RingSink, chrome_trace,
                    read_jsonl, write_chrome_trace)
from .report import aggregate_ops, per_op_table
from .metrics import MetricsRegistry, enable_metrics, get_registry
from .context import (RequestAccount, current_trace_id, new_trace_id,
                      request_scope)

__all__ = [
    "Tracer", "Span", "NULL_SPAN", "get_tracer", "configure_from_env",
    "programs",
    "RingSink", "JsonlSink", "CallbackSink",
    "chrome_trace", "write_chrome_trace", "read_jsonl",
    "aggregate_ops", "per_op_table",
    "MetricsRegistry", "get_registry", "enable_metrics",
    "RequestAccount", "request_scope", "current_trace_id",
    "new_trace_id",
]

# apply MRTPU_METRICS_PORT / MRTPU_METRICS_SNAP / MRTPU_FLIGHT once the
# package is first imported (every entry point that builds a MapReduce
# gets here); never raises
from .metrics import configure_from_env as _metrics_env
_metrics_env()

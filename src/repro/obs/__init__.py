"""`repro.obs` — unified observability for the mining stack.

Three layers, threaded through the session (:mod:`repro.api.session`),
the executor (:mod:`repro.core.executor`), the sharded dispatch pool
(:mod:`repro.core.shard`), the compiler (:mod:`repro.core.compiler`),
the streaming service (:mod:`repro.stream.service` /
:mod:`repro.stream.resilience`), and the triage endpoint
(:mod:`repro.launch.serve`):

* :mod:`repro.obs.trace` — nested span tracer on two clocks: every
  span lands in a running JAX profiler session as a
  ``TraceAnnotation`` (on the device trace's clock), and in the
  in-memory tracer when that is enabled (off by default; one branch
  and a flag read per span when both are off), which exports Chrome
  trace-event JSON (``chrome://tracing`` / Perfetto) and a plain-text
  hierarchical summary.  :func:`~repro.obs.trace.phase` spans also add
  their wall time to ``stats["<phase>_ns"]``, always — the mine path's
  phase counters.  Spans time *dispatch*, not device completion — see
  the asynchrony caveat in the module docstring.
* :mod:`repro.obs.metrics` — typed Counter/Gauge/Histogram registry
  with Prometheus-style text exposition; unifies the legacy
  ``executor.STAT_KEYS`` / ``STORE_STAT_KEYS`` / resilience counters.
* :mod:`repro.obs.flight` — bounded flight recorder: the last N tick
  reports + span trees, dumped to a JSONL postmortem bundle on fault.

Quick start::

    from repro import obs
    obs.trace.enable()
    session.mine(backend="sharded")
    obs.trace.get_tracer().export_chrome("/tmp/mine.trace.json")
    print(obs.metrics.get_registry().exposition())
"""
from repro.obs import flight, metrics, trace
from repro.obs.flight import FlightRecorder
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    observe_stats,
)
from repro.obs.trace import Tracer, get_tracer, is_enabled, phase, span

__all__ = [
    "trace",
    "metrics",
    "flight",
    "Tracer",
    "get_tracer",
    "is_enabled",
    "span",
    "phase",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "get_registry",
    "observe_stats",
    "FlightRecorder",
]

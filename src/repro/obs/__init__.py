"""``repro.obs`` — zero-dependency telemetry: metrics, tracing, exposition.

The observability layer of the reproduction (see the "Observability"
section of docs/architecture.md).  Three pieces:

* :mod:`repro.obs.metrics` — counter/gauge/histogram registry with
  labeled series, Prometheus text exposition, atomic snapshot writer;
* :mod:`repro.obs.trace` — span API with two sinks (Chrome-trace/Perfetto
  JSON, or ``jax.profiler`` host events on the device trace's clock) and
  a process-ambient tracer so library code needs no plumbing;
* :mod:`repro.obs.summarize` — ``python -m repro.obs summarize
  [--check]`` renders/validates the emitted files (used by CI).

:class:`Telemetry` bundles a registry with an optional tracer — the
single handle the service, daemon, and CLIs pass around.  Everything here
is stdlib-only (the profiler sink imports JAX when it is built) and
strictly off-path: instrumentation observes host-side values the
instrumented code already materialized, never issues device work, and
telemetry-on runs are bit-identical to telemetry-off runs
(tests/test_obs.py).
"""
from __future__ import annotations

from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      to_prometheus, write_snapshot)
from .trace import (ProfilerRecorder, TraceRecorder, current_tracer,
                    set_tracer, span)

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "to_prometheus", "write_snapshot", "TraceRecorder",
           "ProfilerRecorder", "current_tracer", "set_tracer", "span",
           "Telemetry"]


class Telemetry:
    """A metrics registry plus an optional trace recorder, as one handle.

    ``Telemetry()`` gives live metrics only; pass ``tracer=`` to also
    record spans.  ``spans()`` proxies to the tracer when present and is
    a no-op context manager otherwise, so instrumented code never
    branches on tracer presence.
    """

    def __init__(self, registry: MetricsRegistry | None = None,
                 tracer: TraceRecorder | ProfilerRecorder | None = None):
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self.tracer = tracer

    def spans(self, name: str, cat: str = "repro",
              args: dict | None = None):
        """Span on this bundle's tracer; inert if no tracer attached."""
        from .trace import _NULL
        if self.tracer is None:
            return _NULL
        return self.tracer.span(name, cat=cat, args=args)

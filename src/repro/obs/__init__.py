"""repro.obs — unified telemetry: spans, metrics, drift, and the
analysis/action tier on top of them (DESIGN.md §15, §19).

Signal modules, dependency-free and threaded through the request
lifecycle:

* :mod:`repro.obs.trace` — structured spans (admission → coalesce →
  placement → dispatch → negotiate) with parent/child links; byte-stable
  JSONL and Chrome-trace/Perfetto exports; written into a JAX profiler
  trace as ``repro.*`` host events, with the host spans (submit, drain,
  launch, wait, sample, …), while the profiler collects.
* :mod:`repro.obs.metrics` — counters / gauges / fixed-bucket
  histograms in one process-global registry; Prometheus text
  exposition and a JSON snapshot (``launch/serve.py --metrics``).
* :mod:`repro.obs.drift` — modeled-vs-observed residual ratios per
  (fingerprint, bucket, dtype), ranked by where memhier is most wrong.

Analysis/action modules (§19) that turn those signals into answers:

* :mod:`repro.obs.critical` — per-request critical path + typed blame
  buckets (queue-wait / region-swap / coalesce / channel-contention /
  negotiate / pallas_build / compute), conservation-checked.
* :mod:`repro.obs.tail` — tail-based sampling: keep every SLO-breaching,
  erroring, or p99 tree even at a 1% baseline rate.
* :mod:`repro.obs.slo` — per-tenant SLOs with multi-window burn rates
  and the admission shed/deprioritise hook queue.submit consults.

Off (no tracer installed, no profiler collecting) a span costs one
global read, one ``TraceAnnotation.is_enabled()`` and the call's
keyword packing, a few hundred nanoseconds on a CPU; the chip
benchmark's untraced runs carry it, so its parent-against-change check
measures it on the chip. What tracing costs when on is read from traced
runs of a cell on the parent and on the change with the same seeds
(PERF.md). ``bench_hotpath``'s ≤ 3% gate (tracer installed against
off) is a CPU, interpret-mode figure and says nothing of the chip.
"""
from repro.obs.critical import (Blame, attribute, blame_report,
                                critical_path, export_jsonl as
                                export_blame_jsonl, format_report,
                                max_residual)
from repro.obs.drift import DriftCell, DriftTracker, watch_programs
from repro.obs.metrics import (DEFAULT_BUCKETS, Counter, Gauge, Histogram,
                               MetricsRegistry, REGISTRY, default_registry,
                               start_http_server)
from repro.obs.slo import Slo, SloMonitor, SloShedder
from repro.obs.tail import TailSampler
from repro.obs.trace import (NULL_SPAN, Span, Tracer, VirtualClock,
                             enabled, get_tracer, host_span, set_tracer,
                             span, using_tracer)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "DEFAULT_BUCKETS", "default_registry", "start_http_server",
    "Span", "Tracer", "VirtualClock", "NULL_SPAN",
    "enabled", "get_tracer", "host_span", "set_tracer", "span",
    "using_tracer",
    "DriftCell", "DriftTracker", "watch_programs",
    "Blame", "attribute", "blame_report", "critical_path",
    "export_blame_jsonl", "format_report", "max_residual",
    "TailSampler", "Slo", "SloMonitor", "SloShedder",
]

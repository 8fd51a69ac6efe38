"""Observability layer: distributed spans, metrics, trace export (PR 7).

* ``metrics``  — the process-global :data:`~repro.obs.metrics.REGISTRY` of
  counters / gauges / fixed-bucket latency histograms (p50/p95/p99),
  disabled by default and zero-cost when off. Instrumented call sites live
  in ``serverless.transport`` / ``socket_transport`` (submits, retries,
  respawns, reconnects, heartbeats, frame bytes, invoke latency),
  ``core.dre`` (result-cache hits/misses/evictions, pool leases/warm rate),
  ``core.dataplane`` (jit trace-cache compiles per pow2 query bucket, bytes
  put on the device) and ``serve.vector_service`` (requests served).
* ``spans``    — span contexts that cross the transport boundary inside the
  ``extra`` envelope (never the budgeted payload), worker-side sub-spans
  echoed back in the response ``info``, and the per-run :class:`Recorder`
  that stitches them into one tree; and :func:`~repro.obs.spans.span`, the
  served path's ``squash.*`` layer spans (request, Stage 1, Algorithm 1,
  plane set-up/upload/dispatch/fetch, GC pauses) written into the JAX
  profiler's trace on the device's clock.
* ``export``   — JSONL persistence under ``results/`` + an in-memory
  exporter for tests.
* ``timeline`` — ``python -m repro.obs.timeline <trace.jsonl>``: a per-node
  text Gantt of the Alg. 2 tree walk.
* ``slo``      — rolling p50/p99 latency, retry/error-budget and
  cache-hit monitors over the run-record stream, with the
  :class:`~repro.obs.slo.SloPolicy` gate API (PR 10).
* ``top``      — ``python -m repro.obs.top <trace.jsonl>``: live text
  dashboard of fleet metrics, SLO status and $/query attribution.

Fleet aggregation (PR 10): ``Counter``/``Gauge``/``Histogram`` merge
losslessly from snapshots; pipe workers echo registry deltas in response
``info`` and socket hosts answer a STATS frame, so
``REGISTRY.fleet_snapshot()`` is one merged, source-labelled view of the
whole fleet.

The whole layer is opt-in via ``RuntimeConfig(obs_enabled=True,
obs_trace_path=...)`` or ``REGISTRY.enable()``, the one switch; ids,
``SearchStats`` and all traces are bitwise-identical with it on or off
(pinned by tests). This module imports only the standard library (``span``
imports jax on first use), so ``core``/``serverless`` can instrument freely
without cycles.
"""

from repro.obs.export import InMemoryExporter, JsonlExporter, read_jsonl, run_record
from repro.obs.metrics import REGISTRY, Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.slo import SloObjective, SloPolicy, SloTracker, default_policy
from repro.obs.spans import Recorder, Span, SpanContext, new_run_id

__all__ = [
    "REGISTRY", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "Recorder", "Span", "SpanContext", "new_run_id",
    "InMemoryExporter", "JsonlExporter", "read_jsonl", "run_record",
    "SloObjective", "SloPolicy", "SloTracker", "default_policy",
]

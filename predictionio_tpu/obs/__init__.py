"""Unified observability layer: metrics + request tracing.

Dependency-free instruments shared by every framework process
(data/.../api/Stats.scala in the reference only ever grew minute
buckets; this is the layer a production scoring tier actually needs —
per-stage latency histograms and queue-wait accounting, the
prerequisite arxiv 2501.10546 names for running at qps, and the
tracing-timeline argument of the TensorFlow system paper 1605.08695):

- :mod:`predictionio_tpu.obs.metrics` — a process-global registry of
  counters, gauges, and log-bucketed latency histograms, rendered as
  Prometheus text format (``GET /metrics`` on every server) and merged
  as a compact ``obs`` block into the existing ``/stats.json`` payloads.
- :mod:`predictionio_tpu.obs.trace` — per-request spans: each HTTP
  request gets a trace id (honoring ``X-PIO-Trace``), stage boundaries
  record spans, and a fixed-size ring retains the N slowest recent
  traces (``GET /traces.json``; waterfall table on the dashboard).
- :mod:`predictionio_tpu.obs.device` — the device side of the story:
  XLA compile tracking per jitted entry point, per-device memory
  gauges, host<->device transfer byte accounting, and on-demand
  ``jax.profiler`` capture (``pio profile`` / ``POST /profile``).
- :mod:`predictionio_tpu.obs.progress` — live training progress via an
  atomic file written at checkpoint segment boundaries, read by
  ``pio status`` and the dashboard while a run is underway.
- :mod:`predictionio_tpu.obs.slo` — declarative objectives over the
  metrics registry, judged with multi-window burn-rate alerting
  (``GET /slo.json``, ``pio_slo_*`` gauges, per-server default sets).
- :mod:`predictionio_tpu.obs.freshness` — end-to-end ingest-to-servable
  latency, observed at the epoch-fenced patch/reload commit
  (``pio_serving_freshness_seconds``; ``freshness`` block on
  ``/stats.json``).
- :mod:`predictionio_tpu.obs.history` — bounded ring-buffer time series
  over the metrics registry (counters as per-step deltas, gauges and
  histogram quantiles as samples), sampled on the SLO ticker's cadence
  (``GET /history.json``; dashboard sparklines; ``pio top``).
- :mod:`predictionio_tpu.obs.incident` — the flight recorder: atomic
  incident bundles under ``$PIO_RUN_DIR/incidents/`` on SLO violation,
  unhandled exception, or ``POST /incident`` (``pio incidents``).

- :mod:`predictionio_tpu.obs.runtime` — host time in which the device
  could have been working, by cause: the batch worker's time by state,
  ``thread_time`` beside wall time for the stages that only enqueue
  (``trace.region(cpu_hist=)``), collector pauses (one ``gc.callbacks``
  hook), process stops (the 20 ms ``obs-beat`` thread; ``runtime``
  block on ``/stats.json``).

Instrumentation is ALWAYS-ON and cheap: what it costs is measured end
to end on the chip by each tracing PR (``PERF.md`` section 6: PR 24,
PR 34) and held by the benchmark's bounds; ``PIO_OBS=0`` turns every
instrument into a no-op for A/B measurement.

``device`` and ``progress`` are intentionally NOT imported here:
``obs.device`` must stay importable-but-inert on jax-free processes,
and eagerly importing it from every ``obs`` user would register its
instruments even where they can never fire. Import them explicitly.
"""

from predictionio_tpu.obs import metrics, trace  # noqa: F401
from predictionio_tpu.obs import freshness, history, incident, runtime, slo  # noqa: F401

__all__ = [
    "metrics",
    "trace",
    "slo",
    "freshness",
    "history",
    "incident",
    "runtime",
    "device",
    "progress",
]

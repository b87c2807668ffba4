"""Run telemetry: structured events, metrics registry, span tracing,
multi-host run reports.

The reference's only instrumentation is trainer wall-clock timing
(``record_training_start/stop``); this subsystem is the §5 "tracing" row
grown to production shape, recording what a run was *doing* — so a hang
or a ``BarrierTimeout`` leaves a timeline naming the host and phase
that stalled instead of silence:

- :mod:`~dist_keras_tpu.observability.events` — append-only per-host
  JSONL under ``DK_OBS_DIR`` (atomic line writer; zero-cost no-op when
  the env is unset; never throws into training code).  Every seam emits
  typed events: epoch ends, chunk boundaries, checkpoint
  save/promote/restore, retry attempts, fault-point fires, preemption
  signals, coordination votes/barriers with durations, dead-peer
  transitions, NaN-sentinel hits.
- :mod:`~dist_keras_tpu.observability.metrics` — process-wide named
  counters/gauges/histograms, histogram samples stamped so a reader
  can cut one interval out; snapshots ride the event stream at epoch
  boundaries.
- :mod:`~dist_keras_tpu.observability.spans` — distributed tracing:
  nested ``span(name)`` regions minting ``trace_id``/``span_id``/
  ``parent_id``, capturable/resumable across threads, propagated
  cross-process via a ``traceparent`` header and the ``DK_TRACE_ID``
  env; forwarded to ``jax.profiler.TraceAnnotation`` while a device
  trace is active.
- :mod:`~dist_keras_tpu.observability.flight` — crash-safe flight
  recorder: a bounded ring of recent records, dumped to ``DK_OBS_DIR``
  on watchdog alerts, preemption, unhandled crash, or ``/tracez``.
- :mod:`~dist_keras_tpu.observability.trace_export` — Chrome
  trace-event (Perfetto-loadable) export + per-trace connectivity
  report; CLI ``--perfetto`` / ``--traces`` / ``--dumps``.
- :mod:`~dist_keras_tpu.observability.statusz` — the shared
  ``/statusz`` build/config/open-span renderer both HTTP servers serve.
- :mod:`~dist_keras_tpu.observability.report` — merge per-host logs
  into one (time, rank)-ordered timeline with per-phase summaries;
  also the CLI: ``python -m dist_keras_tpu.observability <dir>``
  (``--perf`` adds the perf-attribution + watchdog section).
- :mod:`~dist_keras_tpu.observability.timeseries` — bounded per-metric
  ``(t, value)`` rings sampled from the registry by a background
  ``MetricsSampler`` at ``DK_OBS_SAMPLE_S`` — post-mortem snapshots
  grown into a live, queryable signal.
- :mod:`~dist_keras_tpu.observability.perf` — always-on CPU-measurable
  perf attribution: jit retrace/trace counts, dispatch counts, H2D/D2H
  bytes+walls, per-phase (data/step/comm/ckpt) host wall histograms.
- :mod:`~dist_keras_tpu.observability.watchdog` — declarative anomaly
  rules over the time series (step-time regression, throughput stall,
  queue growth, quiet hosts) -> typed ``watchdog_alert`` events + the
  ``resilience.supervisor`` alert seam.
- :mod:`~dist_keras_tpu.observability.prometheus` — text exposition of
  the registry; serving ``/metricsz?format=prometheus`` and the
  standalone per-host ``DK_METRICS_PORT`` exporter serve it.

See the README "Observability" section for the env knobs
(``DK_OBS_DIR`` / ``DK_OBS_FLUSH``), the event schema table and CLI
examples.
"""

import importlib

from dist_keras_tpu.observability import events, metrics, report, spans
from dist_keras_tpu.observability.events import (
    EventWriter,
    emit,
    enabled,
    obs_dir,
)
from dist_keras_tpu.observability.metrics import (
    counter,
    emit_snapshot,
    gauge,
    histogram,
    snapshot,
    to_prometheus,
)
from dist_keras_tpu.observability.spans import span

# the telemetry plane (sampler thread, watchdog rules, http exposition)
# resolves lazily: every process imports `events` at startup — through
# checkpoint/faults/retry — and must not pay for numpy rule math or
# http.server unless it actually arms the sampler or an exporter
_LAZY = {
    "flight": "dist_keras_tpu.observability.flight",
    "perf": "dist_keras_tpu.observability.perf",
    "prometheus": "dist_keras_tpu.observability.prometheus",
    "statusz": "dist_keras_tpu.observability.statusz",
    "timeseries": "dist_keras_tpu.observability.timeseries",
    "trace_export": "dist_keras_tpu.observability.trace_export",
    "watchdog": "dist_keras_tpu.observability.watchdog",
    "Exporter": ("dist_keras_tpu.observability.prometheus", "Exporter"),
    "MetricsSampler": ("dist_keras_tpu.observability.timeseries",
                       "MetricsSampler"),
    "TimeSeries": ("dist_keras_tpu.observability.timeseries",
                   "TimeSeries"),
    "Watchdog": ("dist_keras_tpu.observability.watchdog", "Watchdog"),
}


def __getattr__(name):
    spec = _LAZY.get(name)
    if spec is None:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}")
    if isinstance(spec, tuple):
        value = getattr(importlib.import_module(spec[0]), spec[1])
    else:
        value = importlib.import_module(spec)
    globals()[name] = value  # resolve once
    return value


def __dir__():
    return sorted(set(globals()) | set(_LAZY))

__all__ = [
    "events", "flight", "metrics", "perf", "prometheus", "report",
    "spans", "statusz", "timeseries", "trace_export", "watchdog",
    "EventWriter", "emit", "enabled", "obs_dir",
    "counter", "gauge", "histogram", "snapshot", "emit_snapshot",
    "to_prometheus", "span",
    "TimeSeries", "MetricsSampler", "Watchdog", "Exporter",
]

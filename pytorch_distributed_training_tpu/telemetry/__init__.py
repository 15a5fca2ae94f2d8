"""Structured telemetry: metric registry, JSONL sink, straggler detection.

The three pieces, wired together by the Trainer (train/loop.py):

- ``MetricsRegistry`` (registry.py) — counters/gauges/timer histograms that
  instrumentation sites record into; a process-wide default registry lets
  loaders, the checkpointer and the supervisor instrument without plumbing;
- ``JsonlSink`` (sink.py) — process-0-gated append-only JSONL stream
  (``--metrics-dir``): run-metadata header, per-step timing breakdown,
  per-epoch records, checkpoint/restart events;
- ``epoch_straggler_stats`` (straggler.py) — cross-host step-time gather so
  process 0 can name the slowest host instead of just a slow fleet.

The serving observability plane layers on top of the same sink:

- ``Tracer``/``Span`` (spans.py) — request-span tracing keyed by
  ``X-Request-Id``; ``trace_coverage`` is the bench/test completeness
  verdict;
- ``FlightRecorder`` (flight.py) — ring-buffer of engine tick summaries
  dumped as ``flight_dump`` records on watchdog stall, fatal tick,
  SIGTERM and ``/debug/flight``;
- ``BurnRateMonitor`` (slo.py) — per-tier multi-window SLO burn rates
  (``slo_burn`` records + the optional autoscaler/brownout signal).

``scripts/summarize_metrics.py`` folds a stream back into a per-epoch table;
``scripts/trace_view.py`` renders one trace's waterfall + a fleet timeline.
"""

from pytorch_distributed_training_tpu.utils.lazy import lazy_exports

# resolved on first use: registry/slo/spans/flight are jax-free and imported
# by the fleet coordinator; sink and straggler pull in jax (utils/lazy.py)
_LAZY = {
    "FlightRecorder": "flight",
    "MetricsRegistry": "registry",
    "TimerStat": "registry",
    "get_registry": "registry",
    "set_registry": "registry",
    "JsonlSink": "sink",
    "run_metadata": "sink",
    "BurnRateMonitor": "slo",
    "SloConfig": "slo",
    "Span": "spans",
    "Tracer": "spans",
    "trace_coverage": "spans",
    "epoch_straggler_stats": "straggler",
}

__all__ = sorted(_LAZY)
__getattr__ = lazy_exports(__name__, _LAZY)

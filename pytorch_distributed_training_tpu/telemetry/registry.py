"""Metric primitives: counters, gauges, timer histograms, one registry.

The reference repo's observability is rank-0 ``print`` (SURVEY.md §5); every
HISTORY_* artifact in this repo was hand-assembled from it. The
registry is the in-process half of the replacement: instrumentation sites
(loaders, checkpointer, supervisor, the train loop) record into whatever
registry is installed — cheap enough to stay on unconditionally — and the
Trainer snapshots it per epoch. The persistence half is ``sink.JsonlSink``;
when one is attached, ``emit`` forwards event records through it
(process-0-gated inside the sink, so call sites never branch on rank).

A module-level default registry exists so layers with no Trainer handle
(data loaders, the checkpointer, the supervisor) can instrument without
threading a registry through every constructor; the Trainer installs its
own registry as the default for the duration of its run.
"""

from __future__ import annotations

import contextlib
import random
import threading
import time

import numpy as np


class TimerStat:
    """Observations of one timed quantity (seconds); summarizes on demand.

    Bounded: count, total, min and max are exact; the percentiles come
    from a uniform sample of at most ``SAMPLE`` observations (reservoir
    sampling from a fixed seed), exact while the window holds no more
    than that. A replica observes ``serve/tick`` for the life of its
    process and never resets."""

    SAMPLE = 1024

    __slots__ = ("count", "total", "min", "max", "values", "_rng")

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = float("-inf")
        self.values: list[float] = []   # the sample
        self._rng = random.Random(0)

    def observe(self, seconds: float) -> None:
        x = float(seconds)
        self.count += 1
        self.total += x
        self.min = min(self.min, x)
        self.max = max(self.max, x)
        if len(self.values) < self.SAMPLE:
            self.values.append(x)
        else:
            j = self._rng.randrange(self.count)
            if j < self.SAMPLE:
                self.values[j] = x

    def summary(self) -> dict:
        if self.count == 0:
            return {"count": 0, "total_s": 0.0}
        v = np.asarray(self.values, np.float64)
        return {
            "count": self.count,
            "total_s": self.total,
            "mean_s": self.total / self.count,
            "min_s": self.min,
            "max_s": self.max,
            "p50_s": float(np.percentile(v, 50)),
            "p95_s": float(np.percentile(v, 95)),
        }


class MetricsRegistry:
    """Counters + gauges + timer histograms, with an optional JSONL sink.

    - counters are monotonic per snapshot window (``inc``);
    - gauges hold the last value set (``gauge``);
    - timers accumulate observations in seconds (``observe`` or the
      ``timer(name)`` context manager) and summarize to
      count/total/mean/min/max/p50/p95.

    ``snapshot(reset=True)`` returns the current window and optionally
    clears it (the Trainer resets per epoch so epoch records don't smear).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[str, float] = {}
        self._gauges: dict[str, float] = {}
        self._timers: dict[str, TimerStat] = {}
        self._sink = None

    # ------------------------------------------------------------- recording

    def inc(self, name: str, n: float = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = float(value)

    def observe(self, name: str, seconds: float) -> None:
        with self._lock:
            stat = self._timers.get(name)
            if stat is None:
                stat = self._timers[name] = TimerStat()
            stat.observe(seconds)

    @contextlib.contextmanager
    def timer(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.observe(name, time.perf_counter() - t0)

    # ----------------------------------------------------------------- sink

    def attach_sink(self, sink) -> None:
        self._sink = sink

    @property
    def sink(self):
        return self._sink

    def emit(self, record: dict) -> None:
        """Forward an event record to the attached sink (no-op without one;
        the sink itself gates on process 0)."""
        if self._sink is not None:
            self._sink.emit(record)

    # ------------------------------------------------------------- snapshot

    def snapshot(self, *, reset: bool = False) -> dict:
        with self._lock:
            out = {
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "timers": {k: s.summary() for k, s in self._timers.items()},
            }
            if reset:
                self._counters.clear()
                self._gauges.clear()
                self._timers.clear()
        return out


_DEFAULT: MetricsRegistry | None = None
_DEFAULT_LOCK = threading.Lock()


def get_registry() -> MetricsRegistry:
    """The process-wide default registry (created lazily)."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = MetricsRegistry()
        return _DEFAULT


def set_registry(registry: MetricsRegistry | None) -> MetricsRegistry | None:
    """Install ``registry`` as the process default; returns the previous one
    (pass it back to restore — tests and nested Trainers)."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        prev = _DEFAULT
        _DEFAULT = registry
        return prev

"""Tracing/profiling hooks (the reference has none — SURVEY.md §5).

``maybe_profile`` wraps a code region in a ``jax.profiler`` trace when a
directory is configured (view with TensorBoard/XProf or `xprof`); trace
annotations label steps inside the timeline. ``debug_nans`` toggles JAX's
NaN checker — jit purity makes data races structurally impossible on TPU, so
NaN propagation is the analogous safety-net toggle here (SURVEY.md §5 race
detection).

Multi-host runs write per-host subdirectories (``trace_dir/host_{i}``):
``start_trace`` is per-process, and concurrent traces pointed at one shared
filesystem path collide on the plugin's dump files.
"""

from __future__ import annotations

import contextlib
import os

import jax

from pytorch_distributed_training_tpu.telemetry.spans import Phase
from pytorch_distributed_training_tpu.utils.logging import get_logger, log0


@contextlib.contextmanager
def maybe_profile(trace_dir: str | None):
    if not trace_dir:
        yield
        return
    if jax.process_count() > 1:
        trace_dir = os.path.join(trace_dir, f"host_{jax.process_index()}")
    started = False
    try:
        jax.profiler.start_trace(trace_dir)
        started = True
        log0(f"profiler trace started → {trace_dir}")
    except Exception as e:
        # a failed start (unwritable dir, a trace already running) must not
        # kill the training run it was meant to observe
        get_logger().warning(
            "profiler trace failed to start (%s: %s); continuing untraced",
            type(e).__name__,
            e,
        )
    try:
        yield
    finally:
        if started:
            jax.profiler.stop_trace()
            log0(f"profiler trace written → {trace_dir}")


def annotate(name: str) -> Phase:
    """Label a region in the profiler timeline (a ``Phase`` with no
    collector: the ``TraceAnnotation`` and two clock reads)."""
    return Phase(name)


def set_debug_nans(enabled: bool) -> None:
    jax.config.update("jax_debug_nans", bool(enabled))

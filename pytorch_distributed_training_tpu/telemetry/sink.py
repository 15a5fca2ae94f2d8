"""Process-0-gated JSONL metrics sink + the run-metadata header.

One record per line, appended and flushed as they happen, so a crashed or
preempted run leaves a readable stream up to its last completed step — the
machine-readable replacement for hand-assembling HISTORY_* artifacts
from rank-0 prints. Record types written by the framework:

- ``run_meta``   — one header per (re)started run: mesh shape, chip/process
                   counts, jax version, the fully-resolved model/train config;
- ``step``       — per-step timing breakdown (data wait, dispatch, device
                   block) + loss; ``compile_inclusive`` marks the first step;
- ``epoch``      — the Trainer's history record + straggler stats + the
                   epoch's timer summaries (checkpoint/loader/eval timings);
- ``checkpoint_save`` / ``checkpoint_restore`` / ``restart`` — events.

Every record gains a ``ts`` wall-clock field at write time. The file opens
in append mode: a supervised restart (utils/supervisor.py) continues the
same stream, with a fresh ``run_meta`` header marking the attempt boundary.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from typing import Any

from pytorch_distributed_training_tpu.analysis import concurrency


def _jsonable(x: Any):
    """Best-effort coercion for config values (paths, numpy scalars)."""
    if isinstance(x, (str, int, float, bool)) or x is None:
        return x
    if isinstance(x, dict):
        return {str(k): _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    try:
        return float(x)
    except (TypeError, ValueError):
        return str(x)


class JsonlSink:
    """Append-mode JSONL writer, active on process 0 only.

    Construct it on every process — non-0 processes get an inert sink, so
    call sites (checkpointer, supervisor, loaders) never branch on rank.
    """

    def __init__(
        self,
        metrics_dir: str,
        *,
        filename: str = "metrics.jsonl",
        process_index: int | None = None,
    ):
        if process_index is None:
            # jax only when asked to resolve the rank: the fleet coordinator
            # passes its own and stays jax-free
            import jax

            process_index = jax.process_index()
        self._file = None
        # serving emits from many threads at once (router request handlers,
        # the health loop, fleet monitors); a lock keeps each JSONL line
        # atomic — interleaved torn lines would poison the whole stream.
        # Instrumented: sink contention is the first suspect when every
        # thread funnels telemetry through one file (per-acquire stats are
        # in-memory only, so instrumenting the sink's own lock can't
        # recurse into emit)
        self._lock = concurrency.lock("telemetry.sink")
        self.path = os.path.join(os.path.abspath(metrics_dir), filename)
        if process_index == 0:
            os.makedirs(os.path.dirname(self.path), exist_ok=True)
            self._file = open(self.path, "a")

    @property
    def active(self) -> bool:
        return self._file is not None

    def emit(self, record: dict) -> None:
        if self._file is None:
            return
        rec = dict(record)
        rec.setdefault("ts", time.time())
        line = json.dumps(_jsonable(rec)) + "\n"
        with self._lock:
            if self._file is None:      # closed while we serialized
                return
            self._file.write(line)
            self._file.flush()

    def flush(self, *, fsync: bool = False) -> None:
        """Push buffered records to the OS — and with ``fsync``, to disk.
        The crash/preemption/watchdog exits call this so the last records
        (the ones explaining the exit) survive the process."""
        with self._lock:
            if self._file is None:
                return
            self._file.flush()
            if fsync:
                os.fsync(self._file.fileno())

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                self._file.close()
                self._file = None


def run_metadata(mesh, model_config=None, train_config=None, **extra) -> dict:
    """The ``run_meta`` header record: everything needed to interpret the
    stream without the launching shell — mesh shape, chip count, resolved
    configs, jax version."""
    import jax

    rec = {
        "record": "run_meta",
        "mesh_shape": {k: int(v) for k, v in dict(mesh.shape).items()},
        "chip_count": len(mesh.devices.flat),
        "process_count": jax.process_count(),
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "config": {},
    }
    for key, cfg in (("model", model_config), ("train", train_config)):
        if cfg is not None:
            rec["config"][key] = _jsonable(dataclasses.asdict(cfg))
    rec.update(extra)
    return rec

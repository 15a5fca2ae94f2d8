"""Seconds of the trainer's warm start that are tracing and lowering of
the train and the eval step (the program's own work on the host, paid on
a cache hit too): its `warm_start.train.lower` and `warm_start.eval.lower`
spans. The training driver attaches no sink, so they are read from the
program's in-process list of set-up spans; the run is this process."""

from harness import spans


def read(obs):
    try:
        from pytorch_distributed_training_tpu.telemetry.spans import (
            SETUP_SPANS,
        )
    except ImportError:  # a program without the spans
        return None
    parts = [spans.setup_span_s(SETUP_SPANS, f"warm_start.{step}.lower")
             for step in ("train", "eval")]
    return None if None in parts else sum(parts)
